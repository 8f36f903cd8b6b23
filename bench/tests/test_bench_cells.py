"""Whole runs of each cell at a small size on the CPU, through the
harness's own functions: the program agrees with the reference, the
control (the reference in float32) does not, and each fault planted in the
timed path turns ``correct`` false."""
import os

import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import run_small

CELLS = ["pk_collab_c10k", "nopk_collab_c10k", "pk_point_reads",
         "pk_collab_c10k_overlap10"]


def runner_of_cell(root, workload):
    """The runner class the cell's mix names, as ``run_cell`` finds it."""
    cell = harness.cell_of(harness.load_benchmark(root), workload)
    return harness.runner_of(harness.traffic_of(cell, root)["kind"], root)


def every_runner(root):
    return [harness.runner_of(name[:-3], root) for name in
            sorted(os.listdir(os.path.join(root, "bench", "runners")))
            if name.endswith(".py")]


@pytest.mark.parametrize("workload", CELLS)
def test_program_agrees_with_the_reference(small_root, workload):
    r = run_small(small_root, workload)
    assert r["correct"] is True, r["check"]
    assert r["failed"] == 0 and r["attempted"] > 0
    compared = [c for c in r["check"].values() if c["limit"] is not None]
    assert compared and all(c["value"] == 0 for c in compared)
    assert "setup_s" in r["metrics"]
    assert list(r)[-1] == "check"


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_fails(small_root, workload, monkeypatch):
    """The reference computed in float32 in the program's place."""
    seen = {}

    def check(self, lower_precision=False):
        seen["program"] = orig(self)
        return orig(self, lower_precision=True)

    runner = runner_of_cell(small_root, workload)
    orig = runner.check
    monkeypatch.setattr(runner, "check", check)
    r = run_small(small_root, workload)
    assert r["correct"] is False
    assert any(c["limit"] is not None and c["value"] > c["limit"]
               for c in r["check"].values())
    assert all(v == 0 for v, lim in seen["program"].values()
               if lim is not None)


def test_trace_run_reads_the_span_metrics(small_root):
    r = run_small(small_root, "pk_collab_c10k", traced=True)
    assert r["correct"] is True
    m = r["metrics"]
    for name in ("delta_ms_per_diff", "diff_agg_self_ms",
                 "plan_merge_ms_per_publish", "seal_ms_per_commit"):
        assert m[name]["value"] > 0, name
    assert m["compiles_in_window"]["value"] == 0
    # the CPU trace has no device plane: no roofline or idle share is made
    assert not any(k.endswith("roofline") or k.startswith("idle_share")
                   for k in m)
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_reads_trace_counts_objects_probed(small_root):
    r = run_small(small_root, "pk_point_reads", traced=True)
    assert r["correct"] is True
    assert r["metrics"]["objects_probed_per_read"]["value"] >= 1


# ----------------------------------------------------- faults in the program

def in_window(monkeypatch, root, owner, attr, make_fault):
    """Plant ``make_fault(original)`` as ``owner.attr`` for the measured
    window only, so that set-up and warm-up run the sound program."""
    for runner in every_runner(root):
        def window(self, seconds, _orig=runner.window):
            orig = getattr(owner, attr)
            setattr(owner, attr, make_fault(orig))
            try:
                return _orig(self, seconds)
            finally:
                setattr(owner, attr, orig)
        monkeypatch.setattr(runner, "window", window)


def test_fault_publish_leaves_main_unchanged(small_root, monkeypatch):
    from repro.core import workspace

    def fault(orig):
        def publish(self, mode=None, **kw):
            self.status = "published"
            return {}
        return publish

    in_window(monkeypatch, small_root, workspace.PullRequest, "publish",
              fault)
    r = run_small(small_root, "pk_collab_c10k")
    assert r["correct"] is False
    assert r["check"]["table_rows_wrong"]["value"] > 0


@pytest.mark.parametrize("workload", ["pk_collab_c10k", "nopk_collab_c10k"])
def test_fault_half_the_update_left_out(small_root, monkeypatch, workload):
    from repro.core import engine

    def insert(orig):
        def run(self, table, batch, sigs=None):
            n = len(next(iter(batch.values())))
            half = {k: np.asarray(v)[: n // 2] for k, v in batch.items()}
            return orig(self, table, half, sigs)
        return run

    def delete_rowids(orig):
        def run(self, table, rowids):
            return orig(self, table, np.asarray(rowids)[: len(rowids) // 2])
        return run

    if "nopk" in workload:
        in_window(monkeypatch, small_root, engine.Txn, "delete_rowids",
                  delete_rowids)
    else:
        in_window(monkeypatch, small_root, engine.Txn, "insert", insert)
    r = run_small(small_root, workload)
    assert r["correct"] is False
    assert r["check"]["diff_rows_wrong"]["value"] > 0


def test_fault_a_diff_answer_altered(small_root, monkeypatch):
    from repro.core import repo as repo_mod

    def fault(orig):
        def snapshot_diff(store, a, b):
            d = orig(store, a, b)
            cnt = d.diff_cnt.copy()
            cnt[0] = -cnt[0]
            d.diff_cnt = cnt
            return d
        return snapshot_diff

    in_window(monkeypatch, small_root, repo_mod, "snapshot_diff", fault)
    r = run_small(small_root, "pk_collab_c10k")
    assert r["correct"] is False
    assert r["check"]["diff_rows_wrong"]["value"] > 0


def test_fault_a_read_answer_altered(small_root, monkeypatch):
    import repro.core

    def fault(orig):
        def gather_payload(store, schema, rowids, **kw):
            rows = orig(store, schema, rowids, **kw)
            rows["l_tax"] = rows["l_tax"] + 0.01
            return rows
        return gather_payload

    in_window(monkeypatch, small_root, repro.core, "gather_payload", fault)
    r = run_small(small_root, "pk_point_reads")
    assert r["correct"] is False
    assert r["check"]["read_rows_wrong"]["value"] > 0


def test_fault_a_read_misses_its_row(small_root, monkeypatch):
    from repro.core import table

    def fault(orig):
        def locate_keys(self, key_lo, key_hi, directory=None):
            return np.zeros(key_lo.shape, np.uint64)
        return locate_keys

    in_window(monkeypatch, small_root, table.Table, "locate_keys", fault)
    r = run_small(small_root, "pk_point_reads")
    assert r["correct"] is False


def test_fault_published_in_skip_mode(small_root, monkeypatch):
    """The overlap cell's PRs published in SKIP mode: main keeps the
    earlier PR's version of each conflicting row."""
    runner = runner_of_cell(small_root, "pk_collab_c10k_overlap10")
    orig = runner.window

    def window(self, seconds):
        self.mode = "skip"
        return orig(self, seconds)

    monkeypatch.setattr(runner, "window", window)
    r = run_small(small_root, "pk_collab_c10k_overlap10")
    assert r["correct"] is False
    assert r["check"]["table_rows_wrong"]["value"] > 0


def test_fault_conflicts_go_unseen(small_root, monkeypatch):
    """The merge planner aligns no key of the two change sets: every
    conflicting key is taken as the PR's alone. Main ends as under ACCEPT,
    so only the count of true conflicts shows the fault."""
    from repro.core import merge

    def fault(orig):
        def align(t, s):
            # each key both sides changed becomes a target-only entry and
            # a source-only one, side by side, so the union stays in order
            t_idx, s_idx = orig(t, s)
            both = (t_idx >= 0) & (s_idx >= 0)
            rep = np.where(both, 2, 1)
            first = (np.cumsum(rep) - rep)[both]
            t_idx, s_idx = np.repeat(t_idx, rep), np.repeat(s_idx, rep)
            t_idx[first + 1] = -1
            s_idx[first] = -1
            return t_idx, s_idx
        return align

    in_window(monkeypatch, small_root, merge, "_align_keys", fault)
    r = run_small(small_root, "pk_collab_c10k_overlap10")
    assert r["correct"] is False
    assert r["check"]["conflicts_wrong"]["value"] > 0
    assert r["check"]["table_rows_wrong"]["value"] == 0
