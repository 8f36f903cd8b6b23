"""Whole runs of each cell at a small size on the CPU, through the
harness's own functions: the program agrees with the reference, the
control (the reference in float32) does not, and each fault planted in the
timed path turns ``correct`` false."""
import numpy as np
import pytest

from bench.tests.conftest import run_small

CELLS = ["pk_collab_c10k", "nopk_collab_c10k", "pk_point_reads"]


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
    from bench import workload as wl
    seen = {}

    def check(self, lower_precision=False):
        seen["program"] = orig(self)
        return orig(self, lower_precision=True)

    runner = wl.RUNNERS["point_reads" if "reads" in workload else "collab"]
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

def in_window(monkeypatch, owner, attr, make_fault):
    """Plant ``make_fault(original)`` as ``owner.attr`` for the measured
    window only, so that set-up and warm-up run the sound program."""
    from bench import workload as wl
    for runner in wl.RUNNERS.values():
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

    in_window(monkeypatch, workspace.PullRequest, "publish", fault)
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
        in_window(monkeypatch, engine.Txn, "delete_rowids", delete_rowids)
    else:
        in_window(monkeypatch, engine.Txn, "insert", insert)
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

    in_window(monkeypatch, repo_mod, "snapshot_diff", fault)
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

    in_window(monkeypatch, repro.core, "gather_payload", fault)
    r = run_small(small_root, "pk_point_reads")
    assert r["correct"] is False
    assert r["check"]["read_rows_wrong"]["value"] > 0


def test_fault_a_read_misses_its_row(small_root, monkeypatch):
    from repro.core import table

    def fault(orig):
        def locate_keys(self, key_lo, key_hi, directory=None):
            return np.zeros(key_lo.shape, np.uint64)
        return locate_keys

    in_window(monkeypatch, table.Table, "locate_keys", fault)
    r = run_small(small_root, "pk_point_reads")
    assert r["correct"] is False
