"""Operation telemetry suite (ISSUE 8).

Pins the four contracts of ``core.telemetry``:

* the registry — span/metric names register once, idempotently, and the
  ``stats_json`` key set is a golden schema (bump ``STATS_SCHEMA`` on any
  change);
* the tracer — spans nest with the call stack, record counter deltas,
  and the ARMED tree for the branch -> PR -> publish -> revert workflow
  is pinned by name and nesting;
* derived-state only — a replayed engine reports a clean registry and no
  armed tracer (traces never survive recovery);
* the exports — EXPLAIN renders zero-valued invariants
  (``commit.rows_rehashed=0``), the Chrome-tracing file is schema-stable
  JSON, and the CLI surfaces (``stats --format json``, ``--trace``) work
  end to end.  Plus a coarse smoke bound on armed overhead.
"""
import json
from time import perf_counter

import numpy as np
import pytest

from repro.core import Engine, Repo, snapshot_diff
from repro.core import telemetry
from repro.core.statements import execute

from conftest import VCS_SCHEMA, kv_batch

#: the golden ``datagit stats`` key set — a rename or addition is a schema
#: change: update this list AND bump telemetry.STATS_SCHEMA together
PINNED_METRICS = [
    "cache.delta_hits",
    "commit.apply_sort_merged",
    "commit.apply_sort_skipped",
    "commit.apply_sorts",
    "commit.lob_rows_hashed",
    "commit.rows_carried",
    "commit.rows_rehashed",
    "delta.bytes_scanned",
    "delta.objects_scanned",
    "delta.objects_skipped_shared",
    "delta.rows_scanned",
    "gc.objects_freed",
    "gc.pinned_horizons",
    "gc.versions_pruned",
    "index.aux_rows",
    "index.lookup_values",
    "ops.ranksum_calls",
    "ops.ranksum_runs",
    "probe.expansions",
    "probe.hits",
    "probe.objects_probed",
    "probe.objects_pruned",
    "probe.queries",
    "probe.shard_parts",
    "publish.plans_replanned",
    "publish.plans_reused",
    "store.bytes_packed",
    "store.evictions",
    "store.faults",
    "store.hits",
    "store.objects_pulled",
    "store.objects_pushed",
    "store.spills",
    "vis.builds",
    "vis.derives",
    "vis.extends",
    "vis.hits",
    "wal.bytes",
    "wal.frames",
    "wal.fsyncs",
    "xfer.h2d_bytes",
]


def _mk_repo(rows=1000):
    repo = Repo()
    repo.engine.create_table("t", VCS_SCHEMA)
    tx = repo.engine.begin()
    tx.insert("t", kv_batch(range(rows)))
    tx.commit()
    return repo


def _names(spans):
    return [s.name for s in spans]


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def test_registry_idempotent_and_conflicting():
    # same doc re-registers as a no-op (module reimport)...
    assert telemetry.register_span("diff", telemetry.registered_spans()
                                   ["diff"]) == "diff"
    n = len(telemetry.registered_spans())
    telemetry.register_span("diff", telemetry.registered_spans()["diff"])
    assert len(telemetry.registered_spans()) == n
    # ...a different doc is a bug
    with pytest.raises(ValueError):
        telemetry.register_span("diff", "something else entirely")
    with pytest.raises(ValueError):
        telemetry.register_metric("vis.builds", "something else entirely")


def test_disarmed_span_is_the_null_singleton():
    assert telemetry.current() is None
    s = telemetry.span("diff")
    assert s is telemetry._NULL
    assert telemetry.span("publish") is s          # one object, ever
    with s:
        pass                                       # and it is a no-op CM


def test_armed_span_must_be_registered():
    with telemetry.trace(None):
        with pytest.raises(KeyError):
            telemetry.span("never.registered")


def test_trace_does_not_nest():
    with telemetry.trace(None):
        with pytest.raises(RuntimeError):
            with telemetry.trace(None):
                pass
    assert telemetry.current() is None             # cleared on exit


def test_stats_json_golden_schema():
    repo = _mk_repo()
    doc = telemetry.stats_json(repo.engine)
    assert set(doc) == {"schema", "metrics"}
    assert doc["schema"] == telemetry.STATS_SCHEMA == 7
    assert list(doc["metrics"]) == PINNED_METRICS  # sorted AND complete
    # engine=None (CLI arms before the store loads): same keys, all zero
    empty = telemetry.stats_json(None)
    assert list(empty["metrics"]) == PINNED_METRICS
    assert not any(empty["metrics"].values())
    json.dumps(doc)                                # round-trippable


# --------------------------------------------------------------------------
# span trees
# --------------------------------------------------------------------------

def test_cold_diff_span_tree():
    repo = _mk_repo()
    e = repo.engine
    sn1 = e.create_snapshot("s1", "t")
    tx = e.begin()
    tx.update_by_keys("t", kv_batch(range(100), vals=np.arange(100) * 2.0))
    tx.commit()
    sn2 = e.create_snapshot("s2", "t")
    # cold everything: a fresh process would have empty caches
    e.store.vis_cache.clear()
    if e.store.delta_cache is not None:
        e.store.delta_cache.clear()
    with repo.trace() as t:
        repo.diff("snap:s1", "snap:s2", table="t")
    assert _names(t.roots) == ["diff"]
    (diff,) = t.roots
    assert _names(diff.children) == ["signed_delta"]
    (sd,) = diff.children
    assert set(_names(sd.children)) == {"visibility.build"}
    assert sd.counters["vis.builds"] >= 1
    assert diff.counters["delta.rows_scanned"] > 0
    assert diff.dur_s > 0 and sd.t0_rel >= diff.t0_rel


def test_workflow_e2e_span_tree():
    repo = _mk_repo()
    repo.branch("dev", ["t"])
    with repo.trace() as t:
        tx = repo.engine.begin()
        tx.insert("dev/t", kv_batch(range(1000, 1100)))
        tx.commit()
        pr = repo.open_pr("dev")
        repo.publish(pr.id)
        repo.revert_pr(pr.id)
    # pinned by name AND nesting: the mutation commit, then publish with
    # its per-table plan -> commit(seal, swing), then the inverse-Δ revert
    assert _names(t.roots) == ["commit", "publish", "revert_publish"]
    commit, publish, revert = t.roots
    assert _names(commit.children) == ["commit.seal", "commit.swing"]
    assert _names(publish.children) == ["plan_merge", "commit"]
    plan, pcommit = publish.children
    assert set(_names(plan.children)) == {"signed_delta", "gather_payload"}
    assert _names(pcommit.children) == ["commit.seal", "commit.swing"]
    assert pcommit.counters["commit.rows_carried"] > 0
    assert pcommit.counters.get("commit.rows_rehashed", 0) == 0
    assert "commit" in _names(revert.children)
    assert "signed_delta" in _names(revert.children)


def test_gc_span_and_gauge():
    repo = _mk_repo()
    e = repo.engine
    tx = e.begin()
    tx.update_by_keys("t", kv_batch(range(10), vals=np.arange(10) * 3.0))
    tx.commit()
    with repo.trace() as t:
        e.gc()
    (g,) = t.roots
    assert g.name == "gc"
    stats = repo.stats()
    assert stats["gc.pinned_horizons"] == e.gc().pinned_horizons  # gauge


# --------------------------------------------------------------------------
# derived state only: replay comes back clean
# --------------------------------------------------------------------------

def test_replayed_engine_reports_clean_metrics():
    repo = _mk_repo()
    e = repo.engine
    tx = e.begin()
    tx.update_by_keys("t", kv_batch(range(50), vals=np.arange(50) * 2.0))
    tx.commit()
    e.create_snapshot("s", "t")
    repo.diff("snap:s", "HEAD", table="t")        # accumulate counters
    assert any(telemetry.metrics_snapshot(e).values())
    e2 = Engine.replay(e.wal)
    snap = telemetry.metrics_snapshot(e2)
    assert sorted(snap) == PINNED_METRICS
    assert not any(snap.values()), {k: v for k, v in snap.items() if v}
    assert telemetry.current() is None            # no tracer leaked


# --------------------------------------------------------------------------
# surfaces: status / statements / EXPLAIN
# --------------------------------------------------------------------------

def test_repo_status_and_statement_carry_metrics():
    repo = _mk_repo()
    st = repo.status()
    assert list(st["metrics"]) == PINNED_METRICS
    assert st["metrics"]["wal.frames"] == repo.stats()["wal.frames"]
    msg = execute(repo, "STATUS").message
    assert "metric wal.frames=" in msg
    res = execute(repo, "STATS")
    assert res.kind == "stats"
    assert res.data == telemetry.stats_json(repo.engine)
    assert any(line.startswith("wal.frames=") for line
               in res.message.splitlines())


def test_explain_merge_shows_zero_rehash():
    repo = _mk_repo()
    repo.branch("dev", ["t"])
    tx = repo.engine.begin()
    tx.insert("dev/t", kv_batch(range(1000, 1200)))
    tx.commit()
    res = execute(repo, "EXPLAIN MERGE BRANCH dev INTO main")
    # the span tree renders merge -> plan_merge and the seal counters make
    # the zero-rehash invariant VISIBLE (group expansion prints the zero)
    assert res.kind == "explain"
    assert "merge" in res.message and "plan_merge" in res.message
    assert "commit.rows_rehashed=0" in res.message
    assert "commit.rows_carried=200" in res.message


def test_explain_warm_diff_shows_zero_builds():
    repo = _mk_repo()
    e = repo.engine
    e.create_snapshot("s1", "t")
    tx = e.begin()
    tx.update_by_keys("t", kv_batch(range(20), vals=np.arange(20) * 2.0))
    tx.commit()
    e.create_snapshot("s2", "t")
    repo.diff("snap:s1", "snap:s2", table="t")    # warm the vis cache
    tx = e.begin()
    tx.update_by_keys("t", kv_batch(range(5), vals=np.arange(5) * 7.0))
    tx.commit()
    e.create_snapshot("s3", "t")
    # delta cache misses (new pair) but visibility stays warm: the vis
    # group is touched, so its zero build count is printed, not omitted
    res = execute(repo, "EXPLAIN DIFF 'snap:s1' AGAINST 'snap:s3' "
                        "FOR TABLE t")
    assert "vis.builds=0" in res.message
    assert "signed_delta" in res.message


def test_explain_unknown_verb_suggests():
    repo = _mk_repo()
    from repro.core.statements import StatementError
    with pytest.raises(StatementError):
        execute(repo, "EXPLAIN EXPLAIN STATUS")
    with pytest.raises(StatementError):
        execute(repo, "EXPLAIN FROBNICATE")


def test_explain_nests_under_an_armed_tracer():
    repo = _mk_repo()
    with repo.trace() as t:
        res = execute(repo, "EXPLAIN STATS")
    assert res.kind == "explain"
    assert "explain" in _names(t.roots)           # no second tracer armed


# --------------------------------------------------------------------------
# chrome-tracing export + CLI surfaces
# --------------------------------------------------------------------------

def test_chrome_trace_schema(tmp_path):
    repo = _mk_repo()
    e = repo.engine
    e.create_snapshot("s", "t")
    with repo.trace() as t:
        repo.diff("snap:s", "HEAD", table="t")
    out = tmp_path / "trace.json"
    telemetry.write_chrome_trace(str(out), t)
    events = json.loads(out.read_text())
    assert events, "no events exported"
    for ev in events:
        assert set(ev) == {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                           "args"}
        assert ev["ph"] == "X" and ev["cat"] == "datagit"
        assert ev["dur"] >= 0 and ev["ts"] >= 0
    # one event per line: line-splittable for streaming consumers
    lines = out.read_text().splitlines()
    assert lines[0] == "[" and lines[-1] == "]"
    assert len(lines) == len(events) + 2


def test_cli_stats_and_trace(tmp_path, capsys):
    from repro.vcs_cli import main
    store = str(tmp_path / "s.wal")

    def dg(*a):
        rc = main(["--store", store, *a])
        out = capsys.readouterr().out
        assert rc == 0, out
        return out

    dg("init")
    dg("seed", "t", "--rows", "200")
    doc = json.loads(dg("stats", "--format", "json"))
    assert doc["schema"] == telemetry.STATS_SCHEMA
    assert list(doc["metrics"]) == PINNED_METRICS
    text = dg("stats")
    assert any(ln.startswith("wal.frames=") for ln in text.splitlines())

    trace = tmp_path / "out.jsonl"
    dg("--trace", str(trace), "seed", "u", "--rows", "100")
    events = json.loads(trace.read_text())
    names = [ev["name"] for ev in events]
    assert names[0] == "cli.seed"                 # the invocation root
    assert "replay" in names                      # armed before load
    assert "commit" in names
    for ev in events:
        assert set(ev) == {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                           "args"}


# --------------------------------------------------------------------------
# armed overhead smoke (the REAL parity gate is the interleaved A/B bench
# against the previous HEAD — this catches only gross regressions)
# --------------------------------------------------------------------------

def test_tracer_armed_overhead_smoke():
    repo = _mk_repo(rows=60_000)
    e = repo.engine
    a = e.create_snapshot("s1", "t")
    tx = e.begin()
    tx.update_by_keys("t", kv_batch(range(5000),
                                    vals=np.arange(5000) * 2.0))
    tx.commit()
    b = e.create_snapshot("s2", "t")

    def once():
        # cold every rep so both sides do identical full work
        e.store.vis_cache.clear()
        if e.store.delta_cache is not None:
            e.store.delta_cache.clear()
        t0 = perf_counter()
        snapshot_diff(e.store, a, b)
        return perf_counter() - t0

    once()                                        # warm numpy/allocator
    disarmed, armed = [], []
    for _ in range(5):                            # interleaved, min-fold
        disarmed.append(once())
        with telemetry.trace(e):
            armed.append(once())
    assert min(armed) <= min(disarmed) * 1.3, (min(armed), min(disarmed))
