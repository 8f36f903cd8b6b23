"""Workflow porcelain e2e (ISSUE 3): branch refs, PRs, CI gating, atomic
publish, Δ-based revert, and GC pin semantics."""
import numpy as np
import pytest

from conftest import VCS_SCHEMA as SCH
from conftest import VCS_SCHEMA_NOPK as SCH_NOPK
from conftest import content_digest as digest
from conftest import kv_batch as _batch
from repro.core import (ConflictMode, Engine, GCStats, MergeConflictError,
                        PKViolation, PublishBlocked, RevertConflict, WAL,
                        snapshot_diff)


def mk_engine(nopk=False):
    e = Engine()
    e.create_table("t", SCH_NOPK if nopk else SCH)
    e.create_table("u", SCH)
    e.insert("t", _batch([1, 2, 3]))
    e.insert("u", _batch([10, 20]))
    return e


# ------------------------------------------------------------- branch refs

def test_branch_is_metadata_only_and_namespaced():
    e = mk_engine()
    bytes_before = e.store.bytes_written
    br = e.create_branch("dev", ["t", "u"])
    assert e.store.bytes_written == bytes_before      # zero data copied
    assert br.tables == {"t": "dev/t", "u": "dev/u"}
    assert set(br.base) == {"t", "u"}
    assert [b.name for b in e.list_branches()] == ["dev"]
    # branch isolation both ways
    e.insert("dev/t", _batch([4]))
    e.delete_by_keys("t", {"k": np.asarray([1])})
    assert e.table("dev/t").count() == 4
    assert e.table("t").count() == 2
    e.drop_branch("dev")
    assert "dev/t" not in e.tables and "dev/u" not in e.tables
    assert e.list_branches() == []


def test_branch_from_branch_and_name_validation():
    e = mk_engine()
    e.create_branch("dev", ["t"])
    e.insert("dev/t", _batch([4]))
    br2 = e.create_branch("dev2", ["t"], from_ref="dev")
    assert br2.parent == "dev"
    assert e.table("dev2/t").count() == 4
    with pytest.raises(ValueError):
        e.create_branch("dev", ["t"])         # exists
    with pytest.raises(ValueError):
        e.create_branch("main", ["t"])        # reserved
    with pytest.raises(ValueError):
        e.create_branch("a/b", ["t"])         # namespace separator
    with pytest.raises(KeyError):
        e.create_branch("x", ["missing"])


def test_list_snapshots():
    e = mk_engine()
    e.create_snapshot("s1", "t")
    e.create_snapshot("s2", "u")
    rows = e.list_snapshots()
    assert [r[0] for r in rows] == ["s1", "s2"]
    assert rows[0][1] == "t"


# ------------------------------------------------------ PR review surfaces

def test_pr_diff_pins_base_horizon():
    e = mk_engine()
    e.create_branch("dev", ["t"])
    e.update_by_keys("dev/t", _batch([2], vals=[99.0]))
    pr = e.open_pr("main", "dev")
    d1 = pr.diff()["t"].n_groups
    # base moves AFTER open: the review diff must not shift
    e.insert("t", _batch([7]))
    assert pr.diff()["t"].n_groups == d1 == 2
    # second review round hits the delta cache
    d = pr.diff()["t"]
    assert d.stats.delta_cache_hits >= 1


def test_dry_run_merge_reports_conflicts_without_mutation():
    e = mk_engine()
    e.create_branch("dev", ["t"])
    e.update_by_keys("dev/t", _batch([2], vals=[99.0]))
    e.update_by_keys("t", _batch([2], vals=[55.0]))    # conflicting base edit
    pr = e.open_pr("main", "dev")
    before = digest(e, "t"), digest(e, "dev/t")
    wal_len = len(e.wal)
    oids = set(e.store.oids())
    rep = pr.dry_run_merge()["t"]
    assert rep.true_conflicts == 1
    assert rep.commit_ts is None
    assert (digest(e, "t"), digest(e, "dev/t")) == before
    assert len(e.wal) == wal_len and set(e.store.oids()) == oids


# ------------------------------------------------- CI checks gate publish

def test_failing_check_blocks_publish_then_fix_publishes():
    e = mk_engine()
    e.create_branch("dev", ["t", "u"])
    e.update_by_keys("dev/t", _batch([2], vals=[999.0]))
    e.insert("dev/u", _batch([30]))
    pr = e.open_pr("main", "dev")
    pr.add_check(lambda ctx: bool((ctx.scan("t")[0]["v"] < 100).all()),
                 "v-limit")
    before = digest(e, "t"), digest(e, "u")
    ts0, oids0 = e.ts, set(e.store.oids())
    with pytest.raises(PublishBlocked) as exc:
        pr.publish()
    assert [c.name for c in exc.value.checks if not c.ok] == ["v-limit"]
    # blocked publish left EVERYTHING untouched: state, ts, store, WAL
    assert (digest(e, "t"), digest(e, "u")) == before
    assert e.ts == ts0 and set(e.store.oids()) == oids0
    assert pr.status == "open"
    # fix on the branch -> checks pass -> atomic publish
    e.update_by_keys("dev/t", _batch([2], vals=[42.0]))
    reports = pr.publish()
    assert pr.status == "published"
    assert pr.publish_ts is not None
    # every table landed at ONE commit timestamp
    assert e.table("t").directory.ts == pr.publish_ts
    assert e.table("u").directory.ts == pr.publish_ts
    assert reports["t"].commit_ts == reports["u"].commit_ts == pr.publish_ts
    assert sorted(e.table("u").scan()[0]["k"].tolist()) == [10, 20, 30]
    assert 42.0 in e.table("t").scan()[0]["v"].tolist()


def test_check_exception_is_a_failure_and_preview_is_ephemeral():
    e = mk_engine()
    e.create_branch("dev", ["t"])
    e.insert("dev/t", _batch([4]))
    pr = e.open_pr("main", "dev")

    def boom(ctx):
        raise RuntimeError("bad data")

    pr.add_check(boom)
    seen = {}

    def peek(ctx):
        seen["count"] = ctx.count("t")
        return True

    pr.add_check(peek, "peek")
    results = pr.run_checks()
    assert [r.ok for r in results] == [False, True]
    assert "RuntimeError" in results[0].error
    # the check saw the MERGED preview (3 base rows + 1 branch row) ...
    assert seen["count"] == 4
    # ... but the preview never escaped: ts, oid counter, WAL all clean
    assert e.table("t").count() == 3
    assert e.ts == Engine.replay(
        WAL.deserialize(e.wal.serialize())).ts


def test_device_error_in_check_propagates():
    """A device failure inside a check is an error, not a failed check:
    it reaches the caller, and the preview is still cleaned up."""
    import jax
    e = mk_engine()
    e.create_branch("dev", ["t"])
    e.insert("dev/t", _batch([4]))
    pr = e.open_pr("main", "dev")
    oids = set(e.store.oids())

    def device_fault(ctx):
        raise jax.errors.JaxRuntimeError("INTERNAL: device halted")

    pr.add_check(device_fault, "device")
    with pytest.raises(jax.errors.JaxRuntimeError):
        pr.run_checks()
    with pytest.raises(jax.errors.JaxRuntimeError):
        pr.publish()
    assert set(e.store.oids()) == oids and pr.status == "open"
    assert e.table("t").count() == 3


# -------------------------------------------------- publish atomicity

def test_conflict_in_second_table_unwinds_whole_publish():
    e = mk_engine()
    e.create_branch("dev", ["t", "u"])
    e.insert("dev/t", _batch([4]))                        # clean change
    e.update_by_keys("dev/u", _batch([10], vals=[1.0]))   # will conflict
    e.update_by_keys("u", _batch([10], vals=[2.0]))       # divergent base
    pr = e.open_pr("main", "dev")
    before = digest(e, "t"), digest(e, "u")
    ts0 = e.ts
    with pytest.raises(MergeConflictError):
        pr.publish(mode=ConflictMode.FAIL)
    # the clean table did NOT land: all-or-nothing
    assert (digest(e, "t"), digest(e, "u")) == before
    assert e.ts == ts0
    assert pr.status == "open"
    # force-resolve and the same PR publishes atomically
    reports = pr.publish(mode=ConflictMode.ACCEPT)
    assert reports["u"].true_conflicts == 1
    assert e.table("t").directory.ts == e.table("u").directory.ts


def test_publish_conflict_raises_merge_error_even_with_checks():
    """The exception type for a conflict must not depend on whether CI
    checks happen to be registered."""
    e = mk_engine()
    e.create_branch("dev", ["t"])
    e.update_by_keys("dev/t", _batch([2], vals=[9.0]))
    e.update_by_keys("t", _batch([2], vals=[5.0]))     # divergent base
    pr = e.open_pr("main", "dev")
    pr.add_check(lambda ctx: True, "always-green")
    with pytest.raises(MergeConflictError) as exc:
        pr.publish(mode=ConflictMode.FAIL)
    assert exc.value.report.true_conflicts == 1
    assert pr.status == "open"


def test_user_check_named_merge_still_gates_publish():
    """A user check whose name collides with the synthetic preview-conflict
    sentinel must still block publish (structural flag, not name match)."""
    e = mk_engine()
    e.create_branch("dev", ["t"])
    e.insert("dev/t", _batch([4]))
    pr = e.open_pr("main", "dev")

    def merge(ctx):          # fn.__name__ == "merge"
        return False

    pr.add_check(merge)
    with pytest.raises(PublishBlocked):
        pr.publish()
    assert pr.status == "open"
    assert e.table("t").count() == 3


def test_multi_table_commit_unwinds_on_seal_failure():
    """Engine-level atomicity: a PK violation in the second table of one
    transaction leaves the first table untouched and no sealed garbage."""
    e = mk_engine()
    oids0 = set(e.store.oids())
    d_t = e.table("t").directory
    tx = e.begin()
    tx.insert("t", _batch([100]))
    tx.insert("u", _batch([10]))       # duplicate PK in "u"
    with pytest.raises(PKViolation):
        tx.commit()
    assert e.table("t").directory is d_t
    assert e.table("t").count() == 3
    assert set(e.store.oids()) == oids0


# ------------------------------------------------------- Δ-based revert

@pytest.mark.parametrize("nopk", [False, True])
def test_revert_publish_restores_base_and_preserves_history(nopk):
    e = mk_engine(nopk=nopk)
    e.create_branch("dev", ["t", "u"])
    if nopk:
        t = e.table("dev/t")
        _, rowids = t.scan()
        tx = e.begin()
        tx.delete_rowids("dev/t", rowids[:1])
        tx.insert("dev/t", _batch([8, 8], vals=[7.0, 7.0],
                                  docs=[b"x", b"x"]))
        tx.commit()
    else:
        e.update_by_keys("dev/t", _batch([2], vals=[99.0]))
        e.delete_by_keys("dev/t", {"k": np.asarray([3])})
        e.insert("dev/t", _batch([8]))
    e.insert("dev/u", _batch([30]))
    pr = e.open_pr("main", "dev")
    pre = digest(e, "t"), digest(e, "u")
    history_len = len(e.table("t").history)
    pr.publish()
    post = digest(e, "t"), digest(e, "u")
    assert post != pre
    ts_rev = pr.revert_publish()
    assert pr.status == "reverted"
    # base is byte-identical to the pre-publish state ...
    assert (digest(e, "t"), digest(e, "u")) == pre
    # ... via NEW commits, not a head rewrite: history grew monotonically
    # and the published state is still reachable through PITR
    assert ts_rev > pr.publish_ts
    assert len(e.table("t").history) > history_len
    # published state differs from reverted head at the PITR horizon
    snap = e.snapshot_at("t", pr.publish_ts)
    assert snapshot_diff(e.store, snap, e.current_snapshot("t")).n_groups > 0


def test_engine_revert_is_delta_sized_and_strict():
    e = Engine()
    e.create_table("t", SCH)
    e.insert("t", _batch(np.arange(1000)))
    s1 = e.current_snapshot("t")
    e.update_by_keys("t", _batch([5], vals=[99.0]))
    s2 = e.current_snapshot("t")
    # Δ-sized: the revert scans the changed rows, not the 1000-row table
    ts = e.revert("t", s1, s2)
    assert ts == e.ts
    assert digest_equal(e, s1)
    # inverse of an empty delta is a no-op (no commit)
    s3 = e.current_snapshot("t")
    assert e.revert("t", s3, s3) is None
    # strictness: if the key moved on since, the revert conflicts
    e.update_by_keys("t", _batch([5], vals=[99.0]))
    s4 = e.current_snapshot("t")
    e.update_by_keys("t", _batch([5], vals=[123.0]))   # concurrent edit
    with pytest.raises(RevertConflict):
        e.revert("t", s3, s4)


def digest_equal(e, snap):
    _, _, lo, hi = e.table(snap.table).scan(with_sigs=True)
    _, _, lo2, hi2 = e.table(snap.table).scan(snap.directory,
                                              with_sigs=True)
    o, o2 = np.lexsort((hi, lo)), np.lexsort((hi2, lo2))
    return (np.array_equal(lo[o], lo2[o2])
            and np.array_equal(hi[o], hi2[o2]))


def test_revert_conflict_on_retaken_key():
    e = Engine()
    e.create_table("t", SCH)
    e.insert("t", _batch([1, 2]))
    s1 = e.current_snapshot("t")
    e.delete_by_keys("t", {"k": np.asarray([2])})
    s2 = e.current_snapshot("t")
    e.insert("t", _batch([2], vals=[77.0]))     # key re-taken since
    with pytest.raises(RevertConflict):
        e.revert("t", s1, s2)


# ----------------------------------------------------------- GC pinning

def test_gc_honors_pr_pinned_horizons():
    e = Engine(retention_versions=1)
    e.create_table("t", SCH)
    e.insert("t", _batch([1, 2, 3]))
    e.create_branch("dev", ["t"])
    e.update_by_keys("dev/t", _batch([2], vals=[9.0]))
    pr = e.open_pr("main", "dev")
    pin_ts = pr.base_pins["t"].created_ts
    # base churns past the retention window
    for i in range(5):
        e.update_by_keys("t", _batch([1], vals=[float(i)]))
    stats = e.gc()
    assert isinstance(stats, GCStats)
    assert stats.pinned_horizons >= 1
    assert stats.versions_pruned > 0
    # the pinned horizon is still resolvable AND scannable after GC
    d = e.table("t").directory_at(pin_ts)
    batch, _ = e.table("t").scan(d)
    assert sorted(batch["k"].tolist()) == [1, 2, 3]
    # review + publish still work after GC
    assert pr.diff()["t"].n_groups == 2
    pr.publish(mode=ConflictMode.ACCEPT)
    # once the PR is done and the branch dropped, the pin is released
    pr.close()
    e.drop_branch("dev")
    e.gc()
    assert e.table("t").count() == 3


def test_gc_keeps_published_pr_revertible():
    e = Engine(retention_versions=1)
    e.create_table("t", SCH)
    e.insert("t", _batch([1, 2, 3]))
    e.create_branch("dev", ["t"])
    e.update_by_keys("dev/t", _batch([2], vals=[9.0]))
    pr = e.open_pr("main", "dev")
    pre = digest(e, "t")
    pr.publish()
    e.gc()                       # published PR pins pre/post states
    pr.revert_publish()
    assert digest(e, "t") == pre


def test_gc_retention_zero_keeps_all_history():
    """Engine(retention_versions=0) has always meant 'retain everything'
    (history[-0:] == the whole list) — trim_history must preserve that."""
    e = Engine(retention_versions=0)
    e.create_table("t", SCH)
    e.insert("t", _batch([1]))
    ts1 = e.ts
    for i in range(5):
        e.insert("t", _batch([10 + i]))
    e.gc()
    d = e.table("t").directory_at(ts1)
    batch, _ = e.table("t").scan(d)
    assert batch["k"].tolist() == [1]


def test_drop_branch_refused_while_pr_live():
    e = mk_engine()
    e.create_branch("dev", ["t"])
    pr = e.open_pr("main", "dev")
    with pytest.raises(ValueError):
        e.drop_branch("dev")                 # open PR holds the branch
    e.insert("dev/t", _batch([4]))
    pr.publish()
    with pytest.raises(ValueError):
        e.drop_branch("dev")                 # published PR must stay
    #                                          revertible until closed
    pr.close()
    e.drop_branch("dev")
    assert pr.status == "closed"


def test_noop_publish_and_revert_replay():
    """A PR with no changes publishes (ts=None), reverts as a no-op, and
    the WAL still replays cleanly."""
    e = mk_engine()
    e.create_branch("dev", ["t"])
    pr = e.open_pr("main", "dev")
    reports = pr.publish()
    assert pr.publish_ts is None
    assert reports["t"].inserted == reports["t"].deleted == 0
    assert pr.revert_publish() is None
    e2 = Engine.replay(WAL.deserialize(e.wal.serialize()))
    assert e2.ts == e.ts
    assert digest(e, "t") == digest(e2, "t")


# ------------------------------------------------------------- e2e + WAL

def test_full_workflow_e2e_wal_replay():
    """branch -> mutate -> PR -> blocked -> fix -> atomic publish -> revert,
    then the WAL replays to an identical engine."""
    e = mk_engine()
    e.create_branch("dev", ["t", "u"])
    e.update_by_keys("dev/t", _batch([2], vals=[999.0]))
    e.insert("dev/u", _batch([30]))
    pr = e.open_pr("main", "dev")
    pr.add_check(lambda ctx: bool((ctx.scan("t")[0]["v"] < 100).all()))
    with pytest.raises(PublishBlocked):
        pr.publish()
    e.update_by_keys("dev/t", _batch([2], vals=[42.0]))
    pr.publish()
    pr.revert_publish()
    e.drop_branch("dev")

    e2 = Engine.replay(WAL.deserialize(e.wal.serialize()))
    assert e2.ts == e.ts
    assert set(e2.tables) == set(e.tables)
    for tbl in e.tables:
        assert digest(e, tbl) == digest(e2, tbl), tbl
    assert set(e2.branches) == set(e.branches) == set()
    assert {i: p.status for i, p in e2.prs.items()} == \
        {i: p.status for i, p in e.prs.items()}
