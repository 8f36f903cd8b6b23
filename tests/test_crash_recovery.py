"""Crash-consistency sweep + durable-format corruption tests (ISSUE 6).

The capstone property: for EVERY registered crash point, killing the
process there and recovering from the durable WAL leaves each logical
operation either fully applied or fully absent — the recovered state is
byte-identical (content digests, registries, timestamp) to one of the
states a clean run passes through — and ``fsck`` reports clean.

Corruption is the second axis: a flipped bit or torn tail in the durable
bytes must surface as a typed error naming the frame/object (CorruptFrame,
TornFrame, StoreVersionError, fsck signature_mismatch), never as pickle
garbage or a silent wrong answer.
"""
import os
import pickle

import numpy as np
import pytest

from conftest import VCS_SCHEMA as SCH
from conftest import kv_batch as _batch
from test_wal_roundtrip import digests

from repro.core import (CorruptFrame, Engine, FaultPlan, InjectedCrash,
                        StoreVersionError, TornFrame, TornTransaction, WAL,
                        compact_objects, fsck, inject, registered)
from repro.core.faults import corrupt_object_bit, flip_bit
from repro.core.wal import MAGIC, STORE_HEADER
from repro.vcs_cli import load_repo, save_repo

import repro.store  # noqa: F401  — registers the store.* crash points

# the engine-level op script exercises these; cli.* seams need a store
# file and store.* seams need a pack directory — both swept separately
# (store.* in tests/test_store_tiers.py)
ENGINE_POINTS = sorted(p for p in registered()
                       if not p.startswith(("cli.", "store.")))
CLI_POINTS = sorted(p for p in registered() if p.startswith("cli."))
STORE_POINTS = sorted(p for p in registered() if p.startswith("store."))


def script(e):
    """The representative op script (seed -> branch -> PR -> publish ->
    revert -> gc). Each yield marks ONE completed logical operation, so
    the state after each yield is a legal all-or-nothing recovery target."""
    e.create_table("t", SCH);                                 yield "create_t"
    e.create_table("u", SCH);                                 yield "create_u"
    e.insert("t", _batch([1, 2, 3, 4, 5]));                   yield "seed_t"
    e.insert("u", _batch([10, 11, 12]));                      yield "seed_u"
    tx = e.begin()
    tx.insert("t", _batch([6]))
    tx.insert("u", _batch([13]))
    tx.commit();                                              yield "multi"
    e.delete_by_keys("t", {"k": np.asarray([5])});            yield "delete"
    e.create_snapshot("s1", "t");                             yield "snap"
    e.create_branch("dev", ["t", "u"]);                       yield "branch"
    e.update_by_keys("dev/t", _batch([2], vals=[7.0]));       yield "mut_dt"
    e.update_by_keys("dev/u", _batch([11], vals=[8.0]));      yield "mut_du"
    pr = e.open_pr("main", "dev");                            yield "open_pr"
    pr.publish();                                             yield "publish"
    pr.revert_publish();                                      yield "rev_pub"
    compact_objects(e, "t", list(e.table("t").directory.data_oids))
    yield "compact"
    s_a = e.current_snapshot("t")
    e.update_by_keys("t", _batch([1], vals=[44.0]));          yield "mut_t"
    e.revert("t", s_a, e.current_snapshot("t"));              yield "revert"
    e.create_table("tmp", SCH);                               yield "mk_tmp"
    e.insert("tmp", _batch([100]));                           yield "seed_tmp"
    e.drop_table("tmp");                                      yield "drop_tmp"
    e.gc();                                                   yield "gc"


@pytest.fixture(scope="module")
def oracle():
    """One clean run: the set of legal recovery states + how many times
    each crash point is hit (armed with a never-tripping plan)."""
    e = Engine()
    plan = FaultPlan({})
    states = [digests(e)]
    with inject(plan):
        for _ in script(e):
            states.append(digests(e))
    return states, dict(plan.hits)


@pytest.mark.parametrize("point", ENGINE_POINTS)
def test_crash_sweep_all_or_nothing(point, oracle):
    """Kill at hit n of `point` for EVERY n the script reaches; recovery
    via WAL replay must land exactly on a clean-run state and fsck clean."""
    states, hits = oracle
    assert hits.get(point, 0) > 0, \
        f"op script never reaches crash point {point!r} — extend it"
    for n in range(1, hits[point] + 1):
        e = Engine()
        tripped = False
        with inject(FaultPlan.at(point, n)) as plan:
            try:
                for _ in script(e):
                    pass
            except InjectedCrash as crash:
                tripped = True
                assert crash.point == point and crash.hit == n
        assert tripped and plan.tripped == point
        recovered = Engine.replay(WAL.deserialize(e.wal.serialize()))
        assert digests(recovered) in states, (
            f"crash at {point} hit {n}: recovered state matches no "
            "clean-run state (partial operation survived)")
        report = fsck(recovered)
        assert report.ok, (point, n, [str(i) for i in report.issues])


@pytest.mark.parametrize("point", ["workspace.publish.planned",
                                   "workspace.publish.pre_log"])
def test_crash_in_checked_publish_recovers_no_publish(point):
    """A publish whose checks' preview plan is committed as it was: a kill
    after planning or before the publish record recovers to no publish."""
    e = Engine()
    e.create_table("t", SCH)
    e.create_table("u", SCH)
    e.insert("t", _batch([1, 2, 3]))
    e.insert("u", _batch([10]))
    e.create_branch("dev", ["t", "u"])
    e.update_by_keys("dev/t", _batch([2], vals=[7.0]))
    e.insert("dev/u", _batch([11]))
    pr = e.open_pr("main", "dev")
    pr.add_check(lambda ctx: ctx.count("t") == 3, "row-count")
    before = digests(e)
    with inject(FaultPlan.at(point)):
        with pytest.raises(InjectedCrash):
            pr.publish()
    assert e.store.metrics.counters["publish.plans_reused"] == 2
    recovered = Engine.replay(WAL.deserialize(e.wal.serialize()))
    assert digests(recovered) == before
    assert recovered.prs[pr.id].status == "open"
    assert fsck(recovered).ok


def test_mid_swing_crash_recovers_whole_transaction():
    """Log-before-swing: by the time the first directory swings, the FULL
    commit group is in the WAL — a mid-swing kill recovers to ALL tables
    committed, never a partial multi-table transaction."""
    e = Engine()
    e.create_table("a", SCH)
    e.create_table("b", SCH)
    tx = e.begin()
    tx.insert("a", _batch([1]))
    tx.insert("b", _batch([2]))
    with inject(FaultPlan.at("engine.commit.mid_swing")):
        with pytest.raises(InjectedCrash):
            tx.commit()
    recovered = Engine.replay(WAL.deserialize(e.wal.serialize()))
    assert recovered.table("a").scan()[0]["k"].tolist() == [1]
    assert recovered.table("b").scan()[0]["k"].tolist() == [2]
    assert fsck(recovered).ok


def test_torn_trailing_commit_group_drops_whole_transaction():
    """A commit group missing records at the END of the log is the torn
    tail of a crash during logging: replay drops the transaction whole
    (from the log too, so re-serialization cannot resurrect half of it)."""
    e = Engine()
    e.create_table("a", SCH)
    e.create_table("b", SCH)
    tx = e.begin()
    tx.insert("a", _batch([1]))
    tx.insert("b", _batch([2]))
    tx.commit()
    w = WAL.deserialize(e.wal.serialize())
    assert w.records[-1].kind == "commit" and w.records[-1].payload["ntab"] == 2
    w.records.pop()                       # tear the group's second record
    recovered = Engine.replay(w)
    assert recovered.table("a").scan()[0]["k"].shape[0] == 0
    assert recovered.table("b").scan()[0]["k"].shape[0] == 0
    assert recovered.ts == 0              # the torn txn's ts is not leaked
    assert w.records[-1].kind == "create_table"  # group gone from the log
    assert fsck(recovered).ok


def test_mid_log_incomplete_group_raises_typed_error():
    """An incomplete group with records AFTER it cannot be crash fallout
    (groups are logged contiguously before any swing): replay refuses with
    TornTransaction instead of guessing."""
    e = Engine()
    e.create_table("a", SCH)
    e.create_table("b", SCH)
    tx = e.begin()
    tx.insert("a", _batch([1]))
    tx.insert("b", _batch([2]))
    tx.commit()
    e.insert("a", _batch([3]))
    w = WAL.deserialize(e.wal.serialize())
    assert w.records[-2].payload["ntab"] == 2
    del w.records[-2]                     # tear a MID-log group
    with pytest.raises(TornTransaction):
        Engine.replay(w)


# --------------------------------------------------------------------------
# durable-format corruption: typed errors, never pickle garbage
# --------------------------------------------------------------------------

def _small_wal():
    e = Engine()
    e.create_table("t", SCH)
    e.insert("t", _batch([1, 2, 3]))
    return e


def test_serialized_wal_bitflip_is_corrupt_frame():
    blob = bytearray(_small_wal().wal.serialize())
    blob[len(STORE_HEADER) + 8 + 40] ^= 0x10    # inside the frame payload
    with pytest.raises(CorruptFrame) as err:
        WAL.deserialize(bytes(blob))
    assert err.value.frame_index == 0           # typed, names the frame


def test_truncated_wal_is_torn_frame():
    blob = _small_wal().wal.serialize()
    with pytest.raises(TornFrame) as err:
        WAL.deserialize(blob[:-3])
    assert len(err.value.tail) > 0
    # ...and cutting into the length/crc prefix itself is still torn
    with pytest.raises(TornFrame):
        WAL.deserialize(blob[:len(STORE_HEADER) + 4])


def test_wrong_store_version_is_typed_with_upgrade_hint():
    blob = bytearray(_small_wal().wal.serialize())
    blob[4] = 99
    with pytest.raises(StoreVersionError, match="version 99"):
        WAL.deserialize(bytes(blob))
    bad_magic = b"NOPE" + bytes(blob[4:])
    with pytest.raises(StoreVersionError, match="bad magic"):
        WAL.deserialize(bad_magic)


def test_legacy_headerless_wal_still_loads():
    e = _small_wal()
    legacy = pickle.dumps(e.wal.records, protocol=pickle.HIGHEST_PROTOCOL)
    assert not legacy.startswith(MAGIC)
    w = WAL.deserialize(legacy)
    assert digests(Engine.replay(w)) == digests(e)


def test_object_bit_rot_is_reported_by_name_and_repairable():
    e = Engine()
    for name in script(e):
        pass
    oid = e.table("t").directory.data_oids[0]
    corrupt_object_bit(e.store.get(oid), row=0, bit=5)
    report = fsck(e)
    kinds = {(i.kind, i.oid) for i in report.issues}
    assert ("signature_mismatch", oid) in kinds   # typed, names the object
    repaired = fsck(e, repair=True, check_replay=False)
    assert oid in repaired.quarantined
    assert repaired.refs_unreachable
    # post-repair the engine is internally consistent again; only the
    # replay check still (correctly) reports divergence from the WAL
    clean = fsck(e, check_replay=False)
    assert clean.ok, [str(i) for i in clean.issues]
    assert {i.kind for i in fsck(e).issues} == {"replay_divergence"}


def test_fsck_flags_missing_object():
    e = _small_wal()
    e.store.delete(e.table("t").directory.data_oids[0])
    report = fsck(e)
    assert any(i.kind == "missing_object" for i in report.issues)


# --------------------------------------------------------------------------
# CLI store: crash points around the frame write/fsync
# --------------------------------------------------------------------------

def _cli_script(repo):
    repo.create_table("t", SCH)
    repo.insert("t", _batch([1, 2, 3]))


def test_cli_mid_frame_crash_recovers_and_preserves_tail(tmp_path, capsys):
    store = str(tmp_path / "s.wal")
    repo = load_repo(store)
    _cli_script(repo)
    save_repo(store, repo)
    pre = digests(repo.engine)
    repo2 = load_repo(store)
    repo2.insert("t", _batch([4, 5]))
    with inject(FaultPlan.at("cli.save.mid_frame")):
        with pytest.raises(InjectedCrash):
            save_repo(store, repo2)
    # the on-disk frame is genuinely torn: recovery = last acked state,
    # torn bytes preserved (never silently discarded), hint printed ONCE
    repo3 = load_repo(store)
    assert digests(repo3.engine) == pre
    assert os.path.getsize(store + ".corrupt") > 0
    assert "torn" in capsys.readouterr().err
    repo3b = load_repo(store)
    assert "torn" not in capsys.readouterr().err   # second load: silent
    assert digests(repo3b.engine) == pre
    # the next WRITE truncates the tail; the store is clean again
    repo3.insert("t", _batch([9]))
    save_repo(store, repo3)
    repo4 = load_repo(store)
    assert sorted(repo4.table("t").scan()[0]["k"].tolist()) == [1, 2, 3, 9]
    assert fsck(repo4.engine).ok


def test_cli_pre_fsync_crash_leaves_complete_frame(tmp_path):
    store = str(tmp_path / "s.wal")
    repo = load_repo(store)
    _cli_script(repo)
    save_repo(store, repo)
    repo2 = load_repo(store)
    repo2.insert("t", _batch([4]))
    post = digests(repo2.engine)
    with inject(FaultPlan.at("cli.save.pre_fsync")):
        with pytest.raises(InjectedCrash):
            save_repo(store, repo2)
    # all bytes written (fsync pending): both outcomes are all-or-nothing;
    # in-process the page cache survives, so the frame is present
    assert digests(load_repo(store).engine) == post


def test_cli_store_bitflip_is_corrupt_frame(tmp_path):
    store = str(tmp_path / "s.wal")
    repo = load_repo(store)
    _cli_script(repo)
    save_repo(store, repo)
    flip_bit(store, os.path.getsize(store) - 10, 2)
    with pytest.raises(CorruptFrame):
        load_repo(store)


def test_cli_legacy_store_upgrades_on_save(tmp_path):
    store = str(tmp_path / "s.wal")
    e = _small_wal()
    with open(store, "wb") as f:          # pre-ISSUE-6 headerless format
        pickle.dump(e.wal.records, f, protocol=pickle.HIGHEST_PROTOCOL)
    repo = load_repo(store)               # one-shot legacy path
    assert digests(repo.engine) == digests(e)
    repo.insert("t", _batch([7]))
    save_repo(store, repo)                # rewrites in the framed format
    with open(store, "rb") as f:
        assert f.read(4) == MAGIC
    repo2 = load_repo(store)
    assert sorted(repo2.table("t").scan()[0]["k"].tolist()) == [1, 2, 3, 7]
    assert fsck(repo2.engine).ok


# --------------------------------------------------------------------------
# fault-plan mechanics
# --------------------------------------------------------------------------

def test_fault_plan_validates_and_counts():
    with pytest.raises(KeyError):
        FaultPlan.at("no.such.point")
    with pytest.raises(ValueError):
        FaultPlan.at("wal.append", 0)
    e = Engine()
    with inject(FaultPlan.at("wal.append", 2)) as plan:
        e.create_table("t", SCH)          # hit 1 — survives
        with pytest.raises(InjectedCrash):
            e.create_table("u", SCH)      # hit 2 — trips
        with pytest.raises(RuntimeError):
            with inject(FaultPlan({})):   # no nesting
                pass
    assert plan.hits["wal.append"] == 2 and plan.tripped == "wal.append"
    e2 = Engine()
    e2.create_table("t", SCH)             # disarmed again: no-op
