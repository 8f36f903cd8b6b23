"""ISSUE 2 regression + property tests.

Covers the sort-free Δ pipeline (k-way merge of presorted runs vs. the
``np.lexsort`` oracle, including crafted lo64-collision signatures) and the
four bugfix satellites: WAL replay of ``clone(with_indices=...)``,
snapshot-consistent index cloning, ``drop_table`` index cleanup, and
conflict-key reporting in non-FAIL merge modes.
"""
import numpy as np
import pytest

try:  # property tests run under hypothesis when present; the deterministic
    # seeded oracle tests below run everywhere (the CI container lacks it)
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

    def given(*a, **k):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*a, **k):
        return lambda f: f

from repro.core import (Column, ConflictMode, CType, Engine, Schema,
                        three_way_merge)
from repro.core import telemetry
from repro.core.delta import SignedStream
from repro.core.indices import create_index, lookup_eq
from repro.core.sigs import key_sigs_for_lookup
from repro.kernels import ops

SCH = Schema((Column("id", CType.I64), Column("cat", CType.I32),
              Column("val", CType.F64)), primary_key=("id",))
SCH_NOPK = Schema(SCH.columns, primary_key=None)


# ===================================================== k-way merge property

def _oracle(lo, hi):
    return np.lexsort((hi, lo))


# runs of sorted (lo, hi) pairs; small value domains force duplicates and
# cross-run ties so stability is actually exercised
if HAVE_HYPOTHESIS:
    _pair = st.tuples(st.integers(0, 7), st.integers(0, 3))
    _run = st.lists(_pair, max_size=12).map(sorted)
    _runs = st.lists(_run, min_size=1, max_size=6)
else:  # pragma: no cover - @given is a skip marker; value never sampled
    _runs = None


def _random_runs(rng, k, n, lo_dom, hi_dom):
    """Deterministic stand-in for the hypothesis strategy."""
    out = []
    for _ in range(k):
        m = int(rng.integers(0, n + 1))
        lo = rng.integers(0, lo_dom, m).astype(np.uint64)
        hi = rng.integers(0, hi_dom, m).astype(np.uint64)
        o = np.lexsort((hi, lo))
        out.append(list(zip(lo[o].tolist(), hi[o].tolist())))
    return out


def _flatten(runs):
    starts, lo, hi = [], [], []
    for r in runs:
        starts.append(len(lo))
        lo.extend(p[0] for p in r)
        hi.extend(p[1] for p in r)
    return (np.asarray(lo, np.uint64), np.asarray(hi, np.uint64),
            np.asarray(starts, np.int64))


@settings(max_examples=200, deadline=None)
@given(_runs)
def test_merge128_runs_matches_lexsort_oracle(runs):
    lo, hi, starts = _flatten(runs)
    order = ops.merge128_runs(lo, hi, starts)
    want = _oracle(lo, hi)
    np.testing.assert_array_equal(order, want)


@settings(max_examples=200, deadline=None)
@given(_runs)
def test_ranksum_merge_matches_lexsort_oracle(runs):
    # the Pallas-backend searchsorted rank-sum path, exercised directly
    # (merge128_runs dispatches it only on the kernel backend)
    lo, hi, starts = _flatten(runs)
    if lo.shape[0] == 0:
        return
    order = ops._merge128_ranksum(lo, hi, starts)
    np.testing.assert_array_equal(order, _oracle(lo, hi))


@pytest.mark.parametrize("seed", range(8))
def test_kway_merge_matches_oracle_seeded(seed):
    """Deterministic k-way-merge-vs-lexsort oracle sweep (runs without
    hypothesis): varied run counts/sizes, tie-heavy domains, both the
    dispatching entry point and the rank-sum kernel path, plus stream
    concat + merge_by_key round-trip."""
    rng = np.random.default_rng([seed] + list(b"KWAY"))
    runs = _random_runs(rng, k=int(rng.integers(1, 9)),
                        n=int(rng.integers(1, 64)),
                        lo_dom=int(rng.integers(2, 32)),
                        hi_dom=int(rng.integers(2, 8)))
    lo, hi, starts = _flatten(runs)
    want = _oracle(lo, hi)
    np.testing.assert_array_equal(ops.merge128_runs(lo, hi, starts), want)
    if lo.shape[0]:
        np.testing.assert_array_equal(
            ops._merge128_ranksum(lo, hi, starts), want)
    parts = []
    for r in runs:
        rlo = np.asarray([p[0] for p in r], np.uint64)
        rhi = np.asarray([p[1] for p in r], np.uint64)
        n = rlo.shape[0]
        parts.append(SignedStream(
            np.ones((n,), np.int32), rlo, rhi, rlo, rhi,
            np.arange(n, dtype=np.uint64),
            runs=np.zeros((1,), np.int64) if n else np.zeros((0,), np.int64),
            key_is_row=True))
    cat = SignedStream.concat(parts)
    merged = cat.merge_by_key()
    np.testing.assert_array_equal(merged.key_lo, cat.key_lo[want])
    np.testing.assert_array_equal(merged.rowid, cat.rowid[want])


def test_kway_merge_lo64_collisions():
    """Crafted signatures sharing the lo word must rank by the hi word —
    both in the run-merge and in the searchsorted refinement."""
    rng = np.random.default_rng(7)
    runs = []
    for _ in range(5):
        n = 200
        lo = rng.integers(0, 4, n).astype(np.uint64)  # massive lo collisions
        hi = rng.integers(0, 1 << 63, n).astype(np.uint64)
        o = np.lexsort((hi, lo))
        runs.append([(int(lo[i]), int(hi[i])) for i in o])
    lo, hi, starts = _flatten(runs)
    np.testing.assert_array_equal(ops.merge128_runs(lo, hi, starts),
                                  _oracle(lo, hi))
    np.testing.assert_array_equal(ops._merge128_ranksum(lo, hi, starts),
                                  _oracle(lo, hi))
    # searchsorted128 exact refinement under equal-lo runs
    order = _oracle(lo, hi)
    t_lo, t_hi = lo[order], hi[order]
    q = rng.permutation(lo.shape[0])[:64]
    pos = ops.searchsorted128(t_lo, t_hi, lo[q], hi[q])
    want = np.array([np.searchsorted(
        t_lo.astype(object) * (1 << 64) + t_hi.astype(object), int(l) * (1 << 64) + int(h))
        for l, h in zip(lo[q], hi[q])], np.int64)
    np.testing.assert_array_equal(pos, want)


@pytest.fixture
def device_path(monkeypatch):
    monkeypatch.setattr(ops, "FORCE_PALLAS_INTERPRET", True)


def _shared_key_runs(rng, k, collide):
    """k non-empty presorted runs; about a third of each later run's keys
    are copied from the run before it, so equal keys meet across runs (a
    row deleted in one run and re-added in the next). Within a run the lo
    words are distinct unless ``collide``, which draws them from a domain
    of 3 so that lo64 collisions fill every run."""
    runs, prev = [], None
    for _ in range(k):
        m = int(rng.integers(20, 90))
        lo = (rng.integers(0, 3, m) if collide else
              rng.choice(1 << 40, m, replace=False)).astype(np.uint64)
        hi = rng.integers(0, 1 << 62, m).astype(np.uint64)
        if prev is not None:
            c = min(m // 3, prev[0].shape[0])
            take = rng.choice(prev[0].shape[0], c, replace=False)
            lo[:c], hi[:c] = prev[0][take], prev[1][take]
            if not collide:   # keep the run's own lo words distinct
                keep = np.unique(lo, return_index=True)[1]
                lo, hi = lo[keep], hi[keep]
        o = np.lexsort((hi, lo))
        prev = (lo[o], hi[o])
        runs.append(list(zip(prev[0].tolist(), prev[1].tolist())))
    return runs


def _count_lower_bound_calls(monkeypatch):
    """Wrap ``ops.lower_bound`` the way the benchmark's kernel-work
    recorder does: one count per non-empty call."""
    calls = []
    inner = ops.lower_bound

    def counted(t, q):
        if t.shape[0] and q.shape[0]:
            calls.append(q.shape[0])
        return inner(t, q)
    monkeypatch.setattr(ops, "lower_bound", counted)
    return calls


@pytest.mark.parametrize("k", [1, 2, 5, 24])
@pytest.mark.parametrize("collide", [False, True], ids=["distinct", "lo64"])
def test_ranksum_merge_searches_once_per_run_and_side(
        device_path, monkeypatch, k, collide):
    """The device rank-sum merge equals the lexsort oracle under cross-run
    equal keys, and with distinct lo words in each run it makes exactly
    2(k-1) device lower-bound calls: one per run and side."""
    rng = np.random.default_rng([k, collide] + list(b"RANKSUM"))
    lo, hi, starts = _flatten(_shared_key_runs(rng, k, collide))
    calls = _count_lower_bound_calls(monkeypatch)
    np.testing.assert_array_equal(ops._merge128_ranksum(lo, hi, starts),
                                  _oracle(lo, hi))
    if not collide:
        assert len(calls) == 2 * (k - 1)


def test_ranksum_merge_counts_runs_and_calls(device_path):
    rng = np.random.default_rng(23)
    k = 5
    lo, hi, starts = _flatten(_shared_key_runs(rng, k, collide=False))
    before = dict(telemetry.PROCESS.counters)
    order = ops.merge128_runs(lo, hi, starts)
    np.testing.assert_array_equal(order, _oracle(lo, hi))
    got = {name: telemetry.PROCESS.counters.get(name, 0) - before.get(name, 0)
           for name in ("ops.ranksum_runs", "ops.ranksum_calls")}
    assert got == {"ops.ranksum_runs": k, "ops.ranksum_calls": 2 * (k - 1)}


def test_sort128_radix_fallback_large_unsorted():
    """The unsorted-fallback radix pre-pass must stay a stable 128-bit sort
    above the size cutoff that enables it."""
    rng = np.random.default_rng(11)
    n = (1 << 15) + 1000
    lo = rng.integers(0, 1 << 20, n).astype(np.uint64)  # many duplicates
    hi = rng.integers(0, 1 << 20, n).astype(np.uint64)
    np.testing.assert_array_equal(ops._sort128(lo, hi), _oracle(lo, hi))


@settings(max_examples=100, deadline=None)
@given(_runs)
def test_signed_stream_concat_merge_by_key(runs):
    """SignedStream.concat preserves run structure; merge_by_key yields the
    oracle order with emission-order ties."""
    parts = []
    for r in runs:
        lo = np.asarray([p[0] for p in r], np.uint64)
        hi = np.asarray([p[1] for p in r], np.uint64)
        n = lo.shape[0]
        parts.append(SignedStream(
            np.ones((n,), np.int32), lo, hi, lo, hi,
            np.arange(n, dtype=np.uint64),
            runs=np.zeros((1,), np.int64) if n else np.zeros((0,), np.int64),
            key_is_row=True))
    cat = SignedStream.concat(parts)
    merged = cat.merge_by_key()
    assert merged.sorted_by_key
    want = _oracle(cat.key_lo, cat.key_hi)
    np.testing.assert_array_equal(merged.key_lo, cat.key_lo[want])
    np.testing.assert_array_equal(merged.key_hi, cat.key_hi[want])
    np.testing.assert_array_equal(merged.rowid, cat.rowid[want])


# =============================================== bugfix satellite coverage

def _setup_indexed(n=50):
    e = Engine()
    e.create_table("T", SCH)
    e.insert("T", {"id": np.arange(n), "cat": np.arange(n) % 5,
                   "val": np.arange(n) * 1.0})
    create_index(e, "T", "by_cat", ["cat"])
    return e


def test_replay_preserves_clone_with_indices():
    """WAL replay must honour the recorded ``with_indices`` flag."""
    e = _setup_indexed()
    snap = e.create_snapshot("s", "T")
    e.clone_table("C", snap, with_indices=True)
    e2 = Engine.replay(e.wal)
    assert [s.name for s in e2.indices.get("C", [])] == ["by_cat"]
    hits = lookup_eq(e2, "C", "by_cat", {"cat": np.int32(3)})["id"].tolist()
    assert sorted(hits) == sorted(
        lookup_eq(e, "C", "by_cat", {"cat": np.int32(3)})["id"].tolist())


def test_clone_with_indices_snapshot_consistent():
    """Cloning an older snapshot must clone the aux index at that snapshot's
    horizon (or rebuild), never at the aux table's current head."""
    e = _setup_indexed()
    snap = e.create_snapshot("old", "T")
    # advance the base table (and thus the aux index) past the snapshot
    e.update_by_keys("T", {"id": np.arange(10), "cat": np.full(10, 9),
                           "val": np.zeros(10)})
    e.clone_table("C", "old", with_indices=True)
    # at "old", no row had cat==9 and ids 0..9 still had cat == id % 5
    assert lookup_eq(e, "C", "by_cat", {"cat": np.int32(9)})["id"].shape[0] == 0
    hits = sorted(lookup_eq(e, "C", "by_cat", {"cat": np.int32(3)})["id"]
                  .tolist())
    assert hits == [i for i in range(50) if i % 5 == 3]


def test_clone_with_indices_rebuilds_index_younger_than_snapshot():
    """An index created after the snapshot can't be cloned at the horizon —
    it must be rebuilt from the cloned data, not cloned at head."""
    e = Engine()
    e.create_table("T", SCH)
    e.insert("T", {"id": np.arange(20), "cat": np.arange(20) % 5,
                   "val": np.zeros(20)})
    snap = e.create_snapshot("s", "T")
    e.update_by_keys("T", {"id": [0], "cat": [9], "val": [0.0]})
    create_index(e, "T", "by_cat", ["cat"])  # younger than the snapshot
    e.clone_table("C", "s", with_indices=True)
    assert lookup_eq(e, "C", "by_cat", {"cat": np.int32(9)})["id"].shape[0] == 0
    assert sorted(lookup_eq(e, "C", "by_cat", {"cat": np.int32(0)})["id"]
                  .tolist()) == [0, 5, 10, 15]


def test_drop_table_drops_indices_and_aux_tables():
    e = _setup_indexed()
    aux = e.indices["T"][0].aux_table
    assert aux in e.tables
    e.drop_table("T")
    assert "T" not in e.indices
    assert aux not in e.tables
    assert "T" not in e.tables


def test_replay_roundtrip_clone_indices_and_drop_table():
    """Replay round-trip over clone-with-indices + drop_table: the replayed
    engine matches, with no dangling index state."""
    e = _setup_indexed()
    e.create_snapshot("s", "T")
    e.clone_table("C", "s", with_indices=True)
    aux_t = e.indices["T"][0].aux_table
    e.drop_table("T")
    e2 = Engine.replay(e.wal)
    assert set(e2.tables) == set(e.tables)
    assert "T" not in e2.indices and aux_t not in e2.tables
    assert [s.name for s in e2.indices.get("C", [])] == ["by_cat"]
    hits = lookup_eq(e2, "C", "by_cat", {"cat": np.int32(2)})["id"].tolist()
    assert sorted(hits) == [i for i in range(50) if i % 5 == 2]


# -------------------------------------- conflict keys in non-FAIL modes

def _conflicting(pk: bool):
    e = Engine()
    sch = SCH if pk else SCH_NOPK
    e.create_table("T", sch)
    e.insert("T", {"id": np.arange(10), "cat": np.zeros(10, np.int64),
                   "val": np.zeros(10)})
    sn = e.create_snapshot("base", "T")
    e.clone_table("C", "base")
    if pk:
        e.update_by_keys("T", {"id": [3], "cat": [1], "val": [30.0]})
        e.update_by_keys("C", {"id": [3], "cat": [2], "val": [300.0]})
    else:
        # §3 rule 3: both branches change the count of the SAME value group
        # (target inserts a copy, source deletes its copy) → true conflict
        e.insert("T", {"id": [3], "cat": [0], "val": [0.0]})  # dup of base row
        batch, rowids = e.table("C").scan()
        victim = rowids[np.flatnonzero(batch["id"] == 3)[:1]]
        tx = e.begin()
        tx.delete_rowids("C", victim)
        tx.commit()
    return e, sn


@pytest.mark.parametrize("mode", [ConflictMode.SKIP, ConflictMode.ACCEPT])
def test_conflict_keys_reported_in_non_fail_modes_pk(mode):
    e, sn = _conflicting(pk=True)
    rep = three_way_merge(e, "T", e.current_snapshot("C"), base=sn, mode=mode)
    assert rep.true_conflicts == 1
    assert rep.conflict_key_lo.shape == (1,) == rep.conflict_key_hi.shape
    lo, hi = key_sigs_for_lookup(SCH, {"id": np.asarray([3], np.int64)})
    assert rep.conflict_key_lo[0] == lo[0] and rep.conflict_key_hi[0] == hi[0]


@pytest.mark.parametrize("mode", [ConflictMode.SKIP, ConflictMode.ACCEPT])
def test_conflict_keys_reported_in_non_fail_modes_nopk(mode):
    e, sn = _conflicting(pk=False)
    rep = three_way_merge(e, "T", e.current_snapshot("C"), base=sn, mode=mode)
    assert rep.true_conflicts >= 1
    assert rep.conflict_key_lo.shape[0] == rep.true_conflicts
    assert rep.conflict_key_hi.shape[0] == rep.true_conflicts


def test_conflict_keys_match_fail_mode_report():
    """Non-FAIL reports must name the same keys FAIL mode raises with."""
    from repro.core import MergeConflictError
    e, sn = _conflicting(pk=True)
    with pytest.raises(MergeConflictError) as ei:
        three_way_merge(e, "T", e.current_snapshot("C"), base=sn,
                        mode=ConflictMode.FAIL)
    fail_rep = ei.value.report
    rep = three_way_merge(e, "T", e.current_snapshot("C"), base=sn,
                          mode=ConflictMode.SKIP)
    np.testing.assert_array_equal(rep.conflict_key_lo,
                                  fail_rep.conflict_key_lo)
    np.testing.assert_array_equal(rep.conflict_key_hi,
                                  fail_rep.conflict_key_hi)
