"""ISSUE 9 tests: fused 128-bit probe + key-range-sharded merge/aggregate.

Pins the two byte-identity contracts of the perf work:

* ``ops.probe128`` collapses the lower/upper-bound + collision-run
  expansion chain into one pass — the chained reference implementation
  here is the oracle, exercised on adversarial seeded lo64-collision
  workloads (hypothesis property on top when the container has it);
* key-range sharding (``merge128_runs(cuts=...)``,
  ``diff_aggregate(_rows)(shards=...)``, and the end-to-end engine under
  ``set_key_shards``) is a partitioning of the SAME computation — every
  output must be byte-identical to the unsharded path.

Plus the probe edge cases: all-invisible duplicate runs, zone-prune
boundary keys (query == zmin/zmax), empty-table/empty-query guards, and
the EXPLAIN MERGE surface reporting ``probe.*`` deltas next to the
``commit.rows_rehashed=0`` invariant.
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

from repro.core import Column, CType, Engine, Repo, Schema
from repro.core.objects import pack_rowid, seal_data_object
from repro.core.statements import execute
from repro.distributed import sharding
from repro.kernels import ops

from conftest import VCS_SCHEMA, VCS_SCHEMA_NOPK, content_digest, kv_batch

SCH_PLAIN = Schema((Column("k", CType.I64), Column("v", CType.F64)),
                   primary_key=("k",))


# ================================================= probe128 vs chained oracle

def _chained_probe(t_lo, t_hi, q_lo, q_hi):
    """The pre-fusion reference: lo64 lower/upper bound pair, then expand
    every lo64-collision run and count/rank the hi64 refinement with
    reduceat — exactly the chain ``probe128`` replaced."""
    n, nq = t_lo.shape[0], q_lo.shape[0]
    start = np.zeros((nq,), np.int64)
    cnt = np.zeros((nq,), np.int64)
    if n == 0 or nq == 0:
        return start, cnt
    lb = np.searchsorted(t_lo, q_lo, side="left").astype(np.int64)
    ub = np.searchsorted(t_lo, q_lo, side="right").astype(np.int64)
    start[:] = lb
    run = ub > lb
    ridx = np.flatnonzero(run)
    for i in ridx.tolist():  # oracle clarity over speed
        a, b = int(lb[i]), int(ub[i])
        seg = t_hi[a:b]
        start[i] = a + int(np.searchsorted(seg, q_hi[i], side="left"))
        cnt[i] = int((seg == q_hi[i]).sum())
    return start, cnt


def _sorted_table(rng, n, lo_dom, hi_dom):
    lo = rng.integers(0, lo_dom, n).astype(np.uint64)
    hi = rng.integers(0, hi_dom, n).astype(np.uint64)
    o = np.lexsort((hi, lo))
    return lo[o], hi[o]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_probe128_matches_chained_oracle_seeded(seed):
    rng = np.random.default_rng([seed] + list(b"PROBE"))
    for n, nq, lo_dom, hi_dom in [(0, 5, 4, 3), (7, 0, 4, 3),
                                  (1, 8, 2, 2), (64, 200, 9, 4),
                                  (500, 300, 17, 3), (500, 300, 3, 50)]:
        t_lo, t_hi = _sorted_table(rng, n, lo_dom, hi_dom)
        # query mix: present keys, lo64-collision misses (right lo, wrong
        # hi), and fully absent keys beyond both domains
        q_lo = rng.integers(0, lo_dom + 2, nq).astype(np.uint64)
        q_hi = rng.integers(0, hi_dom + 2, nq).astype(np.uint64)
        got_s, got_c = ops.probe128(t_lo, t_hi, q_lo, q_hi)
        want_s, want_c = _chained_probe(t_lo, t_hi, q_lo, q_hi)
        np.testing.assert_array_equal(got_c, want_c)
        np.testing.assert_array_equal(got_s, want_s)


if HAVE_HYPOTHESIS:
    _sig = st.tuples(st.integers(0, 6), st.integers(0, 3))
    _tbl = st.lists(_sig, max_size=40).map(sorted)
    _qry = st.lists(_sig, max_size=20)
else:  # pragma: no cover - @given is a skip marker; value never sampled
    _tbl = _qry = None


@settings(max_examples=200, deadline=None)
@given(_tbl, _qry)
def test_probe128_property(tbl, qry):
    t_lo = np.asarray([p[0] for p in tbl], np.uint64)
    t_hi = np.asarray([p[1] for p in tbl], np.uint64)
    q_lo = np.asarray([p[0] for p in qry], np.uint64)
    q_hi = np.asarray([p[1] for p in qry], np.uint64)
    got_s, got_c = ops.probe128(t_lo, t_hi, q_lo, q_hi)
    want_s, want_c = _chained_probe(t_lo, t_hi, q_lo, q_hi)
    np.testing.assert_array_equal(got_c, want_c)
    np.testing.assert_array_equal(got_s, want_s)


def test_probe128_interpret_matches_cpu():
    """The device search (run here on the CPU backend) agrees with the
    numpy fallback, including on duplicate runs that straddle the query
    padding block."""
    rng = np.random.default_rng(list(b"PROBEK"))
    t_lo, t_hi = _sorted_table(rng, 700, 23, 5)
    q_lo = rng.integers(0, 25, 333).astype(np.uint64)
    q_hi = rng.integers(0, 7, 333).astype(np.uint64)
    want = ops.probe128(t_lo, t_hi, q_lo, q_hi)
    prev = ops.FORCE_PALLAS_INTERPRET
    ops.FORCE_PALLAS_INTERPRET = True
    try:
        got = ops.probe128(t_lo, t_hi, q_lo, q_hi)
    finally:
        ops.FORCE_PALLAS_INTERPRET = prev
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_device_search_all_ones_keys_beside_sentinel_padding():
    """The device search pads tables to a power of two with all-ones
    sentinel keys: real all-ones keys (a run at the table's end) and
    all-ones queries must still get exact bounds and run lengths."""
    top = np.uint64(0xFFFFFFFFFFFFFFFF)
    t_lo = np.array([1, 5, 5, top, top, top], np.uint64)
    t_hi = np.array([0, 2, 3, 7, top, top], np.uint64)
    q_lo = np.array([top, top, top, 5, 0, 6], np.uint64)
    q_hi = np.array([top, 7, 8, 3, 0, 0], np.uint64)
    prev = ops.FORCE_PALLAS_INTERPRET
    ops.FORCE_PALLAS_INTERPRET = True
    try:
        got = ops.probe128(t_lo, t_hi, q_lo, q_hi)
        lb = ops.lower_bound(t_lo, q_lo)
        ub = ops.upper_bound(t_lo, q_lo)
    finally:
        ops.FORCE_PALLAS_INTERPRET = prev
    want = _chained_probe(t_lo, t_hi, q_lo, q_hi)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], [2, 1, 0, 1, 0, 0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(lb, np.searchsorted(t_lo, q_lo, "left"))
    np.testing.assert_array_equal(ub, np.searchsorted(t_lo, q_lo, "right"))


# ============================================== probe edge cases (visibility)

class _StubVI:
    """Visibility stand-in: a fixed mask, so duplicate-key objects (which
    the PK engine never seals) can exercise the run-expansion path."""

    def __init__(self, mask):
        self._mask = np.asarray(mask, bool)

    def visible_mask(self, obj):
        return self._mask


def _dup_key_object(oid=7):
    """key runs: (1,7)x3, (2,3)x2, (5,0)x1 — sorted, with duplicates."""
    k_lo = np.array([1, 1, 1, 2, 2, 5], np.uint64)
    k_hi = np.array([7, 7, 7, 3, 3, 0], np.uint64)
    n = k_lo.shape[0]
    batch = {"k": np.arange(n, dtype=np.int64),
             "v": np.arange(n, dtype=np.float64)}
    return seal_data_object(
        oid, SCH_PLAIN, batch, np.ones((n,), np.uint64),
        np.arange(10, 10 + n, dtype=np.uint64),
        np.arange(20, 20 + n, dtype=np.uint64), k_lo, k_hi, {})


def test_probe_object_duplicate_run_visibility():
    engine = Engine()
    engine.create_table("t", SCH_PLAIN)
    t = engine.table("t")
    obj = _dup_key_object()
    q_lo = np.array([1, 2, 5, 3], np.uint64)   # 3 -> absent key
    q_hi = np.array([7, 3, 0, 0], np.uint64)

    def rid(off):
        return pack_rowid(obj.oid, np.array([off], np.uint64))[0]

    # head visible: every run resolves at its first row, no expansion
    base = engine.store.metrics.counters.get("probe.expansions", 0)
    out = t._probe_object(obj, _StubVI(np.ones(6, bool)), q_lo, q_hi)
    np.testing.assert_array_equal(out, [rid(0), rid(3), rid(5), 0])
    assert engine.store.metrics.counters.get("probe.expansions", 0) == base

    # head invisible, deeper duplicate visible: expansion finds the FIRST
    # visible row of the exactly-equal run
    out = t._probe_object(obj, _StubVI([False, False, True, False, True,
                                        True]), q_lo, q_hi)
    np.testing.assert_array_equal(out, [rid(2), rid(4), rid(5), 0])
    assert engine.store.metrics.counters.get("probe.expansions", 0) == base + 2

    # all-invisible duplicate runs: misses, never a dead rowid
    out = t._probe_object(obj, _StubVI(np.zeros(6, bool)), q_lo, q_hi)
    np.testing.assert_array_equal(out, [0, 0, 0, 0])


def test_locate_rowsig_all_invisible_duplicate_run():
    """NoPK: identical rows seal one duplicate run; deleting them one by
    one walks the run down to all-invisible (locate returns nothing)."""
    engine = Engine()
    engine.create_table("t", VCS_SCHEMA_NOPK)
    tx = engine.begin()
    tx.insert("t", kv_batch([5, 5, 5], vals=[1.0, 1.0, 1.0],
                            docs=[b"x", b"x", b"x"]))
    tx.insert("t", kv_batch([9]))
    tx.commit()
    t = engine.table("t")
    _, _, lo, hi = t.scan(with_sigs=True)
    # the duplicated signature is the one appearing 3x
    vals, counts = np.unique(lo, return_counts=True)
    dup_lo = vals[np.argmax(counts)]
    dup_hi = hi[lo == dup_lo][0]
    assert int(counts.max()) == 3
    q_lo, q_hi = np.array([dup_lo]), np.array([dup_hi])

    found = t.locate_rowsig_multi(q_lo, q_hi, np.array([3]))[0]
    assert found.shape[0] == 3
    # delete two: the run's newest rows become invisible, locate degrades
    tx = engine.begin()
    tx.delete_rowids("t", found[:2])
    tx.commit()
    found = t.locate_rowsig_multi(q_lo, q_hi, np.array([3]))[0]
    assert found.shape[0] == 1
    tx = engine.begin()
    tx.delete_rowids("t", found)
    tx.commit()
    # all-invisible duplicate run: empty, in both return shapes
    assert t.locate_rowsig_multi(q_lo, q_hi, np.array([3]))[0].shape[0] == 0
    assert t.locate_rowsig_multi(q_lo, q_hi, np.array([3]),
                                 flat=True).shape[0] == 0


def test_locate_keys_zone_boundaries_and_deleted():
    """Zone pruning is inclusive at both edges (key_lo == zmin/zmax must
    probe, not prune) and deleted keys miss; counters move accordingly."""
    from repro.core.sigs import key_sigs_for_lookup
    engine = Engine()
    engine.create_table("t", VCS_SCHEMA)
    keys = list(range(100, 200))
    tx = engine.begin()
    tx.insert("t", kv_batch(keys))
    tx.commit()
    tx = engine.begin()
    tx.delete_by_keys("t", {"k": np.array([150], np.int64)})
    tx.commit()
    t = engine.table("t")
    obj = engine.store.get(t.directory.data_oids[0])
    zmin, zmax = obj.zone
    # recover the int keys sitting exactly on the zone edges
    q_lo, q_hi = key_sigs_for_lookup(
        VCS_SCHEMA, {"k": np.asarray(keys, np.int64)})
    kmin = keys[int(np.flatnonzero(q_lo == zmin)[0])]
    kmax = keys[int(np.flatnonzero(q_lo == zmax)[0])]
    for k, want_hit in [(kmin, True), (kmax, True), (150, False),
                        (999, False)]:
        s_lo, s_hi = key_sigs_for_lookup(VCS_SCHEMA,
                                         {"k": np.array([k], np.int64)})
        got = t.locate_keys(s_lo, s_hi)
        assert (got[0] != 0) == want_hit, k
    assert engine.store.metrics.counters.get("probe.queries", 0) >= 4
    assert engine.store.metrics.counters.get("probe.hits", 0) >= 2


def test_locate_keys_empty_table_and_empty_object_skip():
    engine = Engine()
    engine.create_table("t", VCS_SCHEMA)
    t = engine.table("t")
    q = np.array([1, 2, 3], np.uint64)
    np.testing.assert_array_equal(t.locate_keys(q, q), [0, 0, 0])
    assert engine.store.metrics.counters.get("probe.objects_probed", 0) == 0
    # a zero-row sealed object in the directory is skipped before zone
    # pruning or probing
    empty = seal_data_object(
        engine.store.new_oid(), SCH_PLAIN,
        {"k": np.zeros((0,), np.int64), "v": np.zeros((0,), np.float64)},
        np.zeros((0,), np.uint64), np.zeros((0,), np.uint64),
        np.zeros((0,), np.uint64), np.zeros((0,), np.uint64),
        np.zeros((0,), np.uint64), {})
    engine.store.put(empty)
    d = t.directory
    d2 = type(d)(data_oids=d.data_oids + (empty.oid,),
                 tomb_oids=d.tomb_oids, ts=d.ts)
    np.testing.assert_array_equal(t.locate_keys(q, q, d2), [0, 0, 0])
    assert engine.store.metrics.counters.get("probe.objects_probed", 0) == 0


# ======================================== key-range sharding: byte identity

def _random_stream(rng, k, n, lo_dom, hi_dom):
    parts, starts, off = [], [], 0
    for _ in range(k):
        m = int(rng.integers(1, n + 1))
        lo = rng.integers(0, lo_dom, m).astype(np.uint64)
        hi = rng.integers(0, hi_dom, m).astype(np.uint64)
        o = np.lexsort((hi, lo))
        parts.append((lo[o], hi[o]))
        starts.append(off)
        off += m
    lo = np.concatenate([p[0] for p in parts])
    hi = np.concatenate([p[1] for p in parts])
    return lo, hi, np.asarray(starts, np.int64)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shards", [2, 4, 7])
def test_merge128_runs_sharded_byte_identical(seed, shards):
    rng = np.random.default_rng([seed, shards] + list(b"SHARD"))
    for k, n, lo_dom, hi_dom in [(2, 50, 5, 3), (5, 200, 31, 2),
                                 (9, 400, 7, 7)]:
        lo, hi, starts = _random_stream(rng, k, n, lo_dom, hi_dom)
        want = ops.merge128_runs(lo, hi, starts)
        cuts = sharding.plan_key_cuts(lo, hi, starts, shards)
        if cuts is None:
            continue
        assert cuts[0].shape[0] >= 1
        got = ops.merge128_runs(lo, hi, starts, cuts=cuts)
        np.testing.assert_array_equal(got, want)
        # the plan itself: ascending, strictly distinct boundary keys
        key = list(zip(cuts[0].tolist(), cuts[1].tolist()))
        assert key == sorted(set(key))


@pytest.mark.parametrize("seed", [0, 1])
def test_diff_aggregate_sharded_byte_identical(seed):
    rng = np.random.default_rng([seed] + list(b"AGG"))
    lo = rng.integers(0, 40, 500).astype(np.uint64)
    hi = rng.integers(0, 3, 500).astype(np.uint64)
    o = np.lexsort((hi, lo))
    lo, hi = lo[o], hi[o]
    sg = rng.choice(np.array([-1, 1], np.int32), 500)
    _, want = ops.diff_aggregate(lo, hi, sg, presorted=True)
    for shards in (2, 4, 9):
        _, got = ops.diff_aggregate(lo, hi, sg, presorted=True,
                                    shards=shards)
        np.testing.assert_array_equal(got.boundary, want.boundary)
        np.testing.assert_array_equal(got.run_sums, want.run_sums)
    # rows variant, PK-style distinct row signatures under duplicate keys
    r_lo = rng.permutation(500).astype(np.uint64)
    r_hi = rng.integers(0, 2, 500).astype(np.uint64)
    _, want = ops.diff_aggregate_rows(lo, hi, r_lo, r_hi, sg,
                                      presorted=True)
    for shards in (2, 4, 9):
        _, got = ops.diff_aggregate_rows(lo, hi, r_lo, r_hi, sg,
                                         presorted=True, shards=shards)
        np.testing.assert_array_equal(got.boundary, want.boundary)
        np.testing.assert_array_equal(got.run_sums, want.run_sums)
    # NoPK aliasing (key IS the row signature) survives the slicing
    _, want = ops.diff_aggregate_rows(lo, hi, lo, hi, sg, presorted=True)
    _, got = ops.diff_aggregate_rows(lo, hi, lo, hi, sg, presorted=True,
                                     shards=4)
    np.testing.assert_array_equal(got.boundary, want.boundary)


@pytest.mark.parametrize("pk", [True, False])
def test_end_to_end_sharded_workload_identical(pk):
    """The full engine under a forced 4-way shard plan produces the same
    diff/merge/scan digests as the unsharded run — sharding is a plan,
    never a semantic."""
    from benchmarks.digests import run_workload
    want = run_workload(pk, n_rows=20_000, csize=1_500)
    prev = sharding.set_key_shards(4)
    try:
        got = run_workload(pk, n_rows=20_000, csize=1_500)
    finally:
        sharding.set_key_shards(prev)
    assert got == want


def test_forced_shards_delta_digest_and_counter():
    """A forced shard plan partitions the Δ merge (multi-object signed
    stream) without changing the diff, and the shard_parts counter moves."""
    from repro.core import snapshot_diff
    from benchmarks.digests import diff_digest

    def build():
        e = Engine()
        e.create_table("t", VCS_SCHEMA_NOPK)
        rng = np.random.default_rng(list(b"E2E"))
        sn0 = e.create_snapshot("s0", "t")
        for step in range(4):
            tx = e.begin()
            tx.insert("t", kv_batch(rng.integers(0, 500, 700)))
            tx.commit()
        return e, snapshot_diff(e.store, sn0, e.current_snapshot("t"))

    prev = sharding.set_key_shards(4)
    try:
        engine_shard, d_shard = build()
        assert engine_shard.store.metrics.counters.get(
            "probe.shard_parts", 0) > 0
    finally:
        sharding.set_key_shards(prev)
    engine_plain, d_plain = build()
    assert diff_digest(d_shard) == diff_digest(d_plain)
    assert (content_digest(engine_shard, "t")
            == content_digest(engine_plain, "t"))


def test_key_shard_count_policy():
    assert sharding.key_shard_count(sharding.KEY_SHARD_MIN_ROWS - 1) == 1
    big = sharding.key_shard_count(sharding.KEY_SHARD_MIN_ROWS)
    assert 2 <= big <= sharding.KEY_SHARD_MAX
    prev = sharding.set_key_shards(6)
    try:
        assert sharding.key_shard_count(10) == 6
    finally:
        sharding.set_key_shards(prev)
    assert sharding.key_shard_count(10) == 1


# ============================================================ EXPLAIN surface

def test_explain_merge_reports_probe_counters():
    repo = Repo()
    repo.engine.create_table("t", VCS_SCHEMA)
    tx = repo.engine.begin()
    tx.insert("t", kv_batch(range(1000)))
    tx.commit()
    repo.branch("dev", ["t"])
    tx = repo.engine.begin()
    tx.update_by_keys("dev/t", kv_batch(range(200),
                                        vals=np.arange(200) * 3.0))
    tx.commit()
    res = execute(repo, "EXPLAIN MERGE BRANCH dev INTO main")
    assert res.kind == "explain"
    assert "commit.rows_rehashed=0" in res.message
    assert "probe.queries=" in res.message
    assert "probe.hits=" in res.message
