"""Jit'd wrappers + backend dispatch for the VCS kernels.

The version-control engine (``repro.core``) calls these ops on its hot
paths. On a TPU backend they run on the device: the Pallas kernels
``rowhash.py`` and ``segsum_diff.py``, and the XLA search programs in
``search.py``. On CPU they run semantically identical vectorized numpy
paths, so that tests on the CPU measure algorithmic behaviour, not Pallas
interpret-mode overhead. Setting ``FORCE_PALLAS_INTERPRET = True`` (tests
only) routes everything through the device path on the CPU, with the
Pallas kernels in interpret mode. There is no fallback: on a TPU the
device path is the only path, and its errors propagate.

Each device call is traced as two telemetry spans: ``ops.stage`` (padding,
lane unpacking, the host-to-device copies and the program's dispatch) and
``ops.fetch`` (waiting for the result, the device-to-host copy, slicing
off the padding). The bytes each call copies to the device, padding
included, count in ``xfer.h2d_bytes`` (``telemetry.PROCESS``). The numpy
paths open no span and count nothing. Each rank-sum run merge counts its
runs in ``ops.ranksum_runs`` and its ``searchsorted128`` calls in
``ops.ranksum_calls``.

Signature convention: a 64-bit word is carried host-side as numpy uint64;
kernels see it as (hi32, lo32) uint32 lanes. A row signature is 128 bits =
two uint64 words (lo64, hi64); sorting is lexicographic by (hi64, lo64) --
but since the words are uniformly mixed, we sort by the single packed lo64
word and resolve the rare lo64 collisions with the hi64 word at run level.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core import telemetry
from . import ref
from .rowhash import rowhash_pallas, DEFAULT_BLOCK_ROWS
from .search import lower_bound_lanes, probe_lanes
from .segsum_diff import (boundary_pallas, LANES,
                          DEFAULT_BLOCK_ROWS as SEG_BLOCK_ROWS)

# Toggled by tests; on a real TPU backend the device path is the default.
FORCE_PALLAS_INTERPRET = False

#: smallest padded query batch of the device search
QUERY_BLOCK = 1024
_ALL_ONES = np.uint64(0xFFFFFFFFFFFFFFFF)
#: rows hashed per device call, so device memory stays bounded however
#: large the batch (a whole table load hashes in one ``rowhash`` call)
ROWHASH_CHUNK = 1 << 18

SP_STAGE = telemetry.register_span(
    "ops.stage", "device call: pad and unpack the inputs, copy them to the "
    "device, dispatch the program")
SP_FETCH = telemetry.register_span(
    "ops.fetch", "device call: wait for the result, copy it to the host, "
    "slice off the padding")
XFER_H2D = "xfer.h2d_bytes"
RANKSUM_RUNS = "ops.ranksum_runs"
RANKSUM_CALLS = "ops.ranksum_calls"


def backend_uses_pallas() -> bool:
    return FORCE_PALLAS_INTERPRET or jax.default_backend() == "tpu"


def _interp() -> bool:
    return jax.default_backend() != "tpu"


def _to_device(*arrays: np.ndarray):
    """Host arrays -> device arrays, counting the bytes sent."""
    telemetry.PROCESS.add(XFER_H2D, sum(a.nbytes for a in arrays))
    return tuple(jnp.asarray(a) for a in arrays)


def _device_lanes(*words: np.ndarray):
    """uint64 words -> one tuple of device uint32 lanes, each word as
    (hi32, lo32), most significant lane first."""
    return _to_device(*(lane for w in words for lane in unpack64(w)))


def _pow2(n: int, least: int = 1) -> int:
    return max(least, 1 << (int(n) - 1).bit_length())


def _search_table(*words: np.ndarray):
    """Device lanes of a sorted table padded to a power of two with
    all-ones sentinel keys, so that each search program compiles once per
    size class. No sentinel sorts below a real key, so lower bounds are
    exact; bounds that count rows <= the all-ones key clamp to the real
    length on the host."""
    n = words[0].shape[0]
    size = _pow2(n)
    return _device_lanes(*(_pad_rows(np.asarray(w, np.uint64), size,
                                     fill=_ALL_ONES) for w in words))


def _search_queries(*words: np.ndarray):
    """Device lanes of a query batch padded to a power of two rows (at
    least ``QUERY_BLOCK``); the padded answers are sliced off."""
    size = _pow2(words[0].shape[0], QUERY_BLOCK)
    return _device_lanes(*(_pad_rows(np.asarray(w, np.uint64), size)
                           for w in words))


# ---------------------------------------------------------------- packing

def pack64(hi32: np.ndarray, lo32: np.ndarray) -> np.ndarray:
    return (hi32.astype(np.uint64) << np.uint64(32)) | lo32.astype(np.uint64)


def unpack64(w: np.ndarray):
    w = w.astype(np.uint64)
    return (w >> np.uint64(32)).astype(np.uint32), (w & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _pad_rows(a: np.ndarray, mult: int, fill=0) -> np.ndarray:
    n = a.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return a
    padding = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, padding, constant_values=fill)


# ---------------------------------------------------------------- rowhash

def rowhash(lanes_u32: np.ndarray) -> np.ndarray:
    """(R, C) uint32 lanes -> (R, 4) uint32 signature words."""
    r = lanes_u32.shape[0]
    if r == 0:
        return np.zeros((0, 4), np.uint32)
    if backend_uses_pallas():
        lanes = np.asarray(lanes_u32, np.uint32)
        c = lanes.shape[1]
        out = np.empty((r, 4), np.uint32)
        for s in range(0, r, ROWHASH_CHUNK):
            part = lanes[s:s + ROWHASH_CHUNK]
            k = part.shape[0]
            with telemetry.span(SP_STAGE):
                cols = np.ascontiguousarray(
                    _pad_rows(part, DEFAULT_BLOCK_ROWS).T).reshape(
                        c, -1, LANES)
                res = rowhash_pallas(*_to_device(cols), interpret=_interp())
            with telemetry.span(SP_FETCH):
                out[s:s + k] = np.asarray(res).reshape(4, -1)[:, :k].T
        return out
    # CPU fast path: identical math in numpy (wrapping uint32).
    return _rowhash_np(np.asarray(lanes_u32, np.uint32))


def _fmix32_np(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32)
    with np.errstate(over="ignore"):
        h ^= h >> np.uint32(16)
        h = (h * np.uint32(0x85EBCA6B)).astype(np.uint32)
        h ^= h >> np.uint32(13)
        h = (h * np.uint32(0xC2B2AE35)).astype(np.uint32)
        h ^= h >> np.uint32(16)
    return h


def _rowhash_np(lanes: np.ndarray) -> np.ndarray:
    r, c = lanes.shape
    seeds = [np.uint32(int(s)) for s in ref._SEEDS]
    out = np.empty((r, 4), np.uint32)
    with np.errstate(over="ignore"):
        for s, seed in enumerate(seeds):
            h = np.full((r,), seed, np.uint32)
            for j in range(c):
                x = lanes[:, j]
                salt = np.uint32(((j * 2 + 1) * 0x9E3779B1 + s * 0x7F4A7C15) & 0xFFFFFFFF)
                h = _fmix32_np(h ^ (x * np.uint32(0x9E3779B1) + salt).astype(np.uint32))
                h = (h * np.uint32(0x95D0BE4F) + np.uint32(1)).astype(np.uint32)
            out[:, s] = _fmix32_np(h ^ np.uint32(c))
    return out


def signatures_from_lanes(lanes_u32: np.ndarray):
    """(R, C) uint32 -> (sig_lo (R,) uint64, sig_hi (R,) uint64)."""
    w = rowhash(lanes_u32)
    lo = pack64(w[:, 1], w[:, 0])
    hi = pack64(w[:, 3], w[:, 2])
    return lo, hi


# ------------------------------------------------------------ lower bound

def lower_bound(sorted_u64: np.ndarray, queries_u64: np.ndarray) -> np.ndarray:
    """First index i with sorted[i] >= q, per query. Returns int64 indices."""
    if queries_u64.shape[0] == 0 or sorted_u64.shape[0] == 0:
        return np.zeros(queries_u64.shape, np.int64)
    if backend_uses_pallas():
        nq = queries_u64.shape[0]
        with telemetry.span(SP_STAGE):
            idx = lower_bound_lanes(_search_table(sorted_u64),
                                    _search_queries(queries_u64))
        with telemetry.span(SP_FETCH):
            return np.asarray(idx)[:nq].astype(np.int64)
    return np.searchsorted(sorted_u64, queries_u64, side="left").astype(np.int64)


def upper_bound(sorted_u64: np.ndarray, queries_u64: np.ndarray) -> np.ndarray:
    """First index i with sorted[i] > q, per query. Returns int64 indices.

    Served by the same device lower bound: ub(q) == lb(q + 1) for any q
    below the uint64 maximum (equal-key runs are what the probe paths
    resolve vectorized with lb/ub pairs)."""
    if queries_u64.shape[0] == 0 or sorted_u64.shape[0] == 0:
        return np.zeros(queries_u64.shape, np.int64)
    if backend_uses_pallas():
        q = np.asarray(queries_u64, np.uint64)
        with np.errstate(over="ignore"):
            idx = lower_bound(sorted_u64, q + np.uint64(1))
        return np.where(q == np.uint64(0xFFFFFFFFFFFFFFFF),
                        np.int64(sorted_u64.shape[0]), idx)
    return np.searchsorted(sorted_u64, queries_u64, side="right").astype(np.int64)


def searchsorted128(t_lo: np.ndarray, t_hi: np.ndarray,
                    q_lo: np.ndarray, q_hi: np.ndarray,
                    side: str = "left") -> np.ndarray:
    """Exact 128-bit searchsorted against a stream sorted by (lo, hi).

    Primary ranks come from the 64-bit lower bound on the lo word.
    Queries whose lo word exists in the table — the COMMON case for the
    merge-join and rank-sum callers, where most queries are exact key
    matches — refine against the hi word in one vectorized gather+compare
    (the table run has length 1 for distinct hashed signatures); only
    genuine lo64 collisions (run length > 1) pay a scalar bisect."""
    n = t_lo.shape[0]
    if q_lo.shape[0] == 0 or n == 0:
        return np.zeros(q_lo.shape, np.int64)
    lb = lower_bound(t_lo, q_lo)
    out = lb.copy()
    hit = (lb < n) & (t_lo[np.minimum(lb, n - 1)] == q_lo)
    # the matched run extends past lb only on a genuine lo64 collision
    multi = hit & (lb + 1 < n) & (t_lo[np.minimum(lb + 1, n - 1)] == q_lo)
    one = hit & ~multi
    if one.any():
        idx = lb[one]
        after = (t_hi[idx] < q_hi[one] if side == "left"
                 else t_hi[idx] <= q_hi[one])
        out[one] = idx + after
    midx = np.flatnonzero(multi)
    if midx.shape[0]:
        ub = upper_bound(t_lo, q_lo[midx])
        for j, i in enumerate(midx):
            s, e = int(lb[i]), int(ub[j])
            out[i] = s + int(np.searchsorted(t_hi[s:e], q_hi[i], side=side))
    return out


def probe128(t_lo: np.ndarray, t_hi: np.ndarray,
             q_lo: np.ndarray, q_hi: np.ndarray):
    """Fused probe of a (lo, hi)-key-sorted table: per query key, the exact
    128-bit lower bound (``start``) and the equal-key run length (``cnt``,
    0 == key absent). ``start`` is defined for misses too — it is where the
    key WOULD insert — so the contract is total and backend-independent.

    This one call replaces the probe paths' lower_bound → key-compare →
    upper_bound → segment_expand → reduceat chain: the run of rows exactly
    equal to the query is ``[start, start + cnt)``, contiguous because
    sealed objects sort by (lo, hi). Queries SHOULD arrive sorted by
    (lo, hi) — correctness never depends on it, but the CPU searchsorted
    degrades on shuffled batches (documented probe contract, ROADMAP
    §Performance).

    Backend dispatch: on the device both bounds come out of one fused
    fixed-depth descent over the four uint32 lanes; on CPU one lo64
    searchsorted resolves every query whose lo64 run has length 1 (the
    common case for hashed keys) and only genuine lo64 collisions pay the
    vectorized hi-word refinement."""
    n = t_lo.shape[0]
    nq = q_lo.shape[0]
    if nq == 0 or n == 0:
        return np.zeros((nq,), np.int64), np.zeros((nq,), np.int64)
    if backend_uses_pallas():
        with telemetry.span(SP_STAGE):
            start, ub = probe_lanes(_search_table(t_lo, t_hi),
                                    _search_queries(q_lo, q_hi))
        with telemetry.span(SP_FETCH):
            start = np.asarray(start)[:nq].astype(np.int64)
            ub = np.minimum(np.asarray(ub)[:nq], n).astype(np.int64)
        return start, ub - start
    # CPU fused fast path: one primary-word searchsorted for everything
    lb = np.searchsorted(t_lo, q_lo, side="left").astype(np.int64)
    start = lb.copy()
    cnt = np.zeros((nq,), np.int64)
    idx = np.minimum(lb, n - 1)
    hit = (lb < n) & (t_lo[idx] == q_lo)
    if not hit.any():
        return start, cnt
    # the lo64 run extends past lb only on a genuine lo64 collision
    multi = hit & (lb + 1 < n) & (t_lo[np.minimum(lb + 1, n - 1)] == q_lo)
    one = hit & ~multi
    if one.any():
        i1 = lb[one]
        start[one] = i1 + (t_hi[i1] < q_hi[one])
        cnt[one] = (t_hi[i1] == q_hi[one]).astype(np.int64)
    midx = np.flatnonzero(multi)
    if midx.shape[0]:
        ub = np.searchsorted(t_lo, q_lo[midx], side="right").astype(np.int64)
        seg, base, flat = segment_expand(lb[midx], ub - lb[midx])
        t_run, q_seg = t_hi[flat], q_hi[midx][seg]
        start[midx] = lb[midx] + np.add.reduceat(
            (t_run < q_seg).astype(np.int64), base)
        cnt[midx] = np.add.reduceat((t_run == q_seg).astype(np.int64), base)
    return start, cnt


def segment_expand(starts: np.ndarray, lens: np.ndarray):
    """Expand per-segment (start, len) pairs into flat element indices.

    Returns (seg, base, flat): ``seg[j]`` is the segment owning flat slot j,
    ``base[i]`` the first flat slot of segment i (valid reduceat offsets when
    every ``lens[i] > 0``), and ``flat[j]`` the source index — i.e. segment
    ``seg[j]`` contributes ``starts[i] .. starts[i]+lens[i]-1`` in order.
    Callers must pre-filter zero-length segments."""
    total = int(lens.sum())
    seg = np.repeat(np.arange(lens.shape[0]), lens)
    base = np.concatenate([[0], np.cumsum(lens)[:-1]])
    flat = starts[seg] + (np.arange(total, dtype=np.int64) - base[seg])
    return seg, base, flat


# --------------------------------------------------------- diff aggregate

class DiffAgg:
    """Result of diff aggregation over a sorted signed stream.

    Attributes:
      boundary:   (N,) bool  — new-run start flags.
      run_starts: (K,) int64 — index of each run's first element.
      run_lens:   (K,) int64
      run_sums:   (K,) int32 — net sign per run (0 == fully cancelled).
      run_ids:    (N,) int64 — run index per element (computed lazily).
    """

    __slots__ = ("boundary", "run_starts", "_n", "_run_lens", "run_sums",
                 "_run_ids")

    def __init__(self, boundary, signs):
        boundary = np.asarray(boundary, bool)
        signs = np.asarray(signs, np.int32)
        self.boundary = boundary
        self.run_starts = np.flatnonzero(boundary).astype(np.int64)
        n = boundary.shape[0]
        self._n = n
        if n:
            # net sign per run via one cumsum + end-point differences
            # (faster than add.reduceat when runs are short, the Δ-stream
            # common case)
            cs = np.cumsum(signs, dtype=np.int64)
            ends = np.append(self.run_starts[1:], n)
            sums = cs[ends - 1]
            sums[1:] -= cs[self.run_starts[1:] - 1]
            self.run_sums = sums.astype(np.int32)
        else:
            self.run_sums = np.zeros((0,), np.int32)
        self._run_lens = None
        self._run_ids = None

    @property
    def run_lens(self) -> np.ndarray:
        if self._run_lens is None:
            ends = np.append(self.run_starts[1:], self._n)
            self._run_lens = ends - self.run_starts
        return self._run_lens

    @property
    def run_ids(self) -> np.ndarray:
        if self._run_ids is None:
            self._run_ids = np.cumsum(self.boundary).astype(np.int64) - 1
        return self._run_ids


_RADIX_MIN_N = 1 << 15


def _radix16_argsort(a: np.ndarray) -> np.ndarray:
    """Stable LSD radix argsort of uint64 in four 16-bit passes.

    numpy's stable sort on uint16 keys IS a radix sort, so each pass is
    O(n); on unstructured uint64 input this beats the 64-bit stable sort
    (timsort) ~2x at Δ-pipeline sizes."""
    # lint: sort-ok this IS the sort kernel — radix passes are its body
    order = np.argsort((a & np.uint64(0xFFFF)).astype(np.uint16),
                       kind="stable")
    for shift in (16, 32, 48):
        d = ((a[order] >> np.uint64(shift)) & np.uint64(0xFFFF)
             ).astype(np.uint16)
        # lint: sort-ok this IS the sort kernel — radix passes are its body
        order = order[np.argsort(d, kind="stable")]
    return order


def _argsort64_stable(a: np.ndarray) -> np.ndarray:
    """Stable uint64 argsort with a bucket/radix pre-pass decision.

    Presorted-run-structured input (the Δ pipeline's emission order) is
    near-linear under timsort's galloping merge; unstructured input is ~2x
    faster under 16-bit LSD radix. One O(n) descent count picks the path."""
    n = a.shape[0]
    if n >= _RADIX_MIN_N:
        descents = int(np.count_nonzero(a[1:] < a[:-1]))
        if descents > (n >> 6):
            return _radix16_argsort(a)
    return np.argsort(a, kind="stable")  # lint: sort-ok the kernel itself


def _sort128(sig_lo: np.ndarray, sig_hi: np.ndarray, *,
             stable: bool = True) -> np.ndarray:
    """Lexicographic argsort by (sig_lo, sig_hi), stable by default.

    Equivalent to ``np.lexsort((sig_hi, sig_lo))`` but faster: one argsort
    on the primary word (radix/run-aware when stable, introsort when the
    caller's signatures are known distinct and stability is moot), then an
    exact refinement of the (vanishingly rare for hashed sigs) equal-lo
    runs whose hi words are out of order."""
    # lint: sort-ok _sort128 is the one blessed 128-bit sort entry point
    order = _argsort64_stable(sig_lo) if stable else np.argsort(sig_lo)
    lo_s = sig_lo[order]
    dup = np.flatnonzero(lo_s[1:] == lo_s[:-1])
    if dup.shape[0]:
        hi_s = sig_hi[order]
        bad = dup[hi_s[dup + 1] < hi_s[dup]]
        if bad.shape[0]:
            # collision runs whose hi words are out of order: stable-sort
            # each such equal-lo slice by hi, each exactly once
            n = lo_s.shape[0]
            neq = np.empty((n,), bool)
            neq[0] = True
            neq[1:] = lo_s[1:] != lo_s[:-1]
            starts = np.flatnonzero(neq)
            ends = np.append(starts[1:], n)
            rid = np.searchsorted(starts, bad, side="right") - 1
            # lint: sort-ok hash-collision refinement — runs are a handful
            # of rows, reached only when equal-lo sigs are out of hi order
            for ri in np.unique(rid):
                s, e = int(starts[ri]), int(ends[ri])
                # lint: sort-ok hash-collision refinement (see above)
                order[s:e] = order[s:e][np.argsort(hi_s[s:e], kind="stable")]
    return order.astype(np.int64)


def merge128_runs(lo: np.ndarray, hi: np.ndarray,
                  starts: np.ndarray, *, cuts=None) -> np.ndarray:
    """Stable merge permutation for concatenated presorted runs.

    ``starts`` (k,) int64 holds each run's first offset (``starts[0] == 0``);
    run i spans ``[starts[i], starts[i+1])`` and is sorted by (lo, hi).
    Returns ``order`` such that ``lo[order], hi[order]`` is the stable k-way
    merge — identical to ``np.lexsort((hi, lo))`` on the whole stream (ties
    resolved by run order, then in-run position).

    ``cuts`` (optional) is a key-range shard plan from
    ``distributed.sharding.plan_key_cuts``: a (cut_lo, cut_hi) pair of
    ascending distinct 128-bit boundary keys. When given, the merge runs
    per key-range shard and concatenates — byte-identical to the unsharded
    merge (see ``_merge128_sharded``), so multi-device backends can split
    by key range and CPU gets cache-sized partitions for free.

    Backend dispatch: on the device backend the runs are merged by
    searchsorted rank-sums (2(k-1) calls of the device lower bound, no sort
    at all; see ``_merge128_ranksum``); on CPU the run-aware stable argsort
    is measurably faster (timsort's galloping merge on run-structured
    input: ~4ms vs ~40ms per 200k rows x 9 runs), so the rank-sum path is
    reserved for the kernel backend."""
    n = lo.shape[0]
    starts = np.asarray(starts, np.int64)
    if n == 0 or starts.shape[0] <= 1:
        return np.arange(n, dtype=np.int64)
    if cuts is not None and cuts[0].shape[0]:
        return _merge128_sharded(lo, hi, starts, cuts)
    if backend_uses_pallas() and starts.shape[0] <= 64:
        return _merge128_ranksum(lo, hi, starts)
    return _sort128(lo, hi)


def _merge128_sharded(lo: np.ndarray, hi: np.ndarray, starts: np.ndarray,
                      cuts) -> np.ndarray:
    """Key-range-sharded stable k-way merge, byte-identical to unsharded.

    Every run is split at the exact 128-bit LOWER bound of each cut key —
    the same rule in every run — so all elements with keys equal to a
    boundary land in the shard that begins at that boundary and equal keys
    never straddle shards. Each shard is then a self-contained stable
    k-way merge (run order and in-run position restricted to the shard are
    exactly the global tie-break restricted to the shard), so per-shard
    merges concatenated in cut order reproduce the global stable merge
    permutation element for element."""
    cut_lo, cut_hi = cuts
    n = lo.shape[0]
    k = starts.shape[0]
    s = cut_lo.shape[0] + 1
    bounds = np.append(starts, n)
    split = np.empty((k, s + 1), np.int64)
    for r in range(k):
        a, b = int(bounds[r]), int(bounds[r + 1])
        split[r, 0], split[r, s] = a, b
        split[r, 1:s] = a + searchsorted128(lo[a:b], hi[a:b],
                                            cut_lo, cut_hi, side="left")
    parts = []
    for j in range(s):
        gidx, run_starts, off = [], [], 0
        for r in range(k):
            a, b = int(split[r, j]), int(split[r, j + 1])
            if b > a:
                run_starts.append(off)
                off += b - a
                gidx.append(np.arange(a, b, dtype=np.int64))
        if not gidx:
            continue
        piece = gidx[0] if len(gidx) == 1 else np.concatenate(gidx)
        if len(run_starts) > 1:
            sub = merge128_runs(lo[piece], hi[piece],
                                np.asarray(run_starts, np.int64))
            piece = piece[sub]
        parts.append(piece)
    if not parts:
        return np.zeros((0,), np.int64)
    return parts[0] if len(parts) == 1 else np.concatenate(parts)


def _merge128_ranksum(lo: np.ndarray, hi: np.ndarray,
                      starts: np.ndarray) -> np.ndarray:
    """k-way merge by rank sums: each element's merged position is its
    in-run rank plus, per other run, the count of elements that must precede
    it (less-or-equal for earlier runs, strictly-less for later runs — that
    tie-break makes the merge stable).

    Each run serves as the search table twice: once for all later runs
    together (one contiguous query slice, ``side="right"``) and once for all
    earlier runs together (``side="left"``), so a merge of k runs issues
    2(k-1) ``searchsorted128`` calls. The device descent is fixed-depth, so
    the queries need not arrive sorted."""
    n = lo.shape[0]
    bounds = np.append(starts, n)
    k = starts.shape[0]
    # in-run rank of every element
    dest = np.arange(n, dtype=np.int64) - np.repeat(starts, np.diff(bounds))
    calls = 0
    for q in range(k):
        qs, qe = int(bounds[q]), int(bounds[q + 1])
        if qe == qs:
            continue
        t_lo, t_hi = lo[qs:qe], hi[qs:qe]
        if qe < n:
            dest[qe:] += searchsorted128(t_lo, t_hi, lo[qe:], hi[qe:],
                                         side="right")
            calls += 1
        if qs > 0:
            dest[:qs] += searchsorted128(t_lo, t_hi, lo[:qs], hi[:qs],
                                         side="left")
            calls += 1
    telemetry.PROCESS.add(RANKSUM_RUNS, k)
    telemetry.PROCESS.add(RANKSUM_CALLS, calls)
    order = np.empty((n,), np.int64)
    order[dest] = np.arange(n, dtype=np.int64)
    return order


def _shard_slices(s_lo: np.ndarray, s_hi: np.ndarray,
                  shards: int) -> np.ndarray:
    """Slice starts for a key-range-sharded boundary pass over a SORTED
    stream: equal-width candidate positions snapped to the START of the
    equal-key run containing them, so no run straddles a slice and every
    slice's first element begins a fresh run — per-slice boundary flags
    are then globally correct by construction. Returns the interior slice
    starts (ascending, distinct, possibly empty)."""
    n = s_lo.shape[0]
    pos = (np.arange(1, shards, dtype=np.int64) * n) // shards
    aligned = searchsorted128(s_lo, s_hi, s_lo[pos], s_hi[pos], side="left")
    # keys at ascending positions are non-decreasing, so aligned is too:
    # dedupe by adjacent-distinct (no sort) and drop degenerate 0 starts
    aligned = aligned[aligned > 0]
    if aligned.shape[0] > 1:
        keep = np.empty(aligned.shape, bool)
        keep[0] = True
        keep[1:] = aligned[1:] != aligned[:-1]
        aligned = aligned[keep]
    return aligned


def _boundary_flags(s_lo: np.ndarray, s_hi: np.ndarray) -> np.ndarray:
    """New-run boundary flags of one sorted slice (backend dispatch)."""
    if backend_uses_pallas():
        return _segsum_boundary(s_lo, s_hi)
    n = s_lo.shape[0]
    neq = np.empty((n,), bool)
    neq[0] = True
    neq[1:] = (s_lo[1:] != s_lo[:-1]) | (s_hi[1:] != s_hi[:-1])
    return neq


def diff_aggregate(sig_lo: np.ndarray, sig_hi: np.ndarray,
                   signs: np.ndarray, *, presorted: bool = False,
                   shards: int = 1):
    """Sort a signed stream by 128-bit signature and aggregate runs.

    Returns (order, DiffAgg): ``order`` is the permutation applied (identity
    if presorted). Runs are maximal groups of equal (sig_lo, sig_hi).

    ``shards > 1`` partitions the boundary pass into key-range slices
    aligned to run starts (``_shard_slices``) — byte-identical flags,
    embarrassingly parallel per slice. Only meaningful with ``presorted``
    (an unsorted stream pays the sort first and shards nothing).
    """
    n = sig_lo.shape[0]
    if n == 0:
        return np.zeros((0,), np.int64), DiffAgg(np.zeros((0,), bool), np.zeros((0,), np.int32))
    if presorted:
        order = np.arange(n, dtype=np.int64)
        s_lo, s_hi, s_sg = sig_lo, sig_hi, np.asarray(signs, np.int32)
    else:
        order = _sort128(sig_lo, sig_hi)
        s_lo, s_hi = sig_lo[order], sig_hi[order]
        s_sg = np.asarray(signs, np.int32)[order]

    if presorted and shards > 1 and n > shards:
        starts = _shard_slices(s_lo, s_hi, shards)
        if starts.shape[0]:
            bnd = np.empty((n,), bool)
            edges = np.concatenate([[0], starts, [n]])
            for a, b in zip(edges[:-1], edges[1:]):
                bnd[a:b] = _boundary_flags(s_lo[a:b], s_hi[a:b])
            return order, DiffAgg(bnd, s_sg)

    return order, DiffAgg(_boundary_flags(s_lo, s_hi), s_sg)


def _segsum_boundary(s_lo: np.ndarray, s_hi: np.ndarray) -> np.ndarray:
    """New-run boundary flags of a sorted stream via the segsum kernel."""
    n = s_lo.shape[0]
    rows = -(-n // LANES)
    block_rows = min(SEG_BLOCK_ROWS, -(-rows // 8) * 8)
    block = block_rows * LANES
    with telemetry.span(SP_STAGE):
        lanes = [_pad_rows(x, block).reshape(-1, LANES)
                 for w in (s_lo, s_hi) for x in unpack64(w)]
        flags = boundary_pallas(*_to_device(*lanes),
                                block_rows=block_rows, interpret=_interp())
    with telemetry.span(SP_FETCH):
        bnd = np.asarray(flags).reshape(-1)[:n] != 0
    # a block's first element has its predecessor in the previous block
    first = np.arange(block, n, block)
    bnd[first] = ((s_lo[first] != s_lo[first - 1])
                  | (s_hi[first] != s_hi[first - 1]))
    bnd[0] = True
    return bnd


def _boundary_flags_rows(k_lo, k_hi, r_lo, r_hi, same: bool) -> np.ndarray:
    """(key OR row)-change boundary flags of one key-sorted slice."""
    if backend_uses_pallas():
        bnd = _segsum_boundary(k_lo, k_hi)
        if not same:
            bnd |= _segsum_boundary(r_lo, r_hi)
        return bnd
    n = k_lo.shape[0]
    neq = np.empty((n,), bool)
    neq[0] = True
    neq[1:] = (k_lo[1:] != k_lo[:-1]) | (k_hi[1:] != k_hi[:-1])
    if not same:
        neq[1:] |= (r_lo[1:] != r_lo[:-1]) | (r_hi[1:] != r_hi[:-1])
    return neq


def diff_aggregate_rows(key_lo: np.ndarray, key_hi: np.ndarray,
                        row_lo: np.ndarray, row_hi: np.ndarray,
                        signs: np.ndarray, *, presorted: bool = False,
                        shards: int = 1):
    """Aggregate a signed stream into (key, row-signature) runs along KEY
    order — the sort-free execution of Listing-2 value grouping.

    The stream must be (or is stably made) sorted by (key_lo, key_hi); runs
    are maximal groups of equal (key, row). For NoPK streams key == row, so
    this is exactly value-group aggregation; for PK streams each run is a
    sub-group of one key's (≤ 2-element, by PK uniqueness) run, so
    equal-valued ± pairs cancel exactly as the row-sorted aggregation would,
    while the key order itself is free at emission time.

    ``shards > 1`` partitions the boundary pass into key-range slices
    aligned to KEY-run starts — a key-run start is also a (key, row) group
    start, so the per-slice flags are globally correct and byte-identical
    to the unsharded pass. Only meaningful with ``presorted``.

    Returns (order, DiffAgg); ``order`` is identity when presorted.
    """
    n = key_lo.shape[0]
    if n == 0:
        return (np.zeros((0,), np.int64),
                DiffAgg(np.zeros((0,), bool), np.zeros((0,), np.int32)))
    if presorted:
        order = np.arange(n, dtype=np.int64)
        k_lo, k_hi, r_lo, r_hi = key_lo, key_hi, row_lo, row_hi
        s_sg = np.asarray(signs, np.int32)
    else:
        order = _sort128(key_lo, key_hi)
        k_lo, k_hi = key_lo[order], key_hi[order]
        r_lo, r_hi = row_lo[order], row_hi[order]
        s_sg = np.asarray(signs, np.int32)[order]

    same = r_lo is k_lo and r_hi is k_hi  # NoPK: key IS the row signature
    if presorted and shards > 1 and n > shards:
        starts = _shard_slices(k_lo, k_hi, shards)
        if starts.shape[0]:
            bnd = np.empty((n,), bool)
            edges = np.concatenate([[0], starts, [n]])
            for a, b in zip(edges[:-1], edges[1:]):
                bnd[a:b] = _boundary_flags_rows(
                    k_lo[a:b], k_hi[a:b], r_lo[a:b], r_hi[a:b], same)
            return order, DiffAgg(bnd, s_sg)

    return order, DiffAgg(
        _boundary_flags_rows(k_lo, k_hi, r_lo, r_hi, same), s_sg)


# --------------------------------------------------------- attention entry

def attention(q, k, v, *, causal: bool = True, impl: str = "auto",
              block_q: int = 256, block_k: int = 256,
              interpret: bool = False):
    """Attention dispatcher for the model stack.

    q: (B,S,H,hd); k/v: (B,Sk,KV,hd) (GQA: H % KV == 0). impl:
      * "pallas" — the flash kernel (TPU target; the §Perf lever that keeps
        score tiles in VMEM). GQA handled by repeating kv heads.
      * "xla"    — models.layers.block_causal_attention (the measured
        dry-run path; HLO cost model sees its dots).
      * "auto"   — pallas on TPU backends, xla elsewhere.
    """
    from ..models.layers import block_causal_attention
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return block_causal_attention(q, k, v, causal=causal, block=block_q)
    from .flash_attention import flash_attention_pallas
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, v.shape[1], hd)
    bq = min(block_q, S)
    while S % bq:
        bq -= 1
    out = flash_attention_pallas(qf, kf, vf, causal=causal, block_q=bq,
                                 block_k=min(block_k, kf.shape[1]),
                                 interpret=interpret or _interp())
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
