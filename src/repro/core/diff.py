"""SNAPSHOT DIFF (paper §3, §5.1).

Two execution paths, benchmarked against each other exactly as the paper
does:

  * ``snapshot_diff``  — the built-in path: Δ-object scan + diff aggregation.
    Cost ∝ changed data.
  * ``sql_diff``       — the Listing-2 SQL-equivalent baseline: full scans of
    both snapshots, UNION ALL with ±1, GROUP BY all columns, HAVING ≠ 0.
    Cost ∝ table size.

Both return the same ``DiffResult``: per surviving value-group, the net count
(diffCnt, <0 ⇒ only in the left snapshot, >0 ⇒ only in the right) plus the
payload. Payload values are gathered lazily by rowid — only for surviving
rows (the paper's "lookup ... only if needed").
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from ..kernels import ops
from . import telemetry
from .delta import DeltaStats, SignedStream, full_scan_stream, signed_delta

SP_DIFF = telemetry.register_span(
    "diff", "SNAPSHOT DIFF: Δ-scan + diff aggregation")
SP_GATHER = telemetry.register_span(
    "gather_payload", "materialize full rows by physical rowid")
from .directory import Snapshot
from .objects import ObjectStore, rowid_off, rowid_oid
from .schema import CType, Schema, concat_batches, take_batch
from .sigs import SigBatch


@dataclass
class DiffResult:
    """Result of SNAPSHOT DIFF between snapshots (left=a, right=b)."""
    schema: Schema
    diff_cnt: np.ndarray          # (k,) int32 net count per surviving group
    key_lo: np.ndarray            # (k,) uint64 key signature of the group
    key_hi: np.ndarray
    row_lo: np.ndarray            # (k,) uint64 value signature of the group
    row_hi: np.ndarray
    rowid: np.ndarray             # (k,) uint64 representative payload row
    stats: DeltaStats = field(default_factory=DeltaStats)

    @property
    def n_groups(self) -> int:
        return int(self.diff_cnt.shape[0])

    def is_empty(self) -> bool:
        return self.n_groups == 0

    def payload(self, store: ObjectStore) -> Dict[str, np.ndarray]:
        """Gather the representative row values for each surviving group."""
        return gather_payload(store, self.schema, self.rowid)

    def per_key_conflicts(self):
        """Group surviving value-groups by key signature: keys with entries
        from BOTH snapshots are the paper's 'potential conflicts'.

        Vectorized: per-run sign presence via segmented reductions; only
        the (typically few) conflicting runs are materialized."""
        if self.n_groups == 0:
            return []
        # NoPK results are value-sorted and key == value, so the key
        # grouping is free; PK results need the (small) key sort
        order, agg = ops.diff_aggregate(self.key_lo, self.key_hi,
                                        np.ones_like(self.diff_cnt),
                                        presorted=not self.schema.has_pk)
        starts = agg.run_starts
        sg = np.sign(self.diff_cnt[order])
        any_pos = np.add.reduceat((sg > 0).astype(np.int64), starts) > 0
        any_neg = np.add.reduceat((sg < 0).astype(np.int64), starts) > 0
        both = any_pos & any_neg
        return [order[s:s + l]
                for s, l in zip(starts[both], agg.run_lens[both])]


def gather_payload(store: ObjectStore, schema: Schema,
                   rowids: np.ndarray, *, with_sigs: bool = False,
                   runs: Optional[np.ndarray] = None):
    """Materialize full rows by physical rowid (preserves input order).

    ``with_sigs=True`` returns ``(batch, SigBatch)``: the rows' write-once
    row/key signatures and LOB content signatures gathered from the same
    objects — zero hashing — so the batch can be re-sealed verbatim
    (``Txn.insert(..., sigs=...)``). ``runs`` is the CALLER's sortedness
    claim about the ``rowids`` sequence (key-sorted run-start offsets; the
    gather preserves input order, so the claim transfers to the batch) and
    is carried into the sidecar untouched. Never claim runs that aren't
    real — the seal path's order depends on it."""
    with telemetry.span(SP_GATHER):
        return _gather_payload(store, schema, rowids, with_sigs, runs)


def _gather_payload(store: ObjectStore, schema: Schema, rowids: np.ndarray,
                    with_sigs: bool, runs: Optional[np.ndarray]):
    n = rowids.shape[0]
    oids = rowid_oid(rowids)
    offs = rowid_off(rowids)
    alias = with_sigs and not schema.has_pk
    lob_names = ([c.name for c in schema.columns if c.ctype is CType.LOB]
                 if with_sigs else [])
    if n and oids[0] == oids[-1] and (oids == oids[0]).all():
        # single-object fast path (the common post-compaction merge shape):
        # every rowid lives in ONE object, so the per-object split, concat
        # and inverse-permutation round-trip all collapse into direct takes
        obj = store.get(int(oids[0]))
        batch = take_batch(obj.cols, offs)
        if not with_sigs:
            return batch
        row_lo, row_hi = obj.row_lo[offs], obj.row_hi[offs]
        if alias:
            key_lo, key_hi = row_lo, row_hi
        else:
            key_lo, key_hi = obj.key_lo[offs], obj.key_hi[offs]
        lob = {c: obj.lob_sigs[c][offs] for c in lob_names}
        return batch, SigBatch(row_lo, row_hi, key_lo, key_hi, lob,
                               runs=runs)
    batches, perm, sig_parts = [], [], []
    for oid in np.unique(oids):
        sel = np.flatnonzero(oids == oid)
        obj = store.get(int(oid))
        o = offs[sel]
        batches.append(take_batch(obj.cols, o))
        perm.append(sel)
        if with_sigs:
            sig_parts.append(
                (obj.row_lo[o], obj.row_hi[o],
                 None if alias else obj.key_lo[o],
                 None if alias else obj.key_hi[o],
                 {c: obj.lob_sigs[c][o] for c in lob_names}))
    if not batches:
        empty = concat_batches(schema, [])
        if not with_sigs:
            return empty
        z64 = np.zeros((0,), np.uint64)
        return empty, SigBatch(z64, z64, z64, z64,
                               {c: z64 for c in lob_names},
                               runs=np.zeros((0,), np.int64))
    merged = concat_batches(schema, batches)
    flat = np.concatenate(perm)
    if flat.shape[0] > 1 and (flat[1:] > flat[:-1]).all():
        # ascending oids ⇒ the per-object concat order IS the input order:
        # skip building (and applying) the inverse permutation entirely
        inv = None
        batch = merged
    else:
        inv = np.empty((n,), np.int64)
        inv[flat] = np.arange(n)
        batch = take_batch(merged, inv)
    if not with_sigs:
        return batch
    reorder = (lambda a: a) if inv is None else (lambda a: a[inv])
    row_lo = reorder(np.concatenate([p[0] for p in sig_parts]))
    row_hi = reorder(np.concatenate([p[1] for p in sig_parts]))
    if alias:
        key_lo, key_hi = row_lo, row_hi
    else:
        key_lo = reorder(np.concatenate([p[2] for p in sig_parts]))
        key_hi = reorder(np.concatenate([p[3] for p in sig_parts]))
    lob = {c: reorder(np.concatenate([p[4][c] for p in sig_parts]))
           for c in lob_names}
    return batch, SigBatch(row_lo, row_hi, key_lo, key_hi, lob, runs=runs)


def gather_rowsigs(store: ObjectStore,
                   rowids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Row-value signatures at physical rowids (preserves input order).

    The Δ-sized value identity probe: two rows are byte-identical iff their
    128-bit row signatures match, so revert's "is the current row still the
    one being reverted away?" check never gathers payloads."""
    oids = rowid_oid(rowids)
    offs = rowid_off(rowids)
    if rowids.shape[0] and oids[0] == oids[-1] and (oids == oids[0]).all():
        obj = store.get(int(oids[0]))  # single-object fast path
        return obj.row_lo[offs], obj.row_hi[offs]
    lo = np.zeros(rowids.shape, np.uint64)
    hi = np.zeros(rowids.shape, np.uint64)
    for oid in np.unique(oids):
        sel = oids == oid
        obj = store.get(int(oid))
        lo[sel] = obj.row_lo[offs[sel]]
        hi[sel] = obj.row_hi[offs[sel]]
    return lo, hi


def _aggregate_stream(schema: Schema, stream: SignedStream,
                      stats: DeltaStats,
                      store: Optional[ObjectStore] = None) -> DiffResult:
    """Diff aggregation: cancel identical changes, keep net per value-group.

    Grouping is by full row signature (Listing-2 multiset semantics),
    executed sort-free along the stream's presorted key order: for NoPK
    streams key == value so the groups are immediate; for PK streams the
    value groups are sub-groups of the (≤ 2-element) per-key runs and only
    the *surviving* groups pay a final sort into the value-signature output
    order. The representative payload rowid per group prefers a + row
    (payload exists in the right snapshot) and falls back to a − row
    (gathered from the left / base objects — the paper's tombstone join)."""
    if stream.n == 0:
        z64 = np.zeros((0,), np.uint64)
        return DiffResult(schema, np.zeros((0,), np.int32),
                          z64, z64, z64, z64, z64, stats)
    # streams served from the delta memo are immutable, so their aggregation
    # is a pure function too: reuse it across repeated diffs of the same
    # directory pair (fields are shared read-only; stats stay per-op)
    memo = getattr(stream, "_agg_memo", None)
    if memo is not None:
        return DiffResult(schema, *memo, stats)
    # key-range sharding (derived plan, never WAL-logged): big streams
    # merge and aggregate per shard — byte-identical to unsharded
    from ..distributed import sharding as ksh
    shards = ksh.key_shard_count(stream.n)
    cuts = None
    if shards > 1 and not stream.sorted_by_key and stream.runs is not None:
        cuts = ksh.plan_key_cuts(stream.key_lo, stream.key_hi,
                                 stream.runs, shards)
        if cuts is not None and store is not None:
            store.metrics.add("probe.shard_parts", cuts[0].shape[0] + 1)
    st = stream.merge_by_key(cuts=cuts)  # always globally key-sorted, n > 0
    _, agg = ops.diff_aggregate_rows(st.key_lo, st.key_hi,
                                     st.row_lo, st.row_hi, st.sign,
                                     presorted=True, shards=shards)
    surviving = agg.run_sums != 0
    if surviving.all():  # pure-churn diffs: nothing cancelled
        keep = slice(None)
        diff_cnt, starts = agg.run_sums, agg.run_starts
    else:
        keep = np.flatnonzero(surviving)
        diff_cnt, starts = agg.run_sums[keep], agg.run_starts[keep]
    # representative rowid: first element in the run whose sign matches the
    # net direction (all elements share the same value, so any matching-sign
    # element's payload is correct). The run head already matches in the
    # overwhelmingly common case (single-element runs, or net in the head's
    # direction); only mismatching runs pay the per-run argmin.
    n = st.n
    want = np.sign(agg.run_sums)
    rep_pos = agg.run_starts.copy()
    bad = np.flatnonzero((st.sign[agg.run_starts] != want)
                         & (agg.run_sums != 0))
    if bad.shape[0]:
        seg, base, flat = ops.segment_expand(agg.run_starts[bad],
                                             agg.run_lens[bad])
        score = np.where(st.sign[flat] == want[bad][seg], flat, n)
        rep_pos[bad] = np.minimum.reduceat(score, base)
    key_lo, key_hi = st.key_lo[starts], st.key_hi[starts]
    row_lo = key_lo if st.row_lo is st.key_lo else st.row_lo[starts]
    row_hi = key_hi if st.row_hi is st.key_hi else st.row_hi[starts]
    fields = [diff_cnt.astype(np.int32), key_lo, key_hi, row_lo, row_hi,
              st.rowid[rep_pos[keep]]]
    if not st.key_is_row and diff_cnt.shape[0] > 1:
        # PK stream: groups surfaced in key order, but the DiffResult
        # contract is value-signature order — sort just the survivors
        # (distinct signatures, so an unstable primary sort is exact)
        fo = ops._sort128(fields[3], fields[4], stable=False)
        fields = [f[fo] for f in fields]
    fields = tuple(fields)
    for a in fields:
        a.setflags(write=False)
    stream._agg_memo = fields
    return DiffResult(schema, *fields, stats)


def snapshot_diff(store: ObjectStore, a: Snapshot, b: Snapshot) -> DiffResult:
    """Built-in SNAPSHOT DIFF: Δ-scan + diff aggregation (paper §5.1)."""
    if not a.schema.compatible_with(b.schema):
        raise ValueError("SNAPSHOT DIFF: snapshots have incompatible schemas")
    with telemetry.span(SP_DIFF):
        stats = DeltaStats()
        stream = signed_delta(store, a.directory, b.directory, stats)
        return _aggregate_stream(a.schema, stream, stats, store)


def sql_diff(store: ObjectStore, a: Snapshot, b: Snapshot) -> DiffResult:
    """Listing-2 baseline: full scan of both snapshots + global aggregation."""
    if not a.schema.compatible_with(b.schema):
        raise ValueError("SNAPSHOT DIFF: snapshots have incompatible schemas")
    stats = DeltaStats()
    stream = SignedStream.concat([
        full_scan_stream(store, a.directory, -1, stats),
        full_scan_stream(store, b.directory, +1, stats),
    ])
    return _aggregate_stream(a.schema, stream, stats, store)
