"""Table: schema + current directory + PITR history + key probes."""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..kernels import ops
from . import telemetry
from .directory import Directory
from .objects import DataObject, pack_rowid
from .schema import CType, Schema, concat_batches, take_batch
from .sigs import SigBatch
from .visibility import visibility_index

SP_LOCATE_KEYS = telemetry.register_span(
    "table.locate_keys", "PK probe: zone-map pruning and one fused probe "
    "per candidate object")


class Table:
    def __init__(self, name: str, schema: Schema, store, ts: int):
        self.name = name
        self.schema = schema
        self._store = store
        self.directory = Directory.empty(ts)
        # PITR history: every directory version, trimmed by Engine GC.
        # Kept sorted by apply-ts (see _history_append) so directory_at is
        # a bisect, not a linear scan.
        self.history: List[Tuple[int, Directory]] = [(ts, self.directory)]

    # ------------------------------------------------------------- state
    def _history_append(self, d: Directory) -> None:
        """Append a directory version, keeping history sorted by ts.

        An out-of-order apply-ts (RESTORE to an older snapshot) shadows
        every existing entry with ts >= its own — those entries could never
        be returned by directory_at again (the restored version is applied
        later and wins any horizon that admits them) — so they are pruned,
        preserving linear-scan semantics exactly."""
        while self.history and self.history[-1][0] >= d.ts:
            self.history.pop()
        self.history.append((d.ts, d))

    def set_directory(self, d: Directory) -> None:
        old = self.directory
        self.directory = d
        self._history_append(d)
        # incremental visibility maintenance: derive the new version's
        # tombstone-target array from the parent's (sorted merge of the
        # freshly sealed batches) instead of re-sorting the world
        cache = self._store.vis_cache
        if cache is not None:
            cache.extend(old, d)

    def trim_history(self, retention: int, pinned_ts=()) -> int:
        """Trim PITR history to the trailing ``retention`` versions while
        keeping every entry still needed to serve ``directory_at`` of a
        pinned horizon (open PR bases, lineage snapshots, branch points).

        For each pin the *latest* entry with apply-ts <= pin survives — the
        one ``directory_at(pin)`` resolves to — so a pinned horizon can
        never be collected out from under its holder. Returns the number of
        entries pruned.

        ``retention <= 0`` keeps everything (the pre-existing
        ``history[-0:]`` semantics of Engine(retention_versions=0))."""
        n = len(self.history)
        if retention <= 0 or n <= retention:
            return 0
        keep = set(range(n - retention, n))
        for ts in pinned_ts:
            i = bisect.bisect_right(self.history, ts, key=lambda e: e[0])
            if i > 0:
                keep.add(i - 1)
        kept = [self.history[i] for i in sorted(keep)]
        pruned = n - len(kept)
        self.history = kept
        return pruned

    def directory_at(self, ts: int) -> Directory:
        """PITR: latest directory version with apply-ts <= ts, horizon ts."""
        i = bisect.bisect_right(self.history, ts, key=lambda e: e[0])
        if i == 0:
            raise KeyError(f"no PITR history for {self.name} at ts={ts}")
        best = self.history[i - 1][1]
        return Directory(best.data_oids, best.tomb_oids, ts)

    # -------------------------------------------------------------- scan
    def scan(self, directory: Optional[Directory] = None,
             with_sigs: bool = False):
        """Materialize all visible rows: (batch, rowids[, row_lo, row_hi])."""
        if with_sigs:
            batch, rid, sigs = self._scan_walk(directory, carry=True)
            return batch, rid, sigs.row_lo, sigs.row_hi
        batch, rid, _ = self._scan_walk(directory, carry=False)
        return batch, rid

    def scan_carry(self, directory: Optional[Directory] = None):
        """Materialize all visible rows WITH their signature sidecar.

        Returns (batch, rowids, SigBatch): row/key signature lanes and LOB
        content signatures gathered straight from the sealed objects (zero
        hashing), plus ``runs`` offsets — every object's visible subset is
        an ascending slice of a key-sorted object, i.e. one presorted run.
        Feeding the result into ``Txn.insert(..., sigs=...)`` re-seals the
        rows without rehashing (clone materialization, ALTER rewrites)."""
        return self._scan_walk(directory, carry=True)

    def _scan_walk(self, directory: Optional[Directory], carry: bool):
        """The one visibility walk behind every scan variant. ``carry``
        additionally collects the signature sidecar (returned third slot
        is a SigBatch; None otherwise)."""
        d = directory or self.directory
        vi = visibility_index(self._store, d)
        alias = not self.schema.has_pk
        lob_names = ([c.name for c in self.schema.columns
                      if c.ctype is CType.LOB] if carry else [])
        batches, rowids, rlo, rhi, klo, khi = [], [], [], [], [], []
        lob = {c: [] for c in lob_names}
        runs, off = [], 0
        for oid in d.data_oids:
            obj: DataObject = self._store.get(oid)
            if obj.nrows == 0:
                continue
            if vi.fully_visible(obj):
                # zone-pruned objects contribute their immutable arrays
                # directly — no mask, no gather (concat copies once below)
                idx = None
            else:
                m = vi.visible_mask(obj)
                if not m.any():
                    continue
                idx = np.flatnonzero(m)
            take = (lambda a: a) if idx is None else (lambda a: a[idx])
            batches.append(obj.cols if idx is None
                           else take_batch(obj.cols, idx))
            rowids.append(obj.rowids() if idx is None
                          else pack_rowid(oid, idx.astype(np.uint64)))
            if not carry:
                continue
            rlo.append(take(obj.row_lo))
            rhi.append(take(obj.row_hi))
            if not alias:
                klo.append(take(obj.key_lo))
                khi.append(take(obj.key_hi))
            for c in lob_names:
                lob[c].append(take(obj.lob_sigs[c]))
            runs.append(off)
            off += rlo[-1].shape[0]
        batch = concat_batches(self.schema, batches)
        z64 = np.zeros((0,), np.uint64)
        rid = np.concatenate(rowids) if rowids else z64
        if not carry:
            return batch, rid, None
        row_lo = np.concatenate(rlo) if rlo else z64
        row_hi = np.concatenate(rhi) if rhi else z64
        if alias:
            key_lo, key_hi = row_lo, row_hi
        else:
            key_lo = np.concatenate(klo) if klo else z64
            key_hi = np.concatenate(khi) if khi else z64
        sigs = SigBatch(
            row_lo, row_hi, key_lo, key_hi,
            {c: (np.concatenate(v) if v else z64) for c, v in lob.items()},
            runs=np.asarray(runs, np.int64))
        return batch, rid, sigs

    def count(self, directory: Optional[Directory] = None) -> int:
        d = directory or self.directory
        vi = visibility_index(self._store, d)
        return int(sum(vi.visible_count(self._store.get(o))
                       for o in d.data_oids))

    # ------------------------------------------------------------ probes
    def locate_keys(self, key_lo: np.ndarray, key_hi: np.ndarray,
                    directory: Optional[Directory] = None) -> np.ndarray:
        """PK probe: rowid of the visible row per key signature, 0 if absent.

        LSM probe with zone-map pruning; per-object fused ``ops.probe128``
        pass. PK uniqueness -> at most one visible match. Query batches
        should arrive sorted by (key_lo, key_hi) — the fused-probe contract
        (ROADMAP §Performance); the merge planner's batches are run starts
        of key-sorted streams, so this is free for the hot callers.
        """
        with telemetry.span(SP_LOCATE_KEYS):
            return self._locate_keys(key_lo, key_hi, directory)

    def _locate_keys(self, key_lo: np.ndarray, key_hi: np.ndarray,
                     directory: Optional[Directory]) -> np.ndarray:
        d = directory or self.directory
        vi = visibility_index(self._store, d)
        q = key_lo.shape[0]
        m = self._store.metrics
        m.add("probe.queries", q)
        out = np.zeros((q,), np.uint64)
        # sorted queries (the hot-caller contract) turn each object's zone
        # filter into two binary searches + one unresolved scan over the
        # window, instead of full-length masks per object
        srt = q > 1 and bool((key_lo[1:] >= key_lo[:-1]).all())
        pending = None if srt else np.arange(q)
        for oid in reversed(d.data_oids):  # newest objects first
            if pending is not None and pending.shape[0] == 0:
                break
            obj: DataObject = self._store.get(oid)
            if obj.nrows == 0:
                continue
            zmin, zmax = obj.zone
            if srt:
                a = int(np.searchsorted(key_lo, zmin, side="left"))
                b = int(np.searchsorted(key_lo, zmax, side="right"))
                cand = (a + np.flatnonzero(out[a:b] == 0) if b > a
                        else np.zeros((0,), np.int64))
            else:
                sel = (key_lo[pending] >= zmin) & (key_lo[pending] <= zmax)
                cand = pending[sel]
            if cand.shape[0] == 0:
                m.add("probe.objects_pruned")
                continue
            found = self._probe_object(obj, vi, key_lo[cand], key_hi[cand])
            hit = found != 0
            m.add("probe.hits", int(hit.sum()))
            out[cand[hit]] = found[hit]
            if not srt:
                pending = np.concatenate([pending[~sel], cand[~hit]])
        return out

    def _probe_object(self, obj: DataObject, vi,
                      q_lo: np.ndarray, q_hi: np.ndarray) -> np.ndarray:
        """rowids of visible matches of (q_lo, q_hi) in obj (0 = miss).

        One fused ``ops.probe128`` pass hands every query its exact-key run
        ``[start, start + cnt)`` directly — no lower/upper-bound pair on
        the lo64 word, no lo64-collision-run expansion. Run heads that are
        visible resolve immediately (the overwhelmingly common case); only
        runs with an invisible head AND length > 1 expand, and the
        expansion covers exactly-equal keys only."""
        n = obj.nrows
        self._store.metrics.add("probe.objects_probed")
        out = np.zeros(q_lo.shape, np.uint64)
        start, cnt = ops.probe128(obj.key_lo, obj.key_hi, q_lo, q_hi)
        hit = cnt > 0
        if not hit.any():
            return out
        vis = vi.visible_mask(obj)
        head = hit & vis[np.minimum(start, n - 1)]
        out[head] = pack_rowid(obj.oid, start[head].astype(np.uint64))
        deep = np.flatnonzero(hit & ~head & (cnt > 1))
        if deep.shape[0]:
            self._store.metrics.add("probe.expansions", int(deep.shape[0]))
            seg, base, flat = ops.segment_expand(start[deep] + 1,
                                                 cnt[deep] - 1)
            first = np.minimum.reduceat(np.where(vis[flat], flat, n), base)
            found = first < n
            out[deep[found]] = pack_rowid(obj.oid,
                                          first[found].astype(np.uint64))
        return out

    def locate_rowsig_multi(self, sig_lo: np.ndarray, sig_hi: np.ndarray,
                            need: np.ndarray,
                            directory: Optional[Directory] = None,
                            *, flat: bool = False):
        """NoPK probe: up to ``need[i]`` visible rowids per row-signature.

        Used by merge to delete k rows among duplicates (paper §3 NoPK
        cardinality resolution). Vectorized: per object, one fused
        ``ops.probe128`` pass hands every still-needy signature its
        exact-key run; only genuine duplicate runs expand (over equal keys
        only — never whole lo64-collision runs), matches are ranked within
        their query segment by a cumulative count and the first
        ``remaining`` of them taken — no nested per-row Python loop.

        ``flat=True`` returns one query-ordered rowid array (exactly the
        concatenation of the per-query buckets), skipping the Python-level
        per-query split — use it when the caller treats all hits alike."""
        d = directory or self.directory
        vi = visibility_index(self._store, d)
        q = sig_lo.shape[0]
        m = self._store.metrics
        m.add("probe.queries", q)
        part_rows: List[np.ndarray] = []   # flat (rowid, query) accumulation
        part_qids: List[np.ndarray] = []
        remaining = need.astype(np.int64).copy()
        # sorted queries: zone windows by binary search (see locate_keys)
        srt = q > 1 and bool((sig_lo[1:] >= sig_lo[:-1]).all())
        for oid in reversed(d.data_oids):
            obj: DataObject = self._store.get(oid)
            if obj.nrows == 0:
                continue
            zmin, zmax = obj.zone
            if srt:
                a = int(np.searchsorted(sig_lo, zmin, side="left"))
                b = int(np.searchsorted(sig_lo, zmax, side="right"))
                act = (a + np.flatnonzero(remaining[a:b] > 0) if b > a
                       else np.zeros((0,), np.int64))
            else:
                act = np.flatnonzero(remaining > 0)
                if act.shape[0] == 0:
                    break
                act = act[(sig_lo[act] >= zmin) & (sig_lo[act] <= zmax)]
            if act.shape[0] == 0:
                m.add("probe.objects_pruned")
                continue
            m.add("probe.objects_probed")
            start, lens = ops.probe128(obj.key_lo, obj.key_hi,
                                       sig_lo[act], sig_hi[act])
            nz = lens > 0
            act, start, lens = act[nz], start[nz], lens[nz]
            if act.shape[0] == 0:
                continue
            vis = vi.visible_mask(obj)
            if bool((lens == 1).all()):
                # unique signatures (the overwhelmingly common case): the
                # run IS its head — no expansion, no rank machinery
                ok = vis[start]
                hit_off = start[ok]
                if hit_off.shape[0]:
                    m.add("probe.hits", int(hit_off.shape[0]))
                    part_rows.append(pack_rowid(obj.oid,
                                                hit_off.astype(np.uint64)))
                    part_qids.append(act[ok])
                    remaining[act[ok]] -= 1
                continue
            m.add("probe.expansions", int((lens > 1).sum()))
            seg, base, offs = ops.segment_expand(start, lens)
            match = vis[offs].astype(np.int64)  # keys equal by construction
            # rank of each match within its query segment (1-based)
            cm = np.cumsum(match)
            seg_base = cm[base] - match[base]
            rank = cm - seg_base[seg]
            take = (match > 0) & (rank <= remaining[act][seg])
            taken = np.flatnonzero(take)
            if taken.shape[0]:
                m.add("probe.hits", int(taken.shape[0]))
                part_rows.append(pack_rowid(obj.oid,
                                            offs[taken].astype(np.uint64)))
                part_qids.append(act[seg[taken]])
            remaining[act] -= np.add.reduceat(take.astype(np.int64), base)
        # bucket the flat hits per query in one pass (stable by discovery
        # order: newest object first, ascending offset within object)
        empty = np.zeros((0,), np.uint64)
        if not part_rows:
            return empty if flat else [empty] * q
        rows = np.concatenate(part_rows)
        qids = np.concatenate(part_qids)
        order = np.argsort(qids, kind="stable")
        rows, qids = rows[order], qids[order]
        if flat:
            return rows
        found = [empty] * q
        cuts = np.flatnonzero(qids[1:] != qids[:-1]) + 1
        heads = np.concatenate([[0], cuts])
        for qi, part in zip(qids[heads], np.split(rows, cuts)):
            found[qi] = part
        return found
