"""MVCC visibility: which rows of which objects a directory can see.

A row r of data object o is visible in directory d iff

    commit_ts[r] <= d.ts   AND   no tombstone t in d with
                                 t.target == rowid(r) and t.commit_ts <= d.ts

Tombstone membership tests are range queries on the per-directory sorted
target array (objects own contiguous rowid ranges), served by the
device lower bound (``kernels/search.py``) via ``ops.lower_bound``.

Hot-path design (ISSUE 1): the sorted target array depends only on
``(d.tomb_oids, d.ts)`` and the immutable tombstone objects, so it is built
once per *directory version* and cached in the store's ``VisibilityCache``
— not rebuilt per operation.  Commits extend the parent version's array
incrementally (sorted merge of the freshly sealed tombstone batch) instead
of re-sorting the world.  The array is partitioned per data object (objects
own contiguous rowid ranges in the sorted array), so ``killed_mask`` slices
instead of searching, objects without tombstones skip masking entirely, and
per-object commit-ts zones let fully-visible objects skip the horizon
compare too.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Optional, Tuple

import numpy as np

from ..kernels import ops
from . import telemetry
from .directory import Directory
from .objects import DataObject, ObjectStore, pack_rowid, rowid_oid

SP_VIS_BUILD = telemetry.register_span(
    "visibility.build", "build a directory's sorted tombstone-target "
    "array from scratch (the cache-miss path)")

_EMPTY_U64 = np.zeros((0,), np.uint64)
_EMPTY_U64.setflags(write=False)


class _Entry:
    """One cached directory version: the sorted target array, its lazy
    per-object partition, and whether the ts-horizon filter dropped rows
    while building (if it did not, the array can be extended to any later
    horizon without rebuilding). ``ts_arr`` (lazy) aligns each target's
    tombstone commit_ts with the sorted array so historical PITR horizons
    derive by masking on commit_ts instead of rebuilding from objects."""

    __slots__ = ("targets", "slices", "complete", "ts_arr")

    def __init__(self, targets: np.ndarray, complete: bool):
        targets.setflags(write=False)
        self.targets = targets
        self.slices: Optional[Dict[int, Tuple[int, int]]] = None
        self.complete = complete
        self.ts_arr: Optional[np.ndarray] = None

    def object_slices(self) -> Dict[int, Tuple[int, int]]:
        if self.slices is None:
            t = self.targets
            if t.shape[0] == 0:
                self.slices = {}
            else:
                oids = rowid_oid(t)
                bnd = np.flatnonzero(oids[1:] != oids[:-1]) + 1
                starts = np.concatenate([[0], bnd])
                ends = np.concatenate([bnd, [t.shape[0]]])
                self.slices = {int(oids[s]): (int(s), int(e))
                               for s, e in zip(starts, ends)}
        return self.slices


def _build_entry(store: ObjectStore, d: Directory) -> _Entry:
    targets, complete = [], True
    ts = np.uint64(d.ts)
    for oid in d.tomb_oids:
        t = store.get(oid)
        m = t.commit_ts <= ts
        if m.all():
            targets.append(t.target)
        else:
            complete = False
            targets.append(t.target[m])
    arr = (np.sort(np.concatenate(targets)) if targets
           else _EMPTY_U64)
    return _Entry(arr, complete)


class KeyedLRU:
    """Tiny keyed LRU shared by the visibility and delta caches."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._cache: OrderedDict = OrderedDict()

    def lookup(self, key):
        v = self._cache.get(key)
        if v is not None:
            self._cache.move_to_end(key)
        return v

    def insert(self, key, value) -> None:
        self._cache[key] = value
        self._cache.move_to_end(key)
        while len(self._cache) > self.capacity:
            self._cache.popitem(last=False)

    def drop_if(self, pred) -> None:
        for k in [k for k in self._cache if pred(k)]:
            del self._cache[k]

    def clear(self) -> None:
        self._cache.clear()


class _Pending:
    """A not-yet-materialized extension: base entry + new sorted batches.

    Commits only record the freshly sealed batches (O(batch) per commit);
    the single merge copy is paid by the first *read* of the version, so a
    write-only burst never copies the full target array per commit."""

    __slots__ = ("base", "batches", "complete")

    def __init__(self, base: _Entry, batches, complete: bool):
        self.base = base
        self.batches = batches
        self.complete = complete


class VisibilityCache(KeyedLRU):
    """LRU cache of tombstone-target arrays keyed by (tomb_oids, ts).

    Correctness is by construction: keys are value-based over immutable
    inputs (tombstone objects are write-once), so a directory change —
    commit, restore, compaction — yields a different key and can never
    observe a stale array.  ``on_delete`` drops entries referencing a
    deleted tombstone; that is load-bearing, not just a memory bound —
    rollback paths (aborted commits, discarded CI previews) rewind the oid
    counter, so a deleted tombstone's oid can be REUSED by a later object
    and a surviving entry would alias it.
    """

    def __init__(self, store: ObjectStore, capacity: int = 32):
        super().__init__(capacity)
        self.store = store
        self.builds = 0    # full target-array constructions
        self.extends = 0   # incremental parent -> child extensions
        self.derives = 0   # PITR horizons derived by commit_ts truncation
        self.hits = 0

    @staticmethod
    def _key(d: Directory) -> Tuple:
        return (d.tomb_oids, d.ts)

    def _hmax(self, d: Directory) -> int:
        """Largest tombstone commit_ts in ``d`` (0 with no tombstones).
        Any horizon >= hmax sees every target — the array no longer
        depends on ts, so all such horizons share ONE canonical entry."""
        return max((self.store.get(o).ts_zone[1] for o in d.tomb_oids),
                   default=0)

    def _lookup_entry(self, key: Tuple) -> Optional[_Entry]:
        val = self.lookup(key)
        if isinstance(val, _Pending):
            val = self._materialize(key, val)
        return val

    def entry(self, d: Directory) -> _Entry:
        key = self._key(d)
        val = self._lookup_entry(key)
        if val is not None:
            self.hits += 1
            return val
        hmax = self._hmax(d)
        # full-coverage horizon (every tombstone commit <= d.ts — ALL
        # directories produced by commits and directory_at are): the array
        # is independent of ts, so every such horizon shares one canonical
        # entry instead of building its own (ROADMAP open item)
        ckey = (d.tomb_oids, hmax) if d.ts >= hmax else key
        hit = None
        if ckey != key:
            hit = self._lookup_entry(ckey)
        if hit is not None:
            self.hits += 1
            val = hit
        else:
            val = self._derive(d, hmax, ckey)
            if val is None:
                with telemetry.span(SP_VIS_BUILD):
                    val = _build_entry(self.store, d)
                    self.builds += 1
                self.insert(ckey, val)
        if ckey != key:
            # alias the exact key to the shared entry: repeat lookups of
            # this horizon must not re-pay the O(#tomb_oids) _hmax scan
            self.insert(key, val)
        return val

    def _derive(self, d: Directory, hmax: int, ckey: Tuple
                ) -> Optional[_Entry]:
        """Serve a historical horizon by truncating a cached HEAD array on
        commit_ts instead of rebuilding from tombstone objects.

        A cached complete entry whose tombstone set is a superset of
        ``d``'s — with every extra object committed entirely after
        ``d.ts`` (exactly what later commits of a linear history add) —
        contains ``d``'s array as the commit_ts <= d.ts subset; masking a
        sorted array preserves sortedness, so the derived array is
        byte-identical to a fresh build. O(cache) key scan + one O(n)
        mask vs. an O(n log n) rebuild."""
        if not d.tomb_oids:
            return None     # empty target array — building is O(1)
        dset = set(d.tomb_oids)
        dts = np.uint64(d.ts)
        for key2 in reversed(list(self._cache.keys())):  # newest first
            toids = key2[0]
            if len(toids) < len(dset) or key2 == ckey:
                continue
            extras = set(toids) - dset
            if len(extras) != len(toids) - len(dset):
                continue                    # not a superset
            if any(self.store.get(o).ts_zone[0] <= d.ts for o in extras):
                continue                    # an extra straddles the horizon
            head = self._lookup_entry(key2)
            if head is None or not head.complete:
                continue
            self._ensure_ts(head, toids)
            val = _Entry(head.targets[head.ts_arr <= dts],
                         complete=d.ts >= hmax)
            self.derives += 1
            self.insert(ckey, val)
            return val
        return None

    def _ensure_ts(self, entry: _Entry, tomb_oids) -> None:
        """Align each target's tombstone commit_ts with the sorted array.
        Valid only for complete entries (every target present exactly once
        — a rowid is killed by at most one tombstone); paid once per head,
        then every historical horizon is an O(n) mask."""
        if entry.ts_arr is not None:
            return
        ts = np.empty(entry.targets.shape, np.uint64)
        for oid in tomb_oids:
            t = self.store.get(oid)
            pos = np.searchsorted(entry.targets, t.target)
            ts[pos] = t.commit_ts
        ts.setflags(write=False)
        entry.ts_arr = ts

    def _materialize(self, key: Tuple, p: _Pending) -> _Entry:
        """Pay the deferred merge: one sort of the accumulated batches and
        one copy of the base array, regardless of how many commits piled
        up since the base was last read."""
        if len(p.batches) == 1:
            add = p.batches[0]
        else:
            add = np.sort(np.concatenate(p.batches))
        merged = p.base.targets
        if add.shape[0] and merged.shape[0] == 0:
            merged = add.copy()
        elif add.shape[0]:
            # manual sorted insert: one allocation + two masked copies
            # (np.insert pays extra normalization overhead per call)
            pos = np.searchsorted(merged, add)
            out = np.empty((merged.shape[0] + add.shape[0],), merged.dtype)
            at = pos + np.arange(add.shape[0])
            mask = np.zeros(out.shape, bool)
            mask[at] = True
            out[at] = add
            out[~mask] = merged
            merged = out
        entry = _Entry(merged, p.complete)
        self.insert(key, entry)
        return entry

    def get(self, d: Directory) -> "VisibilityIndex":
        return VisibilityIndex(self.store, d, _entry=self.entry(d))

    def extend(self, parent: Directory, child: Directory) -> None:
        """Derive the child version's array from the parent's by recording
        the newly added (already sorted at seal time) tombstone batches.
        No-op unless the parent is cached, the child only *adds*
        tombstones, and the parent array was horizon-complete."""
        pval = self._cache.get(self._key(parent))
        ph = None
        if pval is None:
            # full-coverage entries live under their canonical key
            ph = self._hmax(parent)
            if parent.ts >= ph:
                pval = self._cache.get((parent.tomb_oids, ph))
        if pval is None or not pval.complete:
            return
        p_set = set(parent.tomb_oids)
        c_set = set(child.tomb_oids)
        if not (p_set <= c_set) or child.ts < parent.ts:
            return
        complete = True
        ts = np.uint64(child.ts)
        hmax_child = ph if ph is not None else self._hmax(parent)
        batches = []
        for oid in child.tomb_oids:
            if oid in p_set:
                continue
            t = self.store.get(oid)
            hmax_child = max(hmax_child, t.ts_zone[1])
            m = t.commit_ts <= ts
            batches.append(t.target if m.all() else t.target[m])
            complete = complete and bool(m.all())
        # complete children file under the canonical key so later PITR
        # horizons of this version share the entry
        ckey = ((child.tomb_oids, hmax_child) if complete
                else self._key(child))
        if self._cache.get(ckey) is not None:
            return
        if isinstance(pval, _Pending):   # chain of unread commits: flatten
            base, batches = pval.base, pval.batches + batches
        else:
            base = pval
        if not batches:
            self.insert(ckey, _Entry(base.targets, complete))
        else:
            self.insert(ckey, _Pending(base, batches, complete))
        self.extends += 1

    def on_delete(self, oid: int) -> None:
        """A tombstone object was GC'd: drop entries referencing it."""
        self.drop_if(lambda k: oid in k[0])


def visibility_index(store: ObjectStore, d: Directory) -> "VisibilityIndex":
    """The cached entry point every hot path goes through."""
    cache = getattr(store, "vis_cache", None)
    if cache is None:
        cache = VisibilityCache(store)
        store.vis_cache = cache
    return cache.get(d)


class VisibilityIndex:
    """View over one directory version's sorted tombstone-target array."""

    def __init__(self, store: ObjectStore, d: Directory,
                 _entry: Optional[_Entry] = None):
        self.store = store
        self.d = d
        if _entry is None:
            _entry = _build_entry(store, d)
        self._entry = _entry

    @property
    def targets(self) -> np.ndarray:
        return self._entry.targets

    def object_targets(self, oid: int) -> np.ndarray:
        """The slice of targets that can touch data object ``oid``."""
        sl = self._entry.object_slices().get(oid)
        if sl is None:
            return _EMPTY_U64
        return self._entry.targets[sl[0]:sl[1]]

    def has_kills(self, obj: DataObject) -> bool:
        return obj.oid in self._entry.object_slices()

    def fully_visible(self, obj: DataObject) -> bool:
        """Zone pruning: every row passes without masking — no tombstone
        targets the object and its commit-ts zone is within the horizon."""
        return (obj.oid not in self._entry.object_slices()
                and obj.ts_zone[1] <= self.d.ts)

    def killed_mask(self, obj: DataObject) -> np.ndarray:
        """(nrows,) bool — True where a tombstone kills the row."""
        n = obj.nrows
        mask = np.zeros((n,), bool)
        if n == 0:
            return mask
        t = self.object_targets(obj.oid)
        if t.shape[0]:
            base = pack_rowid(obj.oid, np.zeros((1,), np.uint64))[0]
            mask[(t - base).astype(np.int64)] = True
        return mask

    def killed_rowids(self, rowids: np.ndarray) -> np.ndarray:
        """(k,) bool for arbitrary rowids."""
        targets = self._entry.targets
        if targets.shape[0] == 0 or rowids.shape[0] == 0:
            return np.zeros(rowids.shape, bool)
        idx = ops.lower_bound(targets, rowids)
        idx_c = np.minimum(idx, targets.shape[0] - 1)
        return (targets[idx_c] == rowids) & (idx < targets.shape[0])

    def killed_offsets(self, obj: DataObject, offs: np.ndarray) -> np.ndarray:
        """(k,) bool for row offsets within one object — searches only the
        object's slice of the target array, not the global array."""
        t = self.object_targets(obj.oid)
        if t.shape[0] == 0 or offs.shape[0] == 0:
            return np.zeros(offs.shape, bool)
        base = pack_rowid(obj.oid, np.zeros((1,), np.uint64))[0]
        toffs = (t - base).astype(np.int64)
        pos = np.searchsorted(toffs, offs)
        pos_c = np.minimum(pos, toffs.shape[0] - 1)
        return (toffs[pos_c] == offs) & (pos < toffs.shape[0])

    def visible_rows(self, obj: DataObject, offs: np.ndarray) -> np.ndarray:
        """Visibility of selected row offsets without materializing the
        object-wide mask (Δ-scan hot path: cost ∝ candidates, not rows)."""
        ok = ~self.killed_offsets(obj, offs)
        lo, hi = obj.ts_zone
        if hi <= self.d.ts:
            return ok
        if lo > self.d.ts:
            return np.zeros(offs.shape, bool)
        return ok & (obj.commit_ts[offs] <= np.uint64(self.d.ts))

    def visible_mask(self, obj: DataObject) -> np.ndarray:
        if self.fully_visible(obj):
            return np.ones((obj.nrows,), bool)
        if obj.ts_zone[1] <= self.d.ts:
            return ~self.killed_mask(obj)
        return (obj.commit_ts <= np.uint64(self.d.ts)) & ~self.killed_mask(obj)

    def visible_count(self, obj: DataObject) -> int:
        """Visible-row count without materializing a mask when pruned."""
        if self.fully_visible(obj):
            return obj.nrows
        return int(self.visible_mask(obj).sum())


def visible_rowcount(store: ObjectStore, d: Directory) -> int:
    vi = visibility_index(store, d)
    return int(sum(vi.visible_count(store.get(oid)) for oid in d.data_oids))
