"""Secondary indices as auxiliary versioned tables (paper §5.5.4).

MatrixOne implements a secondary index as "an auxiliary table consisting of
the indexed columns and the primary key columns of the original table,
stored and managed as an LSM tree" — and lists cloning those auxiliary
tables as future work. We implement both: index maintenance rides inside
the SAME transaction as the base-table change (atomic), and
``clone_table(..., with_indices=True)`` clones the auxiliary tables
(metadata-only, like any clone).

The auxiliary schema is (isig I64, <pk columns>) with primary key
(isig, pk...): ``isig`` is the 64-bit signature of the indexed column
values, so equality lookups filter one integer column. A production LSM
would cluster by isig; here a lookup answers a whole batch of values in one
pass over the aux table.

A branch carries the indices of its tables (``workspace.create_branch``
clones them with the data), its transactions maintain them, and a restore
puts them back to the version consistent with the restored snapshot
(``Engine.restore_table``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from ..kernels import ops
from . import telemetry
from .schema import Column, CType, Schema
from .sigs import column_lanes, lob_sig64

SP_INDEX_LOOKUP = telemetry.register_span(
    "index.lookup", "batched equality lookup: one pass over a secondary "
    "index's aux table for every value of the batch")
SP_INDEX_MAINTAIN = telemetry.register_span(
    "index.maintain", "aux-table deletes and inserts staged into the commit "
    "of an indexed table")


@dataclass(frozen=True)
class IndexSpec:
    name: str
    table: str
    columns: Tuple[str, ...]   # indexed columns of the base table

    @property
    def aux_table(self) -> str:
        return f"__idx_{self.table}_{self.name}"


def _isig(schema: Schema, batch, columns) -> np.ndarray:
    """Signature of the indexed column values (i64 view of u64 sig_lo)."""
    lob_sigs = {c: lob_sig64(batch[c]) for c in columns
                if schema.column(c).ctype is CType.LOB}
    lanes = column_lanes(schema, batch, columns, lob_sigs)
    lo, _ = ops.signatures_from_lanes(lanes)
    return lo.view(np.int64)


def aux_schema(base: Schema) -> Schema:
    assert base.has_pk, "secondary indices require a primary key"
    cols = (Column("isig", CType.I64),) + tuple(
        base.column(c) for c in base.primary_key)
    return Schema(cols, primary_key=("isig",) + tuple(base.primary_key))


def backfill_index(engine, spec: IndexSpec, batch=None):
    """Create ``spec``'s aux table and backfill it from the base table.

    The single backfill implementation — used by CREATE INDEX and by
    snapshot-clone index rebuilds (sub-operations unlogged either way).
    ``batch`` lets a caller rebuilding several indices share one base-table
    scan; returns the batch actually used so it can be reused."""
    t = engine.table(spec.table)
    engine.create_table(spec.aux_table, aux_schema(t.schema), _log=False)
    if batch is None:
        batch, _ = t.scan()
    if batch[t.schema.primary_key[0]].shape[0]:
        tx = engine.begin()
        tx.insert(spec.aux_table, aux_rows(t.schema, spec, batch))
        engine._commit(tx, _log=False)
        # the index exists from its backfill on: the empty version it was
        # created with must not answer for an older horizon (clone and
        # restore rebuild there instead)
        engine.table(spec.aux_table).trim_history(1)
    return batch


def create_index(engine, table: str, name: str, columns: Sequence[str],
                 *, _log: bool = True) -> IndexSpec:
    """CREATE INDEX name ON table(columns) — backfills existing rows.

    The WAL carries ONE create_index record; replay re-runs the aux-table
    creation and backfill deterministically (sub-operations unlogged)."""
    t = engine.table(table)
    spec = IndexSpec(name, table, tuple(columns))
    for c in columns:
        t.schema.column(c)  # validates
    if _log:
        engine.wal.append("create_index", table=table, name=name,
                          columns=tuple(columns))
    engine.indices.setdefault(table, []).append(spec)
    backfill_index(engine, spec)
    return spec


def drop_index(engine, table: str, name: str, *, _log: bool = True) -> None:
    specs = engine.indices.get(table, [])
    spec = next(s for s in specs if s.name == name)
    specs.remove(spec)
    engine.drop_table(spec.aux_table, _log=False)
    if _log:
        engine.wal.append("drop_index", table=table, name=name)


def aux_rows(schema: Schema, spec: IndexSpec, batch) -> Dict[str, np.ndarray]:
    out = {"isig": _isig(schema, batch, spec.columns)}
    for c in schema.primary_key:
        out[c] = batch[c]
    return out


@dataclass(frozen=True)
class IndexHits:
    """The answer of a batched lookup: the base-table PK columns of every
    match, grouped by looked-up value. Value ``i``'s rows are
    ``offsets[i]:offsets[i + 1]`` of each column, in PK order."""
    offsets: np.ndarray              # int64, one more than the values
    keys: Dict[str, np.ndarray]      # PK column -> matches, grouped

    def __getitem__(self, column: str) -> np.ndarray:
        return self.keys[column]

    def group(self, i: int) -> Dict[str, np.ndarray]:
        lo, hi = self.offsets[i], self.offsets[i + 1]
        return {c: v[lo:hi] for c, v in self.keys.items()}


def _probe_column(ctype: CType, value) -> np.ndarray:
    if ctype is not CType.LOB:
        return np.atleast_1d(np.asarray(value))
    if isinstance(value, (bytes, str)):
        value = [value]
    out = np.empty(len(value), dtype=object)
    out[:] = [v.encode() if isinstance(v, str) else bytes(v) for v in value]
    return out


def lookup_eq(engine, table: str, name: str, values) -> IndexHits:
    """Equality lookup of a batch of values: the base-table PK columns of
    the rows that match each value, grouped per value.

    ``values``: dict {indexed column -> scalar or array}; arrays give one
    value per position (a scalar is the batch of one). One scan of the aux
    table answers the whole batch: each aux row's ``isig`` is searched
    among the batch's sorted distinct signatures."""
    with telemetry.span(SP_INDEX_LOOKUP):
        t = engine.table(table)
        spec = next(s for s in engine.indices.get(table, [])
                    if s.name == name)
        probe = {c: _probe_column(t.schema.column(c).ctype, values[c])
                 for c in spec.columns}
        n = int(probe[spec.columns[0]].shape[0])
        engine.store.metrics.add("index.lookup_values", n)
        pk = t.schema.primary_key
        batch, _ = engine.table(spec.aux_table).scan()
        sig = (_isig(t.schema, probe, spec.columns) if n
               else np.zeros((0,), np.int64))
        uniq, inv = np.unique(sig, return_inverse=True)
        aux_sig = batch["isig"]
        pos = np.searchsorted(uniq, aux_sig)
        hit = pos < uniq.shape[0]
        hit[hit] = uniq[pos[hit]] == aux_sig[hit]
        rows = np.flatnonzero(hit)
        grp = pos[rows]
        # matches grouped by distinct value, PK-ordered inside a group
        order = np.lexsort(tuple(batch[c][rows] for c in reversed(pk))
                           + (grp,))
        rows, grp = rows[order], grp[order]
        cnt_u = np.bincount(grp, minlength=uniq.shape[0])
        start_u = np.cumsum(cnt_u) - cnt_u
        # each looked-up value (repeats included) gets its group's rows
        cnt = cnt_u[inv]
        offsets = np.zeros(n + 1, np.int64)
        np.cumsum(cnt, out=offsets[1:])
        take = (np.repeat(start_u[inv] - offsets[:-1], cnt)
                + np.arange(offsets[-1]))
        return IndexHits(offsets, {c: batch[c][rows[take]] for c in pk})


def maintain_on_commit(engine, tx, table: str,
                       ins_batches, del_rowids) -> None:
    """Expand a txn with the auxiliary-table changes (same-commit atomic)."""
    from .diff import gather_payload
    specs = engine.indices.get(table, [])
    if not specs:
        return
    with telemetry.span(SP_INDEX_MAINTAIN):
        t = engine.table(table)
        dead = (gather_payload(engine.store, t.schema, del_rowids)
                if del_rowids.shape[0] else None)
        n = 0
        for spec in specs:
            if dead is not None:
                n += tx.delete_by_keys(spec.aux_table,
                                       aux_rows(t.schema, spec, dead))
            for b in ins_batches:
                rows = aux_rows(t.schema, spec, b)
                tx.insert(spec.aux_table, rows)
                n += int(rows["isig"].shape[0])
        engine.store.metrics.add("index.aux_rows", n)
