"""Plain reference of the TPC-C New-Order/Payment deployment: its nine
tables as numpy arrays, kept from the generator's rows and the drawn
transaction inputs alone.

It imports nothing of the program. Each engineer's transactions are
applied one at a time to the base tables (clauses 2.4.2 and 2.5.2), each
seeing the effects of those before it; the engineers' pull requests are
then published in order under ACCEPT, where a later PR's version of a row
replaces an earlier one's. From that it says what each diff, each
publish's true conflicts, each by-last-name lookup and the tables after a
round must hold, and counts the rows of an answer that differ. Rows are
compared value for value, every column, never by signature.

The control (``lower_precision``) is this reference with every float64
column in float32, the next precision below the configuration's. A
warehouse's year-to-date near 300,000.00 loses its cents there.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

Rows = Dict[str, np.ndarray]
Tables = Dict[str, Rows]
#: the net-count column of a change set (-1: only before, +1: only after)
CNT = "_cnt"
#: the tables a transaction updates, with the key that finds a row
UPDATED = {"warehouse": ("w_id",), "district": ("d_w_id", "d_id"),
           "customer": ("c_w_id", "c_d_id", "c_id"),
           "stock": ("s_w_id", "s_i_id")}
#: the tables a transaction only inserts into
INSERTED = ("orders", "new_order", "order_line", "history")


@dataclass
class Outcome:
    """What one engineer's transactions did to the base tables: the new
    version of each changed row (``updated[t]``: base row positions and
    their new rows), the rows inserted, and the answer of each
    by-last-name selection as (payment, c_w_id, c_d_id, c_last, sorted
    c_ids)."""
    updated: Dict[str, Tuple[np.ndarray, Rows]] = field(default_factory=dict)
    inserted: Dict[str, Rows] = field(default_factory=dict)
    lookups: List[tuple] = field(default_factory=list)


class _Keys:
    """Row positions of a base table by its key columns (all ints)."""

    def __init__(self, rows: Rows, key: Sequence[str]):
        self.span = [int(rows[c].max()) + 1 if rows[c].shape[0] else 1
                     for c in key]
        packed = self._pack([rows[c] for c in key])
        self.order = np.argsort(packed, kind="stable")
        self.sorted = packed[self.order]

    def _pack(self, cols) -> np.ndarray:
        out = np.zeros(np.asarray(cols[0]).shape, np.int64)
        for col, span in zip(cols, self.span):
            out = out * span + np.asarray(col, np.int64)
        return out

    def find(self, *values) -> int:
        """The base row of one key, or -1."""
        if any(int(v) >= s or int(v) < 0 for v, s in zip(values, self.span)):
            return -1
        p = int(self._pack([np.asarray([v]) for v in values])[0])
        i = int(np.searchsorted(self.sorted, p))
        if i < self.sorted.shape[0] and self.sorted[i] == p:
            return int(self.order[i])
        return -1


class Base:
    """The base tables, with their keys and the customers of each last
    name."""

    def __init__(self, tables: Tables):
        self.t = tables
        self.keys = {name: _Keys(tables[name], key)
                     for name, key in UPDATED.items()}
        self.items = _Keys(tables["item"], ("i_id",))
        cust = tables["customer"]
        by_name = defaultdict(list)
        for i, (w, d, last, c) in enumerate(zip(
                cust["c_w_id"].tolist(), cust["c_d_id"].tolist(),
                cust["c_last"].tolist(), cust["c_id"].tolist())):
            by_name[(w, d, last)].append((c, i))
        self.by_name = {k: sorted(v) for k, v in by_name.items()}

    def row(self, table: str, i: int) -> dict:
        return {c: v[i] for c, v in self.t[table].items()}


class _Engineer:
    """One engineer's view: the base tables under its own changes."""

    def __init__(self, base: Base):
        self.base = base
        self.rows: Dict[str, Dict[int, dict]] = {t: {} for t in UPDATED}
        self.new: Dict[str, List[dict]] = {t: [] for t in INSERTED}
        self.lookups: List[tuple] = []

    def get(self, table: str, *key) -> dict:
        i = self.base.keys[table].find(*key)
        if i < 0:
            raise KeyError(f"{table} has no row {key}")
        if i not in self.rows[table]:
            self.rows[table][i] = self.base.row(table, i)
        return self.rows[table][i]

    def new_order(self, no, j: int, date: int) -> None:
        """Clause 2.4.2.2; a missing item rolls the whole order back."""
        lo, hi = int(no.lines[j]), int(no.lines[j + 1])
        w, d, c = int(no.w[j]), int(no.d[j]), int(no.c[j])
        items = [self.base.items.find(int(i)) for i in no.i_id[lo:hi]]
        if min(items) < 0:
            return
        dist = self.get("district", w, d)
        o_id = int(dist["d_next_o_id"])
        dist["d_next_o_id"] = o_id + 1
        self.get("customer", w, d, c)           # read: discount, credit
        supply = [int(s) for s in no.supply_w[lo:hi]]
        self.new["orders"].append(dict(
            o_id=o_id, o_d_id=d, o_w_id=w, o_c_id=c, o_entry_d=date,
            o_carrier_id=0, o_ol_cnt=hi - lo,
            o_all_local=int(all(s == w for s in supply))))
        self.new["new_order"].append(dict(no_o_id=o_id, no_d_id=d,
                                          no_w_id=w))
        item = self.base.t["item"]
        for k in range(hi - lo):
            i_id, sw = int(no.i_id[lo + k]), supply[k]
            qty = int(no.quantity[lo + k])
            s = self.get("stock", sw, i_id)
            q = int(s["s_quantity"])
            s["s_quantity"] = q - qty if q >= qty + 10 else q - qty + 91
            s["s_ytd"] = int(s["s_ytd"]) + qty
            s["s_order_cnt"] = int(s["s_order_cnt"]) + 1
            if sw != w:
                s["s_remote_cnt"] = int(s["s_remote_cnt"]) + 1
            self.new["order_line"].append(dict(
                ol_o_id=o_id, ol_d_id=d, ol_w_id=w, ol_number=k + 1,
                ol_i_id=i_id, ol_supply_w_id=sw, ol_delivery_d=0,
                ol_quantity=qty,
                ol_amount=qty * float(item["i_price"][items[k]]),
                ol_dist_info=s[f"s_dist_{d:02d}"]))

    def payment(self, p, j: int, date: int) -> None:
        """Clause 2.5.2.2; by last name, the customer at position
        ceil(n / 2) of those of the name sorted by c_first."""
        w, d = int(p.w[j]), int(p.d[j])
        c_w, c_d = int(p.c_w[j]), int(p.c_d[j])
        amount = float(p.amount[j])
        wh = self.get("warehouse", w)
        wh["w_ytd"] = float(wh["w_ytd"]) + amount
        dist = self.get("district", w, d)
        dist["d_ytd"] = float(dist["d_ytd"]) + amount
        if p.by_name[j]:
            found = self.base.by_name.get((c_w, c_d, p.last[j]), [])
            self.lookups.append((j, c_w, c_d, p.last[j],
                                 tuple(c for c, _ in found)))
            first = self.base.t["customer"]["c_first"]
            chosen = sorted((first[i], c) for c, i in found)
            c = chosen[(len(chosen) + 1) // 2 - 1][1]
        else:
            c = int(p.c[j])
        cust = self.get("customer", c_w, c_d, c)
        cust["c_balance"] = float(cust["c_balance"]) - amount
        cust["c_ytd_payment"] = float(cust["c_ytd_payment"]) + amount
        cust["c_payment_cnt"] = int(cust["c_payment_cnt"]) + 1
        if cust["c_credit"] == b"BC":
            head = b"%d %d %d %d %d %.2f " % (c, c_d, c_w, d, w, amount)
            cust["c_data"] = (head + cust["c_data"])[:500]
        self.new["history"].append(dict(
            h_c_id=c, h_c_d_id=c_d, h_c_w_id=c_w, h_d_id=d, h_w_id=w,
            h_date=date, h_amount=amount,
            h_data=wh["w_name"] + b"    " + dist["d_name"]))

    def outcome(self) -> Outcome:
        out = Outcome(lookups=self.lookups)
        t = self.base.t
        for table, rows in self.rows.items():
            idx = np.array(sorted(rows), np.int64)
            new = {c: _column([rows[i][c] for i in idx.tolist()], v.dtype)
                   for c, v in t[table].items()}
            old = {c: v[idx] for c, v in t[table].items()}
            changed = np.zeros(idx.shape[0], bool)
            for c in new:
                changed |= new[c] != old[c]
            out.updated[table] = (idx[changed],
                                  {c: v[changed] for c, v in new.items()})
        for table, rows in self.new.items():
            out.inserted[table] = {
                c: _column([r[c] for r in rows], v.dtype)
                for c, v in t[table].items()}
        return out


def _column(values: list, dtype) -> np.ndarray:
    if dtype == object:
        out = np.empty(len(values), dtype=object)
        out[:] = values
        return out
    return np.asarray(values, dtype=dtype).reshape(-1)


def run(base: Base, batch) -> Outcome:
    """One engineer's transactions, in order, on the base tables."""
    eng = _Engineer(base)
    for kind, pos, date in zip(batch.kind.tolist(), batch.pos.tolist(),
                               batch.date.tolist()):
        if kind == 0:
            eng.new_order(batch.new_orders, pos, date)
        else:
            eng.payment(batch.payments, pos, date)
    return eng.outcome()


def change_set(base: Base, out: Outcome, table: str) -> Rows:
    """What a diff of ``table`` from the base to the engineer's branch
    holds: each changed row's old version with count -1 and its new one
    with +1, and each inserted row with +1."""
    t = base.t[table]
    if table in out.updated:
        idx, new = out.updated[table]
        rows = {c: np.concatenate([v[idx], new[c]]) for c, v in t.items()}
        m = idx.shape[0]
        rows[CNT] = np.r_[np.full(m, -1, np.int64), np.full(m, 1, np.int64)]
        return rows
    if table in out.inserted:
        rows = dict(out.inserted[table])
        rows[CNT] = np.ones(rows[next(iter(rows))].shape[0], np.int64)
        return rows
    rows = {c: v[:0] for c, v in t.items()}
    rows[CNT] = np.zeros(0, np.int64)
    return rows


def true_conflicts(base: Base, outs: Sequence[Outcome]) -> List[int]:
    """Per PR published in turn under ACCEPT: rows of its changed rows that
    an earlier PR also changed, to another version."""
    counts = [0] * len(outs)
    for table in UPDATED:
        seen: Dict[int, tuple] = {}
        cols = list(base.t[table])
        for p, out in enumerate(outs):
            idx, new = out.updated.get(table, (np.zeros(0, np.int64), {}))
            for k, i in enumerate(idx.tolist()):
                version = tuple(new[c][k] for c in cols)
                if i in seen and seen[i] != version:
                    counts[p] += 1
                seen[i] = version
    return counts


def table_after(base: Base, outs: Sequence[Outcome]) -> Tables:
    """The nine tables after the PRs are published in turn under ACCEPT:
    each PR's version of a row replaces the one before it; inserts add."""
    tables = {}
    for table, rows in base.t.items():
        if table in UPDATED:
            cur = {c: v.copy() for c, v in rows.items()}
            for out in outs:
                idx, new = out.updated.get(table, (None, None))
                if idx is not None:
                    for c in cur:
                        cur[c][idx] = new[c]
            tables[table] = cur
        elif table in INSERTED:
            tables[table] = {c: np.concatenate(
                [v] + [out.inserted[table][c] for out in outs])
                for c, v in rows.items()}
        else:
            tables[table] = rows
    return tables


def name_map(base: Base) -> Dict[tuple, tuple]:
    """(c_w_id, c_d_id, c_last) -> the sorted c_ids of that last name: the
    map an index on customer(c_w_id, c_d_id, c_last) holds."""
    return {k: tuple(c for c, _ in v) for k, v in base.by_name.items()}


def _row_keys(rows: Rows, key: Sequence[str]) -> np.ndarray:
    """One opaque, comparable value per row made of its key columns."""
    cols = np.stack([np.asarray(rows[c]).astype(np.int64) for c in key],
                    axis=1)
    return np.ascontiguousarray(cols).view(
        np.dtype((np.void, 8 * len(key)))).reshape(-1)


def mismatched_rows(got: Rows, want: Rows, key: Sequence[str]) -> int:
    """Rows in which ``got`` and ``want`` differ, as multisets: rows of one
    side whose key the other lacks, rows whose key repeats on one side, and
    rows matched by key that differ in any column."""
    if set(got) != set(want):
        raise ValueError(f"columns differ: {sorted(got)} vs {sorted(want)}")
    n_got = np.asarray(got[key[0]]).shape[0]
    n_want = np.asarray(want[key[0]]).shape[0]
    _, gi, wi = np.intersect1d(_row_keys(got, key), _row_keys(want, key),
                               return_indices=True)
    differ = np.zeros(gi.shape[0], bool)
    for c in want:
        differ |= np.asarray(got[c])[gi] != np.asarray(want[c])[wi]
    matched = gi.shape[0]
    return int((n_got - matched) + (n_want - matched) + differ.sum())


def lower_precision_outcome(out: Outcome) -> Outcome:
    """The control's outcome: every changed and inserted row in float32."""
    return Outcome({t: (idx, lower_precision(rows))
                    for t, (idx, rows) in out.updated.items()},
                   {t: lower_precision(rows)
                    for t, rows in out.inserted.items()}, out.lookups)


def lower_precision(rows: Rows) -> Rows:
    """The control: ``rows`` with each float64 column rounded to float32."""
    return {c: (np.asarray(v).astype(np.float32).astype(np.float64)
                if np.asarray(v).dtype == np.float64 else v)
            for c, v in rows.items()}
