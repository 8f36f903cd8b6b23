"""TPC-C New-Order and Payment as data-VCS pull requests, as a runner of
traffic mixes of ``kind`` ``tpcc``: the mix gives ``engineers``,
``new_orders`` and ``payments`` per engineer and round, ``warmup_rounds``
and ``publish_mode`` (``accept``, the mode the reference models).

Each round restores the nine tables, and the customer index with them, to
``base``; branches each engineer; runs the engineer's transactions against
its branch; then diffs, opens and publishes each PR in turn. Every round
draws fresh inputs (clauses 2.4.1 and 2.5.1). An engineer's transactions
read through the branch (the batched index lookup for the
by-last-name Payments, key probes and row gathers for the rest), apply in
order on the client, each seeing the effects of those before it, and land
as one multi-table transaction. A PR's checks are TPC-C's consistency
conditions 1 to 3 (clause 3.3.2) on the merged preview."""
from __future__ import annotations

import time
import traceback
from typing import Dict, List, Optional

import numpy as np

from bench.workload import Verbs, generator_of, log, reference_of

#: the conflict mode whose outcome the reference models
PUBLISH_MODES = ("accept",)
#: the tables a transaction updates (the others it only inserts into)
UPDATED = ("warehouse", "district", "customer", "stock")


def _mean(values: List[float]) -> Optional[float]:
    return float(np.mean(values)) if values else None


def consistent(ctx) -> bool:
    """TPC-C consistency conditions 1 to 3 (clauses 3.3.2.1 to 3.3.2.3) on
    the merged preview: W_YTD = sum(D_YTD) to the cent; D_NEXT_O_ID - 1 =
    max(O_ID) = max(NO_O_ID); and each district's NEW_ORDER rows number
    max(NO_O_ID) - min(NO_O_ID) + 1."""
    wh, dist = ctx.scan("warehouse")[0], ctx.scan("district")[0]
    orders, new = ctx.scan("orders")[0], ctx.scan("new_order")[0]
    span = int(wh["w_id"].max()) + 1
    d_sum = np.zeros(span)
    np.add.at(d_sum, dist["d_w_id"], dist["d_ytd"])
    ok = (np.round(wh["w_ytd"] * 100) ==
          np.round(d_sum[wh["w_id"]] * 100)).all()
    n = span * 11
    dk = dist["d_w_id"] * 11 + dist["d_id"]
    max_o = np.zeros(n, np.int64)
    np.maximum.at(max_o, orders["o_w_id"] * 11 + orders["o_d_id"],
                  orders["o_id"])
    nk = new["no_w_id"] * 11 + new["no_d_id"]
    max_no = np.zeros(n, np.int64)
    np.maximum.at(max_no, nk, new["no_o_id"])
    min_no = np.full(n, np.iinfo(np.int64).max)
    np.minimum.at(min_no, nk, new["no_o_id"])
    count = np.bincount(nk, minlength=n)
    ok &= (dist["d_next_o_id"] - 1 == max_o[dk]).all()
    ok &= (max_o[dk] == max_no[dk]).all()
    ok &= (count[dk] == max_no[dk] - min_no[dk] + 1).all()
    return bool(ok)


class Tpcc:
    """Rounds of the engineers' New-Order/Payment batches, each a PR."""

    def __init__(self, config: dict, mix: dict, seed: int, verbs: Verbs,
                 root: str):
        from repro.core import Column, CType, Repo, Schema
        from repro.core.indices import create_index
        if mix.get("publish_mode") not in PUBLISH_MODES:
            raise ValueError(f"a tpcc mix states its publish_mode, one of "
                             f"{PUBLISH_MODES}: {mix.get('publish_mode')!r}")
        self.gen = generator_of(config, root)
        self.ref = reference_of(config, root)
        self.config, self.mix, self.seed, self.verb = config, mix, seed, verbs
        self.tables = list(config["tables"])
        made = self.gen.columns(config)
        self.schemas = {}
        for t in self.tables:
            cols = [tuple(c) for c in config["columns"][t]]
            if tuple(cols) != tuple(made[t]):
                raise ValueError(f"{config['name']}: columns of {t} differ "
                                 f"from those its generator makes")
            pk = config["primary_keys"][t]
            self.schemas[t] = Schema(
                tuple(Column(n, CType(k)) for n, k in cols),
                primary_key=tuple(pk) if pk else None)
        self.index = config["index"]
        t0 = time.perf_counter()
        self.rows, _ = self.gen.generate(config, seed)
        t1 = time.perf_counter()
        self.repo = repo = Repo()
        for t in self.tables:
            repo.create_table(t, self.schemas[t])
            repo.insert(t, {c: v.copy() for c, v in self.rows[t].items()})
        create_index(repo.engine, self.index["table"], self.index["name"],
                     self.index["columns"])
        for t in self.tables:
            repo.tag(self.base_tag(t), t)
        n = sum(int(next(iter(r.values())).shape[0])
                for r in self.rows.values())
        log(f"setup: generated {n} rows of {len(self.tables)} tables in "
            f"{t1 - t0:.3f} s, loaded and indexed in "
            f"{time.perf_counter() - t1:.3f} s")
        self.engineers = [f"eng{w}" for w in range(int(mix["engineers"]))]
        self.live_prs: List[int] = []
        self.branches: List[str] = []
        self.round_no = 0
        self.last_round: Optional[list] = None
        self.inputs: List[list] = []          # inputs of each recorded round
        self.diffs: List[tuple] = []          # (round, w, table, DiffResult)
        self.conflicts: List[tuple] = []      # (round, w, count)
        self.lookups: List[tuple] = []        # (round, w, answers)
        self.prs_attempted = self.prs_published = self.rows_landed = 0
        self.failed = 0
        self.record = False
        self._base = None

    @staticmethod
    def base_tag(table: str) -> str:
        return f"base_{table}"

    # ------------------------------------------------------------- reads
    def _read(self, phys: str, table: str, keys: Dict[str, np.ndarray]):
        """Rows of ``table`` on a branch by primary key, as a dict from key
        tuple to row dict; absent keys are left out."""
        from repro.core import gather_payload
        from repro.core.sigs import key_sigs_for_lookup
        schema = self.schemas[table]
        pk = schema.primary_key
        keys = {c: np.asarray(keys[c], np.int64) for c in pk}
        lo, hi = key_sigs_for_lookup(schema, keys)
        order = np.lexsort((hi, lo))
        rowid = np.zeros(lo.shape[0], np.uint64)
        rowid[order] = self.repo.table(phys).locate_keys(lo[order],
                                                         hi[order])
        found = np.flatnonzero(rowid != 0)
        rows = gather_payload(self.repo.engine.store, schema, rowid[found])
        cols = {c: v.tolist() for c, v in rows.items()}
        names = list(cols)
        out = {}
        for k in range(found.shape[0]):
            row = {c: cols[c][k] for c in names}
            out[tuple(row[c] for c in pk)] = row
        return out

    # ------------------------------------------------------ transactions
    def update(self, b: str, batch) -> tuple:
        """One engineer's batch against branch ``b``; returns the
        by-last-name answers and the rows it changed."""
        from repro.core.indices import lookup_eq
        engine = self.repo.engine
        phys = lambda t: f"{b}/{t}"
        no, pay = batch.new_orders, batch.payments
        sel = np.flatnonzero(pay.by_name)
        idx = self.index
        hits = lookup_eq(engine, phys(idx["table"]), idx["name"], {
            "c_w_id": pay.c_w[sel], "c_d_id": pay.c_d[sel],
            "c_last": pay.last[sel]})
        by_id = ~pay.by_name
        cust = self._read(phys("customer"), "customer", {
            "c_w_id": np.concatenate([no.w, pay.c_w[by_id], hits["c_w_id"]]),
            "c_d_id": np.concatenate([no.d, pay.c_d[by_id], hits["c_d_id"]]),
            "c_id": np.concatenate([no.c, pay.c[by_id], hits["c_id"]])})
        chosen = {}
        answers = []
        for k, j in enumerate(sel.tolist()):
            g = hits.group(k)
            ids = g["c_id"].tolist()
            answers.append((j, int(pay.c_w[j]), int(pay.c_d[j]),
                            pay.last[j], tuple(sorted(ids))))
            key = (int(pay.c_w[j]), int(pay.c_d[j]))
            firsts = sorted((cust[key + (c,)]["c_first"], c) for c in ids)
            chosen[j] = firsts[(len(firsts) + 1) // 2 - 1][1]
        homes = np.unique(np.concatenate([no.w, pay.w]))
        wh = self._read(phys("warehouse"), "warehouse", {"w_id": homes})
        dist = self._read(phys("district"), "district", {
            "d_w_id": np.repeat(homes, 10),
            "d_id": np.tile(np.arange(1, 11), homes.shape[0])})
        items = np.unique(no.i_id)
        item = self._read(phys("item"), "item", {"i_id": items})
        pairs = np.unique(np.stack([no.supply_w, no.i_id], 1), axis=0)
        stock = self._read(phys("stock"), "stock", {
            "s_w_id": pairs[:, 0], "s_i_id": pairs[:, 1]})
        state = {"warehouse": wh, "district": dist, "customer": cust,
                 "stock": stock}
        before = {t: {k: dict(r) for k, r in rows.items()}
                  for t, rows in state.items()}
        new = {t: [] for t in ("orders", "new_order", "order_line",
                               "history")}
        for kind, j, date in zip(batch.kind.tolist(), batch.pos.tolist(),
                                 batch.date.tolist()):
            if kind == 0:
                self._new_order(state, item, new, no, j, date)
            else:
                self._payment(state, new, pay, j, date, chosen.get(j))
        tx = engine.begin()
        landed = 0
        for t in UPDATED:
            changed = [r for k, r in state[t].items() if r != before[t][k]]
            if changed:
                tx.update_by_keys(phys(t), self._batch(t, changed))
                landed += len(changed)
        for t, rows in new.items():
            if rows:
                tx.insert(phys(t), self._batch(t, rows))
                landed += len(rows)
        tx.commit()
        return answers, landed

    def _batch(self, table: str, rows: List[dict]) -> Dict[str, np.ndarray]:
        out = {}
        for c in self.schemas[table].columns:
            values = [r[c.name] for r in rows]
            if c.ctype.value == "lob":
                col = np.empty(len(values), dtype=object)
                col[:] = values
            else:
                col = np.asarray(values, self.schemas[table].np_dtype(c.name))
            out[c.name] = col
        return out

    @staticmethod
    def _new_order(state, item, new, no, j, date) -> None:
        """Clause 2.4.2.2; an unused item rolls the order back."""
        lo, hi = int(no.lines[j]), int(no.lines[j + 1])
        w, d, c = int(no.w[j]), int(no.d[j]), int(no.c[j])
        i_ids = no.i_id[lo:hi].tolist()
        if any((i,) not in item for i in i_ids):
            return
        dist = state["district"][(w, d)]
        o_id = dist["d_next_o_id"]
        dist["d_next_o_id"] = o_id + 1
        supply = no.supply_w[lo:hi].tolist()
        qty = no.quantity[lo:hi].tolist()
        new["orders"].append({
            "o_id": o_id, "o_d_id": d, "o_w_id": w, "o_c_id": c,
            "o_entry_d": date, "o_carrier_id": 0, "o_ol_cnt": hi - lo,
            "o_all_local": int(all(s == w for s in supply))})
        new["new_order"].append({"no_o_id": o_id, "no_d_id": d,
                                 "no_w_id": w})
        for k, (i, sw, q) in enumerate(zip(i_ids, supply, qty)):
            s = state["stock"][(sw, i)]
            have = s["s_quantity"]
            s["s_quantity"] = have - q if have >= q + 10 else have - q + 91
            s["s_ytd"] += q
            s["s_order_cnt"] += 1
            if sw != w:
                s["s_remote_cnt"] += 1
            new["order_line"].append({
                "ol_o_id": o_id, "ol_d_id": d, "ol_w_id": w,
                "ol_number": k + 1, "ol_i_id": i, "ol_supply_w_id": sw,
                "ol_delivery_d": 0, "ol_quantity": q,
                "ol_amount": q * item[(i,)]["i_price"],
                "ol_dist_info": s[f"s_dist_{d:02d}"]})

    def _payment(self, state, new, pay, j, date, chosen) -> None:
        """Clause 2.5.2.2, with the customer chosen by last name where the
        input says so."""
        w, d = int(pay.w[j]), int(pay.d[j])
        c_w, c_d = int(pay.c_w[j]), int(pay.c_d[j])
        c = int(pay.c[j]) if chosen is None else chosen
        amount = float(pay.amount[j])
        wh = state["warehouse"][(w,)]
        wh["w_ytd"] += amount
        dist = state["district"][(w, d)]
        dist["d_ytd"] += amount
        cust = state["customer"][(c_w, c_d, c)]
        cust["c_balance"] -= amount
        cust["c_ytd_payment"] += amount
        cust["c_payment_cnt"] += 1
        if cust["c_credit"] == b"BC":
            cust["c_data"] = self.gen.text_of_payment(
                c, c_d, c_w, d, w, amount, cust["c_data"])
        new["history"].append({
            "h_c_id": c, "h_c_d_id": c_d, "h_c_w_id": c_w, "h_d_id": d,
            "h_w_id": w, "h_date": date, "h_amount": amount,
            "h_data": wh["w_name"] + b"    " + dist["d_name"]})

    # ------------------------------------------------------------- rounds
    def round(self) -> None:
        repo = self.repo
        batches = self.gen.round_inputs(self.config, self.mix, self.seed,
                                        self.round_no)
        self.round_no += 1
        if self.record:
            self.inputs.append(batches)
        r = len(self.inputs) - 1
        with self.verb("restore"):
            for pr in self.live_prs:
                repo.close_pr(pr)
            for b in self.branches:
                repo.drop_branch(b)
            self.live_prs, self.branches = [], []
            for t in self.tables:
                repo.restore(t, self.base_tag(t))
        with self.verb("branch"):
            for b in self.engineers:
                repo.branch(b, self.tables)
                self.branches.append(b)
        landed = []
        for w, (b, batch) in enumerate(zip(self.engineers, batches)):
            with self.verb("update"):
                answers, n = self.update(b, batch)
            landed.append(n)
            if self.record:
                self.lookups.append((r, w, answers))
        for w, b in enumerate(self.engineers):
            if self.record:
                self.prs_attempted += 1
            with self.verb("diff"):
                diffs = {t: repo.diff(self.base_tag(t), b, table=t)
                         for t in self.tables}
            if self.record:
                self.diffs.extend((r, w, t, d) for t, d in diffs.items())
            with self.verb("open_pr"):
                pr = repo.open_pr(b)
                pr.add_check(consistent, "tpcc-consistency-1-3")
            with self.verb("publish"):
                reports = repo.publish(pr.id, "accept")
            self.live_prs.append(pr.id)
            if self.record:
                self.conflicts.append((r, w, sum(
                    rep.true_conflicts for rep in reports.values())))
                self.prs_published += 1
                self.rows_landed += landed[w]
        self.last_round = batches

    def warm_up(self) -> None:
        for _ in range(int(self.mix["warmup_rounds"])):
            self.round()

    def window(self, seconds: float) -> float:
        """Whole rounds until ``seconds`` have passed; returns the window's
        length. A round that fails ends the window."""
        self.record = True
        t0 = time.perf_counter()
        while True:
            before = self.prs_published
            try:
                self.round()
            except Exception:
                traceback.print_exc()
                self.failed += int(self.mix["engineers"]) - (
                    self.prs_published - before)
                self.last_round = None
                break
            if time.perf_counter() - t0 >= seconds:
                break
        self.record = False
        return time.perf_counter() - t0

    # ------------------------------------------------------------ results
    def end_to_end(self, window_s: float) -> Dict[str, float]:
        w = self.verb.walls
        return {"landed_rows_per_s": self.rows_landed / window_s,
                "diff_s": _mean(w["diff"]), "publish_s": _mean(w["publish"])}

    def counts(self) -> Dict[str, int]:
        return {"attempted": self.prs_attempted, "failed": self.failed,
                "diffs": len(self.verb.walls["diff"]),
                "publishes": len(self.verb.walls["publish"]),
                "prs_published": self.prs_published,
                "rounds": len(self.inputs)}

    def check(self, lower_precision: bool = False) -> Dict[str, tuple]:
        """Numbers compared with the reference, each with its limit. With
        ``lower_precision`` the control stands in the program's place: the
        reference's own answers in float32 against the reference."""
        from repro.core import gather_payload
        ref = self.ref
        if self._base is None:
            self._base = ref.Base(self.rows)
        base = self._base
        outs = [[ref.run(base, b) for b in batches] for batches in self.inputs]
        keys = {t: (tuple(self.config["primary_keys"][t])
                    if self.config["primary_keys"][t]
                    else tuple(self.config["history_key"]))
                for t in self.tables}
        store = self.repo.engine.store
        wrong = compared = 0
        for r, w, t, d in self.diffs:
            want = ref.change_set(base, outs[r][w], t)
            if lower_precision:
                got = ref.lower_precision(want)
            else:
                got = gather_payload(store, self.schemas[t], d.rowid)
                got[ref.CNT] = d.diff_cnt.astype(np.int64)
            wrong += ref.mismatched_rows(got, want, keys[t] + (ref.CNT,))
            compared += want[ref.CNT].shape[0]
        out = {"diffs_compared": (len(self.diffs), None),
               "diff_rows_compared": (compared, None),
               "diff_rows_wrong": (wrong, 0)}
        if self.last_round is None or not outs:
            out["table_rows_wrong"] = (sum(
                next(iter(r.values())).shape[0] for r in self.rows.values()),
                0)
        else:
            want = ref.table_after(base, outs[-1])
            wrong = 0
            for t in self.tables:
                got = (ref.lower_precision(want[t]) if lower_precision
                       else self.repo.table(t).scan()[0])
                wrong += ref.mismatched_rows(got, want[t], keys[t])
            out["table_rows_wrong"] = (wrong, 0)
        out.update(self._check_conflicts(outs, lower_precision))
        out.update(self._check_lookups(outs, lower_precision))
        out.update(self._check_index(lower_precision))
        return out

    def _check_conflicts(self, outs, lower_precision: bool
                         ) -> Dict[str, tuple]:
        """The true conflicts each publish reported against the
        reference's count for its PR; the control counts them on its rows
        in float32."""
        ref, base = self.ref, self._base
        recorded = {r for r, _, _ in self.conflicts}
        want = {r: ref.true_conflicts(base, outs[r]) for r in recorded}
        if lower_precision:
            control = {r: ref.true_conflicts(base, [
                ref.lower_precision_outcome(o) for o in outs[r]])
                for r in recorded}
        got = wrong = 0
        for r, w, n in self.conflicts:
            if lower_precision:
                n = control[r][w]
            got += n
            wrong += abs(n - want[r][w])
        return {"true_conflicts": (got, None), "conflicts_wrong": (wrong, 0)}

    def _check_lookups(self, outs, lower_precision: bool
                       ) -> Dict[str, tuple]:
        """Every by-last-name lookup a branch answered, against the
        customers of that name in the reference."""
        want = {(r, w, a[0]): a for r, row in enumerate(outs)
                for w, o in enumerate(row) for a in o.lookups}
        got = ({(r, w, a[0]): a for r, w, answers in self.lookups
                for a in answers} if not lower_precision else want)
        wrong = sum(got.get(k) != a for k, a in want.items())
        wrong += sum(k not in want for k in got)
        return {"lookups_compared": (len(want), None),
                "lookups_wrong": (wrong, 0)}

    def _check_index(self, lower_precision: bool) -> Dict[str, tuple]:
        """Main's customer index after the window, against the reference's
        (c_w_id, c_d_id, c_last) -> c_id map: every name of every district
        looked up in one batch, and the index's row count."""
        from repro.core.indices import lookup_eq
        want = self.ref.name_map(self._base)
        if lower_precision:
            return {"index_rows_wrong": (0, 0)}
        engine, idx = self.repo.engine, self.index
        names = list(want)
        last = np.empty(len(names), dtype=object)
        last[:] = [k[2] for k in names]
        hits = lookup_eq(engine, idx["table"], idx["name"], {
            "c_w_id": np.array([k[0] for k in names], np.int64),
            "c_d_id": np.array([k[1] for k in names], np.int64),
            "c_last": last})
        wrong = 0
        for i, k in enumerate(names):
            g = hits.group(i)
            got = set(zip(g["c_w_id"].tolist(), g["c_d_id"].tolist(),
                          g["c_id"].tolist()))
            wrong += len(got ^ {k[:2] + (c,) for c in want[k]})
        spec = next(s for s in engine.indices[idx["table"]]
                    if s.name == idx["name"])
        n = sum(len(v) for v in want.values())
        wrong += abs(self.repo.table(spec.aux_table).count() - n)
        return {"index_rows_wrong": (wrong, 0)}


RUNNER = Tpcc
