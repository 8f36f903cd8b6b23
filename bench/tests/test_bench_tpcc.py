"""The TPC-C New-Order/Payment cell and the ``pk_collab_c100`` cell, run
small on the CPU through the harness's own functions: the program agrees
with the reference, the control (the reference in float32) does not, and
faults planted in the index path turn ``correct`` false. Then the TPC-C
reference itself, on transactions applied by hand."""
import numpy as np
import pytest

from bench import tpcc
from bench.reference import tpcc as ref
from bench.tests.conftest import run_small
from bench.tests.test_bench_cells import in_window, runner_of_cell

CELLS = ["tpcc_w12_neworder_payment", "pk_collab_c100"]
TPCC = "tpcc_w12_neworder_payment"
COMPARED = ("diff_rows_wrong", "table_rows_wrong", "conflicts_wrong",
            "lookups_wrong", "index_rows_wrong")


@pytest.mark.parametrize("workload", CELLS)
def test_program_agrees_with_the_reference(small_root, workload):
    r = run_small(small_root, workload)
    assert r["correct"] is True, r["check"]
    assert r["failed"] == 0 and r["attempted"] > 0
    compared = [c for c in r["check"].values() if c["limit"] is not None]
    assert compared and all(c["value"] == 0 for c in compared)
    assert {"setup_s", "landed_rows_per_s", "diff_s",
            "publish_s"} <= set(r["metrics"])
    if workload == TPCC:
        assert set(COMPARED) <= set(r["check"])
        # every table of every PR's diff, and the engineers' lookups
        assert r["check"]["diffs_compared"]["value"] == 9 * r["attempted"]
        assert r["check"]["lookups_compared"]["value"] > 0
        assert r["check"]["true_conflicts"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_fails(small_root, workload, monkeypatch):
    """The reference computed in float32 in the program's place."""
    seen = {}

    def check(self, lower_precision=False):
        seen["program"] = orig(self)
        return orig(self, lower_precision=True)

    runner = runner_of_cell(small_root, workload)
    orig = runner.check
    monkeypatch.setattr(runner, "check", check)
    r = run_small(small_root, workload)
    assert r["correct"] is False
    assert r["check"]["table_rows_wrong"]["value"] > 0
    assert all(v == 0 for v, lim in seen["program"].values()
               if lim is not None)


def test_the_window_draws_fresh_inputs_each_round(small_root, monkeypatch):
    """Each round of the window runs its own draws (clauses 2.4.1 and
    2.5.1), not the warm-up round's again, and the reference checks each
    round against the inputs it ran."""
    seen = {}
    runner = runner_of_cell(small_root, TPCC)
    orig = runner.check

    def check(self, lower_precision=False):
        seen["inputs"] = list(self.inputs)
        return orig(self, lower_precision)

    monkeypatch.setattr(runner, "check", check)
    r = run_small(small_root, TPCC, seconds=1.0)
    assert r["correct"] is True
    rounds = seen["inputs"]
    assert len(rounds) >= 2
    firsts = [tuple(batches[0].new_orders.i_id.tolist()) for batches in rounds]
    assert len(set(firsts)) == len(firsts)


def test_trace_run_reads_the_index_metrics(small_root):
    r = run_small(small_root, TPCC, traced=True)
    assert r["correct"] is True
    m = r["metrics"]
    for name in ("index_lookup_ms_per_pr", "index_maintain_ms_per_publish",
                 "delta_ms_per_diff", "diff_agg_self_ms",
                 "plan_merge_ms_per_publish", "seal_ms_per_commit"):
        assert m[name]["value"] > 0, name


# ------------------------------------------------ faults in the index path

def test_fault_a_branch_without_its_index(small_root, monkeypatch):
    """Branches cloned without their indices: the by-last-name lookup on
    the branch finds no index and the round fails."""
    from repro.core import engine

    def fault(orig):
        def clone_table(self, new_name, src, *, with_indices=False, **kw):
            return orig(self, new_name, src, with_indices=False, **kw)
        return clone_table

    in_window(monkeypatch, small_root, engine.Engine, "clone_table", fault)
    r = run_small(small_root, TPCC)
    assert r["correct"] is False
    assert r["failed"] > 0


def test_fault_publish_maintenance_drops_its_inserts(small_root,
                                                     monkeypatch):
    """A publish's index maintenance deletes the old aux rows of the
    customers it changes and writes none for their new versions."""
    from repro.core import indices

    def fault(orig):
        def maintain_on_commit(engine, tx, table, ins_batches, del_rowids):
            if "/" not in table:            # main's tables: a publish
                ins_batches = []
            return orig(engine, tx, table, ins_batches, del_rowids)
        return maintain_on_commit

    in_window(monkeypatch, small_root, indices, "maintain_on_commit", fault)
    r = run_small(small_root, TPCC)
    assert r["correct"] is False
    assert r["check"]["index_rows_wrong"]["value"] > 0


def test_fault_a_lookup_loses_a_row_of_each_answer(small_root, monkeypatch):
    from repro.core import indices

    def fault(orig):
        def lookup_eq(engine, table, name, values):
            hits = orig(engine, table, name, values)
            keep = np.ones(hits.offsets[-1], bool)
            ends = hits.offsets[1:][np.diff(hits.offsets) > 0]
            keep[ends - 1] = False
            offsets = np.r_[0, np.cumsum(np.maximum(np.diff(hits.offsets)
                                                    - 1, 0))]
            return indices.IndexHits(offsets, {c: v[keep] for c, v in
                                               hits.keys.items()})
        return lookup_eq

    in_window(monkeypatch, small_root, indices, "lookup_eq", fault)
    r = run_small(small_root, TPCC)
    assert r["correct"] is False
    assert r["check"]["lookups_wrong"]["value"] > 0


# ----------------------------------------------------------- the reference

CONFIG = {"warehouses": 2, "scale_factor": 0.002}


def _batch(no_lines, payment):
    """One New-Order (lines of (item, supply warehouse, quantity)) from
    warehouse 1, district 1, customer 1, then one Payment."""
    i_id, supply, qty = (np.array(x, np.int64) for x in zip(*no_lines))
    no = tpcc.NewOrders(np.array([1]), np.array([1]), np.array([1]),
                        np.array([0, len(no_lines)]), i_id, supply, qty)
    pay = tpcc.Payments(*(np.array([v], dtype=object if k == "last"
                                   else None)
                          for k, v in payment.items()))
    return tpcc.Batch(np.array([0, 1]), np.array([0, 0]),
                      np.array([tpcc.POPULATED_AT + 1,
                                tpcc.POPULATED_AT + 2]), no, pay)


def _row(rows, **key):
    hit = np.ones(next(iter(rows.values())).shape[0], bool)
    for c, v in key.items():
        hit &= rows[c] == v
    (i,) = np.flatnonzero(hit)
    return {c: v[i] for c, v in rows.items()}


def test_reference_new_order_and_payment_by_hand():
    tables, _ = tpcc.generate(CONFIG, 5)
    base = ref.Base(tables)
    cust = tables["customer"]
    # the by-name customer: of those named like (w 2, d 1, c 1), the one
    # at position ceil(n / 2) by c_first
    last = _row(cust, c_w_id=2, c_d_id=1, c_id=1)["c_last"]
    named = [(f, c) for f, c, w, d, n in zip(
        cust["c_first"], cust["c_id"], cust["c_w_id"], cust["c_d_id"],
        cust["c_last"]) if (w, d, n) == (2, 1, last)]
    chosen = sorted(named)[(len(named) + 1) // 2 - 1][1]
    batch = _batch([(3, 1, 4), (5, 2, 9)],
                   dict(w=1, d=2, c_w=2, c_d=1, by_name=True, c=0,
                        last=last, amount=12.34))
    out = ref.run(base, batch)
    after = ref.table_after(base, [out])

    s13 = _row(tables["stock"], s_w_id=1, s_i_id=3)
    s25 = _row(tables["stock"], s_w_id=2, s_i_id=5)
    d11 = _row(tables["district"], d_w_id=1, d_id=1)
    o_id = d11["d_next_o_id"]
    assert _row(after["district"], d_w_id=1, d_id=1)["d_next_o_id"] == \
        o_id + 1
    order = _row(after["orders"], o_w_id=1, o_d_id=1, o_id=o_id)
    assert (order["o_c_id"], order["o_ol_cnt"], order["o_all_local"],
            order["o_carrier_id"]) == (1, 2, 0, 0)
    _row(after["new_order"], no_w_id=1, no_d_id=1, no_o_id=o_id)
    for (w, i, q, before) in ((1, 3, 4, s13), (2, 5, 9, s25)):
        s = _row(after["stock"], s_w_id=w, s_i_id=i)
        have = before["s_quantity"]
        assert s["s_quantity"] == (have - q if have >= q + 10
                                   else have - q + 91)
        assert (s["s_ytd"], s["s_order_cnt"], s["s_remote_cnt"]) == \
            (q, 1, int(w != 1))
        line = _row(after["order_line"], ol_w_id=1, ol_d_id=1,
                    ol_o_id=o_id, ol_i_id=i)
        price = _row(tables["item"], i_id=i)["i_price"]
        assert line["ol_amount"] == q * price
        assert line["ol_dist_info"] == before["s_dist_01"]
        assert line["ol_delivery_d"] == 0 and line["ol_supply_w_id"] == w

    w1 = _row(after["warehouse"], w_id=1)
    assert w1["w_ytd"] == 300_000.0 + 12.34
    assert _row(after["district"], d_w_id=1, d_id=2)["d_ytd"] == \
        30_000.0 + 12.34
    c = _row(after["customer"], c_w_id=2, c_d_id=1, c_id=chosen)
    c0 = _row(cust, c_w_id=2, c_d_id=1, c_id=chosen)
    assert (c["c_balance"], c["c_ytd_payment"], c["c_payment_cnt"]) == \
        (-10.0 - 12.34, 10.0 + 12.34, 2)
    if c0["c_credit"] == b"BC":
        head = b"%d 1 2 2 1 12.34 " % chosen
        assert c["c_data"] == (head + c0["c_data"])[:500]
    else:
        assert c["c_data"] == c0["c_data"]
    h = _row(after["history"], h_c_w_id=2, h_c_d_id=1, h_c_id=chosen,
             h_date=tpcc.POPULATED_AT + 2)
    d12 = _row(tables["district"], d_w_id=1, d_id=2)
    assert h["h_amount"] == 12.34 and (h["h_w_id"], h["h_d_id"]) == (1, 2)
    assert h["h_data"] == w1["w_name"] + b"    " + d12["d_name"]
    assert out.lookups == [(0, 2, 1, last,
                            tuple(sorted(int(c) for _, c in named)))]
    # the diff of STOCK: both rows' old versions out, new versions in
    stock = ref.change_set(base, out, "stock")
    assert sorted(stock[ref.CNT].tolist()) == [-1, -1, 1, 1]


def test_reference_rollback_writes_nothing():
    tables, _ = tpcc.generate(CONFIG, 5)
    base = ref.Base(tables)
    unused = tpcc.sizes(CONFIG).items + 1
    batch = _batch([(3, 1, 4), (unused, 1, 1)],
                   dict(w=1, d=1, c_w=1, c_d=1, by_name=False, c=2,
                        last=b"", amount=1.0))
    batch.kind, batch.pos = np.array([0]), np.array([0])
    out = ref.run(base, batch)
    assert all(idx.shape[0] == 0 for idx, _ in out.updated.values())
    assert all(r[next(iter(r))].shape[0] == 0
               for r in out.inserted.values())


def test_reference_counts_conflicts_between_engineers():
    """Two engineers' New-Orders draw one stock row: the later PR's
    version replaces the earlier one's, a true conflict; a Payment's
    warehouse row is its engineer's alone."""
    tables, _ = tpcc.generate(CONFIG, 5)
    base = ref.Base(tables)
    pay = dict(w=1, d=1, c_w=1, c_d=1, by_name=False, c=2, last=b"",
               amount=1.0)
    a = ref.run(base, _batch([(7, 2, 3)], pay))
    pay2 = dict(pay, w=2, c_w=2)
    b = _batch([(7, 2, 5)], pay2)
    b.new_orders.w[:] = 2
    b = ref.run(base, b)
    assert ref.true_conflicts(base, [a, b]) == [0, 1]
    after = ref.table_after(base, [a, b])
    s = _row(after["stock"], s_w_id=2, s_i_id=7)
    assert s["s_ytd"] == 5 and s["s_order_cnt"] == 1
