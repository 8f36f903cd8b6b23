"""TPC-C rows and transaction inputs from a seed, by the TPC-C
specification v5.11.0, vectorised with numpy.

As a configuration's ``generator`` (``"generator": "tpcc"``) it gives the
columns of the nine tables of clause 1.3 (``columns``), their initial
population by clause 4.3.3.1 (``generate``), and, for a runner and the
reference alike, the inputs of a round of New-Order and Payment
transactions by clauses 2.4.1 and 2.5.1 (``round_inputs``).

Columns are in the specification's order. Money and rates are float64,
dates int64 seconds since 1970-01-01 (a null date or carrier is 0), and
text is LOB byte strings. ``scale_factor`` multiplies the specification's
fixed cardinalities (100,000 items; 3,000 customers, 3,000 orders and 900
new orders per district; 1,000 last names); 1 is the specification's. The
number of warehouses and the ten districts of each are the
configuration's own.

Departures, also listed under ``assumed`` in the configuration file:

- each text column draws its values from a seeded pool of 65,536 random
  strings of the column's length range, so generation stays vectorised;
- ``i_data`` and ``s_data`` hold "ORIGINAL" in 10% of rows, placed at a
  random offset of a pool string;
- every date of the population is one instant, ``POPULATED_AT``; the
  transactions of a run date themselves one second apart after it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

I, F, L = "i64", "f64", "lob"
COLUMNS = {
    "warehouse": (("w_id", I), ("w_name", L), ("w_street_1", L),
                  ("w_street_2", L), ("w_city", L), ("w_state", L),
                  ("w_zip", L), ("w_tax", F), ("w_ytd", F)),
    "district": (("d_id", I), ("d_w_id", I), ("d_name", L),
                 ("d_street_1", L), ("d_street_2", L), ("d_city", L),
                 ("d_state", L), ("d_zip", L), ("d_tax", F), ("d_ytd", F),
                 ("d_next_o_id", I)),
    "customer": (("c_id", I), ("c_d_id", I), ("c_w_id", I), ("c_first", L),
                 ("c_middle", L), ("c_last", L), ("c_street_1", L),
                 ("c_street_2", L), ("c_city", L), ("c_state", L),
                 ("c_zip", L), ("c_phone", L), ("c_since", I),
                 ("c_credit", L), ("c_credit_lim", F), ("c_discount", F),
                 ("c_balance", F), ("c_ytd_payment", F),
                 ("c_payment_cnt", I), ("c_delivery_cnt", I),
                 ("c_data", L)),
    "history": (("h_c_id", I), ("h_c_d_id", I), ("h_c_w_id", I),
                ("h_d_id", I), ("h_w_id", I), ("h_date", I),
                ("h_amount", F), ("h_data", L)),
    "new_order": (("no_o_id", I), ("no_d_id", I), ("no_w_id", I)),
    "orders": (("o_id", I), ("o_d_id", I), ("o_w_id", I), ("o_c_id", I),
               ("o_entry_d", I), ("o_carrier_id", I), ("o_ol_cnt", I),
               ("o_all_local", I)),
    "order_line": (("ol_o_id", I), ("ol_d_id", I), ("ol_w_id", I),
                   ("ol_number", I), ("ol_i_id", I), ("ol_supply_w_id", I),
                   ("ol_delivery_d", I), ("ol_quantity", I),
                   ("ol_amount", F), ("ol_dist_info", L)),
    "item": (("i_id", I), ("i_im_id", I), ("i_name", L), ("i_price", F),
             ("i_data", L)),
    "stock": (("s_i_id", I), ("s_w_id", I), ("s_quantity", I),
              ("s_dist_01", L), ("s_dist_02", L), ("s_dist_03", L),
              ("s_dist_04", L), ("s_dist_05", L), ("s_dist_06", L),
              ("s_dist_07", L), ("s_dist_08", L), ("s_dist_09", L),
              ("s_dist_10", L), ("s_ytd", I), ("s_order_cnt", I),
              ("s_remote_cnt", I), ("s_data", L)),
}

DISTRICTS = 10
POPULATED_AT = 1_577_836_800        # 2020-01-01 00:00:00
SYLLABLES = (b"BAR", b"OUGHT", b"ABLE", b"PRI", b"PRES", b"ESE", b"ANTI",
             b"CALLY", b"ATION", b"EING")
POOL = 1 << 16
ALNUM = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz"
                      b"ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789", np.uint8)
DIGITS = np.frombuffer(b"0123456789", np.uint8)
UPPER = np.frombuffer(b"ABCDEFGHIJKLMNOPQRSTUVWXYZ", np.uint8)

# transaction inputs (clauses 2.4.1 and 2.5.1), in percent
REMOTE_LINE = 1           # order lines supplied by a remote warehouse
ROLLBACK = 1              # New-Orders whose last item is unused
REMOTE_CUSTOMER = 15      # Payments for a customer of a remote warehouse
BY_LAST_NAME = 60         # Payments that select the customer by last name


@dataclass(frozen=True)
class Sizes:
    """Cardinalities of one configuration."""
    warehouses: int
    items: int
    customers: int            # per district
    orders: int               # per district, 1..orders
    new_orders: int           # per district: the last ``new_orders`` orders
    names: int                # last names in use, 0..names-1

    @property
    def delivered(self) -> int:
        """The orders of a district that carry a carrier: 1..delivered."""
        return self.orders - self.new_orders


def sizes(config: dict) -> Sizes:
    s = float(config["scale_factor"])
    scaled = lambda n: max(1, int(round(n * s)))
    customers = scaled(3000)
    return Sizes(int(config["warehouses"]), scaled(100_000), customers,
                 customers, min(scaled(900), customers),
                 min(scaled(1000), customers))


def columns(config: dict) -> Dict[str, tuple]:
    return dict(COLUMNS)


def last_name(i: np.ndarray) -> np.ndarray:
    """C_LAST of clause 4.3.2.3: the syllables of the three digits of
    ``i`` in [0, 999]."""
    names = np.array([SYLLABLES[a] + SYLLABLES[b] + SYLLABLES[c]
                      for a in range(10) for b in range(10)
                      for c in range(10)], dtype=object)
    return names[np.asarray(i)]


def nurand(rng: np.random.Generator, a: int, x: int, y: int, c: int,
           n: int) -> np.ndarray:
    """NURand(A, x, y) of clause 2.1.6 with run-time constant ``c``."""
    r = (rng.integers(0, a + 1, n) | rng.integers(x, y + 1, n)) + c
    return r % (y - x + 1) + x


def constants(seed: int) -> Dict[str, int]:
    """The C constants of NURand (clause 2.1.6): one for C_LAST at load and
    one at run time, 65..119 apart and never 96 or 112 (2.1.6.1), one for
    C_ID and one for OL_I_ID."""
    rng = np.random.default_rng([seed, 0xC0C])
    c_load = int(rng.integers(0, 256))
    deltas = [d for d in range(65, 120) if d not in (96, 112)]
    delta = deltas[int(rng.integers(0, len(deltas)))]
    c_run = c_load + delta if c_load + delta <= 255 else c_load - delta
    return {"c_last_load": c_load, "c_last_run": c_run,
            "c_id": int(rng.integers(0, 1024)),
            "ol_i_id": int(rng.integers(0, 8192))}


def _chars(rng, alphabet: np.ndarray, shape) -> np.ndarray:
    return alphabet[rng.integers(0, alphabet.shape[0], shape)]


def _pool(rng, n: int, lo: int, hi: int, alphabet=ALNUM, original=False
          ) -> np.ndarray:
    """``n`` random strings of lengths in [lo, hi]; with ``original``
    each holds "ORIGINAL" at a random offset."""
    lens = rng.integers(lo, hi + 1, n)
    buf = _chars(rng, alphabet, (n, hi))
    if original:
        at = rng.integers(0, lens - 8 + 1)
        for k, ch in enumerate(b"ORIGINAL"):
            buf[np.arange(n), at + k] = ch
    out = np.empty(n, dtype=object)
    out[:] = [row[:k].tobytes() for row, k in zip(buf, lens.tolist())]
    return out


def _text(rng, n: int, lo: int, hi: int, alphabet=ALNUM) -> np.ndarray:
    """``n`` values drawn from a pool of at most ``POOL`` strings."""
    k = min(n, POOL)
    return _pool(rng, k, lo, hi, alphabet)[rng.integers(0, k, n)]


def _data(rng, n: int) -> np.ndarray:
    """I_DATA / S_DATA: 26..50 characters, "ORIGINAL" in 10% of rows."""
    k = min(n, POOL)
    plain = _pool(rng, k, 26, 50)
    orig = _pool(rng, k, 26, 50, original=True)
    pick = rng.integers(0, k, n)
    return np.where(rng.random(n) < 0.1, orig[pick], plain[pick])


def _zip(rng, n: int) -> np.ndarray:
    """4 random digits then "11111" (clause 4.3.2.7)."""
    digits = _chars(rng, DIGITS, (10_000, 4))
    pool = np.empty(10_000, dtype=object)
    pool[:] = [d.tobytes() + b"11111" for d in digits]
    return pool[rng.integers(0, 10_000, n)]


def _address(rng, prefix: str, n: int) -> Dict[str, np.ndarray]:
    return {prefix + "street_1": _text(rng, n, 10, 20),
            prefix + "street_2": _text(rng, n, 10, 20),
            prefix + "city": _text(rng, n, 10, 20),
            prefix + "state": _text(rng, n, 2, 2, UPPER),
            prefix + "zip": _zip(rng, n)}


def _money(rng, lo_cents: int, hi_cents: int, n: int) -> np.ndarray:
    return rng.integers(lo_cents, hi_cents + 1, n) / 100.0


def _full(n: int, value, dtype=np.int64) -> np.ndarray:
    if isinstance(value, bytes):
        out = np.empty(n, dtype=object)
        out[:] = [value] * n
        return out
    return np.full(n, value, dtype)


def population(config: dict, seed: int
               ) -> Dict[str, Dict[str, np.ndarray]]:
    """The nine tables of clause 4.3.3.1, rows in primary-key order."""
    z = sizes(config)
    rng = np.random.default_rng([seed, 0x7CC])
    c = constants(seed)
    W, D = z.warehouses, DISTRICTS
    t: Dict[str, Dict[str, np.ndarray]] = {}

    n = z.items
    t["item"] = {"i_id": np.arange(1, n + 1, dtype=np.int64),
                 "i_im_id": rng.integers(1, 10_001, n),
                 "i_name": _text(rng, n, 14, 24),
                 "i_price": _money(rng, 100, 10_000, n),
                 "i_data": _data(rng, n)}

    w_id = np.arange(1, W + 1, dtype=np.int64)
    t["warehouse"] = {"w_id": w_id, "w_name": _text(rng, W, 6, 10),
                      **_address(rng, "w_", W),
                      "w_tax": rng.integers(0, 2001, W) / 10_000.0,
                      "w_ytd": np.full(W, 300_000.0)}

    n = W * z.items
    stock = {"s_i_id": np.tile(np.arange(1, z.items + 1), W),
             "s_w_id": np.repeat(w_id, z.items),
             "s_quantity": rng.integers(10, 101, n)}
    for d in range(1, D + 1):
        stock[f"s_dist_{d:02d}"] = _text(rng, n, 24, 24)
    stock.update({"s_ytd": np.zeros(n, np.int64),
                  "s_order_cnt": np.zeros(n, np.int64),
                  "s_remote_cnt": np.zeros(n, np.int64),
                  "s_data": _data(rng, n)})
    t["stock"] = stock

    n = W * D
    t["district"] = {"d_id": np.tile(np.arange(1, D + 1), W),
                     "d_w_id": np.repeat(w_id, D),
                     "d_name": _text(rng, n, 6, 10),
                     **_address(rng, "d_", n),
                     "d_tax": rng.integers(0, 2001, n) / 10_000.0,
                     "d_ytd": np.full(n, 30_000.0),
                     "d_next_o_id": np.full(n, z.orders + 1, np.int64)}

    k = z.customers
    n = W * D * k
    c_id = np.tile(np.arange(1, k + 1), W * D)
    dist = np.repeat(np.arange(W * D), k)
    name = np.where(c_id <= z.names, c_id - 1,
                    nurand(rng, 255, 0, z.names - 1, c["c_last_load"], n))
    t["customer"] = {
        "c_id": c_id, "c_d_id": dist % D + 1, "c_w_id": dist // D + 1,
        "c_first": _text(rng, n, 8, 16), "c_middle": _full(n, b"OE"),
        "c_last": last_name(name), **_address(rng, "c_", n),
        "c_phone": _text(rng, n, 16, 16, DIGITS),
        "c_since": _full(n, POPULATED_AT),
        "c_credit": np.where(rng.random(n) < 0.1, b"BC", b"GC").astype(
            object),
        "c_credit_lim": np.full(n, 50_000.0),
        "c_discount": rng.integers(0, 5001, n) / 10_000.0,
        "c_balance": np.full(n, -10.0), "c_ytd_payment": np.full(n, 10.0),
        "c_payment_cnt": _full(n, 1), "c_delivery_cnt": _full(n, 0),
        "c_data": _text(rng, n, 300, 500)}
    t["history"] = {"h_c_id": c_id, "h_c_d_id": dist % D + 1,
                    "h_c_w_id": dist // D + 1, "h_d_id": dist % D + 1,
                    "h_w_id": dist // D + 1,
                    "h_date": _full(n, POPULATED_AT),
                    "h_amount": np.full(n, 10.0),
                    "h_data": _text(rng, n, 12, 24)}

    m = z.orders
    n = W * D * m
    o_id = np.tile(np.arange(1, m + 1), W * D)
    dist = np.repeat(np.arange(W * D), m)
    # o_c_id: a random permutation of the district's customers
    perm = np.argsort(rng.random((W * D, m)), axis=1) % k + 1
    delivered = o_id <= z.delivered
    ol_cnt = rng.integers(5, 16, n)
    t["orders"] = {"o_id": o_id, "o_d_id": dist % D + 1,
                   "o_w_id": dist // D + 1, "o_c_id": perm.reshape(-1),
                   "o_entry_d": _full(n, POPULATED_AT),
                   "o_carrier_id": np.where(delivered,
                                            rng.integers(1, 11, n), 0),
                   "o_ol_cnt": ol_cnt, "o_all_local": _full(n, 1)}
    new = o_id > z.delivered
    t["new_order"] = {"no_o_id": o_id[new], "no_d_id": dist[new] % D + 1,
                      "no_w_id": dist[new] // D + 1}

    n_lines = int(ol_cnt.sum())
    order = np.repeat(np.arange(n), ol_cnt)
    first = np.cumsum(ol_cnt) - ol_cnt
    line_delivered = delivered[order]
    t["order_line"] = {
        "ol_o_id": o_id[order], "ol_d_id": dist[order] % D + 1,
        "ol_w_id": dist[order] // D + 1,
        "ol_number": np.arange(n_lines) - np.repeat(first, ol_cnt) + 1,
        "ol_i_id": rng.integers(1, z.items + 1, n_lines),
        "ol_supply_w_id": dist[order] // D + 1,
        "ol_delivery_d": np.where(line_delivered, POPULATED_AT, 0),
        "ol_quantity": _full(n_lines, 5),
        "ol_amount": np.where(line_delivered, 0.0,
                              _money(rng, 1, 999_999, n_lines)),
        "ol_dist_info": _text(rng, n_lines, 24, 24)}

    for name, rows in t.items():
        for col, kind in COLUMNS[name]:
            if kind == I:
                rows[col] = rows[col].astype(np.int64)
        t[name] = {col: rows[col] for col, _ in COLUMNS[name]}
    return t


def generate(config: dict, seed: int):
    """Each table's rows by name, and the NURand constants of the run."""
    return population(config, seed), constants(seed)


# ------------------------------------------------------- transaction inputs

@dataclass
class NewOrders:
    """Inputs of an engineer's New-Order transactions (clause 2.4.1);
    transaction ``j``'s lines are ``lines[j]:lines[j + 1]`` of the line
    arrays. A rolled-back transaction's last line names item
    ``items + 1``, which does not exist."""
    w: np.ndarray
    d: np.ndarray
    c: np.ndarray
    lines: np.ndarray
    i_id: np.ndarray
    supply_w: np.ndarray
    quantity: np.ndarray


@dataclass
class Payments:
    """Inputs of an engineer's Payment transactions (clause 2.5.1). Where
    ``by_name`` is set the customer is the one of last name ``last``
    chosen by clause 2.5.2.2, else ``c``."""
    w: np.ndarray
    d: np.ndarray
    c_w: np.ndarray
    c_d: np.ndarray
    by_name: np.ndarray
    c: np.ndarray
    last: np.ndarray
    amount: np.ndarray


@dataclass
class Batch:
    """One engineer's transactions of a round, in the order they run:
    ``kind[t]`` is 0 for a New-Order, 1 for a Payment, and ``pos[t]`` its
    index among its kind's inputs; ``date[t]`` is its o_entry_d or
    h_date."""
    kind: np.ndarray
    pos: np.ndarray
    date: np.ndarray
    new_orders: NewOrders
    payments: Payments


def homes(config: dict, mix: dict, engineer: int) -> np.ndarray:
    """The home warehouses of an engineer: a contiguous share."""
    W, e = sizes(config).warehouses, int(mix["engineers"])
    if W % e:
        raise ValueError(f"{W} warehouses do not split among {e} engineers")
    per = W // e
    return np.arange(engineer * per + 1, (engineer + 1) * per + 1)


def _remote(rng, w: np.ndarray, warehouses: int) -> np.ndarray:
    """A warehouse other than each ``w``, uniformly."""
    r = rng.integers(1, warehouses, w.shape[0])
    return np.where(r < w, r, r + 1)


def round_inputs(config: dict, mix: dict, seed: int, rnd: int
                 ) -> List[Batch]:
    """The transactions of round ``rnd``, per engineer."""
    z, c = sizes(config), constants(seed)
    W = z.warehouses
    n_no, n_p = int(mix["new_orders"]), int(mix["payments"])
    out = []
    for e in range(int(mix["engineers"])):
        rng = np.random.default_rng([seed, 0x7C1, rnd, e])
        home = homes(config, mix, e)
        w = home[rng.integers(0, home.shape[0], n_no)]
        cnt = rng.integers(5, 16, n_no)
        lines = np.zeros(n_no + 1, np.int64)
        np.cumsum(cnt, out=lines[1:])
        n_l = int(lines[-1])
        i_id = nurand(rng, 8191, 1, z.items, c["ol_i_id"], n_l)
        rollback = rng.integers(1, 101, n_no) <= ROLLBACK
        i_id[lines[1:][rollback] - 1] = z.items + 1
        line_w = np.repeat(w, cnt)
        remote = (rng.integers(1, 101, n_l) <= REMOTE_LINE) & (W > 1)
        supply = np.where(remote, _remote(rng, line_w, max(W, 2)), line_w)
        no = NewOrders(w, rng.integers(1, DISTRICTS + 1, n_no),
                       nurand(rng, 1023, 1, z.customers, c["c_id"], n_no),
                       lines, i_id, supply, rng.integers(1, 11, n_l))
        pw = home[rng.integers(0, home.shape[0], n_p)]
        pd = rng.integers(1, DISTRICTS + 1, n_p)
        far = (rng.integers(1, 101, n_p) > 100 - REMOTE_CUSTOMER) & (W > 1)
        pay = Payments(
            pw, pd, np.where(far, _remote(rng, pw, max(W, 2)), pw),
            np.where(far, rng.integers(1, DISTRICTS + 1, n_p), pd),
            rng.integers(1, 101, n_p) <= BY_LAST_NAME,
            nurand(rng, 1023, 1, z.customers, c["c_id"], n_p),
            last_name(nurand(rng, 255, 0, z.names - 1, c["c_last_run"],
                             n_p)),
            _money(rng, 100, 500_000, n_p))
        kind = rng.permutation(np.r_[np.zeros(n_no, np.int64),
                                     np.ones(n_p, np.int64)])
        pos = np.zeros_like(kind)
        pos[kind == 0] = np.arange(n_no)
        pos[kind == 1] = np.arange(n_p)
        n_t = n_no + n_p
        # one second per transaction, distinct across engineers and rounds
        date = POPULATED_AT + 1 + (rnd * int(mix["engineers"]) + e) * n_t \
            + np.arange(n_t, dtype=np.int64)
        out.append(Batch(kind, pos, date, no, pay))
    return out


def text_of_payment(c_id: int, c_d: int, c_w: int, d: int, w: int,
                    amount: float, old: bytes) -> bytes:
    """C_DATA of a bad-credit customer after a payment (clause 2.5.2.2):
    the payment's fields inserted at the left, cut to 500 characters."""
    return (b"%d %d %d %d %d %.2f " % (c_id, c_d, c_w, d, w, amount)
            + old)[:500]
