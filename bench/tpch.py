"""TPC-H LINEITEM rows from a seed, by the value rules of the TPC-H
specification (v3.0.1, clauses 4.2.2 and 4.2.3), vectorised with numpy.

Columns come out in the specification's order and types as the benchmark
stores them: decimals as float64, dates as int64 day numbers since
1970-01-01, the one-character flags as int32 character codes, and the three
text columns as object arrays of ``bytes``. Rows are in load order, sorted
by (l_orderkey, l_linenumber).

As a configuration's ``generator`` (``"generator": "tpch"``) it gives the
columns of the configuration's one table (``columns``) and its rows and
text pool from the seed (``generate``).

Departures, also listed under ``assumed`` in each configuration file:

- ``l_suppkey`` is drawn uniformly from [1, SF * 10,000], not by the
  specification's formula over ``l_partkey``;
- comments are substrings of a 4 MiB text pool made from the grammar's word
  lists (clause 4.2.2.14), where dbgen uses a 300 MB pool.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

COLUMNS = (
    ("l_orderkey", "i64"), ("l_partkey", "i64"), ("l_suppkey", "i64"),
    ("l_linenumber", "i32"), ("l_quantity", "f64"),
    ("l_extendedprice", "f64"), ("l_discount", "f64"), ("l_tax", "f64"),
    ("l_returnflag", "i32"), ("l_linestatus", "i32"), ("l_shipdate", "i64"),
    ("l_commitdate", "i64"), ("l_receiptdate", "i64"),
    ("l_shipinstruct", "lob"), ("l_shipmode", "lob"), ("l_comment", "lob"),
)

ORDERS_PER_SF = 1_500_000
STARTDATE = 8035      # 1992-01-01
CURRENTDATE = 9298    # 1995-06-17
ENDDATE = 10591       # 1998-12-31
INSTRUCTIONS = (b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                b"TAKE BACK RETURN")
MODES = (b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB")
COMMENT_LEN = (10, 43)
POOL_BYTES = 1 << 22

_NOUNS = ("foxes ideas theodolites pinto_beans instructions dependencies "
          "excuses platelets asymptotes courts dolphins multipliers "
          "sauternes warthogs frets dinos attainments somas Tiresias' "
          "patterns forges braids hockey_players frays warhorses dugouts "
          "notornis epitaphs pearls tithes waters orbits gifts sheaves "
          "depths sentiments decoys realms pains grouches escapades")
_VERBS = ("sleep wake are cajole haggle nag use boost affix detect "
          "integrate maintain nod was lose sublate solve thrash promise "
          "engage hinder print x-ray breach eat grow impress mold poach "
          "serve run dazzle snooze doze unwind kindle play hang believe "
          "doubt")
_ADJECTIVES = ("furious sly careful blithe quick fluffy slow quiet ruthless "
               "thin close dogged daring brave stealthy permanent enticing "
               "idle busy regular final ironic even bold silent")
_ADVERBS = ("sometimes always never furiously slyly carefully blithely "
            "quickly fluffily slowly quietly ruthlessly thinly closely "
            "doggedly daringly bravely stealthily permanently enticingly "
            "idly busily regularly finally ironically evenly boldly "
            "silently")
_PREPOSITIONS = ("about above according_to across after against along "
                 "alongside_of among around at atop before behind beneath "
                 "beside besides between beyond by despite during except "
                 "for from in_place_of inside instead_of into near of on "
                 "outside over past since through throughout to toward "
                 "under until up upon without with within")
_AUXILIARIES = ("do may might shall will would can could should ought_to "
                "must will_have_to shall_have_to could_have_to "
                "should_have_to must_have_to need_to try_to")
_TERMINATORS = (".", ";", ":", "?", "!", "--")


def _words(text: str):
    return np.array([w.replace("_", " ") for w in text.split()], dtype=object)


def text_pool(rng: np.random.Generator, nbytes: int = POOL_BYTES) -> bytes:
    """Sentences of the specification's grammar, joined, cut to ``nbytes``.

    Each sentence is ``[adjective] noun [auxiliary] verb [adverb]
    [preposition the adjective noun] terminator``, every bracketed part
    present with probability one half."""
    nouns, verbs = _words(_NOUNS), _words(_VERBS)
    adjs, advs = _words(_ADJECTIVES), _words(_ADVERBS)
    preps, auxs = _words(_PREPOSITIONS), _words(_AUXILIARIES)
    n = nbytes // 24 + 1
    pick = lambda words: words[rng.integers(0, words.shape[0], n)]
    half = lambda: rng.random(n) < 0.5
    blank = np.full(n, "", dtype=object)
    parts = [
        np.where(half(), pick(adjs) + " ", blank),
        pick(nouns) + " ",
        np.where(half(), pick(auxs) + " ", blank),
        pick(verbs),
        np.where(half(), " " + pick(advs), blank),
        np.where(half(), " " + pick(preps) + " the " + pick(adjs) + " "
                 + pick(nouns), blank),
        np.array(_TERMINATORS, dtype=object)[
            rng.integers(0, len(_TERMINATORS), n)] + " ",
    ]
    sentences = parts[0]
    for p in parts[1:]:
        sentences = sentences + p
    pool = "".join(sentences.tolist()).encode()
    return pool[:nbytes]


def comments(rng: np.random.Generator, pool: bytes, n: int) -> np.ndarray:
    """``n`` comments: pool substrings of uniform length in [10, 43]."""
    lens = rng.integers(COMMENT_LEN[0], COMMENT_LEN[1] + 1, n)
    offs = rng.integers(0, len(pool) - COMMENT_LEN[1], n)
    out = np.empty(n, dtype=object)
    out[:] = [pool[o:o + k] for o, k in zip(offs.tolist(), lens.tolist())]
    return out


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents (clause 4.2.3)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def sparse_orderkeys(n_orders: int) -> np.ndarray:
    """dbgen's sparse order keys: of every 32 keys, the first 8 are used."""
    i = np.arange(1, n_orders + 1, dtype=np.int64)
    return ((i >> 3) << 5) | (i & 7)


def lineitem(scale_factor: float, seed: int
             ) -> Tuple[Dict[str, np.ndarray], bytes]:
    """The LINEITEM table of one scale factor, its rows drawn from ``seed``,
    and the text pool its comments were cut from (updates draw new
    comments from the same pool)."""
    rng = np.random.default_rng([seed, 0x7C])
    n_orders = int(round(ORDERS_PER_SF * scale_factor))
    sf_parts = max(1, int(round(200_000 * scale_factor)))
    sf_supps = max(1, int(round(10_000 * scale_factor)))
    orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_orders)
    lines = rng.integers(1, 8, n_orders)
    n = int(lines.sum())
    order_of = np.repeat(np.arange(n_orders), lines)
    first = np.cumsum(lines) - lines
    linenumber = (np.arange(n) - np.repeat(first, lines) + 1).astype(np.int32)
    odate = orderdate[order_of]
    partkey = rng.integers(1, sf_parts + 1, n).astype(np.int64)
    quantity = rng.integers(1, 51, n)
    shipdate = odate + rng.integers(1, 122, n)
    commitdate = odate + rng.integers(30, 91, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    returned = np.where(rng.random(n) < 0.5, ord("R"), ord("A"))
    pool = text_pool(rng)
    rows = {
        "l_orderkey": sparse_orderkeys(n_orders)[order_of],
        "l_partkey": partkey,
        "l_suppkey": rng.integers(1, sf_supps + 1, n).astype(np.int64),
        "l_linenumber": linenumber,
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": quantity * retail_cents(partkey) / 100.0,
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_returnflag": np.where(receiptdate <= CURRENTDATE, returned,
                                 ord("N")).astype(np.int32),
        "l_linestatus": np.where(shipdate > CURRENTDATE, ord("O"),
                                 ord("F")).astype(np.int32),
        "l_shipdate": shipdate.astype(np.int64),
        "l_commitdate": commitdate.astype(np.int64),
        "l_receiptdate": receiptdate.astype(np.int64),
        "l_shipinstruct": np.array(INSTRUCTIONS, dtype=object)[
            rng.integers(0, len(INSTRUCTIONS), n)],
        "l_shipmode": np.array(MODES, dtype=object)[
            rng.integers(0, len(MODES), n)],
        "l_comment": comments(rng, pool, n),
    }
    return rows, pool


def columns(config: dict) -> Dict[str, tuple]:
    """The columns of each table the configuration names: its one table,
    LINEITEM."""
    return {config["table"]: COLUMNS}


def generate(config: dict, seed: int
             ) -> Tuple[Dict[str, Dict[str, np.ndarray]], bytes]:
    """The rows of each table of the configuration, by table name, and the
    text pool that updates draw new comments from."""
    rows, pool = lineitem(float(config["scale_factor"]), seed)
    return {config["table"]: rows}, pool
