"""The one traffic generator. A mix is a JSON file of parameters in
``bench/traffic/``; this module turns its parameters and a seed into the
operations a cell sends: which rows each engineer of a collaborative round
changes and to what, and which keys a point-read client asks for.

Every draw depends on the seed and the round or stream number alone, so
two runs with one seed send the same work, and runs with different seeds
send the same amount of it.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import tpch

Update = Tuple[np.ndarray, Dict[str, np.ndarray]]    # (row indices, changes)

#: YCSB's ScrambledZipfianGenerator draws ranks over this many items and
#: hashes them onto the key space (``ZETAN`` is zeta(ITEM_COUNT, 0.99))
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZETAN = 26.46902820178302
FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)


def round_updates(rows: Dict[str, np.ndarray], pool: bytes, mix: dict,
                  seed: int, rnd: int) -> List[Update]:
    """One collaborative round: per engineer, ``rows_per_engineer`` distinct
    rows drawn uniformly, and their new ``l_quantity`` and ``l_comment``.

    With the mix's ``overlap`` (a fraction, 0 where absent), each engineer
    after the first shares ``k = round(overlap * rows_per_engineer)`` rows
    with the one before it: the first ``k`` rows of its draw are replaced by
    rows ``k .. 2k-1`` of the previous engineer's, which that engineer
    keeps. So no row is in three change sets; without overlap the change
    sets are disjoint. The replacement draws nothing, so the new values
    come from the same stream either way."""
    rng = np.random.default_rng([seed, 0xC0, rnd])
    n = rows["l_orderkey"].shape[0]
    e, m = int(mix["engineers"]), int(mix["rows_per_engineer"])
    k = int(round(float(mix.get("overlap", 0)) * m))
    if 2 * k > m:
        raise ValueError(f"overlap {mix['overlap']} shares more than half "
                         "of an engineer's rows")
    pick = rng.choice(n, size=e * m, replace=False)
    for w in range(1, e):
        pick[w * m:w * m + k] = pick[(w - 1) * m + k:(w - 1) * m + 2 * k]
    out = []
    for w in range(e):
        idx = np.sort(pick[w * m:(w + 1) * m])
        old_q = rows["l_quantity"][idx]
        changes = {
            # a new quantity inside TPC-H's domain [1, 50], never the old
            "l_quantity": np.mod(old_q + rng.integers(0, 49, m), 50) + 1,
            "l_comment": tpch.comments(rng, pool, m),
        }
        out.append((idx, changes))
    return out


def fnv1_64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1 over the 8 bytes of a long, then
    the absolute value as a signed long."""
    x = x.astype(np.uint64)
    h = np.full(x.shape, FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h = (h ^ ((x >> np.uint64(8 * i)) & np.uint64(0xFF))) * FNV_PRIME
    signed = h.view(np.int64)
    return np.abs(signed).astype(np.uint64)


def zipf_ranks(rng: np.random.Generator, count: int, items: int,
               theta: float, zetan: float) -> np.ndarray:
    """YCSB's ZipfianGenerator.nextLong over ``items`` items (Gray et al.)."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(count)
    uz = u * zetan
    rank = np.floor(items * np.power(eta * u - eta + 1.0, alpha))
    rank = np.where(uz < 1.0 + 0.5 ** theta, 1.0, rank)
    rank = np.where(uz < 1.0, 0.0, rank)
    return rank.astype(np.uint64)


def point_read_items(mix: dict, n_rows: int, seed: int, count: int,
                     stream: int = 0) -> np.ndarray:
    """``count`` row indices in [0, n_rows) by YCSB workload C's request
    distribution: scrambled Zipfian ranks hashed onto the rows."""
    if mix["distribution"] != "scrambled_zipfian":
        raise ValueError(f"unknown distribution {mix['distribution']!r}")
    rng = np.random.default_rng([seed, 0x2EAD, stream])
    ranks = zipf_ranks(rng, count, YCSB_ITEM_COUNT, float(mix["theta"]),
                       YCSB_ZETAN)
    return (fnv1_64(ranks) % np.uint64(n_rows)).astype(np.int64)
