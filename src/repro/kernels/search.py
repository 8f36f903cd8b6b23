"""Device search over sorted multi-lane keys: lower bound and fused probe.

A key is a tuple of uint32 lanes, most significant first: ``(hi32, lo32)``
for a 64-bit word, ``(lo_hi32, lo_lo32, hi_hi32, hi_lo32)`` for a 128-bit
signature whose packed lo64 word is the primary sort word (the seal order;
see ``ops.py``'s signature convention).

Each query descends the table in a fixed number of steps (the bit length of
the table's row count, a static), one branchless gather per lane per step.
This is a plain XLA program rather than a Pallas kernel: Mosaic lowers no
1-D vector gather (``Only 2D gather is supported``), and a table as large as
a whole Δ stream does not fit in VMEM, so a kernel would still need a
per-query gather of its table block. XLA gathers from HBM directly, so one
program serves every table length the callers pass.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Lanes = Tuple[jnp.ndarray, ...]


def _less(a: Lanes, b: Lanes) -> jnp.ndarray:
    """Lexicographic ``a < b`` over lane tuples, most significant first."""
    lt = a[-1] < b[-1]
    for x, y in zip(a[-2::-1], b[-2::-1]):
        lt = (x < y) | ((x == y) & lt)
    return lt


def _count(tab: Lanes, q: Lanes, pred) -> jnp.ndarray:
    """Length of the longest table prefix whose every row satisfies
    ``pred(row, q)``, per query; ``pred`` must be monotone along the table
    (true, then false). Built bit by bit from the top: exact for any
    table length, with no data-dependent control flow."""
    n = tab[0].shape[0]
    pos = jnp.zeros(q[0].shape, jnp.int32)
    for bit in reversed(range(n.bit_length())):
        step = 1 << bit
        cand = pos + (step - 1)
        inside = cand < n
        idx = jnp.minimum(cand, n - 1)
        row = tuple(lane.at[idx].get(mode="promise_in_bounds")
                    for lane in tab)
        pos = jnp.where(inside & pred(row, q), pos + step, pos)
    return pos


@jax.jit
def lower_bound_lanes(tab: Lanes, q: Lanes) -> jnp.ndarray:
    """First index i with ``tab[i] >= q`` per query, as (Q,) int32 in
    [0, N]. ``tab``: lanes of (N,) uint32, sorted; ``q``: lanes of (Q,)
    uint32. N >= 1."""
    return _count(tab, q, _less)


@jax.jit
def probe_lanes(tab: Lanes, q: Lanes):
    """Fused probe: per query, its lower bound and its upper bound (first
    index i with ``tab[i] > q``), as two (Q,) int32 arrays; the run of rows
    equal to the query is ``[lower, upper)``. Both bounds are true
    multi-lane descents, so the all-ones key needs no +1 guard."""
    lb = _count(tab, q, _less)
    ub = _count(tab, q, lambda row, key: ~_less(key, row))
    return lb, ub
