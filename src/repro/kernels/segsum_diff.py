"""Pallas TPU kernel: run boundaries of the diff-aggregation operator (§5.1).

Input is a signed stream sorted by 128-bit key. Equal keys form one run, and
``ops.DiffAgg`` nets each run's signs on the host from the boundary flags
this kernel produces: 1 where an element's key differs from its
predecessor's.

Layout: each of the four uint32 key lanes arrives as an (R, 128) array in
row-major element order, tiled over the grid in (block_rows, 128) VMEM
blocks. The predecessor of element (r, c) is (r, c - 1), or (r - 1, 127)
at c == 0; two rotations (lanes, then sublanes) line it up. The first
element of each block has its predecessor in the previous block, so its
flag here is meaningless and the caller sets it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 256  # 32Ki keys per grid step


def _boundary_kernel(k0_ref, k1_ref, k2_ref, k3_ref, out_ref):
    shape = out_ref.shape
    col0 = jax.lax.broadcasted_iota(jnp.int32, shape, 1) == 0
    ne = None
    for ref in (k0_ref, k1_ref, k2_ref, k3_ref):
        x = ref[...]
        left = pltpu.roll(x, 1, 1)     # x[r, c - 1]  (c == 0: x[r, 127])
        up = pltpu.roll(left, 1, 0)    # x[r - 1, 127] at c == 0
        d = x != jnp.where(col0, up, left)
        ne = d if ne is None else ne | d
    out_ref[...] = ne.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def boundary_pallas(k0: jnp.ndarray, k1: jnp.ndarray, k2: jnp.ndarray,
                    k3: jnp.ndarray, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: bool = False) -> jnp.ndarray:
    """k0..k3: (R, 128) uint32 key lanes of a sorted stream, R a multiple
    of ``block_rows`` (itself a multiple of 8). Returns (R, 128) int32:
    1 where the key differs from its predecessor's, except at each block's
    first element (see the module docstring)."""
    r = k0.shape[0]
    assert k0.shape[1] == LANES and r % block_rows == 0, (k0.shape,
                                                          block_rows)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _boundary_kernel,
        grid=(r // block_rows,),
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.int32),
        interpret=interpret,
    )(k0, k1, k2, k3)
