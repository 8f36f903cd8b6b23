"""Pallas TPU kernel: 128-bit row signatures from uint32 column lanes.

This is the paper's §5.5.5 signature idea promoted to the universal row
identity (DESIGN.md §2): every diff/merge inner loop operates on signatures,
so signature computation is on the critical path of every version-control
operation and is the most bandwidth-hungry elementwise op in the system.

TPU adaptation: all arithmetic is uint32 (VPU native). Lanes arrive
column-major as (C, R // 128, 128), so each column of a block is a dense
(block_rows // 128, 128) tile and the C columns unroll inside the kernel
(C is a compile-time constant, = 2 * n_table_columns). A row-major (R, C)
block would hand the kernel one-lane-wide columns, which the chip's
compiler takes tens of seconds to unroll at lineitem's C = 24.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref

LANES = 128
DEFAULT_BLOCK_ROWS = 8192  # x C lanes x 4B: 768 KiB per block at C = 24


def _rowhash_kernel(lanes_ref, out_ref, *, n_lanes: int):
    """One VMEM block: (C, S, 128) uint32 lanes -> (4, S, 128) signature
    words, row r of the block at [:, r // 128, r % 128]."""
    shape = out_ref.shape[1:]
    for s, seed in enumerate(ref._SEEDS):
        h = jnp.full(shape, np.uint32(seed), dtype=jnp.uint32)
        for j in range(n_lanes):  # unrolled: n_lanes is static
            x = lanes_ref[j]
            salt = np.uint32(((j * 2 + 1) * 0x9E3779B1 + s * 0x7F4A7C15) & 0xFFFFFFFF)
            h = ref.fmix32(h ^ (x * ref._LANE_C1 + salt))
            h = h * ref._LANE_C2 + np.uint32(1)
        out_ref[s] = ref.fmix32(h ^ np.uint32(n_lanes))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def rowhash_pallas(lanes: jnp.ndarray, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = False) -> jnp.ndarray:
    """(C, R // 128, 128) uint32 column-major lanes -> (4, R // 128, 128)
    uint32 signature words. R must be a multiple of ``block_rows`` (ops.py
    pads with zero rows), itself a multiple of 1024."""
    c, s, lanes_per_row = lanes.shape
    bs = block_rows // LANES
    assert lanes_per_row == LANES and s % bs == 0 and bs % 8 == 0, (
        lanes.shape, block_rows)
    return pl.pallas_call(
        functools.partial(_rowhash_kernel, n_lanes=c),
        grid=(s // bs,),
        in_specs=[pl.BlockSpec((c, bs, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((4, bs, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((4, s, LANES), jnp.uint32),
        interpret=interpret,
    )(lanes)
