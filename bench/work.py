"""What each device kernel must move, worked out from its shapes, and the
peaks of the chips the benchmark runs on.

The counts are the algorithm's own: the bytes a kernel has to read and
write for the rows it was given, whatever implements it. Padding the
program adds, and tables it copies to the device again on every call, are
not counted, so a kernel that pads or re-copies shows a lower share.

v5e publishes no peak for integer vector work, so every kernel's share is
taken against the HBM roof: the least time its bytes need at the chip's
memory bandwidth, over the time the trace shows it running.
"""
from __future__ import annotations

import math
from typing import Dict

#: device_kind -> published peaks of one chip
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e)",
    },
}

#: kernel -> the stable name of its device program in a profiler trace
PROGRAMS = {
    "rowhash": "jit_rowhash_pallas",
    "boundary": "jit_boundary_pallas",
    "lower_bound": "jit_lower_bound_lanes",
    "probe": "jit_probe_lanes",
}

WORD = 4  # bytes of one uint32 lane


def peaks(device_kind: str) -> Dict[str, object]:
    """The peaks of ``device_kind``; a chip missing from the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       "published figures to bench/work.py") from None


def rowhash_bytes(rows: int, lanes: int) -> int:
    """128-bit signatures of ``rows`` rows of ``lanes`` uint32 lanes: every
    lane read once, four signature words written."""
    return rows * (lanes + 4) * WORD


def boundary_bytes(n: int) -> int:
    """Run-start flags of a sorted stream of ``n`` 128-bit keys: four
    lanes read, one int32 flag written per key."""
    return n * (4 + 1) * WORD


def _depth(n_table: int) -> int:
    """Steps of a binary search over ``n_table`` rows."""
    return max(1, math.ceil(math.log2(n_table + 1)))


def lower_bound_bytes(n_table: int, n_queries: int, lanes: int = 2) -> int:
    """One lower bound per query over a sorted table of 64-bit keys: the
    query read, one table key touched per search step, one int32 out."""
    return n_queries * WORD * (lanes + _depth(n_table) * lanes + 1)


def probe_bytes(n_table: int, n_queries: int, lanes: int = 4) -> int:
    """The fused probe of 128-bit keys: a lower and an upper bound per
    query, each its own descent, and two int32 results."""
    return n_queries * WORD * (lanes + 2 * _depth(n_table) * lanes + 2)


def roofline_share(nbytes: float, device_s: float,
                   device_kind: str) -> float:
    """Percent of the HBM roof: the least time ``nbytes`` need at the
    chip's bandwidth over the time the kernel ran."""
    least = nbytes / float(peaks(device_kind)["hbm_bytes_per_s"])
    return 100.0 * least / device_s
