"""Rehearsal of the chip run on the CPU: the device path end to end.

``chip_smoke.py``'s workflow (load, tag, branch, update both branches, diff
== sql_diff == numpy, PR check, publish, revert) and the golden-style diff
and merge workloads, at a few thousand rows, through the device dispatch
(``ops.FORCE_PALLAS_INTERPRET``: Pallas kernels in interpret mode, the XLA
search on the CPU backend). Its padding, sentinel and block-edge handling
must give the numpy path's digests.
"""
import chip_smoke
from benchmarks.digests import run_workload
from repro.kernels import ops


def _run():
    return (chip_smoke.workflow(4_000, 300, 0, chip_smoke.Compiles()),
            run_workload(True, n_rows=3_000, csize=200),
            run_workload(False, n_rows=3_000, csize=200))


def test_device_path_matches_numpy_path():
    want = _run()
    prev = ops.FORCE_PALLAS_INTERPRET
    ops.FORCE_PALLAS_INTERPRET = True
    try:
        got = _run()
    finally:
        ops.FORCE_PALLAS_INTERPRET = prev
    assert got == want
