"""Byte-identity regression guard for the Δ pipeline.

The workloads and their golden digests live in ``benchmarks/digests.py``,
shared with ``chip_smoke.py``, which checks the same constants on the TPU.
"""
import pytest

from benchmarks.digests import (GOLDEN, GOLDEN_APPLY, run_apply_workload,
                                run_workload)


@pytest.mark.parametrize("pk", [True, False])
def test_diff_pipeline_byte_identical(pk):
    got = run_workload(pk)
    assert got == GOLDEN[pk], got


@pytest.mark.parametrize("pk", [True, False])
def test_apply_path_byte_identical(pk):
    got = run_apply_workload(pk)
    assert got == GOLDEN_APPLY[pk], got


@pytest.mark.parametrize("pk", [True, False])
def test_apply_path_byte_identical_from_evicted_store(pk, tmp_path):
    """ISSUE 10: the SAME goldens with every engine spilled to a pack
    tier and fully evicted around each apply — merge/revert/publish over
    faulted-in objects must land byte-identical tables."""
    got = run_apply_workload(pk, pack_root=tmp_path)
    assert got == GOLDEN_APPLY[pk], got
