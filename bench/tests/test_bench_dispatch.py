"""Traced small runs of each kind of cell on the CPU, through the device
path of the kernels (``FORCE_PALLAS_INTERPRET``): the dispatch layer's
span and transfer metrics and the read path's spans are read and
positive."""
import pytest

from bench.tests.conftest import run_small

COLLAB = ("stage_ms_per_pr", "fetch_ms_per_pr", "h2d_mib_per_pr")
READS = ("locate_ms_per_read", "gather_ms_per_read", "h2d_mib_per_read")


@pytest.mark.parametrize("workload,names", [
    ("pk_collab_c10k", COLLAB), ("nopk_collab_c10k", COLLAB),
    ("pk_point_reads", READS)])
def test_traced_run_reads_the_dispatch_metrics(small_root, monkeypatch,
                                               workload, names):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "FORCE_PALLAS_INTERPRET", True)
    r = run_small(small_root, workload, traced=True)
    assert r["correct"] is True, r["check"]
    m = r["metrics"]
    for name in names:
        assert m[name]["value"] > 0, name
