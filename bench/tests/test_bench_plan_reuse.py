"""The ``plan_reuse_share`` reader: read from the publish counters directly,
and from a traced small collab run on the CPU, where every PR carries a
check and nothing moves the base while it runs."""
import pytest

from bench.tests.conftest import run_small


@pytest.mark.parametrize("counters,want", [
    ({"publish.plans_reused": 36, "publish.plans_replanned": 0}, 100.0),
    ({"publish.plans_reused": 3, "publish.plans_replanned": 1}, 75.0),
    ({"publish.plans_reused": 0, "publish.plans_replanned": 0}, None),
    ({}, None)])
def test_plan_reuse_share_reads_the_publish_counters(counters, want):
    """100 when every table committed its preview plan, less when one was
    planned again, None where no publish ran or the program lacks the
    counters."""
    from bench.harness import Context, reader_of
    ctx = Context(workload="pk_collab_c10k", device_kind="cpu", spans=[],
                  counters=counters, counts={"prs_published": 4},
                  trace=None)
    assert reader_of("plan_reuse_share")(ctx) == want


@pytest.mark.parametrize("workload", ["pk_collab_c10k", "nopk_collab_c10k"])
def test_traced_collab_run_reuses_every_plan(small_root, workload):
    r = run_small(small_root, workload, traced=True)
    assert r["correct"] is True, r["check"]
    assert r["metrics"]["plan_reuse_share"]["value"] == 100.0
