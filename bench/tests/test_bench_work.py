"""The kernels' byte counts, the table of peaks, and the kernel shares the
readers work out from a trace."""
import pytest

from bench import harness, readers, work
from bench.trace import Summary


def test_peaks_of_v5e_with_their_source():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert "TPU v5e" in p["source"]


def test_a_chip_missing_from_the_table_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


def test_rowhash_reads_every_lane_and_writes_four_words():
    assert work.rowhash_bytes(1000, 32) == 1000 * 36 * 4


def test_boundary_reads_four_lanes_and_writes_a_flag():
    assert work.boundary_bytes(1 << 10) == (1 << 10) * 5 * 4


@pytest.mark.parametrize("n_table,depth", [(1, 1), (2, 2), (1023, 10),
                                           (1024, 11), (1 << 18, 19)])
def test_search_counts_one_key_per_step(n_table, depth):
    assert work.lower_bound_bytes(n_table, 10) == 10 * 4 * (2 + 2 * depth + 1)
    assert work.probe_bytes(n_table, 10) == 10 * 4 * (4 + 8 * depth + 2)


def test_search_counts_no_table_copy():
    """Only the queries' own work counts: a table twice as long adds one
    step per query, not its length."""
    small = work.probe_bytes(1 << 18, 1)
    big = work.probe_bytes(1 << 19, 1)
    assert big - small == 2 * 4 * 4


def test_roofline_share():
    # 819 MB in 2 ms at 819 GB/s: the least time is 1 ms, so 50 %
    assert work.roofline_share(819e6, 2e-3, "TPU v5 lite") == \
        pytest.approx(50.0)


def test_every_program_has_a_stable_name():
    assert set(work.PROGRAMS) == {"rowhash", "boundary", "lower_bound",
                                  "probe"}
    assert all(v.startswith("jit_") for v in work.PROGRAMS.values())


def _ctx(program_s, given):
    trace = Summary(window_s=1.0, busy_s=sum(program_s.values()), devices=1,
                    program_s=program_s,
                    launches={k: 1 for k in program_s}, idle_by_verb={})
    return harness.Context(workload="w", device_kind="TPU v5 lite", spans=[],
                           counters={}, counts={}, trace=trace,
                           work={k: {"calls": 1, "bytes": b}
                                 for k, b in given.items()})


@pytest.mark.parametrize("kernel", sorted(work.PROGRAMS))
def test_share_of_a_kernel_from_its_trace_time(kernel):
    ctx = _ctx({work.PROGRAMS[kernel]: 2e-3}, {kernel: 819e6})
    assert readers.roofline(ctx, [kernel]) == pytest.approx(50.0)


@pytest.mark.parametrize("kernel", sorted(work.PROGRAMS))
def test_a_kernel_that_ran_with_no_work_recorded_is_an_error(kernel):
    """Its calls went round the watched entry point: the share would go
    silent while the kernel still runs, so the reader refuses."""
    ctx = _ctx({work.PROGRAMS[kernel]: 2e-3}, {})
    with pytest.raises(RuntimeError, match="no call reached"):
        readers.roofline(ctx, [kernel])


def test_a_kernel_that_did_not_run_leaves_the_share_silent():
    assert readers.roofline(_ctx({}, {}), ["rowhash"]) is None
    # the searches: the probes ran, the lower bounds did not
    ctx = _ctx({"jit_probe_lanes": 1e-3}, {"probe": 819e6 / 4})
    assert readers.search_roofline(ctx) == pytest.approx(25.0)


@pytest.mark.parametrize("name", ["idle_share.collab", "idle_share.reads"])
def test_idle_share_readers(name):
    ctx = _ctx({"jit_probe_lanes": 0.25}, {"probe": 1})
    assert harness.reader_of(name)(ctx) == pytest.approx(75.0)
