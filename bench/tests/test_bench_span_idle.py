"""Idle time charged to program spans (``bench/span_idle.py``), on synthetic
planes and on a recorded CPU profile of a small traced diff."""
import pytest

from bench import span_idle as si
from bench import trace as tm
from bench.tests.test_bench_trace import _planes
from bench.trace import Plane


def _program_span_planes():
    """Window [0, 110) us. Host: verb diff [0, 60) holding the program's
    diff [0, 60) > signed_delta [5, 40) > ops.stage [10, 20) and ops.fetch
    [20, 25); verb publish [60, 100) holding publish [60, 100); nothing
    after 100. Device ops at [12, 18) and [70, 80)."""
    import repro.kernels.ops  # noqa: F401  registers the ops.* spans
    us = 1000.0
    host = Plane("/host:CPU", {"python": [
        (tm.WINDOW, 0.0, 110 * us),
        ("bench.diff", 0.0, 60 * us),
        ("diff", 0.0, 60 * us),
        ("signed_delta", 5 * us, 35 * us),
        ("ops.stage", 10 * us, 10 * us),
        ("ops.fetch", 20 * us, 5 * us),
        ("bench.publish", 60 * us, 40 * us),
        ("publish", 60 * us, 40 * us),
        ("unregistered runtime event", 0.0, 110 * us),
    ]})
    dev = Plane("/device:TPU:0", {tm.OPS_LINE: [("op", 12 * us, 6 * us),
                                                ("op", 70 * us, 10 * us)]})
    return [host, dev]


def test_idle_by_span_charges_the_innermost_program_span():
    planes = _program_span_planes()
    by_span = si.idle_by_span(planes)
    s = tm.reduce(planes)
    # gaps [0,12), [18,70), [80,110) cut at the span edges
    assert by_span == pytest.approx({
        "diff": 25e-6, "signed_delta": 20e-6, "ops.stage": 4e-6,
        "ops.fetch": 5e-6, "publish": 30e-6, "none": 10e-6})
    assert sum(by_span.values()) == pytest.approx(s.window_s - s.busy_s)
    # the verbs keep the midpoint rule: [18,70) goes whole to diff
    assert s.idle_by_verb == pytest.approx({"diff": 64e-6, "publish": 30e-6})
    assert si.idle_in_dispatch_share(by_span, s.window_s) == \
        pytest.approx(100.0 * 9 / 110)


def test_a_gap_straddling_two_spans_is_split_exactly():
    us = 1000.0
    host = Plane("/host:CPU", {"t": [
        (tm.WINDOW, 0.0, 100 * us), ("plan_merge", 0.0, 50 * us),
        ("commit", 50 * us, 50 * us)]})
    dev = Plane("/device:TPU:0", {tm.OPS_LINE: [("op", 0.0, 20 * us),
                                                ("op", 90 * us, 10 * us)]})
    # the gap [20, 90) has its middle in commit; it is split at 50
    assert si.idle_by_span([host, dev]) == pytest.approx(
        {"plan_merge": 30e-6, "commit": 40e-6})


def test_idle_by_span_averages_over_active_devices():
    planes = _program_span_planes()
    dev1 = Plane("/device:TPU:1", {tm.OPS_LINE: [("op", 0.0, 110 * 1000.0)]})
    assert tm.reduce(planes + [dev1]).devices == 2
    assert si.idle_by_span(planes + [dev1])["publish"] == pytest.approx(15e-6)


def test_no_dispatch_span_reads_none():
    assert si.idle_in_dispatch_share({"diff": 1.0, "none": 2.0}, 10.0) is None
    assert si.idle_by_span(_planes()[:1]) == {}      # no device plane


def test_program_spans_leave_verbs_and_breakdown_unchanged():
    plain = _planes()
    us = 1000.0
    spanned = _planes()
    spanned[0].lines["python"] += [("diff", 0.0, 40 * us),
                                   ("ops.stage", 2 * us, 20 * us),
                                   ("publish", 50 * us, 50 * us)]
    a, b = tm.reduce(plain), tm.reduce(spanned)
    assert a.idle_by_verb == b.idle_by_verb
    assert tm.breakdown(a) == tm.breakdown(b)
    assert (a.busy_s, a.program_s, a.launches) == \
        (b.busy_s, b.program_s, b.launches)


def test_no_program_span_is_named_like_a_harness_verb():
    import repro.kernels.ops  # noqa: F401
    import repro.vcs_cli  # noqa: F401
    names = si.program_spans()
    assert {"ops.stage", "ops.fetch", "table.locate_keys",
            "gather_payload", "diff"} <= set(names)
    assert not any(n.startswith(tm.VERB_PREFIX) for n in names)


def test_each_armed_span_is_one_host_annotation(tmp_path, monkeypatch):
    """A small diff on the device path, traced by telemetry under a CPU
    profile: one host annotation per span, same names, same durations."""
    import jax
    import numpy as np
    from repro.core import Repo, Schema, Column, CType, telemetry
    from repro.kernels import ops
    schema = Schema((Column("k", CType.I64), Column("v", CType.F64)),
                    primary_key=("k",))
    repo = Repo()
    repo.engine.create_table("t", schema)
    repo.engine.insert("t", {"k": np.arange(3000, dtype=np.int64),
                             "v": np.zeros(3000)})
    repo.engine.create_snapshot("s", "t")
    for part in range(3):
        keys = np.arange(part, 3000, 7, dtype=np.int64)
        repo.engine.update_by_keys("t", {"k": keys,
                                         "v": np.full(keys.shape, part + 1.)})
    monkeypatch.setattr(ops, "FORCE_PALLAS_INTERPRET", True)
    repo.diff("snap:s", "HEAD", table="t")          # compile first
    repo.engine.store.vis_cache.clear()             # then diff cold
    repo.engine.store.delta_cache.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tm.WINDOW):
        with telemetry.trace(repo.engine) as tracer:
            repo.diff("snap:s", "HEAD", table="t")
    jax.profiler.stop_trace()

    spans = []

    def walk(ss):
        for s in ss:
            spans.append(s)
            walk(s.children)
    walk(tracer.roots)
    names = set(si.program_spans())
    notes = sorted((ev for ev in tm.host_events(tm.planes_from_file(
        str(tmp_path))) if ev[0] in names), key=lambda ev: (ev[1], -ev[2]))
    assert {"diff", "ops.stage", "ops.fetch"} <= {s.name for s in spans}
    assert [n for n, _, _ in notes] == [s.name for s in spans]
    for (_, _, dur_ns), s in zip(notes, spans):
        assert abs(dur_ns / 1e9 - s.dur_s) <= max(0.1 * s.dur_s, 50e-6)
