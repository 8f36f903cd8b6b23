"""The trace reduction, on synthetic planes and on a recorded CPU trace."""
import pytest

from bench import trace as tm
from bench.trace import Plane


def _planes():
    """Host verbs diff [0, 40) and publish [50, 100) inside a window
    [0, 100) us; device ops at [10, 20), [15, 30) and [60, 70) us."""
    us = 1000.0
    host = Plane("/host:CPU", {"python": [
        (tm.WINDOW, 0.0, 100 * us),
        ("bench.diff", 0.0, 40 * us),
        ("bench.publish", 50 * us, 50 * us),
        ("unrelated", 5 * us, 1 * us),
    ]})
    dev = Plane("/device:TPU:0", {
        tm.MODULES_LINE: [("jit_probe_lanes(3)", 10 * us, 20 * us),
                          ("jit_rowhash_pallas(7)", 60 * us, 10 * us),
                          ("jit_probe_lanes(3)", 200 * us, 5 * us)],
        tm.OPS_LINE: [("fusion.1", 10 * us, 10 * us),
                      ("fusion.2", 15 * us, 15 * us),
                      ("custom-call", 60 * us, 10 * us),
                      ("fusion.1", 200 * us, 5 * us)],
    })
    idle_dev = Plane("/device:TPU:1", {tm.OPS_LINE: []})
    return [host, dev, idle_dev]


def test_busy_union_and_window():
    s = tm.reduce(_planes())
    assert s.window_s == pytest.approx(100e-6)
    assert s.devices == 1               # the idle chip does not count
    assert s.busy_s == pytest.approx(30e-6)   # [10,30) and [60,70)
    assert s.idle_share == pytest.approx(0.7)


def test_programs_by_stable_name_inside_the_window():
    s = tm.reduce(_planes())
    assert s.program_s == pytest.approx({"jit_probe_lanes": 20e-6,
                                         "jit_rowhash_pallas": 10e-6})
    assert s.launches == {"jit_probe_lanes": 1, "jit_rowhash_pallas": 1}


def test_idle_gaps_charged_to_the_open_verb():
    s = tm.reduce(_planes())
    # gaps: [0,10) diff, [30,60) mid 45 -> none, [70,100) publish
    assert s.idle_by_verb == pytest.approx({"diff": 10e-6, "none": 30e-6,
                                            "publish": 30e-6})
    b = tm.breakdown(s)
    assert b["idle_gaps"][0][0] in ("none", "publish")
    assert b["device_ops"][0] == ["jit_probe_lanes", pytest.approx(20e-6)]


def test_innermost_verb_wins():
    us = 1000.0
    host = Plane("/host:CPU", {"t": [
        (tm.WINDOW, 0.0, 100 * us), ("bench.publish", 0.0, 100 * us),
        ("bench.check", 20 * us, 30 * us)]})
    dev = Plane("/device:TPU:0", {tm.OPS_LINE: [("op", 0.0, 20 * us),
                                                ("op", 50 * us, 50 * us)]})
    s = tm.reduce([host, dev])
    assert s.idle_by_verb == pytest.approx({"check": 30e-6})


def test_one_window_annotation_required():
    host = Plane("/host:CPU", {"t": [("bench.diff", 0.0, 5.0)]})
    with pytest.raises(ValueError):
        tm.reduce([host])


def test_stable_name():
    assert tm.stable_name("jit_lower_bound_lanes(118)") == \
        "jit_lower_bound_lanes"
    assert tm.stable_name("jit_rowhash_pallas") == "jit_rowhash_pallas"


def test_recorded_trace_reads_back(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 3).sum())
    x = jnp.ones((256,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tm.WINDOW):
        with jax.profiler.TraceAnnotation("bench.diff"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = tm.planes_from_file(str(tmp_path))
    names = {ev[0] for p in planes for evs in p.lines.values()
             for ev in evs}
    assert {tm.WINDOW, "bench.diff"} <= names
    s = tm.reduce(planes)
    assert s.window_s > 0
