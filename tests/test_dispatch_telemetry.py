"""Telemetry of the kernel dispatch layer (``kernels/ops.py``).

On the device path (here ``FORCE_PALLAS_INTERPRET``: the device programs
on the CPU backend) every device call opens one ``ops.stage`` and one
``ops.fetch`` span and counts the bytes it copies to the device, padding
included, in ``xfer.h2d_bytes``; the numpy paths open no span and count
nothing. The byte counts below are worked out by hand from the padding
rules: search tables pad to a power of two rows, query batches to a power
of two of at least 1024, rowhash chunks to 8192 rows, boundary streams to
a whole block of 8 x 128 keys.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import telemetry
from repro.kernels import ops

H2D = "xfer.h2d_bytes"


@pytest.fixture
def device_path(monkeypatch):
    monkeypatch.setattr(ops, "FORCE_PALLAS_INTERPRET", True)


def _sorted_u64(rng, n):
    return np.sort(rng.integers(0, 2**62, n).astype(np.uint64))


def _calls():
    rng = np.random.default_rng(13)
    t_lo = _sorted_u64(rng, 300)
    t_hi = rng.integers(0, 2**62, 300).astype(np.uint64)
    q = rng.integers(0, 2**62, 10).astype(np.uint64)
    lanes = rng.integers(0, 2**32, (1025, 4), dtype=np.uint64).astype(
        np.uint32)
    s_lo = _sorted_u64(rng, 1000)
    s_hi = rng.integers(0, 2**62, 1000).astype(np.uint64)
    return {
        # table 300 -> 512 rows x 4 lanes; queries 10 -> 1024 rows x 4 lanes
        "probe128": (lambda: ops.probe128(t_lo, t_hi, q, q),
                     (512 * 4 + 1024 * 4) * 4),
        # table 777 -> 1024 rows x 2 lanes; queries 333 -> 1024 x 2 lanes
        "lower_bound": (lambda: ops.lower_bound(
            _sorted_u64(rng, 777), q[:1].repeat(333)),
            (1024 * 2 + 1024 * 2) * 4),
        # 1025 rows x 4 lanes -> one chunk of 8192 rows x 4 lanes
        "rowhash": (lambda: ops.rowhash(lanes), 8192 * 4 * 4),
        # 1000 keys x 4 lanes -> one block of 8 x 128 keys x 4 lanes
        "_segsum_boundary": (lambda: ops._segsum_boundary(s_lo, s_hi),
                             1024 * 4 * 4),
    }


@pytest.mark.parametrize("op", ["probe128", "lower_bound", "rowhash",
                                "_segsum_boundary"])
def test_h2d_bytes_are_the_padded_lanes(device_path, op):
    call, want = _calls()[op]
    before = telemetry.PROCESS.counters.get(H2D, 0)
    call()
    assert telemetry.PROCESS.counters.get(H2D, 0) - before == want


@pytest.mark.parametrize("op", ["probe128", "lower_bound", "rowhash",
                                "_segsum_boundary"])
def test_a_device_call_opens_stage_then_fetch(device_path, op):
    call, _ = _calls()[op]
    with telemetry.trace(None) as t:
        call()
    assert [s.name for s in t.roots] == ["ops.stage", "ops.fetch"]
    assert all(not s.children for s in t.roots)


def test_upper_bound_and_searchsorted128_pass_through_lower_bound(
        device_path):
    rng = np.random.default_rng(5)
    t = _sorted_u64(rng, 100)
    q = np.sort(rng.integers(0, 2**62, 7).astype(np.uint64))
    with telemetry.trace(None) as tr:
        ops.upper_bound(t, q)
        ops.searchsorted128(t, t, q, q)
    assert [s.name for s in tr.roots] == ["ops.stage", "ops.fetch"] * 2


@pytest.mark.parametrize("op", ["probe128", "lower_bound", "rowhash"])
def test_the_numpy_path_opens_no_span_and_counts_nothing(op):
    assert not ops.backend_uses_pallas()
    call, _ = _calls()[op]
    before = telemetry.PROCESS.counters.get(H2D, 0)
    with telemetry.trace(None) as t:
        call()
    assert t.roots == []
    assert telemetry.PROCESS.counters.get(H2D, 0) == before


def test_the_counter_folds_into_the_engine_snapshot(device_path):
    from repro.core import Engine
    e = Engine()
    before = telemetry.metrics_snapshot(e)[H2D]
    _calls()["probe128"][0]()
    assert telemetry.metrics_snapshot(e)[H2D] - before == 24576
    e.reset_metrics()
    assert telemetry.metrics_snapshot(e)[H2D] == 0


def test_ops_imports_before_core_in_a_fresh_interpreter():
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("import repro.kernels.ops\n"
            "from repro.core import Repo, telemetry\n"
            "assert 'ops.stage' in telemetry.registered_spans()\n"
            "print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "ok"
