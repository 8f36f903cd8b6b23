"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

Everything particular to a cell is found by name:

- its configuration: the ``file`` of its ``configs`` entry (JSON), which
  names its generator, ``bench/<generator>.py``, and its plain reference,
  ``bench/reference/<reference>.py`` (``bench/workload.py``);
- its traffic mix: ``bench/traffic/<traffic>.json``, read by the one
  traffic generator (``bench/traffic.py``) and driven by the runner its
  ``kind`` names, ``bench/runners/<kind>.py``, which loads the
  configuration itself;
- each per-layer metric: ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns the number or None when it finds nothing to read.

So a new configuration, kind of mix, cell or metric is new files and
entries alone.

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the telemetry spans and
counters of the window, a profiler trace of it, and the kernels' work.
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

from . import trace as trace_mod
from . import work
from .workload import Verbs, load_module, log

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- discovery

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def config_of(bench: dict, cell: dict, root: str = ROOT) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == cell["config"]:
            with open(os.path.join(root, entry["file"])) as f:
                config = json.load(f)
            config["name"] = entry["name"]
            return config
    raise KeyError(f"no config {cell['config']!r} in BENCHMARK.json")


def traffic_of(cell: dict, root: str = ROOT) -> dict:
    path = os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def reader_of(name: str, root: str = ROOT) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    return load_module(os.path.join(root, "bench", "metrics",
                                    name + ".py")).read


def runner_of(kind: str, root: str = ROOT) -> Callable:
    """``RUNNER`` of ``bench/runners/<kind>.py``: called with the cell's
    configuration, mix, seed, verbs and root, it loads the configuration
    and gives ``repo``, ``warm_up()``, ``window(seconds)``,
    ``end_to_end(window_s)``, ``counts()`` and ``check(lower_precision)``."""
    return load_module(os.path.join(root, "bench", "runners",
                                    kind + ".py")).RUNNER


# --------------------------------------------------------- what readers see

@dataclass
class Context:
    """What a per-layer metric's reader reads from one traced window."""
    workload: str
    device_kind: str
    spans: list                       # telemetry root spans of the window
    counters: Dict[str, int]          # registry deltas over the window
    counts: Dict[str, int]            # harness operations in the window
    trace: Optional[trace_mod.Summary]
    work: Dict[str, Dict[str, float]] = field(default_factory=dict)
    compiles: int = 0


class KernelWork:
    """Records, per kernel, the calls the program makes into ``ops`` and
    the bytes each must move (``bench/work.py``), by wrapping the ``ops``
    entry points for the traced window only."""

    def __init__(self):
        self.totals: Dict[str, Dict[str, float]] = {}
        self._saved: Dict[str, Callable] = {}

    def _add(self, kernel: str, nbytes: int) -> None:
        t = self.totals.setdefault(kernel, {"calls": 0, "bytes": 0})
        t["calls"] += 1
        t["bytes"] += nbytes

    def install(self) -> None:
        from repro.kernels import ops
        rules = {
            "rowhash": lambda lanes: ("rowhash", work.rowhash_bytes(
                lanes.shape[0], lanes.shape[1])),
            "_segsum_boundary": lambda lo, hi: ("boundary",
                                                work.boundary_bytes(
                                                    lo.shape[0])),
            "lower_bound": lambda t, q: ("lower_bound",
                                         work.lower_bound_bytes(
                                             t.shape[0], q.shape[0])),
            "probe128": lambda tl, th, ql, qh: ("probe", work.probe_bytes(
                tl.shape[0], ql.shape[0])),
        }
        for attr, rule in rules.items():
            fn = getattr(ops, attr)
            self._saved[attr] = fn
            setattr(ops, attr, self._wrap(fn, rule))

    def _wrap(self, fn, rule):
        def run(*args, **kw):
            if all(a.shape[0] for a in args):   # ops skip empty calls
                self._add(*rule(*args))
            return fn(*args, **kw)
        return run

    def uninstall(self) -> None:
        from repro.kernels import ops
        for attr, fn in self._saved.items():
            setattr(ops, attr, fn)
        self._saved.clear()


class Compiles:
    """Programs JAX built, from its monitoring events. JAX times a
    program's compilation or its load from the persistent cache as one
    backend-compile event, and counts the loads apart."""

    def __init__(self):
        self.n = self.loaded = 0

    def on_duration(self, event, _secs, **_):
        if event == COMPILE_EVENT:
            self.n += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.loaded += 1


# ------------------------------------------------------------------ a run

def use_chip(chips: int):
    """The first device, after checking that JAX sees ``chips`` TPUs."""
    import jax
    if jax.default_backend() != "tpu":
        raise NoChip(f"JAX found no TPU (default backend "
                     f"{jax.default_backend()!r})")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def compile_cache(root: str = ROOT) -> str:
    """Every program this process compiles goes to the checkout's
    persistent cache, however quickly it compiled, so that a later run of
    the cell in this checkout compiles nothing."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


@contextlib.contextmanager
def traced_window(engine, verbs: Verbs, kernel_work: KernelWork,
                  trace_dir: str):
    """Profile the block into ``trace_dir`` (host Python tracing off), with
    the telemetry tracer armed, the verbs annotated, the kernels' work
    recorded and the whole block marked as the window; yields the tracer."""
    import jax
    from repro.core import telemetry
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    verbs.annotate = True
    kernel_work.install()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with telemetry.trace(engine) as tracer, \
                jax.profiler.TraceAnnotation(trace_mod.WINDOW):
            yield tracer
    finally:
        jax.profiler.stop_trace()
        kernel_work.uninstall()
        verbs.annotate = False


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, root: str = ROOT, chip: bool = True,
             control: bool = False) -> dict:
    """Set-up, window, reading of the metrics and the comparison with the
    reference. ``chip=False`` (tests only) skips the look for a TPU and
    reports the device JAX has. ``control=True`` (``bench/limits.py``)
    also compares the control at the same requests, under ``control``."""
    import jax
    bench = load_benchmark(root)
    cell = cell_of(bench, workload)
    config = config_of(bench, cell, root)
    mix = traffic_of(cell, root)
    devices = (use_chip(int(cell["chips"])) if chip else jax.devices())
    dev = devices[0]
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(
        compiles.on_duration)
    jax.monitoring.register_event_listener(compiles.on_event)

    verbs = Verbs()
    runner = runner_of(mix["kind"], root)(config, mix, seed, verbs, root)
    runner.warm_up()
    verbs.reset()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}: {compiles.n} programs built, "
        f"{compiles.loaded} of them loaded from the persistent cache")

    from repro.core import telemetry
    engine = runner.repo.engine
    kernel_work = KernelWork()
    trace_dir = os.path.join(root, ".bench_trace", f"{workload}-{seed}")
    c0 = compiles.n
    m0 = telemetry.metrics_snapshot(engine)
    with (traced_window(engine, verbs, kernel_work, trace_dir) if traced
          else contextlib.nullcontext()) as tracer:
        window_s = runner.window(seconds)
    in_window = compiles.n - c0
    m1 = telemetry.metrics_snapshot(engine)
    log(f"window {window_s:.3f} s, {in_window} programs built in it")
    for name, walls in sorted(verbs.walls.items()):
        log(f"  verb {name}: {len(walls)} calls, {sum(walls):.3f} s")
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    summary = None
    if traced:
        summary = trace_mod.reduce(trace_mod.planes_from_file(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    t_check = time.perf_counter()
    numbers = runner.check()
    log(f"check took {time.perf_counter() - t_check:.3f} s")
    correct = all(limit is None or value <= limit
                  for value, limit in numbers.values())
    counts = runner.counts()
    correct = correct and counts["failed"] == 0

    metrics = {}
    if traced:
        ctx = Context(workload=workload, device_kind=dev.device_kind,
                      spans=list(tracer.roots),
                      counters={k: m1[k] - m0.get(k, 0) for k in m1},
                      counts=counts, trace=summary,
                      work=kernel_work.totals, compiles=in_window)
        for m in bench["per_layer"]:
            if applies(m, workload):
                value = reader_of(m["name"], root)(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = runner.end_to_end(window_s)
        e2e["setup_s"] = setup_s
        for m in bench["end_to_end"]:
            if applies(m, workload) and e2e[m["name"]] is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": metrics,
              "device": device}
    if traced:
        result["breakdown"] = trace_mod.breakdown(summary)
    if control:
        result["control"] = {k: v for k, (v, _) in
                             runner.check(lower_precision=True).items()}
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}
    return result


def report(result: dict) -> None:
    """Each compared number beside its limit as the last lines on standard
    error, then the result as the last line on standard output."""
    for name, c in result["check"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
