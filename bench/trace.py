"""Reduction of a JAX profiler trace to what the per-layer metrics read.

A trace is reduced to plain data first (``planes_from_file``): planes, their
lines, and events ``(name, start_ns, duration_ns)``. ``reduce`` then takes

- the window: the host annotation ``bench.window`` that the harness opens
  around the measured window;
- busy time: the union of the device's operation intervals inside the
  window, per device plane, averaged over the planes that ran anything;
- per-program device time and launch counts, by the program's stable name
  (``jit_<function>``, the suffix in parentheses dropped);
- idle gaps: each stretch of the window in which no device operation ran,
  charged to the innermost ``bench.<verb>`` annotation that was open on the
  host at the gap's middle (``none`` where no verb was open).
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

WINDOW = "bench.window"
VERB_PREFIX = "bench."
DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]] = field(default_factory=dict)


@dataclass
class Summary:
    window_s: float
    busy_s: float                           # mean over active device planes
    devices: int                            # device planes that ran anything
    program_s: Dict[str, float]             # stable program name -> seconds
    launches: Dict[str, int]                # stable program name -> count
    idle_by_verb: Dict[str, float]          # host verb -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def stable_name(name: str) -> str:
    """``jit_probe_lanes(42)`` -> ``jit_probe_lanes``."""
    return name.split("(", 1)[0].strip()


def planes_from_file(trace_dir: str) -> List[Plane]:
    """The newest ``*.xplane.pb`` under ``trace_dir``, as plain planes."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for p in data.planes:
        plane = Plane(p.name)
        for line in p.lines:
            plane.lines[line.name] = [(e.name, float(e.start_ns),
                                       float(e.duration_ns))
                                      for e in line.events]
        out.append(plane)
    return out


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s: float, e: float, w0: float, w1: float):
    return max(s, w0), min(e, w1)


def host_events(planes: Sequence[Plane]) -> List[Event]:
    return [ev for p in planes if not p.name.startswith(DEVICE_PLANE)
            for evs in p.lines.values() for ev in evs]


def _window(planes: Sequence[Plane]) -> Tuple[float, float]:
    wins = [(s, s + d) for n, s, d in host_events(planes) if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"want one {WINDOW!r} annotation, found "
                         f"{len(wins)}")
    return wins[0]


def _verb_at(verbs: List[Tuple[float, float, str]], starts: List[float],
             t: float) -> str:
    """Innermost verb open at ``t``: the latest-starting one that covers
    it (verbs nest, so that one is innermost)."""
    i = bisect.bisect_right(starts, t)
    while i > 0:
        i -= 1
        s, e, name = verbs[i]
        if e >= t:
            return name
    return "none"


def reduce(planes: Sequence[Plane]) -> Summary:
    w0, w1 = _window(planes)
    verbs = sorted((s, s + d, n[len(VERB_PREFIX):])
                   for n, s, d in host_events(planes)
                   if n.startswith(VERB_PREFIX) and n != WINDOW)
    starts = [v[0] for v in verbs]
    program_s: Dict[str, float] = defaultdict(float)
    launches: Dict[str, int] = defaultdict(int)
    idle: Dict[str, float] = defaultdict(float)
    busy, devices = 0.0, 0
    for p in planes:
        if not p.name.startswith(DEVICE_PLANE):
            continue
        ops = p.lines.get(OPS_LINE) or p.lines.get(MODULES_LINE) or []
        spans = []
        for _, s, d in ops:
            a, b = _clip(s, s + d, w0, w1)
            if b > a:
                spans.append((a, b))
        for name, s, d in p.lines.get(MODULES_LINE, []):
            a, b = _clip(s, s + d, w0, w1)
            if b > a:
                program_s[stable_name(name)] += (b - a) / 1e9
                launches[stable_name(name)] += 1
        if not spans:
            continue
        devices += 1
        merged = _union(spans)
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle[_verb_at(verbs, starts, (a + b) / 2)] += (b - a) / 1e9
    if devices:
        busy /= devices
        for k in idle:
            idle[k] /= devices
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy, devices=devices,
                   program_s=dict(program_s), launches=dict(launches),
                   idle_by_verb=dict(idle))


def breakdown(summary: Summary, top: int = 10) -> Dict[str, list]:
    """The ``breakdown`` of a result line: the device programs that took
    most time, and the idle time by what the host was doing."""
    progs = sorted(summary.program_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary.idle_by_verb.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in progs],
            "idle_gaps": [[k, v] for k, v in gaps]}
