"""Idle time of a profiler trace charged to the program's own spans.

The program's telemetry spans are host annotations of their own names
while a tracer is armed (``repro.core.telemetry``), on the clock of the
device trace. ``idle_by_span`` splits each idle gap of the window exactly
at the edges of those spans and charges each part to the innermost span
open on the host (``none`` outside every span), averaged over the device
planes that ran anything, as ``bench.trace.reduce`` averages its verb
split. Only names the program registers are matched, so the runtime's own
host events are never charged.

``idle_in_dispatch_share`` is the share of the window that the device sat
idle while the host was inside a device call of the kernel dispatch layer
(``ops.stage`` or ``ops.fetch`` innermost).

A per-layer metric cannot read these yet: the harness reduces the trace to
a ``bench.trace.Summary`` and deletes it before any reader runs, so the
planes have to reach the readers first (see PERF.md, Open questions).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Collection, Dict, List, Optional, Sequence, Tuple

from .trace import (DEVICE_PLANE, MODULES_LINE, OPS_LINE, Plane, _clip,
                    _union, _window, host_events)

Segment = Tuple[float, float, str]        # (start_ns, end_ns, span name)

#: the program spans of a device call in the kernel dispatch layer
DISPATCH_SPANS = ("ops.stage", "ops.fetch")


def program_spans() -> Collection[str]:
    """The span names the program registers; none where the program has
    no telemetry module."""
    try:
        from repro.core import telemetry
    except ImportError:
        return ()
    return telemetry.registered_spans()


def _innermost_segments(spans: Sequence[Segment], w0: float,
                        w1: float) -> List[Segment]:
    """``[w0, w1)`` cut at every span edge into disjoint segments, each
    named after the innermost span open over it (``none`` where no span
    is). Spans of one thread nest; one that outlasts its parent is cut at
    the parent's end."""
    segs: List[Segment] = []
    stack: List[Tuple[float, str]] = []          # (end, name), innermost last
    cur = w0

    def close_until(t: float) -> None:
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                segs.append((cur, end, name))
                cur = end

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        s, e = _clip(s, e, w0, w1)
        if e <= s:
            continue
        close_until(s)
        if s > cur:
            segs.append((cur, s, stack[-1][1] if stack else "none"))
            cur = s
        if stack:
            e = min(e, stack[-1][0])
        stack.append((e, name))
    close_until(w1)
    if w1 > cur:
        segs.append((cur, w1, "none"))
    return segs


def _charge(gaps: Sequence[Tuple[float, float]], segs: Sequence[Segment],
            into: Dict[str, float]) -> None:
    """Add each part of the sorted disjoint ``gaps`` that falls in a
    segment to that segment's name, in seconds."""
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, name = segs[k]
            lo, hi = max(a, s), min(b, e)
            if hi > lo:
                into[name] += (hi - lo) / 1e9
            k += 1


def idle_by_span(planes: Sequence[Plane]) -> Dict[str, float]:
    """Idle seconds of the window by innermost program span, averaged over
    the device planes that ran anything; empty where none did."""
    w0, w1 = _window(planes)
    names = program_spans()
    segs = _innermost_segments(
        [(s, s + d, n) for n, s, d in host_events(planes) if n in names],
        w0, w1)
    idle: Dict[str, float] = defaultdict(float)
    devices = 0
    for p in planes:
        if not p.name.startswith(DEVICE_PLANE):
            continue
        ops = p.lines.get(OPS_LINE) or p.lines.get(MODULES_LINE) or []
        busy = []
        for _, s, d in ops:
            a, b = _clip(s, s + d, w0, w1)
            if b > a:
                busy.append((a, b))
        if not busy:
            continue
        devices += 1
        edges = [w0] + [x for iv in _union(busy) for x in iv] + [w1]
        _charge([(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a],
                segs, idle)
    return {k: v / devices for k, v in idle.items()}


def idle_in_dispatch_share(by_span: Dict[str, float],
                           window_s: float) -> Optional[float]:
    """Percent of the window idle inside ``ops.stage`` or ``ops.fetch``;
    None where the trace shows no such span."""
    if not any(k in by_span for k in DISPATCH_SPANS):
        return None
    return 100.0 * sum(by_span.get(k, 0.0) for k in DISPATCH_SPANS) / window_s
