"""Formulas the per-layer metric readers share for what the program measures
of itself in a window: the time in its telemetry spans and the change of
its counters, each per harness operation."""
from __future__ import annotations

from typing import Optional

from .readers import per, spans_named


def span_ms_per(ctx, name: str, count: str) -> Optional[float]:
    """Milliseconds in the outermost spans called ``name`` per harness
    operation ``count``; None where the window has no such span."""
    spans = list(spans_named(ctx.spans, name))
    if not spans:
        return None
    return per(1e3 * sum(s.dur_s for s in spans), ctx.counts.get(count, 0))


def mib_per(ctx, counter: str, count: str) -> Optional[float]:
    """A byte counter's change over the window, in MiB per harness
    operation ``count``; None where the program has no such counter."""
    if counter not in ctx.counters:
        return None
    return per(ctx.counters[counter] / 2**20, ctx.counts.get(count, 0))
