"""Helpers the per-layer metric readers in ``bench/metrics/`` share: walks
over the telemetry span forest of a window, a kernel's share of the HBM
roof from the trace's device time and the work the kernel was given, and
the device's idle share. A metric measured alike for several kinds of cell
keeps its formula here, and each cell kind's reader names it."""
from __future__ import annotations

from typing import Iterable, Iterator, Optional, Sequence

from . import work


def spans_named(spans: Iterable, name: str) -> Iterator:
    """Every span called ``name`` in the forest, outermost only: a match's
    own descendants are not searched."""
    for s in spans:
        if s.name == name:
            yield s
        else:
            yield from spans_named(s.children, name)


def under(spans: Iterable, outer: str, inner: str) -> Iterator:
    """Spans called ``inner`` nested anywhere inside spans called
    ``outer``."""
    for s in spans_named(spans, outer):
        yield from spans_named(s.children, inner)


def self_s(span) -> float:
    """A span's duration less what its child spans cover."""
    return span.dur_s - sum(c.dur_s for c in span.children)


def per(total: float, count: int) -> Optional[float]:
    return total / count if count else None


def roofline(ctx, kernels: Sequence[str]) -> Optional[float]:
    """Percent of the HBM roof that ``kernels`` reached together in the
    window; None where the trace shows none of them running. A kernel that
    ran on the device with no work recorded for it is an error: its calls
    no longer pass through the ``ops`` entry point the harness watches."""
    if ctx.trace is None:
        return None
    device_s = nbytes = 0.0
    for k in kernels:
        ran_s = ctx.trace.program_s.get(work.PROGRAMS[k], 0.0)
        given = ctx.work.get(k, {}).get("bytes", 0)
        if ran_s > 0 and given <= 0:
            raise RuntimeError(
                f"{work.PROGRAMS[k]} ran {ran_s:.6f} s on the device, but no "
                f"call reached the watched entry point of kernel {k!r}")
        device_s += ran_s
        nbytes += given
    if device_s <= 0:
        return None
    return work.roofline_share(nbytes, device_s, ctx.device_kind)


#: the sorted-key searches: the run merge's lower bounds and the key probes
SEARCH_KERNELS = ("lower_bound", "probe")


def search_roofline(ctx) -> Optional[float]:
    return roofline(ctx, SEARCH_KERNELS)


def idle_share(ctx) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device."""
    if ctx.trace is None or ctx.trace.devices == 0:
        return None
    return 100.0 * ctx.trace.idle_share
