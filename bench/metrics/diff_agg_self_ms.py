"""Milliseconds per PR diff spent in the ``diff`` span itself: aggregation
and payload rowids, without the Δ scan below it."""
from bench.readers import per, self_s, spans_named


def read(ctx):
    total = sum(self_s(s) for s in spans_named(ctx.spans, "diff"))
    return per(1e3 * total, ctx.counts.get("diffs", 0))
