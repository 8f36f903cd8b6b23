"""Milliseconds of Δ scan (``signed_delta`` spans) inside each PR diff."""
from bench.readers import per, under


def read(ctx):
    total = sum(s.dur_s for s in under(ctx.spans, "diff", "signed_delta"))
    return per(1e3 * total, ctx.counts.get("diffs", 0))
