"""Milliseconds of phase 1 of a commit (``commit.seal``: validate and seal
objects) per commit of the window, updates and publishes alike."""
from bench.readers import per, spans_named


def read(ctx):
    commits = list(spans_named(ctx.spans, "commit"))
    total = sum(s.dur_s for s in spans_named(commits, "commit.seal"))
    return per(1e3 * total, len(commits))
