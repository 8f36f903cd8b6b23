"""Milliseconds of batched secondary-index lookups (``index.lookup``
spans) per published PR: the by-last-name Payments of an engineer's batch,
answered on its branch inside the update."""
from bench.program_readers import span_ms_per


def read(ctx):
    return span_ms_per(ctx, "index.lookup", "prs_published")
