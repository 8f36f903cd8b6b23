"""Milliseconds per published PR in the dispatch layer's staging of device
calls (``ops.stage`` spans): padding, lane unpacking, host-to-device copies
and the program's dispatch."""
from bench.program_readers import span_ms_per


def read(ctx):
    return span_ms_per(ctx, "ops.stage", "prs_published")
