"""Milliseconds of row gather (``gather_payload`` spans) per point read."""
from bench.program_readers import span_ms_per


def read(ctx):
    return span_ms_per(ctx, "gather_payload", "reads")
