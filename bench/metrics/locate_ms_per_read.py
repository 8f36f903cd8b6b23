"""Milliseconds of key probe (``table.locate_keys`` spans) per point read."""
from bench.program_readers import span_ms_per


def read(ctx):
    return span_ms_per(ctx, "table.locate_keys", "reads")
