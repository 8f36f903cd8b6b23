"""Milliseconds per published PR in the dispatch layer's fetch of device
results (``ops.fetch`` spans): the wait for the program, the device-to-host
copy and the slicing off of the padding."""
from bench.program_readers import span_ms_per


def read(ctx):
    return span_ms_per(ctx, "ops.fetch", "prs_published")
