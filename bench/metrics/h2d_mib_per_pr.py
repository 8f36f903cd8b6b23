"""MiB the dispatch layer copied to the device per published PR, padding
included (``xfer.h2d_bytes``)."""
from bench.program_readers import mib_per


def read(ctx):
    return mib_per(ctx, "xfer.h2d_bytes", "prs_published")
