"""Device program executions in the traced window per published PR."""
from bench.readers import per


def read(ctx):
    if ctx.trace is None or ctx.trace.devices == 0:
        return None
    return per(sum(ctx.trace.launches.values()),
               ctx.counts.get("prs_published", 0))
