"""Milliseconds of merge planning (``plan_merge`` spans) inside each
publish: the preview merge its check runs and the merge it commits."""
from bench.readers import per, under


def read(ctx):
    total = sum(s.dur_s for s in under(ctx.spans, "publish", "plan_merge"))
    return per(1e3 * total, ctx.counts.get("publishes", 0))
