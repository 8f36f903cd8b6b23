"""Milliseconds of secondary-index maintenance (``index.maintain`` spans)
inside each publish: the aux-table deletes and inserts staged into the
merge commit of an indexed table."""
from bench.readers import per, under


def read(ctx):
    spans = list(under(ctx.spans, "publish", "index.maintain"))
    if not spans:
        return None
    return per(1e3 * sum(s.dur_s for s in spans), ctx.counts.get("publishes", 0))
