"""Sealed objects the key probe searched per point read
(``probe.objects_probed``)."""
from bench.readers import per


def read(ctx):
    return per(ctx.counters.get("probe.objects_probed", 0),
               ctx.counts.get("reads", 0))
