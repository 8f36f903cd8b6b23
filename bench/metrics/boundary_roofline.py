"""Share of the HBM roof reached by the run-boundary kernel of diff
aggregation."""
from bench.readers import roofline


def read(ctx):
    return roofline(ctx, ["boundary"])
