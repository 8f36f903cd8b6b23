"""Share of the HBM roof reached by the row-signature kernel."""
from bench.readers import roofline


def read(ctx):
    return roofline(ctx, ["rowhash"])
