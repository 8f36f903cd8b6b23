"""Share of the HBM roof reached by the sorted-key searches (the lower
bounds of the run merge and the fused probes of the key lookups), in the
collaborative cells."""
from bench.readers import search_roofline as read  # noqa: F401
