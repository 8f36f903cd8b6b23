"""Device kernels for the version-control hot paths + jit'd wrappers.

Each <name>.py holds one device program, ref.py the pure-jnp oracle, and
ops.py the dispatching wrappers (numpy on CPU, the device on TPU):

  * rowhash         — 128-bit row/key signatures from uint32 column lanes
                      (Pallas).
  * search          — lower bound and fused (start, count) probe over
                      sorted multi-lane keys (XLA gathers; any table size).
  * segsum_diff     — run boundaries of the diff-aggregation operator
                      (Pallas).
  * flash_attention — online-softmax attention with VMEM-resident tiles
                      (the model-side hot spot; ops.attention dispatches).
"""
from . import ops, ref  # noqa: F401
