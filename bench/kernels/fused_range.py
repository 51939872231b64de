"""``fused_range_pallas`` (kernels/fused_query.py): the full-precision
cascade and verify writing the dense (Q, B) answer mask and d², the
last pass of every full-precision mixed dispatch.  The profiler names
the call after the jitted wrapper."""
from kernels._common import screen_cost

PATTERN = r"^%?fused_range_pallas(\.\d+)? = "
cost = screen_cost
