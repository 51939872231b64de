"""``fused_topk_pallas`` (kernels/fused_query.py): the full-precision
cascade and verify writing block-local top-k partials, the k-NN
tightening passes of every full-precision mixed dispatch."""
from kernels._common import screen_cost

PATTERN = r"^%?fused_topk_pallas(\.\d+)? = "
cost = screen_cost
