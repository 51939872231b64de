"""``fused_quant_range_pallas`` (kernels/fused_query.py, jitted as
``_quant_range_call``): the int8 tier's widened screen, which
dequantizes in VMEM and writes the dense (Q, B) keep mask and d̂²."""
from kernels._common import screen_cost

PATTERN = r"^%?_quant_range_call(\.\d+)? = "
cost = screen_cost
