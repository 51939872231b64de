"""Bytes and operations of one kernel call, from the call's own shapes.

The TPU profiler names each device operation by its HLO text, which
carries the result and operand shapes of the call:

    %fused_range_pallas.1 = (s32[8,2097152]{...}, f32[8,2097152]{...})
        custom-call(f32[8,256]{...} %q, ..., f32[2097152,256]{...} %s, ...)

Bytes are what the call must move at least: every operand read once and
every result written once, at their logical sizes.  Tiled layouts that
pad a narrow array in HBM, and re-reads across the kernel's blocks, are
not counted: they are the kernel's overhead, which its roofline share
shows.  MXU operations count the (Q, n) x (n, B) distance matmul at
``Precision.HIGHEST``, which the MXU runs as six bfloat16 passes.
"""
import re

HIGHEST_PASSES = 6
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(ITEMSIZE) + r")\[([\d,]*)\]")


def shapes(text: str) -> list:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def call_shapes(hlo: str):
    """(results, operands) of a custom call's HLO text, each a list of
    (dtype, dims)."""
    head, _, rest = hlo.partition(" custom-call(")
    operands, _, _ = rest.partition("), custom_call_target")
    return shapes(head.partition(" = ")[2]), shapes(operands)


def nbytes(shp) -> int:
    total = 0
    for dt, dims in shp:
        size = ITEMSIZE[dt]
        for d in dims:
            size *= d
        total += size
    return total


def screen_cost(hlo: str):
    """(bytes, MXU operations) of a screen kernel call: its queries are
    the first operand (Q, n), its database rows the largest (B, n) one."""
    results, operands = call_shapes(hlo)
    q_rows, n = operands[0][1]
    rows = max(dims[0] for dt, dims in operands
               if len(dims) == 2 and dims[1] == n)
    return (nbytes(results) + nbytes(operands),
            HIGHEST_PASSES * 2 * q_rows * rows * n)
