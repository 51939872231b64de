"""Public jit'd wrappers for the Pallas kernels.

Responsibilities kept out of the kernels themselves:
  * batch padding to the block size (and unpadding of results),
  * the per-query (α, N) MINDIST table panel (cached per alphabet),
  * VMEM budget checks and block-shape selection for the fused megakernel
    (the latency ranking lives in ``core/cost_model.py``; the chip's
    scoped-VMEM limit and rates in ``runtime/roofline.CHIP_PEAKS``),
  * backend dispatch: ``interpret=None`` → interpret mode off TPU (CPU
    test runs execute the kernels through the Pallas interpreter,
    validated against ``ref.py``), compiled Pallas on a TPU.

Every wrapper has a ``ref.py`` oracle with identical semantics; the XLA
engine (core/engine.py) uses the oracle expressions directly, so the Pallas
path is a drop-in for serving on TPU hardware.

The serving hot path no longer chains per-level kernels: the one-pass
megakernel in ``fused_query.py`` (reached through the ``backend="pallas"``
dispatch in ``core/engine.py``) evaluates the whole cascade and the
Euclidean verify in a single database pass.  The single-level
``prune_level`` wrapper remains for level-at-a-time experimentation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.sax import mindist_table
from .fused_prune import fused_prune_level_pallas
from .linfit import linfit_residual_sq_pallas
from .mindist import mindist_sq_pallas
from .paa import paa_pallas
from .sqdist import sqdist_pallas

_LANES = 128

# Candidate fused-megakernel block shapes, largest-first.  block_b is the
# HBM streaming granularity; block_q amortises each resident database
# block over more queries (bounded by the VMEM the (block_q, block_b, N)
# select-sweep accumulator costs).
FUSED_BLOCK_B = (1024, 512, 256, 128)
FUSED_BLOCK_Q = (32, 16, 8)


def vmem_limit() -> int:
    """Scoped-VMEM bytes one kernel may use on this process's chip (the
    target chip where none is attached): ``runtime/roofline.CHIP_PEAKS``."""
    from ..runtime.roofline import local_peaks

    return local_peaks().vmem_limit


def _use_interpret(interpret) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _pad_rows(x: jnp.ndarray, block_b: int):
    B = x.shape[0]
    Bp = (B + block_b - 1) // block_b * block_b
    if Bp == B:
        return x, B
    pad = [(0, Bp - B)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad), B


def _check_vmem(block_b: int, n: int, extra: int = 0):
    # database block f32 + constants + output, doubled for pipelining
    need = 2 * (block_b * n * 4 + extra)
    if need > vmem_limit():
        raise ValueError(
            f"block_b={block_b}, n={n} needs ~{need/2**20:.1f} MiB VMEM "
            f"(> {vmem_limit()/2**20:.0f} MiB); shrink block_b")


# ---------------------------------------------------------------------------
# MINDIST table + per-query panels (cached per alphabet — the (α, α) table
# is a pure function of the alphabet, so rebuilding it per call was wasted
# host work AND a fresh device constant per trace).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _mindist_table_np(alphabet: int):
    import numpy as np

    return np.ascontiguousarray(mindist_table(alphabet), dtype=np.float32)


def mindist_table_cached(alphabet: int) -> jnp.ndarray:
    """(α, α) MINDIST cell table; the host build is cached per alphabet.

    The jnp conversion stays OUTSIDE the cache: under jit it folds into a
    trace constant, and caching a traced value would leak the tracer.
    """
    return jnp.asarray(_mindist_table_np(alphabet))


def query_table(qword, alphabet: int) -> jnp.ndarray:
    """(N,) query word -> (α, N) MINDIST panel tq[a, i] = tab[a, q_i]."""
    return mindist_table_cached(alphabet)[:, qword]


def query_panels(qwords, alphabet: int) -> jnp.ndarray:
    """Batched panel construction: (Q, N) query words -> (Q, α, N) panels.

    ``panels[q, a, i] = tab[a, qwords[q, i]]`` — the per-query slice the
    compare-select sweep needs, for a whole query tile at once (one gather
    on the cached table instead of Q python-level slices).
    """
    tab = mindist_table_cached(alphabet)
    return jnp.transpose(tab[:, qwords], (1, 0, 2))


# ---------------------------------------------------------------------------
# Fused-megakernel block-shape selection: VMEM feasibility here, latency
# ranking in core/cost_model.py (the hook keeps hardware constants out of
# the kernel layer).
# ---------------------------------------------------------------------------

def _vmem_block(shape, itemsize: int) -> int:
    """VMEM bytes of one buffer holding a block of ``shape``, laid out as
    Mosaic lays it out: the last dim padded to 128 lanes and the
    second-to-last to the dtype's sublane tile (8 rows of 4-byte values,
    16 of 2-byte, 32 of 1-byte).  A (rows, 1) column therefore costs
    rows·128·itemsize bytes, not rows·itemsize."""
    *lead, rows, cols = shape
    sub = 32 // itemsize
    return (math.prod(lead) * (-(-rows // sub) * sub)
            * (-(-cols // _LANES) * _LANES) * itemsize)


# In-kernel temporaries per (query, row) pair, in units of one f32
# select-sweep accumulator lane row (128 lanes × 4 bytes).  Fitted as an
# upper bound to the smallest scoped-VMEM limit at which the TPU compiler
# accepts each kernel, less its padded buffers (v5e, n=256, levels
# (8, 16), alphabet 10, block_q 8-32 × block_b 128-1024, range, top-k,
# int8 and bf16 alike): 0.74-1.58 measured.
_TEMP_ACC = 2


def fused_vmem_bytes(block_q: int, block_b: int, n: int, levels,
                     alphabet: int, k: int = 0, mode: str = "none") -> int:
    """VMEM footprint of one fused-megakernel grid step, as Mosaic counts
    it against its scoped-VMEM limit.

    Every input and output block is charged at its padded VMEM layout
    (:func:`_vmem_block`) and twice (the pipeline double-buffers it); the
    in-kernel temporaries — chiefly the (block_q, block_b, N) select-sweep
    accumulator, whose N lanes pad to 128 — are charged per (query, row)
    pair (``_TEMP_ACC``).  ``mode`` is the database layout: ``"none"``
    (float32 columns, ``fused_range_pallas`` / ``fused_topk_pallas``) or
    the quantized ``"int8"`` / ``"bf16"`` layouts of
    ``fused_quant_range_pallas``.
    """
    levels = tuple(int(N) for N in levels)
    bq, bb = block_q, block_b
    row = _vmem_block((1, bb), 4)          # a per-row column, lane-dense
    blocks = [_vmem_block((bq, n), 4)] + [_vmem_block((bq, 1), 4)] * 2
    for N in levels:
        blocks += [_vmem_block((bq, 1), 4),
                   _vmem_block((bq, alphabet, N), 4)]
    if mode == "none":
        blocks += [_vmem_block((bb, n), 4), row]
        for N in levels:
            blocks += [row, _vmem_block((bb, N), 4)]
        temps = 0
    else:
        code = 1 if mode == "int8" else 2
        per_row_f32 = 2 + 2 * (mode == "int8")      # serr, norms(, sc, z)
        blocks += [_vmem_block((bb, n), code)] + [row] * per_row_f32
        for N in levels:
            blocks += [_vmem_block((1, bb), code), _vmem_block((bb, N), 1)]
            blocks += [row] * (3 if mode == "int8" else 1)
        # the dequantized series, and the int8 affine's two transposed
        # (block_b, 1) columns
        temps = _vmem_block((bb, n), 4) + 2 * _vmem_block((bb, 1), 4)
    if k:
        blocks += [_vmem_block((bq, k), 4)] * 2
    else:
        blocks += [_vmem_block((bq, bb), 4)] * 2
    temps += _TEMP_ACC * bq * bb * _LANES * 4
    return 2 * sum(blocks) + temps


def choose_fused_blocks(Q: int, B: int, n: int, levels, alphabet: int,
                        k: int = 0, vmem: int | None = None,
                        mode: str = "none"):
    """Pick (block_q, block_b) for the fused megakernel.

    Feasibility is the chip's scoped-VMEM limit (``vmem`` defaults to
    :func:`vmem_limit`) against :func:`fused_vmem_bytes`; among feasible
    shapes the cheapest one wins under the latency-model hook
    ``core/cost_model.fused_pass_estimate`` (HBM streaming vs compute).
    Raises if nothing fits — the caller should shrink n or levels.
    """
    from ..core import cost_model as _cm

    vmem = vmem_limit() if vmem is None else vmem
    best = None
    for bq in FUSED_BLOCK_Q:
        for bb in FUSED_BLOCK_B:
            if fused_vmem_bytes(bq, bb, n, levels, alphabet, k,
                                mode) > vmem:
                continue
            est = _cm.fused_pass_estimate(
                Q, B, n, levels, alphabet, block_q=bq, block_b=bb, k=k)
            if best is None or est["t_est_s"] < best[0]:
                best = (est["t_est_s"], bq, bb)
    if best is None:
        raise ValueError(
            f"no fused block shape fits {vmem/2**20:.0f} MiB VMEM for "
            f"n={n}, levels={tuple(levels)}, alphabet={alphabet}")
    return best[1], best[2]


def subseq_vmem_bytes(block_q: int, block_w: int, window: int, stride: int,
                      levels, alphabet: int, k: int = 0) -> int:
    """Conservative VMEM footprint of one streaming-subsequence grid step
    (``fused_query.fused_subseq_*_pallas``): the stream segment + a few
    metadata values per window on the database side, plus the transient
    (block_w, window) z-window build and the select-sweep accumulator."""
    levels = tuple(int(N) for N in levels)
    n_lv = len(levels)
    seg_len = (block_w - 1) * stride + window
    db = (seg_len + block_w * (3 + sum(levels) + n_lv)) * 4
    qside = block_q * (window + 2 + n_lv + alphabet * sum(levels)) * 4
    out = block_q * (2 * k if k else 2 * block_w) * 4
    acc = (block_q * block_w * (max(levels) + 3)
           + block_w * window) * 4                 # sweep acc + z build
    return 2 * (db + qside + out) + acc


def choose_subseq_blocks(Q: int, n_windows: int, window: int, stride: int,
                         levels, alphabet: int, k: int = 0,
                         vmem: int | None = None):
    """Pick (block_q, block_w) for the streaming subsequence kernels —
    VMEM feasibility here, latency ranking by
    ``core/cost_model.subseq_pass_estimate`` (same split as
    :func:`choose_fused_blocks`)."""
    from ..core import cost_model as _cm

    vmem = vmem_limit() if vmem is None else vmem
    best = None
    for bq in FUSED_BLOCK_Q:
        for bw in FUSED_BLOCK_B:
            if subseq_vmem_bytes(bq, bw, window, stride, levels, alphabet,
                                 k) > vmem:
                continue
            est = _cm.subseq_pass_estimate(
                Q, n_windows, window, stride, levels, alphabet,
                block_q=bq, block_w=bw, k=k)
            if best is None or est["t_est_s"] < best[0]:
                best = (est["t_est_s"], bq, bw)
    if best is None:
        raise ValueError(
            f"no subseq block shape fits {vmem/2**20:.0f} MiB VMEM for "
            f"window={window}, stride={stride}, levels={tuple(levels)}, "
            f"alphabet={alphabet}")
    return best[1], best[2]


# ---------------------------------------------------------------------------
# Per-kernel wrappers.
# ---------------------------------------------------------------------------

def paa(x, n_segments: int, *, block_b: int = 256, interpret=None):
    """(B, n) -> (B, N) PAA means (Pallas)."""
    _check_vmem(block_b, x.shape[-1], extra=x.shape[-1] * n_segments * 4)
    xp, B = _pad_rows(x, block_b)
    out = paa_pallas(xp, n_segments, block_b=block_b,
                     interpret=_use_interpret(interpret))
    return out[:B]


def linfit_residual_sq(x, n_segments: int, *, block_b: int = 256,
                       interpret=None):
    """(B, n) -> (B,) squared LS residuals (Pallas)."""
    _check_vmem(block_b, x.shape[-1], extra=3 * x.shape[-1] * n_segments * 4)
    xp, B = _pad_rows(x, block_b)
    out = linfit_residual_sq_pallas(xp, n_segments, block_b=block_b,
                                    interpret=_use_interpret(interpret))
    return out[:B]


def mindist_sq(words, qword, n: int, alphabet: int, *, block_b: int = 256,
               interpret=None):
    """(B, N) words × (N,) query word -> (B,) squared MINDIST (Pallas)."""
    N = words.shape[-1]
    _check_vmem(block_b, N, extra=alphabet * N * 4)
    tq = query_table(qword, alphabet)
    wp, B = _pad_rows(words, block_b)
    out = mindist_sq_pallas(wp, tq, n, alphabet, block_b=block_b,
                            interpret=_use_interpret(interpret))
    return out[:B]


def sqdist(x, q, *, block_b: int = 256, interpret=None):
    """(B, n) × (n,) -> (B,) squared Euclidean distances (Pallas)."""
    _check_vmem(block_b, x.shape[-1])
    xp, B = _pad_rows(x, block_b)
    out = sqdist_pallas(xp, q, block_b=block_b,
                        interpret=_use_interpret(interpret))
    return out[:B]


def prune_level(alive, residuals, words, qword, qres, eps, n: int,
                alphabet: int, *, block_b: int = 256, interpret=None):
    """One fused cascade level (C9 + masked C10) -> new alive mask."""
    N = words.shape[-1]
    _check_vmem(block_b, N, extra=(alphabet * N + 2 * block_b) * 4)
    tq = query_table(qword, alphabet)
    ap, B = _pad_rows(alive, block_b)
    rp, _ = _pad_rows(residuals, block_b)
    wp, _ = _pad_rows(words, block_b)
    out = fused_prune_level_pallas(
        ap, rp, wp, tq, qres, eps, n, alphabet, block_b=block_b,
        interpret=_use_interpret(interpret))
    return out[:B]
