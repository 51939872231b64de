"""Latency-time cost model (paper §4, after Schulte et al. 2005).

The paper compares SAX and FAST_SAX by *latency time*: every arithmetic
operation is weighted by its hardware latency and the weighted counts are
summed.  The paper does not print its weight table, so we make ours explicit
here and report it alongside every benchmark.  The qualitative conclusions
(FAST_SAX < SAX; the gap shrinks as epsilon grows and as alphabet size grows)
are insensitive to the exact weights because FAST_SAX strictly removes
operations relative to SAX for the series its first condition excludes.

Weights (relative to one ALU op):
    CMP / ADD / SUB / ABS / LOOKUP : 1
    MUL                            : 1   (fused multiply-add era)
    DIV                            : 4
    SQRT                           : 8
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class OpWeights:
    cmp: float = 1.0
    add: float = 1.0
    sub: float = 1.0
    abs: float = 1.0
    mul: float = 1.0
    div: float = 4.0
    sqrt: float = 8.0
    lookup: float = 1.0


DEFAULT_WEIGHTS = OpWeights()


@dataclasses.dataclass
class OpCounter:
    """Accumulates raw op counts; ``latency()`` applies the weight table."""

    weights: OpWeights = DEFAULT_WEIGHTS
    cmp: int = 0
    add: int = 0
    sub: int = 0
    abs: int = 0
    mul: int = 0
    div: int = 0
    sqrt: int = 0
    lookup: int = 0

    def count(self, **ops: int) -> None:
        for name, k in ops.items():
            setattr(self, name, getattr(self, name) + int(k))

    def latency(self) -> float:
        w = self.weights
        return (
            self.cmp * w.cmp
            + self.add * w.add
            + self.sub * w.sub
            + self.abs * w.abs
            + self.mul * w.mul
            + self.div * w.div
            + self.sqrt * w.sqrt
            + self.lookup * w.lookup
        )

    def total_ops(self) -> int:
        return (
            self.cmp + self.add + self.sub + self.abs
            + self.mul + self.div + self.sqrt + self.lookup
        )

    def merge(self, other: "OpCounter") -> None:
        for f in ("cmp", "add", "sub", "abs", "mul", "div", "sqrt", "lookup"):
            setattr(self, f, getattr(self, f) + getattr(other, f))

    def as_dict(self) -> dict:
        return {
            f: getattr(self, f)
            for f in ("cmp", "add", "sub", "abs", "mul", "div", "sqrt", "lookup")
        }


# ---------------------------------------------------------------------------
# Closed-form op counts for the primitive computations used by both engines.
# Centralising them keeps search.py honest and makes the benchmark auditable.
# ---------------------------------------------------------------------------

def euclidean_cost(n: int) -> dict:
    """Full Euclidean distance between two length-n series + threshold test."""
    return dict(sub=n, mul=n, add=n - 1, sqrt=1, cmp=1)


def mindist_cost(N: int) -> dict:
    """MINDIST between two N-symbol words + threshold test (eq. 3).

    Per symbol pair: one table lookup + one square; then N-1 adds, the
    sqrt(n/N) scale (1 mul after a cached sqrt), one sqrt, one compare.
    """
    return dict(lookup=N, mul=N + 1, add=N - 1, sqrt=1, cmp=1)


def c9_cost() -> dict:
    """FAST_SAX first exclusion condition |d(u,ū) − d(q,q̄)| > ε (eq. 9)."""
    return dict(sub=1, abs=1, cmp=1)


def paa_cost(n: int, N: int) -> dict:
    """PAA of a length-n series into N segments (query-side, online)."""
    return dict(add=n - N, mul=N)  # segment sums + scale by 1/L


def discretize_cost(N: int, alphabet: int) -> dict:
    """Binary-search discretisation of N PAA values over alphabet-1 breakpoints."""
    import math

    return dict(cmp=N * max(1, math.ceil(math.log2(max(2, alphabet)))))


def residual_gap_cost() -> dict:
    """The C9 quantity |d(u,ū) − d(q,q̄)| *as a lower bound* (no threshold
    test) — what the k-NN seed phase computes per series."""
    return dict(sub=1, abs=1)


def heap_push_cost(k: int) -> dict:
    """One sift of a size-k binary heap (the k-NN best-so-far structure)."""
    import math

    return dict(cmp=max(1, math.ceil(math.log2(max(2, k + 1)))))


def select_cost(m: int, k: int) -> dict:
    """Heap-select the k smallest of m values: one compare per value plus a
    sift for the values that enter the size-k heap (charged for all m as the
    pessimistic bound — the accounting must never undercount)."""
    import math

    lg = max(1, math.ceil(math.log2(max(2, k + 1))))
    return dict(cmp=m + m * lg)


def sort_cost(m: int) -> dict:
    """Comparison sort of m keys (candidate ordering before verification)."""
    import math

    if m <= 1:
        return dict(cmp=0)
    return dict(cmp=m * max(1, math.ceil(math.log2(m))))


def linfit_residual_cost(n: int, N: int) -> dict:
    """Closed-form per-segment first-degree LS residual for the query.

    Per segment of length L: sums Σy, Σxc·y, Σy² (3L-ish adds, 2L muls),
    then slope/intercept/residual combination (constant ops).
    """
    return dict(add=3 * n, mul=2 * n + 6 * N, div=N, sqrt=1)


def latency_of(cost: dict, weights: OpWeights = DEFAULT_WEIGHTS) -> float:
    """Weighted latency time of one closed-form op-count dict."""
    return float(sum(int(k) * getattr(weights, name)
                     for name, k in cost.items()))


# ---------------------------------------------------------------------------
# Adaptive cascade: is a level's MINDIST test worth its cost?
#
# The paper always runs both conditions at every level, but C10 only pays
# off when it excludes enough survivors to cover its own per-series cost
# (BENCH_knn_pr1.json showed FAST_SAX losing to plain SAX at k=5, α∈{3,10}
# exactly because the coarse level's MINDIST excluded almost nothing).
# The host engine probes a small survivor sample, estimates the kill
# fraction, and consults this decision.
# ---------------------------------------------------------------------------

def c10_skip_advised(kill_frac: float, n: int, N: int,
                     weights: OpWeights = DEFAULT_WEIGHTS) -> bool:
    """True when a level's MINDIST test is expected to cost more than the
    verification work its exclusions would save.

    Per C9-surviving series the test costs ``mindist_cost(N)``; excluding
    the series saves (at least) its final Euclidean verification,
    ``euclidean_cost(n)``.  With an estimated exclusion probability
    ``kill_frac``, skip when ``kill_frac · gain < cost``.  Skipping is
    always sound — C10 only ever removes candidates the Euclidean verify
    would filter anyway.
    """
    gain = float(kill_frac) * latency_of(euclidean_cost(n), weights)
    return gain < latency_of(mindist_cost(N), weights)


def level_enable_advised(kill_frac: float, n: int, exclude_cost: dict,
                         weights: OpWeights = DEFAULT_WEIGHTS) -> bool:
    """Should a registered *extra* representation level be enabled?

    The per-dataset twin of :func:`c10_skip_advised`, generic over the
    representation registry (``core/representation.py``): an extra level
    costs ``exclude_cost`` per surviving candidate and saves (at least)
    one ``euclidean_cost(n)`` verification per exclusion.  With the
    probe-estimated exclusion probability ``kill_frac``, enable when
    ``kill_frac · gain > cost``.  Either answer is sound — registered
    bounds only ever remove candidates the verify would reject.
    """
    gain = float(kill_frac) * latency_of(euclidean_cost(n), weights)
    return gain > latency_of(exclude_cost, weights)


# ---------------------------------------------------------------------------
# Fused top-k kernel: unroll budget for the in-kernel selection.
#
# ``kernels/fused_query.fused_topk_pallas`` unrolls k_sel = k + guard
# min/argmin sweeps per database block, so kernel code size and compile
# time grow *linearly* in k while the XLA engine's dense ``lax.top_k`` is
# one op at any k.  The per-sweep VPU work (one (block_q, block_b) min +
# argmin + select) costs roughly what one cascade level costs; past
# ~100 sweeps the selection dominates the whole pass and the compile-time
# bill keeps growing with nothing to show for it — the dense XLA path is
# the better engine there (DESIGN.md §7).  The dispatch layer
# (``engine.resolve_knn_backend``) consults this advice and demotes
# ``backend="pallas"`` k-NN to XLA instead of compiling an ever-longer
# kernel; ``knn_query_pallas`` itself stays directly callable at any k.
# ---------------------------------------------------------------------------

PALLAS_TOPK_UNROLL_MAX = 100


def pallas_topk_demote_advised(k_sel: int) -> bool:
    """True when an unrolled k_sel-sweep in-kernel selection is expected to
    cost more (compile time + per-block sweep work) than the XLA dense
    top-k it would replace.  Purely advisory — demotion never changes
    answers, both backends are exact."""
    return int(k_sel) > PALLAS_TOPK_UNROLL_MAX


# ---------------------------------------------------------------------------
# Device latency model for the fused megakernel (kernels/fused_query.py).
#
# The block-shape chooser in kernels/ops.py asks this hook to rank the
# VMEM-feasible (block_q, block_b) candidates.  The rates are the chip's
# (runtime/roofline.CHIP_PEAKS) and the model is deliberately coarse: it
# only needs to order shapes, and the hot path is so memory-bound that the
# HBM term dominates every ranking.
# ---------------------------------------------------------------------------


def _times(bytes_hbm: float, flops_mxu: float, ops_vpu: float):
    from ..runtime.roofline import local_peaks

    peaks = local_peaks()
    return (bytes_hbm / peaks.hbm_bw,
            flops_mxu / peaks.flops + ops_vpu / peaks.vpu_ops)


def fused_pass_estimate(Q: int, B: int, n: int, levels, alphabet: int,
                        block_q: int = 8, block_b: int = 256,
                        k: int = 0) -> dict:
    """Bytes/flops/latency estimate for one fused megakernel pass.

    Returns ``dict(bytes_hbm, flops_mxu, ops_vpu, t_mem_s, t_compute_s,
    t_est_s)``.  The database (series, norms, words, residuals at every
    level) is charged exactly ONE HBM read — that is the kernel's design
    invariant; query-side tiles are re-streamed once per database block
    column (they are tiny).  Output traffic is the (Q, B) mask+d2 pair in
    range form or the (Q, nb·k) partials in top-k form.
    """
    import math

    levels = tuple(int(N) for N in levels)
    nb = math.ceil(B / max(1, block_b))
    nq = math.ceil(Q / max(1, block_q))
    Bp, Qp = nb * block_b, nq * block_q     # padded rows are streamed too
    row_bytes = (n + 1 + sum(levels) + len(levels)) * 4
    q_row_bytes = (n + 2 + len(levels) + alphabet * sum(levels)) * 4
    bytes_hbm = Bp * row_bytes + nb * Qp * q_row_bytes
    bytes_hbm += Qp * (2 * nb * k if k else 2 * Bp) * 4
    flops_mxu = 2.0 * Qp * Bp * n                     # the verify matmul
    ops_vpu = float(Qp * Bp) * (sum(levels) * (alphabet + 2) + 8)
    t_mem, t_compute = _times(bytes_hbm, flops_mxu, ops_vpu)
    return dict(bytes_hbm=float(bytes_hbm), flops_mxu=flops_mxu,
                ops_vpu=ops_vpu, t_mem_s=t_mem, t_compute_s=t_compute,
                t_est_s=max(t_mem, t_compute))


def subseq_pass_estimate(Q: int, n_windows: int, window: int, stride: int,
                         levels, alphabet: int, block_q: int = 8,
                         block_w: int = 128, k: int = 0) -> dict:
    """Latency estimate for one *streaming* subsequence pass
    (``kernels/fused_query.fused_subseq_range_pallas``, DESIGN.md §8).

    The database side of each grid step is a stream **segment** of
    ``(block_w − 1)·stride + window`` samples plus per-window metadata
    (mu, sd, norms, words, residuals), NOT the ``block_w × window``
    materialised window matrix — windows exist only in VMEM.  The dict
    adds ``bytes_hbm_materialized`` (what the window-gather form would
    stream) and ``hbm_read_ratio`` (materialised / streaming, ≈
    window/stride for stride ≪ window): the design claim the benchmark
    suite records and EXPERIMENTS.md §Subsequence reports.
    """
    import math

    levels = tuple(int(N) for N in levels)
    nb = math.ceil(n_windows / max(1, block_w))
    nq = math.ceil(Q / max(1, block_q))
    Wp, Qp = nb * block_w, nq * block_q
    seg_len = (block_w - 1) * stride + window
    meta_row = (3 + sum(levels) + len(levels)) * 4     # mu, sd, norms + levels
    q_row_bytes = (window + 2 + len(levels) + alphabet * sum(levels)) * 4
    bytes_stream = nb * seg_len * 4 + Wp * meta_row + nb * Qp * q_row_bytes
    bytes_stream += Qp * (2 * nb * k if k else 2 * Wp) * 4
    bytes_mat = Wp * (window * 4 + meta_row) + nb * Qp * q_row_bytes
    bytes_mat += Qp * (2 * nb * k if k else 2 * Wp) * 4
    flops_mxu = 2.0 * Qp * Wp * window                 # the verify matmul
    ops_vpu = float(Qp * Wp) * (sum(levels) * (alphabet + 2) + 8)
    ops_vpu += float(Wp) * window * 2                  # in-VMEM z build
    t_mem, t_compute = _times(bytes_stream, flops_mxu, ops_vpu)
    return dict(bytes_hbm=float(bytes_stream),
                bytes_hbm_materialized=float(bytes_mat),
                hbm_read_ratio=float(bytes_mat) / float(bytes_stream),
                flops_mxu=flops_mxu, ops_vpu=ops_vpu, t_mem_s=t_mem,
                t_compute_s=t_compute, t_est_s=max(t_mem, t_compute))
