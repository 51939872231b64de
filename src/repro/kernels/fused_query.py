"""Pallas TPU megakernel: the whole FAST_SAX online phase in ONE database pass.

``fused_prune.py`` fused the two exclusion conditions of one cascade level;
this module fuses the *entire* serving hot path: every cascade level (C9 on
the residual gaps, eq. 9; C10 as the per-query-panel compare-select MINDIST
sweep, eq. 10) AND the Euclidean verification, for a tile of queries at
once, inside a single ``pallas_call``.

Why one pass is the roofline-optimal form (EXPERIMENTS.md §Roofline): each
cascade level has arithmetic intensity far below the TPU ridge point, so a
per-level kernel chain pays one HBM round-trip of the (B,) mask — and one
re-read of the (B, N) words — per level.  Here a database block (series
rows, norms, all levels' words and residuals) is DMA'd into VMEM exactly
once and every downstream test runs while it is resident; the only HBM
writes are the final (Q, B) answer mask + distances (range form) or the
(Q, nb·k) block-local top-k partials (k-NN form).

Grid layout: ``grid = (nb, nq)`` with the **query tile innermost** — the
database block index maps depend only on the outer index ``j``, so Pallas
keeps the block resident across the ``i`` sweep and each database block is
fetched from HBM exactly once per pass, independent of Q.

Per (j, i) step, everything is VMEM-resident:

  * C9: ``|res_l − qres_l| ≤ ε`` on a (block_q, block_b) broadcast — VPU;
  * C10: the (α, N) per-query panel trick of ``mindist.py``, batched — the
    α-way compare-select sweep now selects into a (block_q, block_b, N)
    accumulator, bit-identical to the XLA engine's table gather;
  * verify: one MXU dot of the (block_q, n) query tile against the
    (block_b, n) series tile in the ‖u‖² − 2·u·q + ‖q‖² form — the same
    expression ``core/engine.py::verify_distances`` uses, so the fused
    answers are bit-identical to the oracle (tested).

The k-NN variant replaces the (Q, B) outputs with block-local top-k
partials — an unrolled min/argmin selection (ties resolve to the lowest
database index, the engine-wide tie-break) — merged by the caller in a
cheap epilogue, so k-NN never materialises a (Q, B) distance matrix in HBM.

Padding protocol (the wrappers below): database rows are padded to a
multiple of ``block_b`` with a huge sentinel residual (C9 kills them at any
finite ε — the same mechanism ``core/dist_search.py`` uses for shard
padding); query rows are padded to a multiple of ``block_q`` with ε = −1,
which no non-negative gap can satisfy, so padded query rows answer nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..index import quantized as _quant

# Residual sentinel for padded database rows: C9 excludes them at any
# finite epsilon (mirrors core/dist_search._PAD_RESIDUAL).
PAD_RESIDUAL = 1e30
# Epsilon sentinel for padded query rows: gaps are >= 0, so nothing passes.
PAD_EPSILON = -1.0

# f32 slack on the widened quantized series screen — the single source of
# truth: core/engine.py's XLA oracle imports these, so the two screens
# cannot drift (they are required to agree bit-for-bit, tested).
QUANT_SCREEN_REL = 1e-6
QUANT_SCREEN_ABS = 1e-6


def _split_refs(refs, n_levels: int):
    """Kernel ref layout shared by both kernels.

    Inputs:  q, qnorm, eps, [qres_l, tq_l]*L, series, norms, [res_l, words_l]*L
    Outputs: the trailing refs (2 for both variants).
    """
    q_ref, qn_ref, eps_ref = refs[0], refs[1], refs[2]
    qlv = refs[3:3 + 2 * n_levels]
    series_ref, norms_ref = refs[3 + 2 * n_levels], refs[4 + 2 * n_levels]
    dlv = refs[5 + 2 * n_levels:5 + 4 * n_levels]
    outs = refs[5 + 4 * n_levels:]
    return q_ref, qn_ref, eps_ref, qlv, series_ref, norms_ref, dlv, outs


def _c10_alive(eps2, tq, words, *, alphabet, n, N):
    """(block_q, block_b) C10 (eq. 10) survivors: the batched per-query-panel
    compare-select sweep selects ``tq[q, words[b, i], i]`` into a
    (block_q, block_b, N) accumulator — the engine's ``tab[words, qwords]``
    gather element for element — before the engine's squared-sum reduction.
    Symbols compare as int32: the chip's vector unit has no int8 compare."""
    sel = words.astype(jnp.int32)[None, :, :]          # (1, block_b, N)
    acc = jnp.zeros((tq.shape[0], words.shape[0], N), jnp.float32)
    for a in range(alphabet):
        acc = jnp.where(sel == a, tq[:, a, :][:, None, :], acc)
    md_sq = (float(n) / N) * jnp.sum(acc * acc, axis=-1)
    return md_sq <= eps2


def _cascade_alive(eps, qlv, dlv, *, levels, alphabet, n):
    """(block_q, block_b) alive mask: every cascade level, VMEM-resident.

    Bit-identical to ``core/engine.py::cascade_mask``: the C9 gap is the
    same subtract/abs and C10 is :func:`_c10_alive`.
    """
    eps2 = eps * eps
    alive = None
    for li, N in enumerate(levels):
        qres = qlv[2 * li][...]                      # (block_q, 1)
        res = dlv[2 * li][...]                       # (1, block_b)
        # C9 (eq. 9): |d(u,ū) − d(q,q̄)| > ε kills.
        ok = jnp.abs(res - qres) <= eps
        alive = ok if alive is None else alive & ok
        alive &= _c10_alive(eps2, qlv[2 * li + 1][...],
                            dlv[2 * li + 1][...], alphabet=alphabet, n=n,
                            N=N)
    return alive


def _verify_arrays(q, qn, series, norms):
    """(block_q, block_b) squared distances from the (block_b, n) rows and
    their (1, block_b) squared norms — the engine's matmul form, at full
    f32 precision (the chip's default f32 matmul is one bf16 pass, about
    one unit of d² off at n=256).  Takes VMEM-resident arrays so both the
    whole-series kernels (series read from HBM) and the streaming
    subsequence kernels (windows built in VMEM) share one verify
    expression."""
    cross = jnp.dot(q, series.T, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    d2 = qn - 2.0 * cross + norms
    return jnp.maximum(d2, 0.0)


def _verify_d2(q_ref, qn_ref, series_ref, norms_ref):
    return _verify_arrays(q_ref[...], qn_ref[...], series_ref[...],
                          norms_ref[...])


def _fused_range_kernel(*refs, levels, alphabet, n):
    (q_ref, qn_ref, eps_ref, qlv, series_ref, norms_ref, dlv,
     (ans_ref, d2_ref)) = _split_refs(refs, len(levels))
    eps = eps_ref[...]                               # (block_q, 1)
    alive = _cascade_alive(eps, qlv, dlv,
                           levels=levels, alphabet=alphabet, n=n)
    d2 = _verify_d2(q_ref, qn_ref, series_ref, norms_ref)
    ans = alive & (d2 <= eps * eps)
    ans_ref[...] = ans.astype(jnp.int32)
    d2_ref[...] = jnp.where(ans, d2, jnp.inf)


def _topk_select(d2m, base, k):
    """Unrolled k-sweep min/argmin block-local selection (ties resolve to
    the lowest column, the engine-wide tie-break): (vals (bq, k),
    idx (bq, k)) with +inf / −1 on empty slots.  Shared by the
    whole-series and streaming-subsequence top-k kernels.  Each sweep is
    two lane reductions and selects (the argmin is the lowest column that
    holds the minimum), which Mosaic lowers at any k.  The unroll is why
    large k belongs on the XLA engine (cost_model
    PALLAS_TOPK_UNROLL_MAX)."""
    bq, width = d2m.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, d2m.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    vals = jnp.full((bq, k), jnp.inf, jnp.float32)
    idxs = jnp.full((bq, k), -1, jnp.int32)
    for t in range(k):                               # k static, unrolled
        v = jnp.min(d2m, axis=-1, keepdims=True)     # (bq, 1)
        am = jnp.min(jnp.where(d2m == v, cols, width), axis=-1,
                     keepdims=True)                  # ties → lowest col
        vals = jnp.where(slot == t, v, vals)
        idxs = jnp.where(slot == t, jnp.where(v < jnp.inf, base + am, -1),
                         idxs)
        d2m = jnp.where(cols == am, jnp.inf, d2m)
    return vals, idxs


def _topk_out(Qp: int, nb: int, block_q: int, k: int):
    """(out_specs, out_shape) of the block-local top-k partials.  Each
    grid step writes one (block_q, k) tile of an (nb, Qp, k) array: its
    last two block dims equal the array's, which Mosaic accepts for any k
    (a (block_q, k) tile of a (Qp, nb·k) array needs k % 128 == 0)."""
    spec = pl.BlockSpec((None, block_q, k), lambda j, i: (j, i, 0))
    return ([spec, spec],
            [jax.ShapeDtypeStruct((nb, Qp, k), jnp.float32),
             jax.ShapeDtypeStruct((nb, Qp, k), jnp.int32)])


def _flatten_partials(vals, idx, Q: int):
    """(nb, Qp, k) partials -> (Q, nb·k), slot ``j·k + t`` = block j's
    t-th candidate."""
    nb, Qp, k = vals.shape

    def flat(a):
        return jnp.transpose(a, (1, 0, 2)).reshape(Qp, nb * k)[:Q]

    return flat(idx), flat(vals)


def _fused_topk_kernel(*refs, levels, alphabet, n, k, block_b):
    (q_ref, qn_ref, eps_ref, qlv, series_ref, norms_ref, dlv,
     (vals_ref, idx_ref)) = _split_refs(refs, len(levels))
    eps = eps_ref[...]
    alive = _cascade_alive(eps, qlv, dlv,
                           levels=levels, alphabet=alphabet, n=n)
    d2 = _verify_d2(q_ref, qn_ref, series_ref, norms_ref)
    # k-NN candidates are ALL cascade survivors (no ε² filter on d2): the
    # caller's ε is a verified upper bound on the k-th distance, which
    # bounds the cascade, not the answer values.
    d2m = jnp.where(alive, d2, jnp.inf)
    base = pl.program_id(0) * block_b                # global row offset
    vals, idxs = _topk_select(d2m, base, k)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def _pad_rows(x, block, fill=0.0):
    R = x.shape[0]
    Rp = (R + block - 1) // block * block
    if Rp == R:
        return x
    pad = [(0, Rp - R)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


def _row(x, block, fill=0.0):
    """A per-row vector (R,) or (R, 1) as a lane-dense (1, Rp) row, padded
    to a multiple of ``block`` with ``fill``.  Per-row database columns
    cross into the kernels in this layout: a (R, 1) operand is laid out in
    HBM with its one column padded to 128 lanes — 128× its size."""
    return _pad_rows(x.reshape(-1), block, fill=fill).reshape(1, -1)


def _row_spec(block):
    """BlockSpec of the (1, block) slice of a :func:`_row` column at the
    outer (database) grid index."""
    return pl.BlockSpec((1, block), lambda j, i: (0, j))


def _query_specs(levels, alphabet, n, block_q):
    """Query-side BlockSpecs (index maps depend only on the INNER grid
    index i) — shared by every kernel family in this module."""
    in_specs = [
        pl.BlockSpec((block_q, n), lambda j, i: (i, 0)),        # q
        pl.BlockSpec((block_q, 1), lambda j, i: (i, 0)),        # qnorm
        pl.BlockSpec((block_q, 1), lambda j, i: (i, 0)),        # eps
    ]
    for N in levels:
        in_specs.append(pl.BlockSpec((block_q, 1), lambda j, i: (i, 0)))
        in_specs.append(
            pl.BlockSpec((block_q, alphabet, N), lambda j, i: (i, 0, 0)))
    return in_specs


def _common_specs(levels, alphabet, n, block_q, block_b):
    """(in_specs, pack) for the shared input layout.  The db-side index
    maps depend only on the OUTER grid index j, so each database block is
    fetched from HBM once and stays VMEM-resident across the inner query
    sweep."""
    in_specs = _query_specs(levels, alphabet, n, block_q)
    in_specs.append(pl.BlockSpec((block_b, n), lambda j, i: (j, 0)))  # series
    in_specs.append(_row_spec(block_b))                               # norms
    for N in levels:
        in_specs.append(_row_spec(block_b))
        in_specs.append(pl.BlockSpec((block_b, N), lambda j, i: (j, 0)))
    return in_specs


def _prep_query_inputs(q, q_panels, q_residuals, eps_col, levels, block_q):
    """Pad the query axis and assemble the query-side input pack."""
    Q = q.shape[0]
    q_p = _pad_rows(q.astype(jnp.float32), block_q)
    qn = jnp.sum(q_p * q_p, axis=-1, keepdims=True)   # engine's qnorm form
    eps_p = _pad_rows(eps_col.astype(jnp.float32).reshape(Q, 1), block_q,
                      fill=PAD_EPSILON)
    inputs = [q_p, qn, eps_p]
    for li in range(len(levels)):
        inputs.append(_pad_rows(
            q_residuals[li].astype(jnp.float32).reshape(Q, 1), block_q))
        inputs.append(_pad_rows(q_panels[li].astype(jnp.float32), block_q))
    return inputs, q_p.shape[0]


def _prep_inputs(series, norms_sq, words, residuals, q, q_panels,
                 q_residuals, eps_col, levels, block_q, block_b):
    """Pad both axes and assemble the flat input list (see _split_refs)."""
    inputs, Qp = _prep_query_inputs(q, q_panels, q_residuals, eps_col,
                                    levels, block_q)
    series_p = _pad_rows(series.astype(jnp.float32), block_b)
    inputs += [series_p, _row(norms_sq.astype(jnp.float32), block_b)]
    for li in range(len(levels)):
        inputs.append(_row(residuals[li].astype(jnp.float32), block_b,
                           fill=PAD_RESIDUAL))
        inputs.append(_pad_rows(words[li].astype(jnp.int32), block_b))
    return inputs, Qp, series_p.shape[0]


@functools.partial(jax.jit, static_argnames=(
    "levels", "alphabet", "n", "block_q", "block_b", "interpret"))
def fused_range_pallas(
    series: jnp.ndarray,        # (B, n) f32
    norms_sq: jnp.ndarray,      # (B,)  f32 precomputed ‖u‖²
    words: tuple,               # per level (B, N_l) i32
    residuals: tuple,           # per level (B,) f32
    q: jnp.ndarray,             # (Q, n) f32
    q_panels: tuple,            # per level (Q, α, N_l) f32 — see ops.query_panels
    q_residuals: tuple,         # per level (Q,) f32
    eps_col: jnp.ndarray,       # (Q,) or (Q, 1) f32 per-query ε
    levels: tuple,
    alphabet: int,
    n: int,
    block_q: int = 8,
    block_b: int = 256,
    interpret: bool = True,
):
    """One-pass fused range query: (answers (Q, B) bool, d2 (Q, B) f32).

    Bit-identical to ``core/engine.py::range_query`` (tested): d2 carries
    +inf on non-answer lanes, exactly like the oracle.
    """
    B, Q = series.shape[0], q.shape[0]
    inputs, Qp, Bp = _prep_inputs(series, norms_sq, words, residuals,
                                  q, q_panels, q_residuals, eps_col,
                                  levels, block_q, block_b)
    grid = (Bp // block_b, Qp // block_q)
    ans, d2 = pl.pallas_call(
        functools.partial(_fused_range_kernel, levels=levels,
                          alphabet=alphabet, n=n),
        grid=grid,
        in_specs=_common_specs(levels, alphabet, n, block_q, block_b),
        out_specs=[
            pl.BlockSpec((block_q, block_b), lambda j, i: (i, j)),
            pl.BlockSpec((block_q, block_b), lambda j, i: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, Bp), jnp.int32),
            jax.ShapeDtypeStruct((Qp, Bp), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    return ans[:Q, :B] != 0, d2[:Q, :B]


@functools.partial(jax.jit, static_argnames=(
    "levels", "alphabet", "n", "k", "block_q", "block_b", "interpret"))
def fused_topk_pallas(
    series: jnp.ndarray,
    norms_sq: jnp.ndarray,
    words: tuple,
    residuals: tuple,
    q: jnp.ndarray,
    q_panels: tuple,
    q_residuals: tuple,
    eps_col: jnp.ndarray,
    levels: tuple,
    alphabet: int,
    n: int,
    k: int,
    block_q: int = 8,
    block_b: int = 256,
    interpret: bool = True,
):
    """One-pass fused cascade + verify emitting block-local top-k partials.

    Returns ``(idx (Q, nb·k) i32, d2 (Q, nb·k) f32)``: for every database
    block, the k smallest verified distances among that block's cascade
    survivors (ascending, ties to the lowest index; +inf / −1 on empty
    slots).  The global top-k is a subset of the union of block-local
    top-k sets, so callers merge with :func:`merge_topk_partials` — k-NN
    never writes a (Q, B) distance matrix to HBM.

    The selection is a k-times unrolled min/argmin sweep, so the kernel
    body — and its compile time — grows linearly in k; for very large k
    the dense XLA ``lax.top_k`` path is the better engine.
    """
    Q = q.shape[0]
    inputs, Qp, Bp = _prep_inputs(series, norms_sq, words, residuals,
                                  q, q_panels, q_residuals, eps_col,
                                  levels, block_q, block_b)
    nb = Bp // block_b
    grid = (nb, Qp // block_q)
    out_specs, out_shape = _topk_out(Qp, nb, block_q, k)
    vals, idx = pl.pallas_call(
        functools.partial(_fused_topk_kernel, levels=levels,
                          alphabet=alphabet, n=n, k=k, block_b=block_b),
        grid=grid,
        in_specs=_common_specs(levels, alphabet, n, block_q, block_b),
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    return _flatten_partials(vals, idx, Q)


def merge_topk_partials(idx: jnp.ndarray, d2: jnp.ndarray, k: int):
    """Cheap epilogue: merge (Q, nb·k) block-local partials to the global
    top-k, sorted ascending by (d², index) — the engine-wide deterministic
    tie-break.  Empty slots (d² = +inf, idx = −1) sort last."""
    idx_i = jnp.where(idx < 0, jnp.iinfo(jnp.int32).max, idx)
    d2s, idxs = jax.lax.sort((d2, idx_i), dimension=-1, num_keys=2)
    k = min(int(k), d2.shape[-1])
    out_idx = idxs[:, :k]
    return jnp.where(jnp.isfinite(d2s[:, :k]), out_idx, -1), d2s[:, :k]


# ---------------------------------------------------------------------------
# Streaming subsequence kernels (DESIGN.md §8).
#
# The database is a batch of long streams; the rows are their length-w
# windows under per-window z-normalisation.  Gathering the (W, w) window
# matrix into HBM would re-stream every sample ~w/stride times; instead
# each grid step loads one stream SEGMENT of (block_w − 1)·stride + w
# samples plus the per-window metadata (μ, σ, norms, words, residuals —
# a few values per window), materialises the z windows in VMEM with the
# same f32 expression the XLA oracle uses (core/subseq.device_windows),
# and runs the identical cascade + MXU verify while resident.  Answers
# are bit-identical to the whole-series engines over the materialised
# windows (tested in tests/test_subseq.py).
#
# Window blocks never span streams: each stream's window count is padded
# up to a multiple of block_w, padded windows carry the C9 sentinel
# residual (and padded query rows the ε = −1 sentinel), exactly the
# padding protocol of the kernels above.  Segments are cut OUTSIDE the
# kernel by one small gather (total ≈ stream bytes + overlap — the
# HBM-traffic claim cost_model.subseq_pass_estimate quantifies).
# ---------------------------------------------------------------------------


def _subseq_split_refs(refs, n_levels: int):
    """Inputs: q, qnorm, eps, [qres_l, tq_l]*L,
               seg, mu, sd, norms, [res_l, words_l]*L; outputs trail."""
    q_ref, qn_ref, eps_ref = refs[0], refs[1], refs[2]
    qlv = refs[3:3 + 2 * n_levels]
    base = 3 + 2 * n_levels
    seg_ref, mu_ref, sd_ref, norms_ref = refs[base:base + 4]
    dlv = refs[base + 4:base + 4 + 2 * n_levels]
    outs = refs[base + 4 + 2 * n_levels:]
    return (q_ref, qn_ref, eps_ref, qlv, seg_ref, mu_ref, sd_ref,
            norms_ref, dlv, outs)


def _subseq_z_block(seg_ref, mu_ref, sd_ref, *, window, stride, block_w):
    """(block_w, window) z-normalised windows built from the VMEM-resident
    segment: column j of the window matrix is a static strided slice of
    the segment (the query "slides" across the tile), then the shared
    ``(x − μ)/σ`` normalisation — bit-identical to the materialised rows
    of ``core/subseq.device_windows``."""
    seg = seg_ref[...]                               # (1, seg_len)
    span = (block_w - 1) * stride + 1
    cols = [seg[0, j:j + span:stride] for j in range(window)]
    win = jnp.stack(cols, axis=1)                    # (block_w, window)
    return (win - mu_ref[...].T) / sd_ref[...].T


def _subseq_range_kernel(*refs, levels, alphabet, window, stride, block_w):
    (q_ref, qn_ref, eps_ref, qlv, seg_ref, mu_ref, sd_ref, norms_ref, dlv,
     (ans_ref, d2_ref)) = _subseq_split_refs(refs, len(levels))
    eps = eps_ref[...]
    alive = _cascade_alive(eps, qlv, dlv,
                           levels=levels, alphabet=alphabet, n=window)
    z = _subseq_z_block(seg_ref, mu_ref, sd_ref, window=window,
                        stride=stride, block_w=block_w)
    d2 = _verify_arrays(q_ref[...], qn_ref[...], z, norms_ref[...])
    ans = alive & (d2 <= eps * eps)
    ans_ref[...] = ans.astype(jnp.int32)
    d2_ref[...] = jnp.where(ans, d2, jnp.inf)


def _subseq_topk_kernel(*refs, levels, alphabet, window, stride, k,
                        block_w):
    (q_ref, qn_ref, eps_ref, qlv, seg_ref, mu_ref, sd_ref, norms_ref, dlv,
     (vals_ref, idx_ref)) = _subseq_split_refs(refs, len(levels))
    eps = eps_ref[...]
    alive = _cascade_alive(eps, qlv, dlv,
                           levels=levels, alphabet=alphabet, n=window)
    z = _subseq_z_block(seg_ref, mu_ref, sd_ref, window=window,
                        stride=stride, block_w=block_w)
    d2 = _verify_arrays(q_ref[...], qn_ref[...], z, norms_ref[...])
    d2m = jnp.where(alive, d2, jnp.inf)
    base = pl.program_id(0) * block_w      # PADDED window space (see below)
    vals, idxs = _topk_select(d2m, base, k)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def _subseq_layout(streams, window: int, stride: int, block_w: int):
    """Per-stream window padding + segment plan.

    Returns ``(W_s, W_sp, nb, segments)``: canonical windows per stream,
    padded windows per stream (multiple of block_w, so blocks never span
    streams), total block count, and the f32 segment array
    cut by one gather (positions clipped to the owning stream — the
    clipped samples feed only sentinel-killed padded windows), laid out
    (nb, 1, seg_len) so a (1, seg_len) block spans its array's last two
    dims, the form Mosaic accepts at any seg_len."""
    S, n_stream = streams.shape
    W_s = (n_stream - window) // stride + 1
    W_sp = -(-W_s // block_w) * block_w
    nbs = W_sp // block_w
    nb = S * nbs
    seg_len = (block_w - 1) * stride + window
    flat = streams.astype(jnp.float32).reshape(-1)
    bidx = jnp.arange(nb, dtype=jnp.int32)
    s_of = bidx // nbs
    seg_start = s_of * n_stream + (bidx % nbs) * (block_w * stride)
    lim = (s_of + 1) * n_stream - 1
    pos = jnp.clip(seg_start[:, None]
                   + jnp.arange(seg_len, dtype=jnp.int32)[None, :],
                   0, lim[:, None])
    return W_s, W_sp, nb, flat[pos][:, None, :]


def _pad_windows(x, S: int, W_s: int, W_sp: int, fill):
    """Reshape a canonical stream-major per-window array (W, ...) into the
    padded (S·W_sp, ...) layout the kernel grids over."""
    x2 = x.reshape(S, W_s, *x.shape[1:])
    pad = [(0, 0), (0, W_sp - W_s)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x2, pad, constant_values=fill).reshape(
        S * W_sp, *x.shape[1:])


def _window_row(x, S: int, W_s: int, W_sp: int, fill):
    """A canonical per-window vector (W,) as a padded (1, S·W_sp) row."""
    return _pad_windows(x.reshape(-1), S, W_s, W_sp, fill).reshape(1, -1)


def _subseq_prep(streams, mu, sd, norms_sq, words, residuals,
                 q, q_panels, q_residuals, eps_col, levels,
                 window, stride, block_q, block_w):
    S = streams.shape[0]
    q_inputs, Qp = _prep_query_inputs(q, q_panels, q_residuals, eps_col,
                                      levels, block_q)
    W_s, W_sp, nb, segments = _subseq_layout(streams, window, stride,
                                             block_w)
    f32 = jnp.float32
    db_inputs = [
        segments,
        _window_row(mu.astype(f32), S, W_s, W_sp, 0.0),
        _window_row(sd.astype(f32), S, W_s, W_sp, 1.0),
        _window_row(norms_sq.astype(f32), S, W_s, W_sp, 0.0),
    ]
    for li in range(len(levels)):
        db_inputs.append(_window_row(residuals[li].astype(f32), S, W_s,
                                     W_sp, PAD_RESIDUAL))
        db_inputs.append(_pad_windows(
            words[li].astype(jnp.int32), S, W_s, W_sp, 0))
    return q_inputs + db_inputs, Qp, W_s, W_sp, nb, segments.shape[-1]


def _subseq_specs(levels, alphabet, window, seg_len, block_q, block_w):
    in_specs = _query_specs(levels, alphabet, window, block_q)
    in_specs.append(                                 # stream segment
        pl.BlockSpec((None, 1, seg_len), lambda j, i: (j, 0, 0)))
    for _ in range(3):                               # mu, sd, norms
        in_specs.append(_row_spec(block_w))
    for N in levels:
        in_specs.append(_row_spec(block_w))
        in_specs.append(pl.BlockSpec((block_w, N), lambda j, i: (j, 0)))
    return in_specs


@functools.partial(jax.jit, static_argnames=(
    "levels", "alphabet", "window", "stride", "block_q", "block_w",
    "interpret"))
def fused_subseq_range_pallas(
    streams: jnp.ndarray,       # (S, n_stream) f32 raw streams
    mu: jnp.ndarray,            # (W,) f32 per-window mean
    sd: jnp.ndarray,            # (W,) f32 guarded per-window std
    norms_sq: jnp.ndarray,      # (W,) f32 ‖z‖² of the z windows
    words: tuple,               # per level (W, N_l) i32
    residuals: tuple,           # per level (W,) f32
    q: jnp.ndarray,             # (Q, window) f32 z-normalised queries
    q_panels: tuple,            # per level (Q, α, N_l) f32
    q_residuals: tuple,         # per level (Q,) f32
    eps_col: jnp.ndarray,       # (Q,) or (Q, 1) f32
    levels: tuple,
    alphabet: int,
    window: int,
    stride: int,
    block_q: int = 8,
    block_w: int = 128,
    interpret: bool = True,
):
    """One-pass streaming subsequence range query: ``(answers (Q, W) bool,
    d2 (Q, W) f32)`` in canonical stream-major window order — bit-identical
    to ``engine.range_query`` over the materialised windows (tested)."""
    S = streams.shape[0]
    Q, W = q.shape[0], mu.shape[0]
    inputs, Qp, W_s, W_sp, nb, seg_len = _subseq_prep(
        streams, mu, sd, norms_sq, words, residuals, q, q_panels,
        q_residuals, eps_col, levels, window, stride, block_q, block_w)
    grid = (nb, Qp // block_q)
    ans, d2 = pl.pallas_call(
        functools.partial(_subseq_range_kernel, levels=levels,
                          alphabet=alphabet, window=window, stride=stride,
                          block_w=block_w),
        grid=grid,
        in_specs=_subseq_specs(levels, alphabet, window, seg_len, block_q,
                               block_w),
        out_specs=[
            pl.BlockSpec((block_q, block_w), lambda j, i: (i, j)),
            pl.BlockSpec((block_q, block_w), lambda j, i: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, S * W_sp), jnp.int32),
            jax.ShapeDtypeStruct((Qp, S * W_sp), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    # Padded (S, W_sp) window layout -> canonical (W,) stream-major order.
    ans = ans[:Q].reshape(Q, S, W_sp)[:, :, :W_s].reshape(Q, W)
    d2 = d2[:Q].reshape(Q, S, W_sp)[:, :, :W_s].reshape(Q, W)
    return ans != 0, d2


@functools.partial(jax.jit, static_argnames=(
    "levels", "alphabet", "window", "stride", "k", "block_q", "block_w",
    "interpret"))
def fused_subseq_topk_pallas(
    streams: jnp.ndarray,
    mu: jnp.ndarray,
    sd: jnp.ndarray,
    norms_sq: jnp.ndarray,
    words: tuple,
    residuals: tuple,
    q: jnp.ndarray,
    q_panels: tuple,
    q_residuals: tuple,
    eps_col: jnp.ndarray,
    levels: tuple,
    alphabet: int,
    window: int,
    stride: int,
    k: int,
    block_q: int = 8,
    block_w: int = 128,
    interpret: bool = True,
):
    """Streaming subsequence top-k: block-local partials ``(idx (Q, nb·k)
    i32, d2 (Q, nb·k) f32)`` with ``idx`` already mapped to canonical
    window ids (−1 on empty/padded slots).  Merge with
    :func:`merge_topk_partials`; the k-NN engine re-verifies candidates
    in the diff² form exactly like the whole-series fused path."""
    Q = q.shape[0]
    inputs, Qp, W_s, W_sp, nb, seg_len = _subseq_prep(
        streams, mu, sd, norms_sq, words, residuals, q, q_panels,
        q_residuals, eps_col, levels, window, stride, block_q, block_w)
    grid = (nb, Qp // block_q)
    out_specs, out_shape = _topk_out(Qp, nb, block_q, k)
    vals, idx = pl.pallas_call(
        functools.partial(_subseq_topk_kernel, levels=levels,
                          alphabet=alphabet, window=window, stride=stride,
                          k=k, block_w=block_w),
        grid=grid,
        in_specs=_subseq_specs(levels, alphabet, window, seg_len, block_q,
                               block_w),
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    idx, vals = _flatten_partials(vals, idx, Q)
    # Kernel indices live in the padded (S, W_sp) window space; map them to
    # canonical stream-major ids and kill padded-tail windows explicitly
    # (their sentinel residual already excludes them at any finite ε —
    # this also makes the mapping radius-independent).
    s = idx // W_sp
    t = idx % W_sp
    ok = (idx >= 0) & (t < W_s)
    canon = jnp.where(ok, s * W_s + t, -1)
    return canon, jnp.where(ok, vals, jnp.inf)


# ---------------------------------------------------------------------------
# Quantized dequantize-in-kernel forms (DESIGN.md §9).
#
# The resident tier is QUANTIZED (int8 per-block affine or bf16): what
# crosses HBM→VMEM per database block is the int8/bf16 codes plus a few
# f32 scale rows — 2–4× fewer bytes than the f32 layout — and the kernel
# dequantizes in VMEM with the exact expression of the XLA oracle
# (``core/engine.quantized_screen``), so the two screens are bit-identical
# (tested).  The cascade bounds are WIDENED by the stored per-block error
# (C9) and per-row L2 error (series screen); C10 runs unwidened on the
# losslessly-narrowed int8 symbols.  These kernels emit the *screen* —
# survivors that may be answers — and the tiered engine exact-verifies
# them against the raw mmap tier; the streaming subsequence form streams
# the raw samples anyway, so its in-kernel verify is already exact and it
# emits final answers directly.
#
# Scale-block layout: the per-scale-block columns (one value per
# ``quantized.RESID_BLOCK`` rows) are expanded to per-row values by the
# wrappers, outside the kernel, and cross in the same lane-dense (1, rows)
# layout as every other per-row column (:func:`_row`).  A
# (block_b // 128, 1) block is refused by Mosaic (its row count is not a
# multiple of 8), and expanding it in VMEM needs a reshape Mosaic cannot
# lower.
# ---------------------------------------------------------------------------


def _quant_split_refs(refs, n_levels: int, int8: bool):
    """Quantized kernel ref layout.

    Inputs: q, qnorm, eps, [qres_l, tq_l]*L,
            qseries(, s_scale, s_zero), serr, norms,
            [codes_l(, scale_l, zero_l), err_l, words_l]*L
    (the parenthesised refs exist only in int8 mode; every database-side
    column is per row).
    """
    q_ref, qn_ref, eps_ref = refs[0], refs[1], refs[2]
    qlv = refs[3:3 + 2 * n_levels]
    base = 3 + 2 * n_levels
    if int8:
        qseries_ref, s_scale_ref, s_zero_ref = refs[base:base + 3]
        base += 3
    else:
        qseries_ref = refs[base]
        s_scale_ref = s_zero_ref = None
        base += 1
    serr_ref, norms_ref = refs[base], refs[base + 1]
    base += 2
    per = 5 if int8 else 3
    dlv = refs[base:base + per * n_levels]
    outs = refs[base + per * n_levels:]
    return (q_ref, qn_ref, eps_ref, qlv, qseries_ref, s_scale_ref,
            s_zero_ref, serr_ref, norms_ref, dlv, outs)


def _quant_residuals(dlv, li: int, int8: bool):
    """Dequantized (1, rows) residuals + (1, rows) error bound + words ref
    for one level from per-row columns — ``zero + scale · code`` is THE
    shared dequantizer (bit-identical to engine._dequant_residuals_dev).
    Shared by the whole-series and streaming-subsequence kernels."""
    per = 5 if int8 else 3
    off = per * li
    if int8:
        codes = dlv[off][...]                        # (1, rows) i8
        deq = dlv[off + 2][...] + dlv[off + 1][...] * \
            codes.astype(jnp.float32)
        res = jnp.where(codes.astype(jnp.int32) == _quant.SENTINEL_CODE,
                        jnp.float32(PAD_RESIDUAL), deq)
        err = dlv[off + 3][...]
        words_ref = dlv[off + 4]
    else:
        res = dlv[off][...].astype(jnp.float32)      # (1, rows) bf16
        err = dlv[off + 1][...]
        words_ref = dlv[off + 2]
    return res, err, words_ref


def _quant_cascade_alive(eps, qlv, dlv, *, levels, alphabet, n, int8):
    """(block_q, rows) alive mask under the WIDENED cascade: C9 compares
    the dequantized gap against ε + e_blk; C10 is the exact unwidened
    compare-select sweep on the losslessly-narrowed int8 symbols."""
    eps2 = eps * eps
    alive = None
    for li, N in enumerate(levels):
        qres = qlv[2 * li][...]                      # (block_q, 1)
        res, err, words_ref = _quant_residuals(dlv, li, int8)
        ok = jnp.abs(res - qres) <= eps + err         # (block_q, rows)
        alive = ok if alive is None else alive & ok
        alive &= _c10_alive(eps2, qlv[2 * li + 1][...], words_ref[...],
                            alphabet=alphabet, n=n, N=N)
    return alive


def _quant_screen_d2(q_ref, qn_ref, qseries_ref, s_scale_ref, s_zero_ref,
                     norms_ref, int8: bool):
    """Dequantize the series block in VMEM and evaluate the shared
    matmul-form screen distance d(û, q)² against the dequantized norms."""
    codes = qseries_ref[...]
    if int8:                 # per-row affine: (1, rows) rows -> columns
        u = s_zero_ref[...].T + s_scale_ref[...].T * \
            codes.astype(jnp.float32)
    else:
        u = codes.astype(jnp.float32)
    return _verify_arrays(q_ref[...], qn_ref[...], u, norms_ref[...])


def _quant_keep(alive, d2, eps, serr_ref):
    """The widened series screen: keep rows with d(û,q) ≤ (ε + e_u) plus
    the f32 slack — identical expression to the XLA oracle."""
    thresh = (eps + serr_ref[...]) * (1.0 + QUANT_SCREEN_REL) \
        + QUANT_SCREEN_ABS
    return alive & (d2 <= thresh * thresh)


def _quant_range_kernel(*refs, levels, alphabet, n, int8):
    (q_ref, qn_ref, eps_ref, qlv, qseries_ref, s_scale_ref, s_zero_ref,
     serr_ref, norms_ref, dlv,
     (keep_ref, d2_ref)) = _quant_split_refs(refs, len(levels), int8)
    eps = eps_ref[...]
    alive = _quant_cascade_alive(eps, qlv, dlv, levels=levels,
                                 alphabet=alphabet, n=n, int8=int8)
    d2 = _quant_screen_d2(q_ref, qn_ref, qseries_ref, s_scale_ref,
                          s_zero_ref, norms_ref, int8)
    keep = _quant_keep(alive, d2, eps, serr_ref)
    keep_ref[...] = keep.astype(jnp.int32)
    d2_ref[...] = jnp.where(keep, d2, jnp.inf)


def _quant_topk_kernel(*refs, levels, alphabet, n, k, int8, block_b):
    (q_ref, qn_ref, eps_ref, qlv, qseries_ref, s_scale_ref, s_zero_ref,
     serr_ref, norms_ref, dlv,
     (vals_ref, idx_ref)) = _quant_split_refs(refs, len(levels), int8)
    eps = eps_ref[...]
    alive = _quant_cascade_alive(eps, qlv, dlv, levels=levels,
                                 alphabet=alphabet, n=n, int8=int8)
    d2 = _quant_screen_d2(q_ref, qn_ref, qseries_ref, s_scale_ref,
                          s_zero_ref, norms_ref, int8)
    d2m = jnp.where(_quant_keep(alive, d2, eps, serr_ref), d2, jnp.inf)
    base = pl.program_id(0) * block_b
    vals, idxs = _topk_select(d2m, base, k)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def _quant_db_specs(levels, int8: bool, n: int, block_b: int):
    """Database-side BlockSpecs of the quantized layout (outer index j):
    the (block_b, n) series codes and per-row (1, block_b) rows."""
    def col():
        return _row_spec(block_b)

    specs = [pl.BlockSpec((block_b, n), lambda j, i: (j, 0))]    # qseries
    if int8:
        specs += [col(), col()]                      # s_scale, s_zero
    specs += [col(), col()]                          # serr, norms
    for N in levels:
        specs += [col() for _ in range(4 if int8 else 2)]  # codes(,sc,z),err
        specs.append(pl.BlockSpec((block_b, N), lambda j, i: (j, 0)))
    return specs


def _rows_of_blocks(a, Bp: int, fill):
    """A (nb, 1) per-scale-block column -> (1, Bp) per-row row: runs of
    RESID_BLOCK consecutive rows share a value (the XLA oracle's
    expansion); rows past the stored blocks take ``fill``."""
    a = jnp.asarray(a, jnp.float32).reshape(-1)
    return _row(jnp.repeat(a, _quant.RESID_BLOCK)[:Bp], Bp, fill=fill)


def _quant_prep_inputs(qdev, q, q_panels, q_residuals, eps_col, block_q,
                       block_b):
    """Pad both axes of the quantized layout and assemble the flat input
    list (see _quant_split_refs).  ``qdev`` duck-types
    ``core/engine.QuantizedDeviceIndex``."""
    int8 = qdev.mode == "int8"
    levels = qdev.levels
    B = qdev.series.shape[0]
    inputs, Qp = _prep_query_inputs(q, q_panels, q_residuals, eps_col,
                                    levels, block_q)
    Bp = -(-B // block_b) * block_b
    inputs.append(_pad_rows(qdev.series, block_b, fill=0))
    if int8:
        inputs.append(_row(qdev.series_scale, block_b, fill=1.0))
        inputs.append(_row(qdev.series_zero, block_b, fill=0.0))
    inputs.append(_row(qdev.series_err.astype(jnp.float32), block_b))
    inputs.append(_row(qdev.norms_sq.astype(jnp.float32), block_b))
    for li in range(len(levels)):
        codes = qdev.residuals[li]
        if int8:
            inputs.append(_row(codes, block_b, fill=_quant.SENTINEL_CODE))
            inputs.append(_rows_of_blocks(qdev.resid_scale[li], Bp, 1.0))
            inputs.append(_rows_of_blocks(qdev.resid_zero[li], Bp, 0.0))
        else:
            inputs.append(_row(codes, block_b, fill=PAD_RESIDUAL))
        inputs.append(_rows_of_blocks(qdev.resid_err[li], Bp, 0.0))
        inputs.append(_pad_rows(qdev.words[li], block_b, fill=0))
    return inputs, Qp, Bp


@functools.partial(jax.jit, static_argnames=(
    "Qp", "Bp", "mode", "levels", "alphabet", "n", "block_q", "block_b",
    "interpret"))
def _quant_range_call(inputs, Qp, Bp, mode, levels, alphabet, n, block_q,
                      block_b, interpret):
    int8 = mode == "int8"
    grid = (Bp // block_b, Qp // block_q)
    in_specs = _query_specs(levels, alphabet, n, block_q) + \
        _quant_db_specs(levels, int8, n, block_b)
    return pl.pallas_call(
        functools.partial(_quant_range_kernel, levels=levels,
                          alphabet=alphabet, n=n, int8=int8),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, block_b), lambda j, i: (i, j)),
            pl.BlockSpec((block_q, block_b), lambda j, i: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, Bp), jnp.int32),
            jax.ShapeDtypeStruct((Qp, Bp), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)


def fused_quant_range_pallas(
    qdev,                       # engine.QuantizedDeviceIndex (duck-typed)
    q: jnp.ndarray,             # (Q, n) f32
    q_panels: tuple,            # per level (Q, α, N_l) f32
    q_residuals: tuple,         # per level (Q,) f32
    eps_col: jnp.ndarray,       # (Q,) or (Q, 1) f32
    block_q: int = 8,
    block_b: int = 256,
    interpret: bool = True,
):
    """One-pass quantized screen: ``(keep (Q, B) bool, d̂² (Q, B) f32)``.

    Bit-identical to ``core/engine.quantized_screen`` (tested): the codes
    are dequantized in VMEM, the C9 bound is widened by the per-block
    error, and the series screen by the per-row L2 error + f32 slack.
    Survivors still need the raw-tier exact verify — the tiered engine
    (``core/engine.quantized_range_query``) owns that epilogue.
    """
    B, Q = qdev.series.shape[0], q.shape[0]
    eps = jnp.asarray(eps_col, jnp.float32).reshape(Q, 1)
    inputs, Qp, Bp = _quant_prep_inputs(qdev, q, q_panels, q_residuals,
                                        eps, block_q, block_b)
    keep, d2 = _quant_range_call(
        inputs, Qp=Qp, Bp=Bp, mode=qdev.mode, levels=qdev.levels,
        alphabet=qdev.alphabet, n=qdev.n, block_q=block_q,
        block_b=block_b, interpret=interpret)
    return keep[:Q, :B] != 0, d2[:Q, :B]


@functools.partial(jax.jit, static_argnames=(
    "Qp", "Bp", "mode", "levels", "alphabet", "n", "k", "block_q",
    "block_b", "interpret"))
def _quant_topk_call(inputs, Qp, Bp, mode, levels, alphabet, n, k, block_q,
                     block_b, interpret):
    int8 = mode == "int8"
    nb = Bp // block_b
    grid = (nb, Qp // block_q)
    in_specs = _query_specs(levels, alphabet, n, block_q) + \
        _quant_db_specs(levels, int8, n, block_b)
    out_specs, out_shape = _topk_out(Qp, nb, block_q, k)
    return pl.pallas_call(
        functools.partial(_quant_topk_kernel, levels=levels,
                          alphabet=alphabet, n=n, k=k, int8=int8,
                          block_b=block_b),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)


def fused_quant_topk_pallas(
    qdev,
    q: jnp.ndarray,
    q_panels: tuple,
    q_residuals: tuple,
    eps_col: jnp.ndarray,
    k: int,
    block_q: int = 8,
    block_b: int = 256,
    interpret: bool = True,
):
    """Quantized screen emitting block-local top-k partials of the SCREEN
    distances d(û, q)² among screen survivors — ``(idx (Q, nb·k) i32,
    d̂² (Q, nb·k) f32)``, merged by :func:`merge_topk_partials`.  The
    candidates are screen-level (distances to the dequantized rows); any
    exactness claim still requires the raw-tier verify, which is why the
    tiered k-NN engine prefers the range screen + compaction epilogue —
    this form exists for parity testing and candidate generation.
    """
    Q = q.shape[0]
    eps = jnp.asarray(eps_col, jnp.float32).reshape(Q, 1)
    inputs, Qp, Bp = _quant_prep_inputs(qdev, q, q_panels, q_residuals,
                                        eps, block_q, block_b)
    vals, idx = _quant_topk_call(
        inputs, Qp=Qp, Bp=Bp, mode=qdev.mode, levels=qdev.levels,
        alphabet=qdev.alphabet, n=qdev.n, k=int(k), block_q=block_q,
        block_b=block_b, interpret=interpret)
    return _flatten_partials(vals, idx, Q)


# --- streaming subsequence form --------------------------------------------


def _quant_subseq_split_refs(refs, n_levels: int, int8: bool):
    """Inputs: q, qnorm, eps, [qres_l, tq_l]*L, seg, mu, sd, norms,
    [codes_l(, scale_l, zero_l), err_l, words_l]*L; outputs trail.  The
    per-window scale/zero/err columns are pre-expanded per window (the
    window metadata is already per-window — μ, σ, norms — so the streaming
    layout stores dequant params at the same granularity)."""
    q_ref, qn_ref, eps_ref = refs[0], refs[1], refs[2]
    qlv = refs[3:3 + 2 * n_levels]
    base = 3 + 2 * n_levels
    seg_ref, mu_ref, sd_ref, norms_ref = refs[base:base + 4]
    base += 4
    per = 5 if int8 else 3
    dlv = refs[base:base + per * n_levels]
    outs = refs[base + per * n_levels:]
    return (q_ref, qn_ref, eps_ref, qlv, seg_ref, mu_ref, sd_ref,
            norms_ref, dlv, outs)


def _quant_subseq_range_kernel(*refs, levels, alphabet, window, stride,
                               int8, block_w):
    (q_ref, qn_ref, eps_ref, qlv, seg_ref, mu_ref, sd_ref, norms_ref, dlv,
     (ans_ref, d2_ref)) = _quant_subseq_split_refs(refs, len(levels), int8)
    eps = eps_ref[...]
    alive = _quant_cascade_alive(eps, qlv, dlv, levels=levels,
                                 alphabet=alphabet, n=window, int8=int8)
    # The raw samples are streamed anyway, so the in-kernel verify is
    # EXACT — quantization touched only the screen metadata, and the
    # widened cascade is a superset screen: final answers are identical
    # to the full-precision subsequence kernel (tested).
    z = _subseq_z_block(seg_ref, mu_ref, sd_ref, window=window,
                        stride=stride, block_w=block_w)
    d2 = _verify_arrays(q_ref[...], qn_ref[...], z, norms_ref[...])
    ans = alive & (d2 <= eps * eps)
    ans_ref[...] = ans.astype(jnp.int32)
    d2_ref[...] = jnp.where(ans, d2, jnp.inf)


@functools.partial(jax.jit, static_argnames=(
    "mode", "levels", "alphabet", "window", "stride", "block_q", "block_w",
    "interpret"))
def fused_quant_subseq_range_pallas(
    streams: jnp.ndarray,       # (S, n_stream) f32 raw streams
    mu: jnp.ndarray,            # (W,) f32
    sd: jnp.ndarray,            # (W,) f32
    norms_sq: jnp.ndarray,      # (W,) f32
    qwords: tuple,              # per level (W, N_l) int8
    qresiduals: tuple,          # per level (W,) int8 codes / bf16
    qresid_scale: tuple,        # per level (W,) f32 per-window (int8) / None
    qresid_zero: tuple,         # per level (W,) f32 per-window (int8) / None
    qresid_err: tuple,          # per level (W,) f32 per-window
    q: jnp.ndarray,
    q_panels: tuple,
    q_residuals: tuple,
    eps_col: jnp.ndarray,
    mode: str,
    levels: tuple,
    alphabet: int,
    window: int,
    stride: int,
    block_q: int = 8,
    block_w: int = 128,
    interpret: bool = True,
):
    """Streaming subsequence range query over QUANTIZED window metadata:
    ``(answers (Q, W) bool, d2 (Q, W) f32)`` in canonical stream-major
    order.  Only the screen columns (words, residuals) are quantized —
    the raw samples are streamed and z-normalised in VMEM as before, so
    the verify is exact in-kernel and the answers are set-identical to
    the full-precision :func:`fused_subseq_range_pallas` (tested).
    """
    int8 = mode == "int8"
    S = streams.shape[0]
    Q, W = q.shape[0], mu.shape[0]
    q_inputs, Qp = _prep_query_inputs(q, q_panels, q_residuals, eps_col,
                                      levels, block_q)
    W_s, W_sp, nb, segments = _subseq_layout(streams, window, stride,
                                             block_w)
    f32 = jnp.float32
    db_inputs = [
        segments,
        _window_row(mu.astype(f32), S, W_s, W_sp, 0.0),
        _window_row(sd.astype(f32), S, W_s, W_sp, 1.0),
        _window_row(norms_sq.astype(f32), S, W_s, W_sp, 0.0),
    ]
    for li in range(len(levels)):
        codes = qresiduals[li]
        if int8:
            db_inputs.append(_window_row(codes, S, W_s, W_sp,
                                         _quant.SENTINEL_CODE))
            db_inputs.append(_window_row(qresid_scale[li].astype(f32), S,
                                         W_s, W_sp, 1.0))
            db_inputs.append(_window_row(qresid_zero[li].astype(f32), S,
                                         W_s, W_sp, 0.0))
        else:
            db_inputs.append(_window_row(codes, S, W_s, W_sp, PAD_RESIDUAL))
        db_inputs.append(_window_row(qresid_err[li].astype(f32), S, W_s,
                                     W_sp, 0.0))
        db_inputs.append(_pad_windows(qwords[li], S, W_s, W_sp, 0))
    seg_len = segments.shape[-1]
    in_specs = _query_specs(levels, alphabet, window, block_q)
    in_specs.append(
        pl.BlockSpec((None, 1, seg_len), lambda j, i: (j, 0, 0)))
    for _ in range(3):
        in_specs.append(_row_spec(block_w))
    for N in levels:
        per = 4 if int8 else 2                       # codes(,scale,zero),err
        for _ in range(per):
            in_specs.append(_row_spec(block_w))
        in_specs.append(pl.BlockSpec((block_w, N), lambda j, i: (j, 0)))
    grid = (nb, Qp // block_q)
    ans, d2 = pl.pallas_call(
        functools.partial(_quant_subseq_range_kernel, levels=levels,
                          alphabet=alphabet, window=window, stride=stride,
                          int8=int8, block_w=block_w),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, block_w), lambda j, i: (i, j)),
            pl.BlockSpec((block_q, block_w), lambda j, i: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, S * W_sp), jnp.int32),
            jax.ShapeDtypeStruct((Qp, S * W_sp), jnp.float32),
        ],
        interpret=interpret,
    )(*(q_inputs + db_inputs))
    ans = ans[:Q].reshape(Q, S, W_sp)[:, :, :W_s].reshape(Q, W)
    d2 = d2[:Q].reshape(Q, S, W_sp)[:, :, :W_s].reshape(Q, W)
    return ans != 0, d2
