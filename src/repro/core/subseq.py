"""Subsequence similarity search over long streams (DESIGN.md §8).

The paper's workload is whole-series matching; the workload that made SAX
famous is *subsequence* matching: find every length-w window of a long
stream within ε of a short query, or its k nearest windows, under
per-window z-normalisation.  This module opens that workload by mapping
windows onto the existing whole-series machinery — a window is a database
row, and every engine (XLA cascade, fused Pallas kernels, shard_map,
serving) operates on the windows-as-rows index unchanged.

Three pieces are genuinely new:

  * **Amortised feature extraction.**  Per-window mean/std come from
    cumulative sums of the stream (O(n) total, not O(n·w)); the PAA word
    of the z-normalised window is the affine image of the raw segment
    means (``(m − μ)/σ``), and the linear-fit residual of the z window is
    the raw residual scaled by ``1/σ`` (the LS line class is closed under
    affine maps, so the optimal fit maps to the optimal fit).  Every
    per-window word and residual is therefore computed from O(N) cumsum
    lookups — the whole offline phase is one pass over the stream.

  * **Trivial-match suppression.**  Neighbouring windows of a stream are
    near-duplicates of each other; k-NN answers apply an *exclusion zone*
    (no two reported windows within ``excl`` start positions on the same
    stream, matrix-profile convention).  The greedy ascending-(d², index)
    selection is exact given the top ``k + (k−1)·(Z−1)`` windows, where Z
    bounds the zone population (:func:`knn_fetch_count`) — so the engine
    fetches that many candidates through the ordinary exact k-NN path and
    suppresses in a host epilogue.

  * **The streaming kernel** (``kernels/fused_query.py``): each grid step
    keeps a stream *segment* resident in VMEM and materialises its
    windows in registers — never gathering the (W, w) window matrix into
    HBM.  See :func:`subseq_range_query_pallas`.

Answers on every path are defined against one oracle: materialise each
window, z-normalise it, run the whole-series engine.  The device window
materialisation (:func:`device_windows`) is THE shared f32 expression, so
XLA, Pallas, distributed and served answers are bit-identical to each
other (tested in ``tests/test_subseq.py`` against an independent f64
brute force as well).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import fused_query as _fused
from ..kernels import ops as kernel_ops
from . import engine as _engine
from . import representation as repr_registry
from .engine import DeviceIndex, QueryReprDev, represent_queries
from .fastsax import FastSAXConfig, FastSAXIndex, LevelData
from .options import SearchOptions, resolve_options
from .paa import znormalize_np
from .representation import DEFAULT_STACK
from .sax import discretize_np

# Same floor as paa.znormalize / znormalize_np: a (near-)constant window
# z-normalises through the guarded σ instead of dividing by ~0.
ZNORM_EPS = 1e-8


def n_windows_per_stream(stream_len: int, window: int, stride: int) -> int:
    if window > stream_len:
        raise ValueError(f"window={window} longer than stream={stream_len}")
    if stride < 1:
        raise ValueError(f"stride must be >= 1, got {stride}")
    return (stream_len - window) // stride + 1


# ---------------------------------------------------------------------------
# Offline phase: amortised sliding-window features via cumulative sums.
# ---------------------------------------------------------------------------


def _cumsums(streams: np.ndarray):
    """Zero-prefixed cumulative sums of x, x² and t·x (f64): every window
    or segment sum below is two lookups, independent of its length."""
    S, n = streams.shape
    t = np.arange(n, dtype=np.float64)
    c0 = np.zeros((S, n + 1))
    c1 = np.zeros((S, n + 1))
    c2 = np.zeros((S, n + 1))
    np.cumsum(streams, axis=-1, out=c0[:, 1:])
    np.cumsum(streams * streams, axis=-1, out=c1[:, 1:])
    np.cumsum(streams * t[None, :], axis=-1, out=c2[:, 1:])
    return c0, c1, c2


def _window_moments(c0, c1, starts, window: int):
    """Per-window mean and guarded std, (S, W_s) each, from the cumsums."""
    mu = (c0[:, starts + window] - c0[:, starts]) / window
    ex2 = (c1[:, starts + window] - c1[:, starts]) / window
    sd = np.sqrt(np.maximum(ex2 - mu * mu, 0.0))
    return mu, np.maximum(sd, ZNORM_EPS)


@dataclasses.dataclass
class WindowStats:
    """Amortised per-window segment statistics of one level, handed to a
    representation's ``window_symbolize_np`` hook (``core/representation``)
    so extra stack columns are computed from the same O(N)-per-window
    cumsum lookups as the canonical ones.  ``sxy`` is None when L == 1
    (a one-sample segment has no slope)."""

    sum_y: np.ndarray          # (S, W_s, N) raw segment sums
    sxy: np.ndarray | None     # (S, W_s, N) raw Σ xc·y per segment
    L: int                     # samples per segment
    sxx: float                 # Σ xc² of the centred abscissa (0 if L == 1)
    sd: np.ndarray             # (S, W_s) guarded per-window std
    alphabet: int


def _window_level(c0, c1, c2, starts, window, mu, sd, N, alphabet):
    """One representation level for every window of every stream, O(W·N).

    PAA of the z window is the affine image of the raw segment means:
    ``paa_z = (m − μ)/σ``.  The linear-fit residual of the z window is the
    raw residual over σ: z = (y − μ)/σ is an affine map of y, the
    piecewise-linear class is closed under affine maps, and a uniform
    scale multiplies every pointwise error by 1/σ — so the optimal raw
    fit maps onto the optimal z fit with ‖resid_z‖ = ‖resid_raw‖/σ.
    Returns (words (S, W_s, N) i32, residuals (S, W_s) f64,
    :class:`WindowStats` for the extra-representation hooks).
    """
    L = window // N
    # Segment boundaries of every window: (W_s, N+1) absolute indices.
    bounds = starts[:, None] + np.arange(N + 1)[None, :] * L
    g0 = c0[:, bounds]                          # (S, W_s, N+1)
    sum_y = g0[..., 1:] - g0[..., :-1]          # (S, W_s, N)
    mean = sum_y / L
    paa_z = (mean - mu[..., None]) / sd[..., None]
    words = discretize_np(paa_z, alphabet)
    if L == 1:                                   # exact fit per sample
        ws = WindowStats(sum_y=sum_y, sxy=None, L=1, sxx=0.0, sd=sd,
                         alphabet=alphabet)
        return words, np.zeros(mu.shape), ws
    # Residual: with centred abscissa xc = t − b − (L−1)/2 per segment,
    # Σxc·y = (Σ t·y) − (b + (L−1)/2)·Σy — two more cumsum lookups.
    g1 = c1[:, bounds]
    g2 = c2[:, bounds]
    sum_y2 = g1[..., 1:] - g1[..., :-1]
    t_sum = g2[..., 1:] - g2[..., :-1]
    xc = np.arange(L, dtype=np.float64) - (L - 1) / 2.0
    sxx = float(np.sum(xc * xc))
    off = bounds[:, :-1] + (L - 1) / 2.0        # (W_s, N)
    sxy = t_sum - off[None, :, :] * sum_y
    per_seg = np.maximum(sum_y2 - L * mean * mean - (sxy * sxy) / sxx, 0.0)
    resid_raw = np.sqrt(per_seg.sum(axis=-1))
    ws = WindowStats(sum_y=sum_y, sxy=sxy, L=L, sxx=sxx, sd=sd,
                     alphabet=alphabet)
    return words, resid_raw / sd, ws


@dataclasses.dataclass
class SubseqHostIndex:
    """The offline subsequence artifact: raw streams + per-window features.

    Windows are numbered stream-major: window ``wid`` lives on stream
    ``wid // windows_per_stream`` at start position
    ``(wid % windows_per_stream) · stride``.  The (W, w) window matrix is
    never stored here — it is materialised on demand
    (:func:`materialize_windows_np` for the store column,
    :func:`device_windows` for the device engines).
    """

    config: FastSAXConfig
    window: int
    stride: int
    streams: np.ndarray        # (S, n_stream) float64, RAW (not z-normalised)
    mu: np.ndarray             # (W,) float64 per-window mean
    sd: np.ndarray             # (W,) float64 guarded per-window std
    levels: list               # [LevelData] over z windows, cascade order

    @property
    def n_streams(self) -> int:
        return self.streams.shape[0]

    @property
    def stream_len(self) -> int:
        return self.streams.shape[-1]

    @property
    def windows_per_stream(self) -> int:
        return n_windows_per_stream(self.stream_len, self.window, self.stride)

    @property
    def n_windows(self) -> int:
        return self.n_streams * self.windows_per_stream

    def window_meta(self, wid):
        """Map window ids -> (stream index, start position) arrays."""
        wid = np.asarray(wid)
        W_s = self.windows_per_stream
        return wid // W_s, (wid % W_s) * self.stride


def build_subseq_index(
    streams: np.ndarray,
    config: FastSAXConfig,
    window: int,
    stride: int = 1,
) -> SubseqHostIndex:
    """Offline phase for the subsequence workload: one pass over each
    stream (cumsums), then O(N) work per window and level — O(n·ΣN/s)
    total, never O(n·w).  ``window`` must be divisible by every level's
    segment count (the same constraint the whole-series index has on n).
    """
    streams = np.asarray(streams, dtype=np.float64)
    if streams.ndim == 1:
        streams = streams[None, :]
    if streams.ndim != 2:
        raise ValueError(f"streams must be (S, n_stream), got {streams.shape}")
    for N in config.n_segments:
        if window % N != 0:
            raise ValueError(f"level N={N} does not divide window={window}")
    W_s = n_windows_per_stream(streams.shape[-1], window, stride)
    starts = np.arange(W_s) * stride
    c0, c1, c2 = _cumsums(streams)
    mu, sd = _window_moments(c0, c1, starts, window)
    extras = config.extra_stack
    for name in extras:
        if getattr(repr_registry.get(name), "window_symbolize_np",
                   None) is None:
            raise NotImplementedError(
                f"representation {name!r} defines no window_symbolize_np "
                "hook — it cannot be amortised over sliding windows; drop "
                "it from the stack for the subsequence workload")
    levels = []
    for N in config.levels:
        words, resid, ws = _window_level(c0, c1, c2, starts, window, mu, sd,
                                         N, config.alphabet)
        extra = {}
        for name in extras:
            rep = repr_registry.get(name)
            col = rep.window_symbolize_np(ws)
            extra[name] = (col.reshape(-1, col.shape[-1])
                           if rep.column.per_segment else col.reshape(-1))
        levels.append(LevelData(n_segments=N,
                                words=words.reshape(-1, N),
                                residuals=resid.reshape(-1),
                                extra=extra))
    return SubseqHostIndex(config=config, window=window, stride=stride,
                           streams=streams, mu=mu.reshape(-1),
                           sd=sd.reshape(-1), levels=levels)


def materialize_windows_np(hidx: SubseqHostIndex) -> np.ndarray:
    """(W, window) float64 z-normalised windows — the host/store oracle."""
    W_s = hidx.windows_per_stream
    sid = np.repeat(np.arange(hidx.n_streams), W_s)
    start = np.tile(np.arange(W_s) * hidx.stride, hidx.n_streams)
    win = hidx.streams[sid[:, None],
                       start[:, None] + np.arange(hidx.window)[None, :]]
    return (win - hidx.mu[:, None]) / hidx.sd[:, None]


def subseq_brute_force_d2(
    streams: np.ndarray,
    queries: np.ndarray,
    window: int,
    stride: int = 1,
    normalize_queries: bool = True,
) -> np.ndarray:
    """The f64 reference every engine answer is tested against: materialise
    every window, z-normalise it *independently* (``znormalize_np`` — not
    the cumsum moments), z-normalise each query, full (Q, W) squared
    Euclidean distance matrix.  O(Q·W·w) — a test/benchmark oracle only.
    """
    streams = np.asarray(streams, dtype=np.float64)
    if streams.ndim == 1:
        streams = streams[None, :]
    W_s = n_windows_per_stream(streams.shape[-1], window, stride)
    sid = np.repeat(np.arange(streams.shape[0]), W_s)
    start = np.tile(np.arange(W_s) * stride, streams.shape[0])
    win = streams[sid[:, None], start[:, None] + np.arange(window)[None, :]]
    z = znormalize_np(win)
    q = np.asarray(queries, dtype=np.float64)
    if q.ndim == 1:
        q = q[None, :]
    if normalize_queries:
        q = znormalize_np(q)
    diff = z[None, :, :] - q[:, None, :]
    return np.sum(diff * diff, axis=-1)


# ---------------------------------------------------------------------------
# Trivial-match suppression (exclusion zone).
# ---------------------------------------------------------------------------


def exclusion_zone_span(excl: int, stride: int) -> int:
    """Z = max number of window positions inside one exclusion zone
    (|Δstart| < excl on a stride-s grid): 2·⌊(excl−1)/s⌋ + 1."""
    if excl <= 0:
        return 1
    return 2 * ((int(excl) - 1) // int(stride)) + 1


def knn_fetch_count(k: int, excl: int, stride: int, n_windows: int) -> int:
    """How many globally-nearest windows the greedy exclusion-zone
    selection provably needs to produce k admissible answers.

    Scanning candidates in ascending (d², index) order, every rejected
    candidate lies in the zone of an *already kept* one; each of the
    first k−1 keeps zones ≤ Z−1 other candidates, so the k-th keep has
    global rank ≤ k + (k−1)·(Z−1).  Capped at W, where the scan covers
    everything.
    """
    Z = exclusion_zone_span(excl, stride)
    return min(int(n_windows), int(k) + (int(k) - 1) * (Z - 1))


def suppress_trivial_matches(idx, d2, stream_of, start_of, k: int,
                             excl: int):
    """Greedy exclusion-zone selection over sorted candidate lists.

    ``idx``/``d2``: (Q, K) candidates ascending by (d², index) — the
    engines' output order — with −1 / +inf on empty slots.  A candidate
    is kept unless a previously kept window on the *same stream* starts
    within ``excl`` positions.  Returns (sel_idx (Q, k), sel_d2 (Q, k)),
    −1 / +inf padded when fewer than k admissible windows exist.  Host
    epilogue: k is small and the loop is O(K·k).
    """
    idx = np.asarray(idx)
    d2 = np.asarray(d2)
    Q, K = idx.shape
    sel_idx = np.full((Q, k), -1, dtype=np.int64)
    sel_d2 = np.full((Q, k), np.inf)
    for qi in range(Q):
        kept = 0
        kept_stream = np.empty(k, dtype=np.int64)
        kept_start = np.empty(k, dtype=np.int64)
        for ci in range(K):
            w = int(idx[qi, ci])
            if w < 0 or not np.isfinite(d2[qi, ci]):
                break                     # empties sort last — nothing left
            s, a = int(stream_of[w]), int(start_of[w])
            if excl > 0 and any(
                    kept_stream[j] == s and abs(int(kept_start[j]) - a) < excl
                    for j in range(kept)):
                continue
            kept_stream[kept] = s
            kept_start[kept] = a
            sel_idx[qi, kept] = w
            sel_d2[qi, kept] = d2[qi, ci]
            kept += 1
            if kept == k:
                break
    return sel_idx, sel_d2


# ---------------------------------------------------------------------------
# Device index: windows as rows of a standard DeviceIndex + the streams.
# ---------------------------------------------------------------------------


def device_windows(streams: jnp.ndarray, window: int, stride: int,
                   mu: jnp.ndarray, sd: jnp.ndarray,
                   wid: jnp.ndarray | None = None) -> jnp.ndarray:
    """Materialise z-normalised windows on device, in f32 — THE defining
    expression every engine path shares: the XLA oracle's series rows,
    the streaming kernel's in-VMEM block build and any candidate
    re-gather all evaluate ``(x[a:a+w] − μ)/σ`` on the same f32 inputs,
    which is what makes the backends bit-identical."""
    S, n = streams.shape
    W_s = n_windows_per_stream(n, window, stride)
    if wid is None:
        wid = jnp.arange(S * W_s, dtype=jnp.int32)
    sid = wid // W_s
    start = (wid % W_s) * stride
    flat = streams.reshape(-1)
    win = flat[(sid * n + start)[:, None]
               + jnp.arange(window, dtype=jnp.int32)[None, :]]
    return (win - mu[wid][:, None]) / sd[wid][:, None]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SubseqDeviceIndex:
    """Device-resident subsequence index.

    ``index`` is an ordinary :class:`DeviceIndex` whose rows are the
    z-normalised windows (series materialised by :func:`device_windows`,
    words/residuals from the amortised host build) — every whole-series
    engine consumes it unchanged.  ``streams``/``mu``/``sd`` additionally
    feed the streaming Pallas kernel, which reads stream segments instead
    of the materialised rows (a Pallas-only deployment could drop the
    series column entirely; this repo keeps it as the XLA oracle).
    """

    index: DeviceIndex
    streams: jnp.ndarray       # (S, n_stream) f32 raw streams
    mu: jnp.ndarray            # (W,) f32
    sd: jnp.ndarray            # (W,) f32
    # static:
    window: int = 0
    stride: int = 1

    def tree_flatten(self):
        return ((self.index, self.streams, self.mu, self.sd),
                (self.window, self.stride))

    @classmethod
    def tree_unflatten(cls, aux, children):
        index, streams, mu, sd = children
        return cls(index=index, streams=streams, mu=mu, sd=sd,
                   window=aux[0], stride=aux[1])

    @property
    def n_streams(self) -> int:
        return self.streams.shape[0]

    @property
    def stream_len(self) -> int:
        return self.streams.shape[-1]

    @property
    def windows_per_stream(self) -> int:
        return n_windows_per_stream(self.stream_len, self.window, self.stride)

    @property
    def n_windows(self) -> int:
        return self.index.series.shape[0]

    @property
    def levels(self):
        return self.index.levels

    @property
    def alphabet(self) -> int:
        return self.index.alphabet

    def window_meta(self, wid):
        """Window ids -> (stream index, start position) host arrays.
        Negative ids (empty k-NN slots) map to (−1, −1)."""
        wid = np.asarray(wid)
        W_s = self.windows_per_stream
        sid = np.where(wid >= 0, wid // W_s, -1)
        start = np.where(wid >= 0, (wid % W_s) * self.stride, -1)
        return sid, start


def subseq_device_index(hidx: SubseqHostIndex,
                        dtype=jnp.float32) -> SubseqDeviceIndex:
    """Upload: streams + per-window features; the window rows themselves
    are materialised on device by the shared f32 expression."""
    streams = jnp.asarray(hidx.streams, dtype=dtype)
    mu = jnp.asarray(hidx.mu, dtype=dtype)
    sd = jnp.asarray(hidx.sd, dtype=dtype)
    series = device_windows(streams, hidx.window, hidx.stride, mu, sd)
    stack = tuple(getattr(hidx.config, "stack", DEFAULT_STACK))
    extra = tuple(
        {name: jnp.asarray(arr,
                           jnp.int32 if repr_registry.get(name).kind == "word"
                           else jnp.float32)
         for name, arr in lv.extra.items()}
        for lv in hidx.levels) if repr_registry.extra_names(stack) else ()
    index = DeviceIndex(
        series=series,
        norms_sq=jnp.sum(series * series, axis=-1),
        words=tuple(jnp.asarray(lv.words, dtype=jnp.int32)
                    for lv in hidx.levels),
        residuals=tuple(jnp.asarray(lv.residuals, dtype=dtype)
                        for lv in hidx.levels),
        extra=extra,
        levels=tuple(lv.n_segments for lv in hidx.levels),
        alphabet=hidx.config.alphabet,
        stack=stack,
    )
    return SubseqDeviceIndex(index=index, streams=streams, mu=mu, sd=sd,
                             window=hidx.window, stride=hidx.stride)


def represent_subseq_queries(sidx: SubseqDeviceIndex, queries,
                             normalize: bool = True) -> QueryReprDev:
    """Represent window-length queries at every level of the subseq index.
    A query IS a window, so whole-query z-normalisation is exactly the
    per-window z-normalisation of the database side."""
    q = jnp.asarray(queries, dtype=jnp.float32)
    if q.ndim == 1:
        q = q[None, :]
    if q.shape[-1] != sidx.window:
        raise ValueError(f"subseq queries must be length window="
                         f"{sidx.window}, got {q.shape[-1]}")
    return represent_queries(q, sidx.levels, sidx.alphabet,
                             normalize=normalize,
                             stack=tuple(getattr(sidx.index, "stack",
                                                 DEFAULT_STACK)))


# ---------------------------------------------------------------------------
# Online phase: range and exclusion-zone k-NN, backend-dispatched.
# ---------------------------------------------------------------------------


def _subseq_blocks(sidx: SubseqDeviceIndex, Q: int, k: int = 0,
                   block_q: int | None = None, block_w: int | None = None):
    if block_q is None or block_w is None:
        bq, bw = kernel_ops.choose_subseq_blocks(
            Q, sidx.n_windows, sidx.window, sidx.stride, sidx.levels,
            sidx.alphabet, k=k)
        block_q, block_w = block_q or bq, block_w or bw
    need = kernel_ops.subseq_vmem_bytes(
        int(block_q), int(block_w), sidx.window, sidx.stride, sidx.levels,
        sidx.alphabet, k)
    if need > kernel_ops.vmem_limit():
        raise ValueError(
            f"subseq blocks block_q={block_q}, block_w={block_w} need "
            f"~{need / 2**20:.1f} MiB VMEM "
            f"(> {kernel_ops.vmem_limit() / 2**20:.0f} MiB); shrink them")
    return int(block_q), int(block_w)


def subseq_range_query_pallas(
    sidx: SubseqDeviceIndex, qr: QueryReprDev, epsilon,
    block_q: int | None = None, block_w: int | None = None,
    interpret: bool | None = None,
):
    """Streaming fused range query — bit-identical to the XLA oracle
    ``engine.range_query(sidx.index, ...)`` (tested).  Each grid step
    reads a stream segment, builds its windows in VMEM and runs the full
    cascade + MXU verify while resident (DESIGN.md §8): the database-side
    HBM traffic is ≈ stride/window of what gathering the (W, w) window
    matrix would stream."""
    Q = qr.q.shape[0]
    block_q, block_w = _subseq_blocks(sidx, Q, 0, block_q, block_w)
    ans, d2 = _fused.fused_subseq_range_pallas(
        sidx.streams, sidx.mu, sidx.sd, sidx.index.norms_sq,
        sidx.index.words, sidx.index.residuals,
        qr.q, _engine._query_panels(qr, sidx.alphabet), qr.residuals,
        _engine._eps_qcol(epsilon, Q),
        levels=sidx.levels, alphabet=sidx.alphabet,
        window=sidx.window, stride=sidx.stride,
        block_q=block_q, block_w=block_w,
        interpret=kernel_ops._use_interpret(interpret))
    return ans, d2


def subseq_range_query(
    sidx: SubseqDeviceIndex, qr: QueryReprDev, epsilon,
    options: SearchOptions | None = None, **legacy,
):
    """Every window within ε of each query: ``(answer_mask (Q, W),
    d2 (Q, W))`` with +inf outside the answer set — the whole-series
    ``engine.range_query`` convention, window ids as row positions
    (map through :meth:`SubseqDeviceIndex.window_meta`).  Range answers
    carry no exclusion zone: the classical definition reports every
    qualifying window.  Knobs ride in ``options``
    (:class:`SearchOptions`); the old ``backend=`` kwarg shims through
    with a :class:`DeprecationWarning`; unrecognised kwargs pass to the
    Pallas kernel.  Extended representation stacks demote Pallas to XLA
    (the streaming kernel hard-codes the canonical pair)."""
    options = _engine._coerce_options(options, legacy)
    opts, pallas_kw = resolve_options(options, legacy, "subseq_range_query")
    if _engine.stack_backend(
            sidx.index, _engine.resolve_backend(opts.backend,
                                                streaming=True)) == "pallas":
        return subseq_range_query_pallas(sidx, qr, epsilon, **pallas_kw)
    return _engine.range_query(sidx.index, qr, epsilon)


def _subseq_knn_pallas(sidx: SubseqDeviceIndex, qr: QueryReprDev, k: int,
                       n_iters: int, block_q, block_w, interpret):
    """Streaming twin of ``engine._knn_pallas_impl``: the same seed +
    tighten + merge + certificate schedule, with each database pass a
    streaming subseq kernel emitting block-local top-k partials in
    canonical window ids; candidates re-verify through the shared diff²
    form, so distances are bit-identical to the XLA engine's."""
    block_q, block_w = _subseq_blocks(sidx, qr.q.shape[0], k, block_q,
                                      block_w)
    interpret = kernel_ops._use_interpret(interpret)
    panels = _engine._query_panels(qr, sidx.alphabet)
    k_sel = min(k + _engine._TOPK_GUARD, block_w)

    def topk_pass(eps):
        idxp, _ = _fused.fused_subseq_topk_pallas(
            sidx.streams, sidx.mu, sidx.sd, sidx.index.norms_sq,
            sidx.index.words, sidx.index.residuals,
            qr.q, panels, qr.residuals, _engine._cascade_eps(eps),
            levels=sidx.levels, alphabet=sidx.alphabet,
            window=sidx.window, stride=sidx.stride, k=k_sel,
            block_q=block_q, block_w=block_w, interpret=interpret)
        return idxp, _engine._reverify_rows(sidx.index, qr, idxp)

    eps = _engine._seed_eps(sidx.index, qr, k, None)
    for _ in range(max(0, int(n_iters) - 1)):
        _, d2v = topk_pass(eps)
        eps = jnp.minimum(eps, jnp.sqrt(_engine._kth_smallest(d2v, k)))
    idxp, d2v = topk_pass(eps)
    nn_idx, nn_d2 = _fused.merge_topk_partials(idxp, d2v, k)
    exact = _engine._topk_exact_certificate(d2v, nn_d2, k, k_sel, block_w)
    return nn_idx, nn_d2, exact


def _subseq_knn_fetch(sidx, qr, kf, opts,
                      block_q, block_w, interpret):
    """Shared fetch for the k-NN entrypoints: the whole-series exact
    k-NN path at the provably-sufficient fetch count, with extended
    stacks demoting Pallas to XLA."""
    be = _engine.stack_backend(
        sidx.index, _engine.resolve_knn_backend(opts.backend, kf,
                                                streaming=True))
    if be == "pallas":
        return _subseq_knn_pallas(sidx, qr, kf, opts.n_iters,
                                  block_q, block_w, interpret)
    return _engine.knn_query_auto(
        sidx.index, qr, kf, capacity=opts.capacity, n_iters=opts.n_iters)


def subseq_knn_query(
    sidx: SubseqDeviceIndex, qr: QueryReprDev, k: int,
    excl: int | None = None, options: SearchOptions | None = None,
    block_q: int | None = None, block_w: int | None = None,
    interpret: bool | None = None, **legacy,
):
    """Exact k nearest *non-trivial* windows per query.

    ``excl`` is the exclusion-zone radius in start positions (default
    ``window // 2``, the matrix-profile convention; 0 disables
    suppression): no two reported windows on the same stream start within
    ``excl`` of each other.  The engine fetches the provably sufficient
    :func:`knn_fetch_count` globally-nearest windows through the exact
    whole-series k-NN path (XLA ``knn_query_auto`` or the streaming
    Pallas form — large fetch counts auto-demote per
    ``engine.resolve_knn_backend``) and greedily suppresses in a host
    epilogue, so the answer equals the brute-force greedy over the full
    f64 distance profile (tested).

    Returns ``(sel_idx (Q, k) int64, sel_d2 (Q, k) f64, exact (Q,))`` as
    host arrays — −1 / +inf slots when fewer than k admissible windows
    exist.  ``exact`` is the underlying fetch's exactness certificate:
    the greedy is exact whenever its candidate list is.
    """
    options = _engine._coerce_options(options, legacy)
    opts, rest = resolve_options(options, legacy, "subseq_knn_query")
    if rest:
        raise TypeError(f"subseq_knn_query: unexpected kwargs {sorted(rest)}")
    W = sidx.n_windows
    excl = (sidx.window // 2) if excl is None else int(excl)
    kf = knn_fetch_count(k, excl, sidx.stride, W)
    idx, d2, exact = _subseq_knn_fetch(sidx, qr, kf, opts,
                                       block_q, block_w, interpret)
    W_s = sidx.windows_per_stream
    wid_all = np.arange(W)
    stream_of = wid_all // W_s
    start_of = (wid_all % W_s) * sidx.stride
    sel_idx, sel_d2 = suppress_trivial_matches(
        np.asarray(idx), np.asarray(d2), stream_of, start_of, int(k), excl)
    return sel_idx, sel_d2, np.asarray(exact)


def subseq_range_query_traced(
    sidx: SubseqDeviceIndex, qr: QueryReprDev, epsilon,
    options: SearchOptions | None = None, **legacy,
):
    """:func:`subseq_range_query` + cascade telemetry: ``(answer_mask,
    d2, trace)``.  Windows are rows, so the trace is the whole-series
    ``engine.cascade_trace`` over the windows-as-rows index — its
    counters bit-agree with the host engine over the materialised-window
    host index at the same ε (tests/test_obs.py)."""
    options = _engine._coerce_options(options, legacy)
    opts, pallas_kw = resolve_options(options, legacy,
                                      "subseq_range_query_traced")
    ans, d2 = subseq_range_query(sidx, qr, epsilon, options=opts,
                                 **pallas_kw)
    trace = _engine.cascade_trace(sidx.index, qr, epsilon)
    answers = jnp.sum(ans, axis=-1, dtype=jnp.int32)
    return ans, d2, dataclasses.replace(trace, answers=answers)


def subseq_knn_query_traced(
    sidx: SubseqDeviceIndex, qr: QueryReprDev, k: int,
    excl: int | None = None, options: SearchOptions | None = None,
    block_q: int | None = None, block_w: int | None = None,
    interpret: bool | None = None, **legacy,
):
    """:func:`subseq_knn_query` + cascade telemetry at the FETCH radius:
    ``(sel_idx, sel_d2, exact, trace)``.

    The trace describes the device work actually done: the engine fetches
    the :func:`knn_fetch_count` globally-nearest windows, so the counters
    are taken at that fetch's final verified radius (the suppression
    epilogue is pure host bookkeeping over already-fetched rows and
    touches no further device memory).  ``answers`` reports the
    post-suppression answer count per query.
    """
    options = _engine._coerce_options(options, legacy)
    opts, rest = resolve_options(options, legacy, "subseq_knn_query_traced")
    if rest:
        raise TypeError(
            f"subseq_knn_query_traced: unexpected kwargs {sorted(rest)}")
    W = sidx.n_windows
    excl = (sidx.window // 2) if excl is None else int(excl)
    kf = knn_fetch_count(k, excl, sidx.stride, W)
    idx, d2, exact = _subseq_knn_fetch(sidx, qr, kf, opts,
                                       block_q, block_w, interpret)
    trace = _engine.knn_radius_trace(sidx.index, qr, d2,
                                     min(int(kf), int(d2.shape[-1])))
    W_s = sidx.windows_per_stream
    wid_all = np.arange(W)
    stream_of = wid_all // W_s
    start_of = (wid_all % W_s) * sidx.stride
    sel_idx, sel_d2 = suppress_trivial_matches(
        np.asarray(idx), np.asarray(d2), stream_of, start_of, int(k), excl)
    answers = jnp.asarray(np.isfinite(sel_d2).sum(axis=-1).astype(np.int32))
    return (sel_idx, sel_d2, np.asarray(exact),
            dataclasses.replace(trace, answers=answers))


# ---------------------------------------------------------------------------
# Persistence: a plain index store whose rows are windows (DESIGN.md §8).
# ---------------------------------------------------------------------------

_SUBSEQ_META = "subseq"
_STREAMS_COL = "subseq_streams"
_MU_COL = "subseq_mu"
_SD_COL = "subseq_sd"


def save_subseq_index(hidx: SubseqHostIndex, path, extra_meta=None):
    """Persist as a standard ``fastsax-index`` store whose rows are the
    materialised z windows, with the raw streams and window moments
    riding along as checksummed extra columns.  Because the layout IS the
    whole-series format, the entire index lifecycle — ``index.cli info``
    / ``verify``, mmap warm start, ``DeviceIndex.from_store``,
    ``SearchService.from_store`` — operates on it unchanged;
    :func:`load_subseq_index` additionally restores the stream-aware
    view (streaming kernel, window_meta, exclusion zones)."""
    from ..index import store as _store

    windows = materialize_windows_np(hidx)
    fsi = FastSAXIndex(config=hidx.config, series=windows, levels=hidx.levels)
    meta = {_SUBSEQ_META: {"window": int(hidx.window),
                           "stride": int(hidx.stride),
                           "n_streams": int(hidx.n_streams),
                           "stream_len": int(hidx.stream_len)},
            **(extra_meta or {})}
    return _store.save_index(
        fsi, path, extra_meta=meta,
        extra_arrays={_STREAMS_COL: hidx.streams, _MU_COL: hidx.mu,
                      _SD_COL: hidx.sd})


def load_subseq_index(path, mmap: bool = True,
                      verify: bool = False) -> SubseqHostIndex:
    """Reopen a committed subsequence store (O(ms) mmap, like every other
    store load).  Raises if the store was not written by
    :func:`save_subseq_index` — a plain whole-series store has no stream
    column to answer subsequence queries from."""
    from ..index import store as _store

    fsi = _store.load_index(path, mmap=mmap, verify=verify)
    manifest = _store.read_manifest(path)
    sub = manifest.get("extra", {}).get(_SUBSEQ_META)
    if sub is None:
        raise IOError(f"{path}: not a subsequence store (no "
                      f"{_SUBSEQ_META!r} metadata — see save_subseq_index)")
    streams = np.asarray(_store.read_array(path, _STREAMS_COL, manifest,
                                           mmap=mmap, verify=verify))
    mu = np.asarray(_store.read_array(path, _MU_COL, manifest, mmap=mmap,
                                      verify=verify))
    sd = np.asarray(_store.read_array(path, _SD_COL, manifest, mmap=mmap,
                                      verify=verify))
    return SubseqHostIndex(config=fsi.config, window=int(sub["window"]),
                           stride=int(sub["stride"]), streams=streams,
                           mu=mu, sd=sd, levels=fsi.levels)


# ---------------------------------------------------------------------------
# Quantized screen metadata (DESIGN.md §9): stream the cascade columns as
# int8/bf16 instead of f32.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SubseqQuantMeta:
    """Quantized per-window screen metadata for the streaming kernel.

    Only the *screen* columns (SAX words, linear-fit residuals) are
    quantized — the raw stream samples are resident anyway (the kernel
    z-normalises them in VMEM), so the in-kernel verify stays exact and
    answers remain set-identical to full precision.  Unlike the
    whole-series tier, the dequant params are stored PER WINDOW: the host
    per-128-row scale blocks do not align with the padded per-stream
    ``(S, W_sp)`` window layout the kernel grids over, and the window
    metadata (μ, σ, ‖·‖²) is per-window already, so the expansion
    ``np.repeat(scale, RESID_BLOCK)`` happens once at build time."""

    mode: str
    words: tuple        # per level (W, N_l) int8
    residuals: tuple    # per level (W,) int8 codes / bf16
    scale: tuple        # per level (W,) f32 (int8) / None (bf16)
    zero: tuple         # per level (W,) f32 (int8) / None (bf16)
    err: tuple          # per level (W,) f32 worst-case dequant error


def _expand_per_window(blocked: np.ndarray, W: int) -> jnp.ndarray:
    from ..index import quantized as _quant

    per_row = np.repeat(np.asarray(blocked, np.float32),
                        _quant.RESID_BLOCK)[:W]
    return jnp.asarray(per_row, dtype=jnp.float32)


def quantize_subseq_meta(hidx: SubseqHostIndex,
                         mode: str = "int8") -> SubseqQuantMeta:
    """Quantize the per-window screen columns of a built subseq index.

    Shares the whole-series encoders (``index/quantized.py``) — same
    codes, same realized worst-case error bound, same ``zero + scale ·
    code`` dequant expression — then expands the per-block affine params
    to per-window granularity for the streaming layout."""
    from ..index import quantized as _quant

    _quant.check_mode(mode)
    if mode == "none":
        raise _quant.QuantizationError(
            "quantize_subseq_meta: mode 'none' has no quantized metadata; "
            "use the full-precision subseq_range_query instead")
    words, residuals, scale, zero, err = [], [], [], [], []
    W = hidx.levels[0].words.shape[0]
    for lv in hidx.levels:
        words.append(jnp.asarray(_quant.narrow_words(lv.words),
                                 dtype=jnp.int8))
        codes, sc, zp, e_blk = _quant.quantize_residuals(lv.residuals, mode)
        residuals.append(_engine._upload_codes(codes))
        scale.append(None if sc is None else _expand_per_window(sc, W))
        zero.append(None if zp is None else _expand_per_window(zp, W))
        err.append(_expand_per_window(e_blk, W))
    return SubseqQuantMeta(mode=mode, words=tuple(words),
                           residuals=tuple(residuals), scale=tuple(scale),
                           zero=tuple(zero), err=tuple(err))


def subseq_range_query_quantized(
    sidx: SubseqDeviceIndex, qmeta: SubseqQuantMeta, qr: QueryReprDev,
    epsilon,
    block_q: int | None = None, block_w: int | None = None,
    interpret: bool | None = None,
):
    """Streaming range query over quantized screen metadata — answers are
    set-identical to :func:`subseq_range_query` (tested): the widened C9
    bound (``gap ≤ ε + err``) keeps the quantized cascade a superset
    screen and the in-kernel verify over the streamed raw samples is
    exact, so the ε cut is made on true f32 distances either way."""
    Q = qr.q.shape[0]
    block_q, block_w = _subseq_blocks(sidx, Q, 0, block_q, block_w)
    ans, d2 = _fused.fused_quant_subseq_range_pallas(
        sidx.streams, sidx.mu, sidx.sd, sidx.index.norms_sq,
        qmeta.words, qmeta.residuals, qmeta.scale, qmeta.zero, qmeta.err,
        qr.q, _engine._query_panels(qr, sidx.alphabet), qr.residuals,
        _engine._eps_qcol(epsilon, Q),
        mode=qmeta.mode, levels=sidx.levels, alphabet=sidx.alphabet,
        window=sidx.window, stride=sidx.stride,
        block_q=block_q, block_w=block_w,
        interpret=kernel_ops._use_interpret(interpret))
    return ans, d2
