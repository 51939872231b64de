"""Vectorised JAX engine for FAST_SAX — the TPU-native execution model.

The 2013 paper is CPU-sequential (per-series early exit).  On TPU the same
cascade is executed as a *masked dataflow* over the whole database shard:

  * C9 (eq. 9) is a vector compare over the precomputed residuals,
  * C10 (MINDIST, eq. 10) is evaluated under the C9 survivor mask — lanes
    already excluded contribute no useful work but keep the VPU dense,
  * the final Euclidean verification is computed for survivors via the
    ‖u‖² − 2·u·q + ‖q‖² form (the database norms are precomputed offline, so
    the verify is a single matvec over the shard — MXU work).

The returned answer set is *identical* to ``core/search.py`` (tested); only
the execution model differs.  ``core/dist_search.py`` wraps this per-shard
engine in ``shard_map`` for the multi-device database.

Batched-query variants (``*_batch``) amortise the database pass over Q
queries — the matvec becomes a matmul, which is how the engine reaches MXU
roofline instead of being memory-bound (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
import functools
from concurrent import futures as _futures
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..index import quantized as _quant
from ..index import store as _store
from ..kernels import fused_query as _fused
from ..kernels import ops as kernel_ops
from ..obs.spans import span
from ..obs.trace import QueryTrace, screen_row_bytes, tier_bytes
from . import cost_model as _cost_model
from . import representation as repr_registry
from .fastsax import FastSAXIndex
from .options import SearchOptions, resolve_options
from .paa import paa, znormalize
from .polyfit import linfit_residual
from .representation import DEFAULT_STACK
from .sax import discretize


# Precision of every distance matmul.  A float32 matmul at default
# precision runs as one bf16 pass on the TPU — about one unit of d² off
# for z-normalised rows at n=256 — which would break the exact answer
# sets, the k-NN certificates and the quantized screen's 1e-6 slack.
_F32 = jax.lax.Precision.HIGHEST


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceIndex:
    """Device-resident FAST_SAX index (pytree).  Leaves are jnp arrays.

    ``words[l]``: (B, N_l) int32, ``residuals[l]``: (B,) f32, ``series``:
    (B, n) f32, ``norms_sq``: (B,) f32 precomputed ‖u‖².

    ``extra[l]`` carries the columns of registered representations beyond
    the canonical paper pair (``core/representation.py``), one
    ``{name: array}`` dict per level; ``stack`` is the static tuple of
    registered names the index was built with (the default paper stack
    leaves ``extra`` empty).
    """

    series: jnp.ndarray
    norms_sq: jnp.ndarray
    words: tuple
    residuals: tuple
    extra: tuple = ()
    # static:
    levels: tuple = dataclasses.field(default=())
    alphabet: int = 10
    stack: tuple = DEFAULT_STACK

    def tree_flatten(self):
        children = (self.series, self.norms_sq, self.words, self.residuals,
                    self.extra)
        aux = (self.levels, self.alphabet, self.stack)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        series, norms_sq, words, residuals, extra = children
        return cls(series=series, norms_sq=norms_sq, words=words,
                   residuals=residuals, extra=extra, levels=aux[0],
                   alphabet=aux[1], stack=aux[2])

    @property
    def n(self) -> int:
        return self.series.shape[-1]

    @classmethod
    def from_store(cls, path, dtype=jnp.float32, with_ids: bool = False):
        """Warm-start from a committed ``repro.index`` store directory.

        Accepts either a single-index store (``index.store.save_index``) or
        a ``MutableIndex`` root (loaded through its live view: tombstoned
        rows are dropped at upload, so no valid-mask plumbing is needed
        and even a k-NN with k ≥ the live count can never surface a
        deleted row).  The arrays are mmap-opened and never rebuilt; for
        a plain store (or a compacted single-segment root) no full host
        copy is made beyond the device upload itself, while a root with
        deltas or tombstones concatenates the live rows on the host first
        — run ``compact()`` to restore the zero-copy path (DESIGN.md §5).

        The device engines answer in *row positions*.  For a mutable root
        with any deletions, positions are NOT external ids — pass
        ``with_ids=True`` to get ``(DeviceIndex, ids)`` where ``ids[pos]``
        maps every answer back to its stable external id; loading such a
        store without ``with_ids`` raises rather than let answers be
        misread as ids.
        """
        import pathlib

        import numpy as np

        from ..index import mutable as _mutable
        from ..index import store as _store

        path = pathlib.Path(path)
        if (path / _mutable.CURRENT).exists():
            host, ids = _mutable.MutableIndex.open(path).live_index()
            ids = np.asarray(ids)
            if not with_ids and not np.array_equal(
                    ids, np.arange(ids.size)):
                raise ValueError(
                    f"{path}: external ids differ from row positions "
                    "(rows were deleted) — call "
                    "from_store(..., with_ids=True) and map answers "
                    "through the returned ids array")
        else:
            host = _store.load_index(path, mmap=True)
            ids = np.arange(host.size)
        dev = device_index_from_host(host, dtype=dtype)
        return (dev, ids) if with_ids else dev


def _dev_extra_levels(x, levels, alphabet: int, stack: tuple) -> tuple:
    """Per-level ``{name: column}`` dicts for the stack's extra
    representations of a (B, n) batch (word-kind → int32, gap-kind →
    f32); () for the default paper stack."""
    extras = repr_registry.extra_names(stack)
    if not extras:
        return ()
    out = []
    for N in levels:
        d = {}
        for name in extras:
            rep = repr_registry.get(name)
            col = rep.symbolize_dev(x, int(N), alphabet)
            d[name] = (col.astype(jnp.int32) if rep.kind == "word"
                       else col.astype(jnp.float32))
        out.append(d)
    return tuple(out)


def device_index_from_host(index: FastSAXIndex, dtype=jnp.float32) -> DeviceIndex:
    series = jnp.asarray(index.series, dtype=dtype)
    stack = tuple(index.config.stack)
    return DeviceIndex(
        series=series,
        norms_sq=jnp.sum(series * series, axis=-1),
        words=tuple(jnp.asarray(lv.words, dtype=jnp.int32) for lv in index.levels),
        residuals=tuple(jnp.asarray(lv.residuals, dtype=dtype)
                        for lv in index.levels),
        extra=tuple(
            {name: jnp.asarray(
                lv.extra[name],
                jnp.int32 if repr_registry.get(name).kind == "word"
                else jnp.float32)
             for name in repr_registry.extra_names(stack)}
            for lv in index.levels),
        levels=tuple(lv.n_segments for lv in index.levels),
        alphabet=index.config.alphabet,
        stack=stack,
    )


def build_device_index(
    series: jnp.ndarray,
    levels: Sequence[int],
    alphabet: int,
    normalize: bool = True,
    stack: tuple = DEFAULT_STACK,
) -> DeviceIndex:
    """Offline phase, fully on device (jit-able) — used by the distributed
    builder in ``dist_search.py`` where each shard indexes its own slice."""
    if normalize:
        series = znormalize(series)
    series = series.astype(jnp.float32)
    stack = repr_registry.validate_stack(stack)
    words, residuals = [], []
    for N in levels:
        words.append(discretize(paa(series, N), alphabet))
        residuals.append(linfit_residual(series, N).astype(jnp.float32))
    return DeviceIndex(
        series=series,
        norms_sq=jnp.sum(series * series, axis=-1),
        words=tuple(words),
        residuals=tuple(residuals),
        extra=_dev_extra_levels(series, levels, alphabet, stack),
        levels=tuple(int(N) for N in levels),
        alphabet=alphabet,
        stack=stack,
    )


@dataclasses.dataclass(frozen=True)
class QueryReprDev:
    """Device query representation (pytree via dataclass fields order).

    ``extra`` mirrors ``DeviceIndex.extra``: per level, ``{name: column}``
    for the stack's registered extras (empty for the paper stack)."""

    q: jnp.ndarray
    words: tuple
    residuals: tuple
    extra: tuple = ()


jax.tree_util.register_pytree_node(
    QueryReprDev,
    lambda r: ((r.q, r.words, r.residuals, r.extra), None),
    lambda _, c: QueryReprDev(*c),
)


def represent_queries(
    q: jnp.ndarray, levels: Sequence[int], alphabet: int,
    normalize: bool = True, stack: tuple = DEFAULT_STACK,
) -> QueryReprDev:
    """Represent a batch of queries (Q, n) at every level (jit-able).

    ``stack`` must match the index's stack (static tuple of registered
    representation names); the default paper stack adds no extras."""
    if normalize:
        q = znormalize(q)
    q = q.astype(jnp.float32)
    words = tuple(discretize(paa(q, N), alphabet) for N in levels)
    residuals = tuple(linfit_residual(q, N).astype(jnp.float32) for N in levels)
    return QueryReprDev(q=q, words=words, residuals=residuals,
                        extra=_dev_extra_levels(q, levels, alphabet, stack))


def _mindist_sq_tab(alphabet: int) -> jnp.ndarray:
    # Shared per-alphabet cache (kernels/ops.py): one host build and one
    # device constant per alphabet, reused by the Pallas panel construction.
    return kernel_ops.mindist_table_cached(alphabet)


def _eps_qcol(epsilon, Q: int) -> jnp.ndarray:
    """Normalise epsilon (scalar or per-query (Q,)) to a (Q, 1) column."""
    eps = jnp.asarray(epsilon, dtype=jnp.float32)
    if eps.ndim == 0:
        eps = jnp.broadcast_to(eps, (Q,))
    return eps.reshape(Q, 1)


def _extra_reps(index) -> tuple:
    """The index stack's extra representations, split (gap, word)."""
    reps = [repr_registry.get(name)
            for name in repr_registry.extra_names(
                getattr(index, "stack", DEFAULT_STACK))]
    return ([r for r in reps if r.kind == "gap"],
            [r for r in reps if r.kind == "word"])


def stack_backend(index, backend: str) -> str:
    """Demote Pallas to XLA for extended stacks: the fused megakernels
    hard-code the canonical two-representation cascade (words+residuals in
    VMEM panels), so an index carrying registered extras runs the XLA
    engine — answers are identical either way, only the execution model
    moves.  A no-op for the default paper stack."""
    if backend == "pallas" and \
            tuple(getattr(index, "stack", DEFAULT_STACK)) != DEFAULT_STACK:
        return "xla"
    return backend


def cascade_mask(
    index: DeviceIndex, qr: QueryReprDev, epsilon: jnp.ndarray
) -> jnp.ndarray:
    """Masked exclusion cascade for a batch of queries.

    qr leaves carry a leading query dim Q.  Returns alive mask (Q, B): True =
    candidate (must be Euclidean-verified).  Pure dataflow — no early exit;
    level count is static so the loop unrolls into one fused HLO region.
    """
    n = index.n
    Q = qr.q.shape[0]
    # eps: scalar or per-query (Q,) — broadcast to (Q, 1) against (Q, B).
    eps = _eps_qcol(epsilon, Q)
    eps2 = eps * eps
    alive = jnp.ones((Q, index.series.shape[0]), dtype=bool)
    tab = _mindist_sq_tab(index.alphabet)
    gap_extras, word_extras = _extra_reps(index)
    for li, N in enumerate(index.levels):
        # C9: |d(u,ū) − d(q,q̄)| > ε  → kill.
        gap = jnp.abs(index.residuals[li][None, :] - qr.residuals[li][:, None])
        alive &= gap <= eps
        for rep in gap_extras:        # registered gap-kind extras after C9
            alive &= rep.dev_gap(index.extra[li][rep.name],
                                 qr.extra[li][rep.name]) <= eps
        # C10 under mask: MINDIST²(q̃,ũ) > ε² → kill.  (lookup-table gather;
        # the Pallas kernel variant uses a per-query (α, N) slice, see
        # kernels/fused_prune.py.)
        cell = tab[index.words[li][None, :, :], qr.words[li][:, None, :]]
        md_sq = (n / N) * jnp.sum(cell * cell, axis=-1)
        alive &= md_sq <= eps2
        for rep in word_extras:       # registered word-kind extras after C10
            alive &= rep.dev_bound_sq(index.extra[li][rep.name],
                                      qr.extra[li][rep.name],
                                      n=n, N=N, tab=tab) <= eps2
    return alive


def verify_distances(
    index: DeviceIndex, qr: QueryReprDev
) -> jnp.ndarray:
    """Squared Euclidean distances (Q, B) via the matmul form (MXU work),
    at full f32 precision (see :data:`_F32`)."""
    qn = jnp.sum(qr.q * qr.q, axis=-1)
    cross = jnp.dot(qr.q, index.series.T, precision=_F32)  # (Q, B)
    d2 = qn[:, None] - 2.0 * cross + index.norms_sq[None, :]
    return jnp.maximum(d2, 0.0)


@functools.partial(jax.jit, static_argnames=())
def range_query(
    index: DeviceIndex, qr: QueryReprDev, epsilon: jnp.ndarray
):
    """Full FAST_SAX range query for a batch of queries.

    Returns (answer_mask (Q, B), d2 (Q, B)): ``answer_mask`` is the exact
    answer set; d2 is only meaningful where the cascade survived (excluded
    lanes still compute in the verify matmul — dense > sparse on TPU until
    survivor fraction is tiny; see two-phase variant below).
    """
    Q = qr.q.shape[0]
    eps = _eps_qcol(epsilon, Q)
    alive = cascade_mask(index, qr, eps)
    d2 = verify_distances(index, qr)
    answers = alive & (d2 <= eps * eps)
    return answers, jnp.where(answers, d2, jnp.inf)


def compact_verify(index: DeviceIndex, qr: QueryReprDev, alive: jnp.ndarray,
                   capacity: int, order_key: jnp.ndarray | None = None):
    """Compact alive lanes to ``capacity`` slots and verify only those rows.

    The shared compaction path of the two-phase range query and the k-NN
    engine.  By default slots are filled prefer-low-index (so slot order —
    and therefore every downstream tie-break — follows ascending database
    index); passing ``order_key`` (Q, B), higher = more important, fills
    them by key instead (the k-NN tightening passes key on the negated
    residual gap so the most promising survivors are verified first).
    Returns (idx (Q, C), valid (Q, C), d2 (Q, C)) with ``d2 = +inf`` on
    invalid slots.
    """
    B = alive.shape[-1]
    if order_key is None:
        keys = jnp.where(alive,
                         B - jnp.arange(B, dtype=jnp.int32)[None, :], 0)
        top, idx = jax.lax.top_k(keys, capacity)              # (Q, C)
        valid = top > 0
    else:
        keys = jnp.where(alive, order_key, -jnp.inf)
        top, idx = jax.lax.top_k(keys, capacity)              # (Q, C)
        valid = top > -jnp.inf
    rows = index.series[idx]                                  # (Q, C, n)
    diff = rows - qr.q[:, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    return idx, valid, jnp.where(valid, d2, jnp.inf)


@functools.partial(jax.jit, static_argnames=("capacity",))
def range_query_compact(
    index: DeviceIndex, qr: QueryReprDev, epsilon: jnp.ndarray, capacity: int
):
    """Two-phase variant: cascade → compact survivors → verify only those.

    Survivors are compacted to a fixed ``capacity`` with top-k on the alive
    mask (ties broken by index), then only ``capacity`` rows of the database
    are gathered for the Euclidean verify.  Sound as long as the true
    survivor count ≤ capacity; the returned ``overflow`` flag reports
    violations so callers can fall back to the dense verify (see
    :func:`range_query_auto`).
    """
    Q = qr.q.shape[0]
    eps = _eps_qcol(epsilon, Q)
    alive = cascade_mask(index, qr, eps)                      # (Q, B)
    B = alive.shape[-1]
    capacity = min(int(capacity), B)
    idx, valid, d2 = compact_verify(index, qr, alive, capacity)
    answers = valid & (d2 <= eps * eps)
    overflow = alive.sum(axis=-1) > capacity
    return idx, answers, jnp.where(answers, d2, jnp.inf), overflow


def range_query_auto(
    index: DeviceIndex, qr: QueryReprDev, epsilon, capacity: int
):
    """Compact-verify range query with the documented dense fallback.

    Runs :func:`range_query_compact`; any query whose survivors overflowed
    ``capacity`` is re-answered by the dense :func:`range_query` (host-side
    branch — overflow is the rare path).  Returns (idx, answers, d2) in the
    compact layout when no query overflowed, else the dense (mask, d2)
    layout for all queries; the second element of the tuple always carries
    the exact answer set.
    """
    idx, answers, d2, overflow = range_query_compact(
        index, qr, epsilon, capacity)
    if not bool(jax.device_get(overflow).any()):
        return idx, answers, d2
    mask, dense_d2 = range_query(index, qr, epsilon)
    B = mask.shape[-1]
    all_idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None, :],
                               mask.shape)
    return all_idx, mask, dense_d2


# ---------------------------------------------------------------------------
# Exact k-NN: iteratively tightened per-query radius over the same cascade.
# ---------------------------------------------------------------------------

_KNN_SEED_SAMPLE = 64     # minimum strided-sample size for the seed radius
# f32 slack on the cascade radius (relative + absolute): the index residuals
# are f64-built then cast while query residuals are computed in f32, so the
# lower-bound lemma only holds up to rounding noise.  Slack only ever *adds*
# survivors, so exactness is unaffected; the absolute term matters when the
# radius tightens to ~0 (exact-duplicate queries).
_KNN_EPS_SLACK = 1e-4
_KNN_EPS_ABS = 1e-3
# Stand-in seed radius for a sample with no information.  When the strided
# sample holds fewer than k valid rows its k-th distance is +inf; an
# infinite radius is still sound for the XLA path (the alive mask is ANDed
# with valid_mask explicitly) but would defeat the fused kernels'
# sentinel-residual exclusion: C9 compares the PAD_RESIDUAL gap (~1e30)
# against ε, and "1e30 ≤ inf" re-admits every masked/padded row.  The
# substitute must upper-bound ANY representable distance — f32 series give
# d² ≤ ~3.4e38 ⇒ d ≤ ~2e19 — while staying well below the sentinel gap, so
# it can never exclude a true neighbour yet always keeps the in-kernel kill
# authoritative.  1e28 leaves two orders of margin on the sentinel side
# (its slacked square overflows f32 to +inf, which only disables the C10
# exclusion — a performance matter, never a correctness one).  Finite seed
# radii pass through untouched: a verified sampled distance is sound at
# any magnitude and, being ≤ ~2e19, can never reach the sentinel gap.
_SEED_EPS_MAX = 1e28


def _slacked(eps: jnp.ndarray) -> jnp.ndarray:
    return eps * (1.0 + _KNN_EPS_SLACK) + _KNN_EPS_ABS


def _kth_smallest(d2: jnp.ndarray, k: int) -> jnp.ndarray:
    """Per-row k-th smallest of (Q, M) values as a (Q, 1) column."""
    return -jax.lax.top_k(-d2, k)[0][:, -1:]


def _kth_smallest_rounds(d2: jnp.ndarray, k: int, block: int = 64) -> jnp.ndarray:
    """:func:`_kth_smallest`, restructured for use INSIDE large fused
    computations.

    ``lax.top_k`` embedded in a big jitted graph lowers (CPU backend)
    to a per-row sort whose runtime degrades by an order of magnitude
    when the computation executes on a serving thread alongside waiter
    threads — even over narrow rows, and even though the same op
    standalone is fast.  So: no ``top_k``, no sort.  Two exact stages
    built from min/argmin reductions only.

    1. block-filter — split the row into ``block``-wide blocks (one
       full-width min-reduce) and keep the k blocks with the smallest
       minima, selected by k argmin-and-mask rounds over the (Q, nb)
       block minima.  Every one of the k smallest values lives in a
       kept block: at most k-1 blocks have a minimum strictly below
       the k-th value and all are kept, and each remaining kept block
       contributes a value no larger than the k-th — so the k-th order
       statistic of the gathered k·block candidates equals the row's,
       tie multiplicities included (adversarial grids in
       tests/test_obs.py).
    2. :func:`_kth_minrounds` over the (k·block)-wide candidates.

    Same ``+inf`` result for rows with fewer than k finite entries.
    Used by the traced twins only; the untraced engines keep
    :func:`_kth_smallest`.
    """
    Q, B = d2.shape
    nb = -(-B // block)
    if nb <= k:
        return _kth_minrounds(d2, k)
    if nb * block != B:
        d2 = jnp.pad(d2, ((0, 0), (0, nb * block - B)),
                     constant_values=jnp.inf)
    blocks = d2.reshape(Q, nb, block)
    bmins = jnp.min(blocks, axis=-1)
    cur, cols = bmins, jnp.arange(nb)
    sel = []
    for _ in range(int(k)):
        j = jnp.argmin(cur, axis=-1)
        sel.append(j)
        cur = jnp.where(cols[None, :] == j[:, None], jnp.inf, cur)
    bi = jnp.stack(sel, axis=-1)
    cand = jnp.take_along_axis(blocks, bi[:, :, None], axis=1)
    return _kth_minrounds(cand.reshape(Q, -1), k)


def _kth_minrounds(d2: jnp.ndarray, k: int) -> jnp.ndarray:
    """Second stage of :func:`_kth_smallest_rounds` (and the whole
    computation when the row is too narrow to block): k min-and-mask
    rounds — each round takes the row minimum, counts its ties, masks
    them to ``+inf`` and records the minimum on the round where the
    cumulative tie count crosses k, so duplicates carry their
    multiplicity."""
    cur = d2
    total = jnp.zeros((d2.shape[0], 1), jnp.int32)
    ans = jnp.full((d2.shape[0], 1), jnp.inf, d2.dtype)
    for _ in range(int(k)):
        m = jnp.min(cur, axis=-1, keepdims=True)
        tie = cur == m
        c = jnp.sum(tie, axis=-1, keepdims=True, dtype=jnp.int32)
        ans = jnp.where((total < k) & (total + c >= k), m, ans)
        total = total + c
        cur = jnp.where(tie, jnp.inf, cur)
    return ans


def _seed_eps(index: "DeviceIndex", qr: "QueryReprDev", k: int, valid_mask):
    """k-NN seed radius from a strided verified row sample (≥ max(k, 64)
    rows): the k-th sampled distance upper-bounds the true k-th distance,
    so it is a sound starting radius.  Shared by :func:`knn_query`,
    :func:`mixed_query` and the fused Pallas variants — one definition so
    the backends cannot drift on the quantity their parity rests on.

    A non-finite radius (a sample with fewer than k valid rows yields
    +inf) is replaced by ``_SEED_EPS_MAX``: a huge-but-finite radius
    (unlike an infinite one) still lets the fused kernels' C9 sentinel
    residual kill masked/padded rows in-kernel.  Finite radii are never
    touched — a verified sampled distance is sound at any magnitude."""
    B = index.series.shape[0]
    S = min(B, max(k, _KNN_SEED_SAMPLE))
    sample = (jnp.arange(S, dtype=jnp.int32) * B) // S   # distinct: S ≤ B
    rows = index.series[sample]                          # (S, n)
    diff = rows[None, :, :] - qr.q[:, None, :]
    d2s = jnp.sum(diff * diff, axis=-1)                  # (Q, S)
    if valid_mask is not None:
        d2s = jnp.where(valid_mask[sample][None, :], d2s, jnp.inf)
    eps = jnp.sqrt(jnp.maximum(_kth_smallest(d2s, k), 0.0))    # (Q, 1)
    return jnp.where(jnp.isfinite(eps), eps, _SEED_EPS_MAX)


def _cascade_eps(eps: jnp.ndarray, knn_col=None) -> jnp.ndarray:
    """Per-row cascade radius: k-NN rows carry the f32 slack (their bound
    tightens towards the true distance), range rows use the caller's ε
    verbatim so the survivor set — and the overflow flag — match the
    dedicated range path.  ``knn_col=None`` means every row is k-NN (the
    dedicated engines)."""
    if knn_col is None:
        return _slacked(eps)
    return jnp.where(knn_col, _slacked(eps), eps)


def _tighten_eps(
    index: "DeviceIndex", qr: "QueryReprDev", eps: jnp.ndarray, k: int,
    capacity: int, n_iters: int, valid_mask, knn_col=None,
) -> jnp.ndarray:
    """The shared promise-ordered k-NN tightening passes (DESIGN.md §1.2).

    Promise = small level-0 residual gap (the same O(1) lower bound the
    host engine seeds from).  Ordering the limited verify slots by promise
    makes ε collapse to ≈ the true k-th distance in one pass even when the
    survivor set overflows capacity; ε stays a verified upper bound
    throughout, so every pass is sound.  One definition serves both the
    dedicated :func:`knn_query` and the mixed :func:`mixed_query` paths
    (``knn_col`` selects which rows tighten — range rows keep the caller's
    ε), so the two cannot drift.
    """
    gap0 = jnp.abs(index.residuals[0][None, :] - qr.residuals[0][:, None])
    for _ in range(max(0, int(n_iters) - 1)):
        alive = cascade_mask(index, qr, _cascade_eps(eps, knn_col))
        if valid_mask is not None:
            alive &= valid_mask[None, :]
        _, _, d2 = compact_verify(index, qr, alive, capacity,
                                  order_key=-gap0)
        tight = jnp.minimum(eps, jnp.sqrt(_kth_smallest(d2, k)))
        eps = tight if knn_col is None else jnp.where(knn_col, tight, eps)
    return eps


@functools.partial(jax.jit, static_argnames=("k", "capacity", "n_iters"))
def knn_query(
    index: DeviceIndex,
    qr: QueryReprDev,
    k: int,
    capacity: int | None = None,
    n_iters: int = 2,
    valid_mask: jnp.ndarray | None = None,
):
    """Batched exact k-NN over the masked cascade (jit-able, fixed shape).

    The best-so-far recursion of ``core/search.py`` becomes an iteratively
    tightened per-query ε *column*:

      1. **seed** — verify a strided row sample (≥ max(k, 64) rows); the
         k-th sampled distance upper-bounds the true k-th distance, so it
         is a sound starting radius;
      2. repeat ``n_iters`` times: run :func:`cascade_mask` under the
         current ε column, compact survivors through the shared
         :func:`compact_verify` path, and shrink ε to the k-th smallest
         *verified* distance (ε is monotonically non-increasing and always
         a verified upper bound — no true neighbour can be excluded);
      3. the final top-k over the last compacted verify is the answer.

    Returns ``(nn_idx (Q, k), nn_d2 (Q, k), exact (Q,))``.  ``exact`` is
    the exactness certificate: True iff the final survivor set fit inside
    ``capacity`` slots, in which case the answer provably equals brute
    force (ties broken by ascending database index, matching
    ``np.lexsort``).  On False, re-run with a larger capacity or fall back
    to dense :func:`verify_distances` + ``top_k`` — soundness is never
    silently lost.

    ``valid_mask`` (B,) excludes rows (e.g. the padded rows of a sharded
    database) from both the seed sample and the answer set.
    """
    Q, B = qr.q.shape[0], index.series.shape[0]
    k = min(int(k), B)
    capacity = min(B, max(4 * k, 64) if capacity is None else int(capacity))
    capacity = max(capacity, k)

    # --- seed radius from a strided verified sample ------------------------
    eps = _seed_eps(index, qr, k, valid_mask)            # (Q, 1)

    # --- tightening passes: verify the most *promising* survivors ----------
    eps = _tighten_eps(index, qr, eps, k, capacity, n_iters, valid_mask)

    # --- final pass: low-index compaction for deterministic tie-breaks -----
    alive = cascade_mask(index, qr, _cascade_eps(eps))
    if valid_mask is not None:
        alive &= valid_mask[None, :]
    idx, valid, d2 = compact_verify(index, qr, alive, capacity)
    overflow = alive.sum(axis=-1) > capacity

    neg, pos = jax.lax.top_k(-d2, k)                     # ascending d2
    nn_d2 = -neg
    nn_idx = jnp.take_along_axis(idx, pos, axis=-1)
    return nn_idx, nn_d2, ~overflow


# ---------------------------------------------------------------------------
# Mixed-workload dispatch: one device pass serving k-NN AND range queries.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "capacity", "n_iters"))
def mixed_query(
    index: DeviceIndex,
    qr: QueryReprDev,
    epsilon: jnp.ndarray,
    is_knn: jnp.ndarray,
    k: int,
    capacity: int,
    n_iters: int = 2,
    valid_mask: jnp.ndarray | None = None,
):
    """One jitted pass answering a *mixed* batch of range and k-NN queries.

    The serving layer (``repro.serve``) coalesces concurrent requests of
    both kinds into a single device batch; this is its bucket-shape-stable
    entry point — the compiled shape depends only on ``(Q, k, capacity,
    n_iters)``, never on the per-request mix, so one compilation serves
    every batch in the bucket (DESIGN.md §6).

    Per query row, ``is_knn[i]`` selects the semantics:

      * **range** (False): ``epsilon[i]`` is the caller's radius — the row
        runs exactly the :func:`range_query_compact` dataflow;
      * **k-NN** (True): ``epsilon[i]`` is ignored; the row seeds its own
        radius from the strided sample and tightens it per pass, exactly
        the :func:`knn_query` dataflow.

    The two paths differ only in their per-row ε column — the cascade,
    promise-ordered tightening and final low-index compaction are shared —
    so every row's answer is bit-identical to the corresponding dedicated
    engine call at equal ``(k, capacity, n_iters)`` (tested in
    ``tests/test_serve.py``).

    Returns ``(idx (Q, C), answer (Q, C), d2 (Q, C), overflow (Q,))``:
    for range rows ``answer`` marks verified in-range slots; for k-NN rows
    it marks valid candidate slots — take the row's top-k via
    :func:`mixed_topk`.  ``overflow`` is the per-row soundness signal
    (range: survivors truncated; k-NN: exactness certificate is its
    negation); :func:`mixed_query_auto` escalates capacity on it.
    """
    Q, B = qr.q.shape[0], index.series.shape[0]
    k = min(int(k), B)
    capacity = max(min(int(capacity), B), k)
    knn_col = is_knn.reshape(Q, 1)
    eps_req = _eps_qcol(epsilon, Q)

    # Seed radius for the k-NN rows (range rows keep the caller's ε); the
    # shared _tighten_eps/_cascade_eps helpers then treat the two row
    # kinds exactly like the dedicated engines do.
    eps = jnp.where(knn_col, _seed_eps(index, qr, k, valid_mask), eps_req)
    eps = _tighten_eps(index, qr, eps, k, capacity, n_iters, valid_mask,
                       knn_col=knn_col)

    alive = cascade_mask(index, qr, _cascade_eps(eps, knn_col))
    if valid_mask is not None:
        alive &= valid_mask[None, :]
    idx, valid, d2 = compact_verify(index, qr, alive, capacity)
    overflow = alive.sum(axis=-1) > capacity
    answer = jnp.where(knn_col, valid, valid & (d2 <= eps_req * eps_req))
    return idx, answer, jnp.where(answer, d2, jnp.inf), overflow


@functools.partial(jax.jit, static_argnames=("k",))
def mixed_query_dense(
    index: DeviceIndex,
    qr: QueryReprDev,
    epsilon: jnp.ndarray,
    is_knn: jnp.ndarray,
    k: int,
    valid_mask: jnp.ndarray | None = None,
):
    """Dense-verify variant of :func:`mixed_query` — no candidate buffer.

    Range rows follow the :func:`range_query` dataflow (cascade mask +
    matmul verify); k-NN rows are answered by brute force over the dense
    distances (``top_k`` ties resolve to the lowest index, the engine-wide
    tie-break).  Cannot overflow, so the answer is unconditionally exact.

    This is the documented fallback of the compaction engines, promoted to
    a serving path: when a workload's survivor sets are a large fraction
    of B, gather-based compaction costs more than the dense matmul it was
    supposed to avoid — the serving backend switches here the moment the
    learned capacity crosses ``dense_fallback_frac`` of B (DESIGN.md §6).
    Same return convention as :func:`mixed_query` with C = B; ``k`` is
    accepted (and static) only so the jit cache keys match the caller's
    bucket ladder.
    """
    del k
    Q, B = qr.q.shape[0], index.series.shape[0]
    knn_col = is_knn.reshape(Q, 1)
    eps = _eps_qcol(epsilon, Q)
    alive = cascade_mask(index, qr, eps)
    d2 = verify_distances(index, qr)
    valid = jnp.ones((Q, B), dtype=bool)
    if valid_mask is not None:
        alive &= valid_mask[None, :]
        valid &= valid_mask[None, :]
    in_range = alive & (d2 <= eps * eps)
    answer = jnp.where(knn_col, valid, in_range)
    idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None, :], (Q, B))
    overflow = jnp.zeros((Q,), dtype=bool)
    return idx, answer, jnp.where(answer, d2, jnp.inf), overflow


def mixed_topk(idx: jnp.ndarray, d2: jnp.ndarray, k: int):
    """Extract per-row ascending top-k from a compacted candidate buffer.

    The buffer comes from low-index compaction, so equal distances resolve
    to the lowest database index — the same deterministic tie-break as
    :func:`knn_query`.  A request served from a bucket with ``k_bucket >
    k`` reads its first k columns: a larger top-k is a sorted superset.
    """
    neg, pos = jax.lax.top_k(-d2, min(int(k), d2.shape[-1]))
    return jnp.take_along_axis(idx, pos, axis=-1), -neg


def mixed_query_auto(
    index: DeviceIndex,
    qr: QueryReprDev,
    epsilon,
    is_knn,
    k: int,
    capacity: int | None = None,
    n_iters: int = 2,
    valid_mask: jnp.ndarray | None = None,
    max_doublings: int = 8,
):
    """Certificate-driven mixed dispatch: escalate capacity until sound.

    The same escalation contract as :func:`knn_query_auto` /
    :func:`range_query_auto`, reused for the mixed batch: while any row
    overflowed its candidate buffer, re-run with 4× the capacity (capped
    at B, where compaction can never overflow, so termination with zero
    overflow is guaranteed).  Each distinct capacity compiles once and is
    cached by jit — the serving bucket ladder (DESIGN.md §6) keeps the set
    of capacities small.
    """
    B = index.series.shape[0]
    k_eff = min(int(k), B)
    cap = min(B, max(4 * k_eff, 64) if capacity is None else int(capacity))
    cap = max(cap, k_eff)
    is_knn = jnp.asarray(is_knn, dtype=bool)
    for _ in range(max_doublings + 1):
        idx, answer, d2, overflow = mixed_query(
            index, qr, epsilon, is_knn, k_eff, capacity=cap,
            n_iters=n_iters, valid_mask=valid_mask)
        if cap >= B or not bool(jax.device_get(overflow).any()):
            return idx, answer, d2, overflow
        cap = min(B, cap * 4)
    return idx, answer, d2, overflow


# ---------------------------------------------------------------------------
# Backend dispatch: the fused Pallas megakernel vs the XLA oracle.
#
# ``backend="auto"`` selects compiled Pallas on TPU and the XLA engine
# everywhere else; ``"pallas"`` off-TPU runs the kernels in interpret mode
# (slow, but bit-identical — the parity-test and CI path).  Block shapes
# come from the VMEM budget in kernels/ops.py ranked by the latency-model
# hook in core/cost_model.py (DESIGN.md §7).
# ---------------------------------------------------------------------------


def resolve_backend(backend: str = "auto", streaming: bool = False) -> str:
    """Map auto|xla|pallas to the concrete engine for this process.

    ``auto`` is compiled Pallas on a TPU and XLA elsewhere, with one
    exception: ``streaming=True`` (the streaming subsequence kernels of
    ``kernels/fused_query.py``, which build their windows in VMEM) is XLA
    on every platform.  Mosaic cannot lower that window build — strided
    lane slices of a segment stacked into columns — so those kernels run
    only in interpret mode, when asked for by name (ROADMAP, Reach 4)."""
    if backend not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"backend must be 'auto', 'xla' or 'pallas', got {backend!r}")
    if backend == "auto":
        return ("pallas" if jax.default_backend() == "tpu" and not streaming
                else "xla")
    return backend


def resolve_knn_backend(backend: str, k: int, streaming: bool = False) -> str:
    """:func:`resolve_backend` plus the top-k unroll demotion (DESIGN.md
    §7): the fused k-NN kernel unrolls ``k + _TOPK_GUARD`` min/argmin
    sweeps per database block, so its code size and compile time grow
    linearly in k while the XLA dense ``lax.top_k`` is one op at any k.
    When the unroll exceeds the cost-model-advised threshold
    (``cost_model.PALLAS_TOPK_UNROLL_MAX``, ~100) a Pallas selection is
    demoted to the XLA engine instead of compiling an ever-longer kernel.
    Demotion never changes answers — both backends are exact — and
    :func:`knn_query_pallas` stays directly callable at any k for
    callers that want the kernel regardless.  ``streaming`` is
    :func:`resolve_backend`'s."""
    be = resolve_backend(backend, streaming)
    if be == "pallas" and _cost_model.pallas_topk_demote_advised(
            int(k) + _TOPK_GUARD):
        return "xla"
    return be


def _fused_blocks(index, Q: int, k: int = 0,
                  block_q: int | None = None, block_b: int | None = None,
                  mode: str = "none"):
    """(block_q, block_b) for a fused kernel over ``index`` (a
    ``DeviceIndex``, or a ``QuantizedDeviceIndex`` with its ``mode``)."""
    n, B = index.n, index.series.shape[0]
    if block_q is None or block_b is None:
        bq, bb = kernel_ops.choose_fused_blocks(
            Q, B, n, index.levels, index.alphabet, k=k, mode=mode)
        block_q, block_b = block_q or bq, block_b or bb
    # Caller-supplied dimensions (either or both) bypass the chooser's
    # feasibility scan — re-check the final shape against the VMEM budget
    # so a mixed override cannot compile an overflowing kernel.
    need = kernel_ops.fused_vmem_bytes(
        int(block_q), int(block_b), n, index.levels, index.alphabet, k,
        mode)
    if need > kernel_ops.vmem_limit():
        raise ValueError(
            f"fused blocks block_q={block_q}, block_b={block_b} need "
            f"~{need / 2**20:.1f} MiB VMEM "
            f"(> {kernel_ops.vmem_limit() / 2**20:.0f} MiB); shrink them")
    return int(block_q), int(block_b)


def _masked_residuals(index: DeviceIndex, valid_mask):
    """Fold an optional row-validity mask into the level-0 residuals: the
    fused kernel then kills invalid rows through the same C9 sentinel
    mechanism the sharded engine uses for padding."""
    if valid_mask is None:
        return index.residuals
    res0 = jnp.where(valid_mask, index.residuals[0], _fused.PAD_RESIDUAL)
    return (res0,) + tuple(index.residuals[1:])


def _query_panels(qr: QueryReprDev, alphabet: int) -> tuple:
    return tuple(kernel_ops.query_panels(w, alphabet) for w in qr.words)


def _reverify_rows(index: DeviceIndex, qr: QueryReprDev, idx: jnp.ndarray,
                   valid_mask: jnp.ndarray | None = None):
    """Exact diff²-form distances for candidate rows.

    The same expression :func:`compact_verify` evaluates, so the k-NN
    distances the fused path reports are bit-identical to the XLA engine's
    for the same candidate indices.

    Candidates outside ``[0, B)`` re-verify to +inf: −1 marks an empty
    slot, and an index ≥ B is a padded kernel row — JAX's gather would
    silently clamp it to row B−1 and hand back a finite bogus distance
    that could survive the merge.  Rows excluded by ``valid_mask`` are
    +inf for the same reason: they must neither tighten a k-NN radius nor
    enter an answer.
    """
    B = index.series.shape[0]
    safe = jnp.clip(idx, 0, B - 1)
    rows = index.series[safe]                         # (Q, C, n)
    diff = rows - qr.q[:, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    ok = (idx >= 0) & (idx < B)
    if valid_mask is not None:
        ok &= valid_mask[safe]
    return jnp.where(ok, d2, jnp.inf)


def _mask_dense(ans: jnp.ndarray, d2: jnp.ndarray, valid_mask):
    """Radius-independent exclusion of masked rows from a dense (Q, B)
    answer/distance pair — the shared epilogue of every fused dense form.

    The sentinel residual already kills masked rows in-kernel at any sane
    ε; masking the dense outputs too makes their exclusion independent of
    the caller's radius magnitude (a ≥ ~1e30 ε would otherwise defeat the
    in-kernel C9 sentinel compare)."""
    if valid_mask is None:
        return ans, d2
    ans = ans & valid_mask[None, :]
    return ans, jnp.where(ans, d2, jnp.inf)


@functools.partial(jax.jit, static_argnames=("block_q", "block_b",
                                             "interpret"))
def _range_pallas_impl(index, qr, eps, valid_mask, block_q, block_b,
                       interpret):
    ans, d2 = _fused.fused_range_pallas(
        index.series, index.norms_sq, index.words,
        _masked_residuals(index, valid_mask),
        qr.q, _query_panels(qr, index.alphabet), qr.residuals, eps,
        levels=index.levels, alphabet=index.alphabet, n=index.n,
        block_q=block_q, block_b=block_b, interpret=interpret)
    return _mask_dense(ans, d2, valid_mask)


def range_query_pallas(
    index: DeviceIndex, qr: QueryReprDev, epsilon,
    valid_mask: jnp.ndarray | None = None,
    block_q: int | None = None, block_b: int | None = None,
    interpret: bool | None = None,
):
    """One-pass fused range query — bit-identical to :func:`range_query`.

    Same return convention: ``(answer_mask (Q, B), d2 (Q, B))`` with +inf
    outside the answer set.  One ``pallas_call``, one HBM read of every
    database block, zero per-level mask round-trips.
    """
    Q = qr.q.shape[0]
    block_q, block_b = _fused_blocks(index, Q, 0, block_q, block_b)
    return _range_pallas_impl(
        index, qr, _eps_qcol(epsilon, Q), valid_mask, block_q, block_b,
        kernel_ops._use_interpret(interpret))


# Extra block-local top-k slots beyond k: the in-kernel selection ranks by
# the matmul-form d², the final merge by the re-verified diff² form — the
# two orderings can swap near-ties (f32 form noise), so a true neighbour
# sitting exactly at a block's k boundary could otherwise miss its
# partial list.  A displacement of more than _TOPK_GUARD positions would
# need > _TOPK_GUARD distinct rows of one block inside the same f32 noise
# window at the boundary (exact duplicates rank identically in both forms
# and cannot displace).  The guard makes a loss improbable; it does NOT by
# itself prove exactness — the certificate below does, by *detecting* the
# only remaining loss mode instead of assuming it away.
_TOPK_GUARD = 4
# Near-tie window for that certificate.  The merge re-verifies every listed
# candidate, so the only way the fused k-NN can lose a true neighbour is a
# row CUT from a FULL block-local partial list by a matmul-vs-diff² rank
# swap at the k_sel boundary.  A cut row's matmul d² is ≥ every kept
# slot's, so its re-verified distance is ≥ the block's worst re-verified
# partial minus the (two-sided) f32 form noise: when every full block's
# worst partial clears the merged k-th distance by this window, no cut row
# can re-enter the true top-k and the answer is provably exact.  The window
# is ~100× wider than the observed matmul-vs-diff² round-off on unit-scale
# data — deliberately conservative, since widening it can only turn a True
# certificate into a False one (exact-duplicate ties at the boundary are
# flagged too, even though identical rows cannot actually displace).
_TOPK_TIE_REL = 1e-4
_TOPK_TIE_ABS = 1e-3


def _fused_tighten_eps(index, qr, eps, k, k_sel, n_iters, valid_mask,
                       residuals, panels, block_q, block_b, interpret,
                       knn_col=None):
    """The fused-backend twin of :func:`_tighten_eps`: each tightening
    pass is one ``fused_topk_pallas`` database read whose re-verified
    partials shrink the k-NN rows' radius.  Shared by the dedicated
    (:func:`knn_query_pallas`) and mixed (:func:`mixed_query_pallas`)
    paths — ``knn_col`` selects which rows tighten, exactly the
    :func:`_tighten_eps` convention — so the two cannot drift."""
    for _ in range(max(0, int(n_iters) - 1)):
        idxp, _ = _fused.fused_topk_pallas(
            index.series, index.norms_sq, index.words, residuals,
            qr.q, panels, qr.residuals, _cascade_eps(eps, knn_col),
            levels=index.levels, alphabet=index.alphabet, n=index.n,
            k=k_sel, block_q=block_q, block_b=block_b, interpret=interpret)
        d2v = _reverify_rows(index, qr, idxp, valid_mask)
        tight = jnp.minimum(eps, jnp.sqrt(_kth_smallest(d2v, k)))
        eps = tight if knn_col is None else jnp.where(knn_col, tight, eps)
    return eps


def _topk_exact_certificate(d2v: jnp.ndarray, nn_d2: jnp.ndarray, k: int,
                            k_sel: int, block_b: int) -> jnp.ndarray:
    """Exactness certificate for a merged block-local top-k (see
    _TOPK_TIE_* above).  Cut rows can only come from a FULL partial list:
    a block with an empty (+inf) slot had fewer cascade survivors than
    slots, and with ``k_sel == block_b`` every row of the block is listed
    — nothing can be cut at all.  (The tightening passes need no such
    check: ε only ever shrinks to re-verified distances of real rows,
    which upper-bound the true k-th distance whatever their partial lists
    dropped.)  Shared by :func:`knn_query_pallas` and the streaming
    subsequence form (``core/subseq.py``)."""
    Q = d2v.shape[0]
    if k_sel >= block_b:
        return jnp.ones((Q,), dtype=bool)
    blk_worst = jnp.max(d2v.reshape(Q, -1, k_sel), axis=-1)  # (Q, nb)
    kth = nn_d2[:, k - 1:k]                                  # (Q, 1)
    at_risk = jnp.isfinite(blk_worst) & (
        blk_worst <= kth * (1.0 + _TOPK_TIE_REL) + _TOPK_TIE_ABS)
    return ~jnp.any(at_risk, axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "n_iters", "block_q",
                                             "block_b", "interpret"))
def _knn_pallas_impl(index, qr, k, n_iters, valid_mask, block_q, block_b,
                     interpret):
    panels = _query_panels(qr, index.alphabet)
    residuals = _masked_residuals(index, valid_mask)
    k_sel = min(k + _TOPK_GUARD, block_b)

    eps = _seed_eps(index, qr, k, valid_mask)
    eps = _fused_tighten_eps(index, qr, eps, k, k_sel, n_iters, valid_mask,
                             residuals, panels, block_q, block_b, interpret)
    idxp, _ = _fused.fused_topk_pallas(
        index.series, index.norms_sq, index.words, residuals,
        qr.q, panels, qr.residuals, _cascade_eps(eps),
        levels=index.levels, alphabet=index.alphabet, n=index.n,
        k=k_sel, block_q=block_q, block_b=block_b, interpret=interpret)
    d2v = _reverify_rows(index, qr, idxp, valid_mask)
    nn_idx, nn_d2 = _fused.merge_topk_partials(idxp, d2v, k)
    exact = _topk_exact_certificate(d2v, nn_d2, k, k_sel, block_b)
    return nn_idx, nn_d2, exact


def knn_query_pallas(
    index: DeviceIndex, qr: QueryReprDev, k: int,
    n_iters: int = 2, valid_mask: jnp.ndarray | None = None,
    block_q: int | None = None, block_b: int | None = None,
    interpret: bool | None = None,
):
    """Fused-megakernel exact k-NN: same tightening schedule as
    :func:`knn_query`, but each pass is ONE database read emitting
    block-local top-k partials (never a (Q, B) distance matrix), merged in
    a cheap epilogue and re-verified in the engine's diff² form.  Returns
    ``(nn_idx, nn_d2, exact)``.

    ``exact`` is computed, not assumed: since the merge re-verifies every
    listed candidate, the only possible loss is a row cut from a *full*
    block-local partial list by a matmul-vs-diff² near-tie rank swap at
    the ``k + _TOPK_GUARD`` boundary; the epilogue flags exactly that
    condition (conservatively — boundary ties between exact duplicates
    are flagged too) and certifies the rest.  On a False row, re-run via
    the XLA :func:`knn_query_auto` (the ``backend="xla"`` path) or with a
    larger ``block_b`` so the partial lists cover more of each block.
    False is rare: it needs a full list whose worst re-verified distance
    sits within the f32 noise window of the merged k-th distance.

    Kernel size and compile time grow linearly in k: the in-kernel
    selection unrolls ``k + _TOPK_GUARD`` min/argmin sweeps per block
    (see :func:`kernels.fused_query.fused_topk_pallas`), so very large k
    (≳ 100) belongs on the XLA engine, where the dense top-k is a single
    ``lax.top_k``."""
    B = index.series.shape[0]
    k_eff = min(int(k), B)
    block_q, block_b = _fused_blocks(index, qr.q.shape[0], k_eff,
                                     block_q, block_b)
    return _knn_pallas_impl(index, qr, k_eff, int(n_iters), valid_mask,
                            block_q, block_b,
                            kernel_ops._use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("k", "n_iters", "block_q",
                                             "block_b", "interpret"))
def _mixed_pallas_impl(index, qr, epsilon, is_knn, k, n_iters, valid_mask,
                       block_q, block_b, interpret):
    Q, B = qr.q.shape[0], index.series.shape[0]
    knn_col = is_knn.reshape(Q, 1)
    eps_req = _eps_qcol(epsilon, Q)
    panels = _query_panels(qr, index.alphabet)
    residuals = _masked_residuals(index, valid_mask)
    eps = jnp.where(knn_col, _seed_eps(index, qr, k, valid_mask), eps_req)

    k_sel = min(k + _TOPK_GUARD, block_b)
    eps = _fused_tighten_eps(index, qr, eps, k, k_sel, n_iters, valid_mask,
                             residuals, panels, block_q, block_b, interpret,
                             knn_col=knn_col)

    # The final pass is the DENSE range form, so (unlike the dedicated
    # k-NN path) partial-list truncation cannot lose answers here: the
    # tightening passes only decide how small ε gets — ε stays a verified
    # upper bound throughout — and the dense mask at the final slacked ε
    # necessarily covers the true top-k of every k-NN row.
    ans, d2 = _fused.fused_range_pallas(
        index.series, index.norms_sq, index.words, residuals,
        qr.q, panels, qr.residuals, _cascade_eps(eps, knn_col),
        levels=index.levels, alphabet=index.alphabet, n=index.n,
        block_q=block_q, block_b=block_b, interpret=interpret)
    ans, d2 = _mask_dense(ans, d2, valid_mask)
    idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None, :], (Q, B))
    overflow = jnp.zeros((Q,), dtype=bool)
    return idx, ans, d2, overflow


def mixed_query_pallas(
    index: DeviceIndex, qr: QueryReprDev, epsilon, is_knn, k: int,
    n_iters: int = 2, valid_mask: jnp.ndarray | None = None,
    block_q: int | None = None, block_b: int | None = None,
    interpret: bool | None = None,
):
    """Fused-megakernel mixed batch in :func:`mixed_query_dense` layout.

    Range rows answer at the caller's ε (bit-identical to
    :func:`range_query`); k-NN rows self-tighten through fused top-k
    passes and answer with the in-range mask at their final slacked
    radius — a superset of the exact top-k, extracted per row by the
    caller (``mixed_topk`` semantics over the dense buffer).  Returns
    ``(idx (Q, B), answer (Q, B), d2 (Q, B), overflow (Q,))`` with
    ``overflow`` always False: there is no candidate buffer to overflow.
    """
    B = index.series.shape[0]
    k_eff = min(int(k), B)
    block_q, block_b = _fused_blocks(index, qr.q.shape[0], k_eff,
                                     block_q, block_b)
    return _mixed_pallas_impl(
        index, qr, jnp.asarray(epsilon, jnp.float32),
        jnp.asarray(is_knn, dtype=bool), k_eff, int(n_iters), valid_mask,
        block_q, block_b, kernel_ops._use_interpret(interpret))


def compact_answers(answer: jnp.ndarray, d2: jnp.ndarray, capacity: int):
    """Compact a dense (Q, B) answer mask into ``capacity`` low-index slots.

    The epilogue that adapts the fused backend's dense layout to the
    compact per-shard buffer convention of ``core/dist_search.py``: slots
    fill prefer-low-index (the engine-wide tie-break order) and
    ``overflow`` flags rows whose answers did not fit.  Returns
    ``(idx (Q, C), valid (Q, C), d2 (Q, C), overflow (Q,))``.
    """
    B = answer.shape[-1]
    capacity = min(int(capacity), B)
    keys = jnp.where(answer, B - jnp.arange(B, dtype=jnp.int32)[None, :], 0)
    top, idx = jax.lax.top_k(keys, capacity)
    valid = top > 0
    d2c = jnp.where(valid, jnp.take_along_axis(d2, idx, axis=-1), jnp.inf)
    return idx, valid, d2c, answer.sum(axis=-1) > capacity


def _coerce_options(options, legacy: dict):
    """Accept a legacy positional ``backend`` string where ``options`` now
    sits (pre-PR-8 call sites passed ``backend`` as the 4th positional
    argument); route it through the deprecation shim."""
    if isinstance(options, str):
        legacy["backend"] = options
        return None
    return options


def range_query_backend(
    index: DeviceIndex, qr: QueryReprDev, epsilon,
    options: SearchOptions | None = None, **legacy,
):
    """Backend-dispatched dense range query (same convention both ways).

    ``options`` is the one knob surface (:class:`SearchOptions`); the old
    ``backend=`` kwarg still works through a :class:`DeprecationWarning`
    shim.  Unrecognised kwargs pass through to the Pallas kernel (expert
    block overrides).  Extended representation stacks demote Pallas to
    XLA (:func:`stack_backend` — the fused megakernels hard-code the
    canonical pair).
    """
    options = _coerce_options(options, legacy)
    opts, pallas_kw = resolve_options(options, legacy, "range_query_backend")
    if stack_backend(index, resolve_backend(opts.backend)) == "pallas":
        return range_query_pallas(index, qr, epsilon, **pallas_kw)
    return range_query(index, qr, epsilon)


def knn_query_backend(
    index: DeviceIndex, qr: QueryReprDev, k: int,
    options: SearchOptions | None = None,
    valid_mask: jnp.ndarray | None = None, **legacy,
):
    """Backend-dispatched exact k-NN: ``(nn_idx, nn_d2, exact)``.

    XLA runs the certificate-escalated :func:`knn_query_auto`; Pallas runs
    the fused path, whose certificate is computed by the block-boundary
    near-tie detector (see :func:`knn_query_pallas` — on a rare False,
    re-issue the query with ``backend="xla"``).  Large k auto-demotes to
    XLA (:func:`resolve_knn_backend`): past the ~100-sweep unroll
    threshold the fused selection costs more to compile than it saves;
    extended representation stacks demote likewise (:func:`stack_backend`).
    Knobs ride in ``options`` (:class:`SearchOptions`); the old
    ``backend=``/``capacity=``/``n_iters=`` kwargs shim through with a
    :class:`DeprecationWarning`.  ``valid_mask`` is data, not an option,
    and stays an explicit kwarg.
    """
    options = _coerce_options(options, legacy)
    opts, pallas_kw = resolve_options(options, legacy, "knn_query_backend")
    if stack_backend(index, resolve_knn_backend(opts.backend, k)) == "pallas":
        return knn_query_pallas(index, qr, k, n_iters=opts.n_iters,
                                valid_mask=valid_mask, **pallas_kw)
    return knn_query_auto(index, qr, k, capacity=opts.capacity,
                          n_iters=opts.n_iters, valid_mask=valid_mask,
                          max_doublings=opts.max_doublings)


def mixed_query_backend(
    index: DeviceIndex, qr: QueryReprDev, epsilon, is_knn, k: int,
    options: SearchOptions | None = None,
    valid_mask: jnp.ndarray | None = None, **legacy,
):
    """Backend-dispatched mixed batch: ``(idx, answer, d2, overflow)``.

    Both backends carry the exact answer set; XLA in the compact
    capacity-escalated layout (:func:`mixed_query_auto`), Pallas in the
    dense overflow-free layout (:func:`mixed_query_pallas`).  The mixed
    Pallas path's tightening passes unroll the same ``k + _TOPK_GUARD``
    selection as the dedicated k-NN kernel, so large k demotes to XLA
    under the same :func:`resolve_knn_backend` advice — a deterministic
    function of (backend, k), so every batch of a (Q, k) bucket takes
    the same float path.  Extended representation stacks demote to XLA
    too (:func:`stack_backend`).  Knobs ride in ``options``
    (:class:`SearchOptions`) with the old kwargs shimmed through a
    :class:`DeprecationWarning`.
    """
    options = _coerce_options(options, legacy)
    opts, pallas_kw = resolve_options(options, legacy, "mixed_query_backend")
    if stack_backend(index, resolve_knn_backend(opts.backend, k)) == "pallas":
        return mixed_query_pallas(index, qr, epsilon, is_knn, k,
                                  n_iters=opts.n_iters,
                                  valid_mask=valid_mask, **pallas_kw)
    return mixed_query_auto(index, qr, epsilon, is_knn, k,
                            capacity=opts.capacity, n_iters=opts.n_iters,
                            valid_mask=valid_mask)


def knn_query_auto(
    index: DeviceIndex,
    qr: QueryReprDev,
    k: int,
    capacity: int | None = None,
    n_iters: int = 2,
    valid_mask: jnp.ndarray | None = None,
    max_doublings: int = 8,
):
    """Certificate-driven exact k-NN: escalate capacity until provably exact.

    Runs :func:`knn_query` and, while any query's exactness certificate is
    False, re-runs with 4× the capacity (capped at B, where the compaction
    can never overflow — so termination with an all-True certificate is
    guaranteed).  The escalation is host-side; each distinct capacity
    compiles once and is cached by jit.
    """
    B = index.series.shape[0]
    k_eff = min(int(k), B)
    cap = min(B, max(4 * k_eff, 64) if capacity is None else int(capacity))
    cap = max(cap, k_eff)
    for _ in range(max_doublings + 1):
        nn_idx, nn_d2, exact = knn_query(
            index, qr, k_eff, capacity=cap, n_iters=n_iters,
            valid_mask=valid_mask)
        if cap >= B or bool(jax.device_get(exact).all()):
            return nn_idx, nn_d2, exact
        cap = min(B, cap * 4)
    return nn_idx, nn_d2, exact


# ---------------------------------------------------------------------------
# Quantized memory-tiered engine (DESIGN.md §9).
#
# Third cascade tier: the device keeps only the QUANTIZED columns (int8
# per-block affine or bf16) of the screen — symbols, residuals, series —
# plus per-block worst-case dequantization errors; the full-precision raw
# series is demoted to a host mmap tier and touched only to exact-verify
# the survivors.  Every lower bound is *widened* by the stored error
# (index/quantized.py has the lemma statements), so every kill remains
# provably admissible and the final answers are set-identical to the
# full-precision engine.
# ---------------------------------------------------------------------------

# f32 slack on the widened series-screen radius: the screen distance d(û,q)
# is evaluated in f32 while the stored per-row error bound e_u was computed
# against the f64 source, so the triangle-inequality kill only holds up to
# f32 rounding of the compare operands.  Widening only ever ADDS survivors
# — exactness is unaffected.  Shared with the fused kernels (defined in
# kernels/fused_query.py) so the two screens agree bit-for-bit.
QUANT_SCREEN_REL = _fused.QUANT_SCREEN_REL
QUANT_SCREEN_ABS = _fused.QUANT_SCREEN_ABS


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedDeviceIndex:
    """Device-resident quantized screen columns (pytree).

    ``series``: (B, n) int8 codes or bf16; ``series_scale``/``series_zero``:
    (B, 1) f32 per-row affine (int8 only, else None); ``series_err``: (B,)
    f32 per-row ‖u − û‖₂ bound; ``norms_sq``: (B,) f32 ‖û‖² of the
    dequantized rows; ``words[l]``: (B, N_l) int8 (lossless);
    ``residuals[l]``: (B,) int8 codes or bf16; ``resid_scale``/``zero``/
    ``err[l]``: (nb_l, 1) f32 per scale block of ``quantized.RESID_BLOCK``
    rows (scale/zero None for bf16).
    """

    series: jnp.ndarray
    series_scale: jnp.ndarray | None
    series_zero: jnp.ndarray | None
    series_err: jnp.ndarray
    norms_sq: jnp.ndarray
    words: tuple
    residuals: tuple
    resid_scale: tuple
    resid_zero: tuple
    resid_err: tuple
    #: per level {name: (B, N_l) int8 codes} for word-kind stack extras
    #: (lossless — symbols fit int8; gap-kind extras are rejected at
    #: quantize time, so the widened C9 stays canonical-only)
    extra: tuple = ()
    # static:
    levels: tuple = dataclasses.field(default=())
    alphabet: int = 10
    mode: str = "int8"
    stack: tuple = DEFAULT_STACK

    def tree_flatten(self):
        children = (self.series, self.series_scale, self.series_zero,
                    self.series_err, self.norms_sq, self.words,
                    self.residuals, self.resid_scale, self.resid_zero,
                    self.resid_err, self.extra)
        aux = (self.levels, self.alphabet, self.mode, self.stack)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, levels=aux[0], alphabet=aux[1], mode=aux[2],
                   stack=aux[3])

    @property
    def n(self) -> int:
        return self.series.shape[-1]


def _upload_codes(codes: np.ndarray) -> jnp.ndarray:
    """Host quantized column -> device: uint16 bf16 bit patterns become
    native device bfloat16 (so kernels dequantize with one astype), int8
    codes upload verbatim."""
    codes = np.asarray(codes)
    if codes.dtype == np.uint16:
        if _quant._BF16 is None:  # pragma: no cover - jax ships ml_dtypes
            raise _quant.QuantizationError("bf16 upload needs ml_dtypes")
        return jnp.asarray(codes.view(_quant._BF16), dtype=jnp.bfloat16)
    return jnp.asarray(codes, dtype=jnp.int8)


def quantized_device_index(qhost) -> QuantizedDeviceIndex:
    """Upload a ``index.quantized.QuantizedHostIndex`` resident tier."""
    int8 = qhost.mode == "int8"

    def col(a):                               # (m,) f32 -> (m, 1) f32
        return jnp.asarray(np.asarray(a, np.float32)).reshape(-1, 1)

    return QuantizedDeviceIndex(
        series=_upload_codes(qhost.series),
        series_scale=col(qhost.series_scale) if int8 else None,
        series_zero=col(qhost.series_zero) if int8 else None,
        series_err=jnp.asarray(qhost.series_err, jnp.float32),
        norms_sq=jnp.asarray(qhost.norms_sq, jnp.float32),
        words=tuple(jnp.asarray(lv.words, jnp.int8) for lv in qhost.levels),
        residuals=tuple(_upload_codes(lv.residuals) for lv in qhost.levels),
        resid_scale=tuple(col(lv.scale) if int8 else None
                          for lv in qhost.levels),
        resid_zero=tuple(col(lv.zero) if int8 else None
                         for lv in qhost.levels),
        resid_err=tuple(col(lv.err) for lv in qhost.levels),
        extra=tuple({name: jnp.asarray(arr, jnp.int8)
                     for name, arr in getattr(lv, "extra", {}).items()}
                    for lv in qhost.levels),
        levels=tuple(lv.n_segments for lv in qhost.levels),
        alphabet=qhost.alphabet,
        mode=qhost.mode,
        stack=tuple(getattr(qhost, "stack", DEFAULT_STACK)),
    )


def _expand_block_col(colv: jnp.ndarray, B: int) -> jnp.ndarray:
    """(nb, 1) per-scale-block f32 -> (B,) per-row (blocks are consecutive
    runs of ``quantized.RESID_BLOCK`` rows)."""
    nb = colv.shape[0]
    per_row = jnp.broadcast_to(colv, (nb, _quant.RESID_BLOCK)).reshape(-1)
    return per_row[:B]


def _dequant_residuals_dev(qindex: QuantizedDeviceIndex, li: int):
    """(B,) dequantized residuals — ``zero + scale · code`` (all f32), THE
    shared dequantizer expression (the Pallas kernels evaluate the same
    one, so the screens are bit-identical).  The reserved int8 sentinel
    code dequantizes to PAD_RESIDUAL regardless of scale."""
    codes = qindex.residuals[li]
    if qindex.mode == "bf16":
        return codes.astype(jnp.float32)
    B = codes.shape[0]
    scale = _expand_block_col(qindex.resid_scale[li], B)
    zero = _expand_block_col(qindex.resid_zero[li], B)
    deq = zero + scale * codes.astype(jnp.float32)
    return jnp.where(codes == _quant.SENTINEL_CODE,
                     jnp.float32(_fused.PAD_RESIDUAL), deq)


def _dequant_series_dev(qindex: QuantizedDeviceIndex) -> jnp.ndarray:
    """(B, n) dequantized series rows û (f32)."""
    if qindex.mode == "bf16":
        return qindex.series.astype(jnp.float32)
    return qindex.series_zero + \
        qindex.series_scale * qindex.series.astype(jnp.float32)


def quantized_cascade_mask(
    qindex: QuantizedDeviceIndex, qr: QueryReprDev, epsilon
) -> jnp.ndarray:
    """Widened exclusion cascade over the quantized columns (Q, B).

    C9 widens to ``|r̂(u) − r(q)| ≤ ε + e_blk`` (|r̂ − r| ≤ e_blk, so the
    widened compare can never kill a true answer); C10 runs UNWIDENED —
    the symbol columns are stored losslessly in int8, so MINDIST is the
    exact full-precision bound.  Word-kind stack extras screen unwidened
    for the same reason (lossless int8 symbols); gap-kind extras never
    reach this tier (``index.quantized`` rejects them).
    """
    n = qindex.n
    Q = qr.q.shape[0]
    eps = _eps_qcol(epsilon, Q)
    eps2 = eps * eps
    B = qindex.series.shape[0]
    alive = jnp.ones((Q, B), dtype=bool)
    tab = _mindist_sq_tab(qindex.alphabet)
    _, word_extras = _extra_reps(qindex)
    for li, N in enumerate(qindex.levels):
        res = _dequant_residuals_dev(qindex, li)
        err = _expand_block_col(qindex.resid_err[li], B)
        gap = jnp.abs(res[None, :] - qr.residuals[li][:, None])
        alive &= gap <= eps + err[None, :]
        cell = tab[qindex.words[li].astype(jnp.int32)[None, :, :],
                   qr.words[li][:, None, :]]
        md_sq = (n / N) * jnp.sum(cell * cell, axis=-1)
        alive &= md_sq <= eps2
        for rep in word_extras:
            col = qindex.extra[li][rep.name].astype(jnp.int32)
            alive &= rep.dev_bound_sq(col, qr.extra[li][rep.name],
                                      n=n, N=N, tab=tab) <= eps2
    return alive


@jax.jit
def quantized_screen(
    qindex: QuantizedDeviceIndex, qr: QueryReprDev, epsilon
):
    """The full quantized screen: (keep (Q, B), d̂² (Q, B)).

    ``keep`` marks rows that MAY be answers; the caller exact-verifies
    them against the raw tier.  The series screen applies the triangle
    inequality to the dequantized rows — d(u,q) ≥ d(û,q) − e_u, so a row
    with d(û,q) > ε + e_u provably has d(u,q) > ε — widened by the f32
    slack above.  This function is the XLA oracle the quantized Pallas
    kernels must match bit-for-bit (tests/test_kernels.py).
    """
    Q = qr.q.shape[0]
    eps = _eps_qcol(epsilon, Q)
    alive = quantized_cascade_mask(qindex, qr, eps)
    u = _dequant_series_dev(qindex)
    qn = jnp.sum(qr.q * qr.q, axis=-1)
    cross = jnp.dot(qr.q, u.T, precision=_F32,
                    preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qn[:, None] - 2.0 * cross + qindex.norms_sq[None, :],
                     0.0)
    thresh = (eps + qindex.series_err[None, :]) * \
        (1.0 + QUANT_SCREEN_REL) + QUANT_SCREEN_ABS
    keep = alive & (d2 <= thresh * thresh)
    return keep, jnp.where(keep, d2, jnp.inf)


def _screen_upper_bounds(qindex: QuantizedDeviceIndex, q: jnp.ndarray,
                         d2hat: jnp.ndarray) -> jnp.ndarray:
    """(Q, B) upper bounds on the true distance d(u, q) of every row the
    screen kept (+inf elsewhere): d(u,q) ≤ d(û,q) + e_u by the triangle
    inequality, with the matmul-form d̂² raised by an allowance for its
    f32 error, n·2⁻²⁰·(‖q‖² + ‖û‖²) — sixteen times the worst-case error
    of an n-term f32 dot, room for the MXU's multi-pass f32."""
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    tol = qindex.n * 2.0 ** -20 * (qn + qindex.norms_sq[None, :])
    return jnp.sqrt(d2hat + tol) + qindex.series_err[None, :]


def _tighten_keep(qindex: QuantizedDeviceIndex, keep, d2hat, eps, radius,
                  knn_col):
    """Re-apply the widened series screen to the k-NN rows (``knn_col``)
    at ``min(eps, radius)``, where ``radius`` upper-bounds each row's true
    k-th neighbour distance.  Every true neighbour is then within the
    tighter radius, so the kept set stays a superset of the answer."""
    tight = jnp.minimum(eps, radius)
    thresh = (tight + qindex.series_err[None, :]) * \
        (1.0 + QUANT_SCREEN_REL) + QUANT_SCREEN_ABS
    return jnp.where(knn_col, keep & (d2hat <= thresh * thresh), keep)


def _kth_smallest_bisect(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """:func:`_kth_smallest` of a wide (Q, M) float32 array without a
    sort: a bisection over the order-preserving int32 image of the bit
    patterns (negative floats have their magnitude bits flipped, so -0.0
    sorts just below +0.0, as in ``lax.top_k``).  Each of the 32 passes
    counts the entries at or below the midpoint; the loop ends at the
    smallest pattern with at least k entries at or below it, which is
    the k-th smallest entry itself — bit for bit, ``+inf`` and ties
    included (tests/test_selection.py).  32 reads of (Q, M) in place of
    a sort over M; NaN-free input."""
    flip = jnp.int32(0x7FFFFFFF)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ flip, bits)
    lo = jnp.full((x.shape[0], 1), jnp.iinfo(jnp.int32).min, jnp.int32)
    hi = jnp.full((x.shape[0], 1), jnp.iinfo(jnp.int32).max, jnp.int32)

    def halve(_, bounds):
        lo, hi = bounds
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)      # floor mean, no overflow
        at_most = jnp.sum(keys <= mid, axis=-1, keepdims=True,
                          dtype=jnp.int32)
        enough = at_most >= k
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    key, _ = jax.lax.fori_loop(0, 32, halve, (lo, hi))
    return jax.lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ flip, key), jnp.float32)


@functools.partial(jax.jit, static_argnames=("k",))
def _tighten_tiered_keep(qindex: QuantizedDeviceIndex, q, keep, d2hat, eps,
                         knn_col, k: int):
    """k-NN radius from the screen itself.  The seed radius (k-th of a
    64-row sample) keeps about k/64 of the database, which at millions of
    rows is more candidates than the raw-tier verify can gather.  Any k
    distinct rows bound the k-th neighbour distance by their largest
    upper bound, so the k-th smallest :func:`_screen_upper_bounds`
    (slacked) is a sound radius, within about 2·e_u of the true one.
    The bounds span the whole database axis, where a sort would cost
    several times the screen, so the k-th comes by bisection."""
    radius = _slacked(_kth_smallest_bisect(
        _screen_upper_bounds(qindex, q, d2hat), k))
    return _tighten_keep(qindex, keep, d2hat, eps, radius, knn_col)


@jax.jit
def _survivor_prefix(keep: jnp.ndarray):
    """Counting half of :func:`_compact_mask`: the (Q, B) keep mask packed
    32 positions to a word (bit b of word w is position 32·w + b, the
    tail padded unset), the inclusive per-word prefix count of set
    positions, and the batch's largest survivor count (a scalar: all the
    host needs to choose a capacity)."""
    Q, B = keep.shape
    W = -(-B // 32)
    bits = jnp.pad(keep, ((0, 0), (0, 32 * W - B))).reshape(Q, W, 32)
    words = jnp.sum(bits.astype(jnp.uint32)
                    << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    prefix = jnp.cumsum(jax.lax.population_count(words).astype(jnp.int32),
                        axis=-1)
    return words, prefix, jnp.max(prefix[:, -1])


def _popcount(w: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.population_count(w).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("capacity",))
def _compact_counted(words: jnp.ndarray, prefix: jnp.ndarray, capacity: int):
    """Selecting half of :func:`_compact_mask`, from
    :func:`_survivor_prefix`'s words and prefix count.  Slot j < count
    holds the first position whose running count of set positions
    reaches j + 1; slot j ≥ count the first whose running count of UNSET
    positions reaches j − count + 1 (the order ``lax.top_k`` documents
    for the ties at key 0 of the keys ``B − i``).  A binary
    search over the monotone per-word prefix finds the word, a second
    over popcounts of its low bits the bit: about log2(B) steps of a
    (Q, C) gather or bit count, and no sort."""
    Q, W = words.shape
    count = prefix[:, -1:]
    j = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    valid = j < count
    rank = jnp.where(valid, j + 1, j - count + 1)            # (Q, C), ≥ 1
    lo = jnp.zeros((Q, capacity), jnp.int32)
    hi = jnp.full((Q, capacity), W - 1, jnp.int32)
    for _ in range((W - 1).bit_length()):
        mid = (lo + hi) // 2
        upto = jnp.take_along_axis(prefix, mid, axis=1)
        reached = jnp.where(valid, upto, 32 * (mid + 1) - upto) >= rank
        hi = jnp.where(reached, mid, hi)
        lo = jnp.where(reached, lo, mid + 1)
    word = jnp.take_along_axis(words, lo, axis=1)
    before = jnp.take_along_axis(prefix, lo, axis=1) - _popcount(word)
    rank = rank - jnp.where(valid, before, 32 * lo - before)  # 1..32
    word = jnp.where(valid, word, ~word)
    blo = jnp.zeros_like(lo)
    bhi = jnp.full_like(lo, 31)
    for _ in range(5):
        mid = (blo + bhi) // 2
        low = word & ((jnp.uint32(2) << mid.astype(jnp.uint32)) - 1)
        reached = _popcount(low) >= rank
        bhi = jnp.where(reached, mid, bhi)
        blo = jnp.where(reached, blo, mid + 1)
    return 32 * lo + blo, valid, count[:, 0] > capacity


@functools.partial(jax.jit, static_argnames=("capacity",))
def _compact_mask(keep: jnp.ndarray, capacity: int):
    """Low-index compaction of a dense keep mask (no distances needed):
    (idx (Q, C), valid (Q, C), overflow (Q,)).  Slots past a row's count
    hold its lowest unset positions, ascending.  Counting, not sorting:
    :func:`_survivor_prefix` then :func:`_compact_counted`."""
    words, prefix, _ = _survivor_prefix(keep)
    return _compact_counted(words, prefix, capacity)


@jax.jit
def _verify_gathered(rows: jnp.ndarray, q: jnp.ndarray, valid: jnp.ndarray):
    """Exact diff²-form distances of gathered raw-tier rows (Q, C)."""
    diff = rows - q[:, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    return jnp.where(valid, d2, jnp.inf)


@dataclasses.dataclass
class TieredIndex:
    """Two-tier serving index: quantized screen resident, raw mmap verify.

    ``dev`` answers the widened screen on device; ``raw`` is the (B, n)
    full-precision series — typically an ``np.memmap`` straight off the
    store, paged in only for the rows the screen could not exclude.
    ``ids`` (optional) maps row positions to external ids for indexes
    loaded from a mutable root with deletions.
    """

    dev: QuantizedDeviceIndex
    raw: np.ndarray
    ids: np.ndarray | None = None

    @property
    def size(self) -> int:
        return self.dev.series.shape[0]

    @property
    def mode(self) -> str:
        return self.dev.mode

    @classmethod
    def from_host(cls, index: FastSAXIndex, mode: str,
                  ids: np.ndarray | None = None) -> "TieredIndex":
        """Quantize a built host index into the tiered layout in memory."""
        qhost = _quant.quantize_host_index(index, mode)
        return cls(dev=quantized_device_index(qhost),
                   raw=np.asarray(index.series), ids=ids)

    @classmethod
    def from_store(cls, path, quantization: str | None = None,
                   with_ids: bool = False):
        """Warm-start the tiered layout from a committed store directory.

        A plain store saved with a matching ``quantization=`` loads its
        quantized columns directly (mmap — no requantization); a store
        without a quantized tier (or with a different mode) is quantized
        in memory from the full-precision columns.  A ``MutableIndex``
        root defaults to the mode its epoch was created with; a compacted
        single-segment root reuses its base segment's stored quantized
        columns (zero-copy, like a plain store), while a root with deltas
        or tombstones quantizes its live view in memory (live-row blocks
        straddle segment boundaries, so per-segment scales are not
        reusable).  The ``with_ids`` contract matches
        :meth:`DeviceIndex.from_store`.
        """
        import pathlib

        from ..index import mutable as _mutable
        from ..index import store as _store

        path = pathlib.Path(path)
        if (path / _mutable.CURRENT).exists():
            mut = _mutable.MutableIndex.open(path)
            mode = quantization or (
                mut.quantization if mut.quantization != "none" else "int8")
            compacted = len(mut._segments) == 1 and not mut._tomb.any()
            host, ids = mut.live_index()
            ids = np.asarray(ids)
            if not with_ids and not np.array_equal(ids,
                                                   np.arange(ids.size)):
                raise ValueError(
                    f"{path}: external ids differ from row positions "
                    "(rows were deleted) — call "
                    "from_store(..., with_ids=True) and map answers "
                    "through the ids array")
            if compacted and mut.quantization == mode:
                seg = path / mut._epoch["base"]
                qhost = _store.load_quantized(seg, mmap=True, mode=mode)
                raw = _store.read_array(seg, "series", mmap=True)
                tiered = cls(dev=quantized_device_index(qhost), raw=raw,
                             ids=ids if with_ids else None)
            else:
                tiered = cls.from_host(host, mode,
                                       ids=ids if with_ids else None)
            return (tiered, ids) if with_ids else tiered
        manifest = _store.read_manifest(path)
        stored = _store.quantized_mode(manifest)
        mode = quantization or (stored if stored != "none" else "int8")
        raw = _store.read_array(path, "series", manifest, mmap=True)
        if stored == mode:
            qhost = _store.load_quantized(path, mmap=True, mode=mode)
            tiered = cls(dev=quantized_device_index(qhost), raw=raw)
        else:
            host = _store.load_index(path, mmap=True)
            tiered = cls.from_host(host, mode)
        ids = np.arange(tiered.size)
        return (tiered, ids) if with_ids else tiered


def _quantized_screen_backend(tindex: TieredIndex, qr: QueryReprDev,
                              eps_col, backend: str):
    """Dispatch the dense quantized screen: XLA oracle or the fused
    dequantize-in-kernel Pallas form (bit-identical — tested).  Extended
    stacks demote to the XLA oracle (:func:`stack_backend`)."""
    if stack_backend(tindex.dev, resolve_backend(backend)) == "pallas":
        from ..kernels.fused_query import fused_quant_range_pallas

        Q = qr.q.shape[0]
        block_q, block_b = _fused_blocks_quant(tindex.dev, Q)
        return fused_quant_range_pallas(
            tindex.dev, qr.q, _query_panels(qr, tindex.dev.alphabet),
            qr.residuals, eps_col, block_q=block_q, block_b=block_b,
            interpret=kernel_ops._use_interpret(None))
    return quantized_screen(tindex.dev, qr, eps_col)


def _fused_blocks_quant(qdev: QuantizedDeviceIndex, Q: int,
                        block_q: int | None = None,
                        block_b: int | None = None):
    """Block shapes for the quantized kernels (their own VMEM layout)."""
    return _fused_blocks(qdev, Q, 0, block_q, block_b, mode=qdev.mode)


#: Double-buffer depth of the prefetched verify path: chunk i+1's mmap
#: read runs on the prefetch thread while chunk i's upload + verify is in
#: flight on device.
_PREFETCH_CHUNKS = 2
_prefetch_pool_singleton = None


def _prefetch_pool() -> _futures.ThreadPoolExecutor:
    global _prefetch_pool_singleton
    if _prefetch_pool_singleton is None:
        _prefetch_pool_singleton = _futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="repro-verify-prefetch")
    return _prefetch_pool_singleton


def _verify_prefetched(raw, idx, q, valid, key: str = "") -> jnp.ndarray:
    """Double-buffered raw-tier verify (DESIGN.md §13).

    Splits the candidate columns into :data:`_PREFETCH_CHUNKS` spans;
    span j+1's host mmap read runs on the prefetch executor while span
    j's rows are uploading and verifying on device (device dispatch is
    async, so the next read genuinely overlaps the compute).  The diff²
    verify is row-local, so the chunked result is bit-identical to the
    synchronous gather — property-tested in tests/test_dist_quantized.py.
    A fault raised inside the prefetch thread (``verify_fetch`` site)
    re-raises at ``result()`` — loud, never silently-wrong.
    """
    C = int(idx.shape[-1])
    nchunks = max(1, min(_PREFETCH_CHUNKS, C))
    bounds = [(C * i) // nchunks for i in range(nchunks + 1)]
    spans = [(lo, hi) for lo, hi in zip(bounds, bounds[1:]) if hi > lo]
    idx_np = np.asarray(jax.device_get(idx))
    pool = _prefetch_pool()

    def fetch(j: int, lo: int, hi: int) -> np.ndarray:
        return _store.gather_rows(raw, idx_np[:, lo:hi], key=f"{key}{j}")

    fut = pool.submit(fetch, 0, *spans[0])
    parts = []
    for j, (lo, hi) in enumerate(spans):
        rows = fut.result()
        if j + 1 < len(spans):
            fut = pool.submit(fetch, j + 1, *spans[j + 1])
        with span("repro.engine.verify"):
            parts.append(_verify_gathered(jnp.asarray(rows), q,
                                          valid[:, lo:hi]))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def _verify_tier(raw, idx, q, valid, opts: SearchOptions,
                 key: str = "") -> jnp.ndarray:
    """The raw-tier exact verify behind every tiered engine: synchronous
    single gather, or the double-buffered prefetch path when
    ``opts.verify_prefetch`` — same d2, bit for bit.

    The synchronous path gathers the candidate rows from the host mmap
    tier and uploads them as f32 — the only touch of full-precision data
    on the query path.  The read goes through ``index.store.gather_rows``:
    ids clamp into the raw tier's row range (the raw tier may hold fewer
    rows than the padded screen tier — padded rows are sentinel-killed
    and their slots are masked), and the ``verify_fetch`` chaos site
    fires on it."""
    if opts.verify_prefetch:
        return _verify_prefetched(raw, idx, q, valid, key=key)
    rows = _store.gather_rows(raw, np.asarray(jax.device_get(idx)),
                              key=key or "0")
    with span("repro.engine.verify"):
        return _verify_gathered(jnp.asarray(rows), q, valid)


def _ladder_cap(cap: int, B: int, max_doublings: int, need: int) -> int:
    """The capacity ladder's stop: the first of ``cap``, 4·cap, 16·cap, …
    (capped at B, where compaction cannot overflow; at most
    ``max_doublings`` steps up) that holds ``need`` survivors, else its
    last rung, where the rows above it overflow."""
    for _ in range(max_doublings):
        if cap >= B or need <= cap:
            break
        cap = min(B, cap * 4)
    return cap


def _screen_and_compact(screen, cap: int, B: int, max_doublings: int):
    """Screen, count the survivors, then compact once at the capacity the
    4× ladder from ``cap`` stops at for the batch's largest count
    (:func:`_ladder_cap`): ``(idx, valid, overflow)``, overflow only past
    ``max_doublings`` steps.  Capacities stay rungs of the ladder, so a
    batch builds the program an escalating loop would have ended on.

    ``screen()`` enqueues the screen and returns the (Q, B) keep mask.
    The span ``repro.engine.screen`` holds the screen (and any radius
    tightening) up to the count's ``device_get``, then enqueues the
    compaction; it carries ``survivors_max`` and the ``cap`` chosen."""
    with span("repro.engine.screen") as sp:
        words, prefix, need = _survivor_prefix(screen())
        need = int(jax.device_get(need))
        cap = _ladder_cap(cap, B, max_doublings, need)
        sp.set(survivors_max=need, cap=cap)
        return _compact_counted(words, prefix, cap)


def _coerce_quant_options(options, legacy: dict):
    """Legacy positional ``capacity`` (int) in the ``options`` slot of the
    ``quantized_*`` entrypoints routes through the deprecation shim."""
    if isinstance(options, int):
        legacy["capacity"] = options
        return None
    return options


def quantized_range_query(
    tindex: TieredIndex, qr: QueryReprDev, epsilon,
    options: SearchOptions | None = None, **legacy,
):
    """Exact range query over the tiered index.

    Screens on the quantized resident tier (widened bounds — no true
    answer can be excluded), compacts survivors, fetches ONLY those rows
    from the raw mmap tier, and exact-verifies them in the engine's diff²
    form.  The capacity is the rung of the 4× ladder from ``capacity``
    (capped at B, where compaction cannot overflow) that holds the
    batch's largest survivor count, counted before the one compaction,
    so the certificate is always True on return.  Returns ``(idx (Q, C),
    answer (Q, C), d2 (Q, C), exact (Q,))`` — set-identical to :func:`range_query` / ``range_query_compact``
    (property-tested in tests/test_quantized.py).  Knobs ride in
    ``options`` (:class:`SearchOptions`); the old ``capacity=`` /
    ``backend=`` / ``max_doublings=`` kwargs shim through with a
    :class:`DeprecationWarning`.
    """
    options = _coerce_quant_options(options, legacy)
    opts, rest = resolve_options(options, legacy, "quantized_range_query")
    if rest:
        raise TypeError(f"quantized_range_query: unexpected kwargs "
                        f"{sorted(rest)}")
    capacity, max_doublings = opts.capacity, opts.max_doublings
    Q, B = qr.q.shape[0], tindex.size
    eps = _eps_qcol(epsilon, Q)
    cap = min(B, 64 if capacity is None else max(1, int(capacity)))
    idx, valid, overflow = _screen_and_compact(
        lambda: _quantized_screen_backend(tindex, qr, eps, opts.backend)[0],
        cap, B, max_doublings)
    d2 = _verify_tier(tindex.raw, idx, qr.q, valid, opts)
    answer = valid & (d2 <= eps * eps)
    return idx, answer, jnp.where(answer, d2, jnp.inf), ~overflow


@functools.partial(jax.jit, static_argnames=("k",))
def _sample_eps(rows: jnp.ndarray, q: jnp.ndarray, k: int) -> jnp.ndarray:
    """Seed radius from verified sample rows: (Q, 1) k-th sampled distance
    (upper-bounds the true k-th distance — a sound starting radius)."""
    diff = rows[None, :, :] - q[:, None, :]
    d2s = jnp.sum(diff * diff, axis=-1)
    eps = jnp.sqrt(jnp.maximum(_kth_smallest(d2s, k), 0.0))
    return jnp.where(jnp.isfinite(eps), eps, _SEED_EPS_MAX)


def _tiered_seed_eps(tindex: TieredIndex, qr: QueryReprDev,
                     k: int) -> jnp.ndarray:
    """k-NN seed radius for the tiered engine: the strided sample is
    fetched from the RAW tier (same strided positions as
    :func:`_seed_eps`), so the radius is a true verified upper bound.
    The sample strides over the raw tier's OWN row count — the screen
    tier may carry trailing sentinel padding the raw tier does not, and
    sampling a pad row would shrink the radius below the true k-th
    distance (unsound)."""
    R = int(tindex.raw.shape[0])
    if R == 0:
        # All-pad shard (failover fleet past n_valid): no row can answer
        # — any radius screens an empty candidate set, 0 is cheapest.
        return jnp.zeros((qr.q.shape[0], 1), jnp.float32)
    S = min(R, max(k, _KNN_SEED_SAMPLE))
    sample = (np.arange(S) * R) // S
    with span("repro.engine.seed", rows=S):
        rows = jnp.asarray(np.asarray(tindex.raw[sample]), jnp.float32)
        return _sample_eps(rows, qr.q, k)


def quantized_knn_query(
    tindex: TieredIndex, qr: QueryReprDev, k: int,
    options: SearchOptions | None = None, **legacy,
):
    """Exact k-NN over the tiered index: ``(nn_idx, nn_d2, exact)``.

    Seeds a per-query radius from a verified raw-tier sample (the k-th
    sampled distance upper-bounds the true k-th distance), screens the
    quantized tier at the slacked radius — every true neighbour has
    d ≤ d_k ≤ ε, and the widened screen never kills a row with d ≤ ε —
    shrinks the radius from the screen's own distance bounds
    (:func:`_tighten_tiered_keep`), then exact-verifies the surviving
    candidates from the raw tier and
    takes their top-k (ties to the lowest index, the engine-wide order).
    The capacity is the 4× ladder's rung that holds the largest survivor
    count (up to B), so ``exact`` is always True on return: the answer
    provably equals brute force.  Knobs ride in
    ``options`` (:class:`SearchOptions`); old kwargs shim through with a
    :class:`DeprecationWarning`.
    """
    options = _coerce_quant_options(options, legacy)
    opts, rest = resolve_options(options, legacy, "quantized_knn_query")
    if rest:
        raise TypeError(f"quantized_knn_query: unexpected kwargs "
                        f"{sorted(rest)}")
    capacity, max_doublings = opts.capacity, opts.max_doublings
    Q, B = qr.q.shape[0], tindex.size
    k_eff = min(int(k), B)
    eps = _slacked(_tiered_seed_eps(tindex, qr, k_eff))      # (Q, 1)

    def screen():
        keep, d2hat = _quantized_screen_backend(tindex, qr, eps,
                                                opts.backend)
        return _tighten_tiered_keep(tindex.dev, qr.q, keep, d2hat, eps,
                                    jnp.ones((Q, 1), bool), k_eff)

    cap = min(B, max(4 * k_eff, 64) if capacity is None else int(capacity))
    idx, valid, overflow = _screen_and_compact(screen, max(cap, k_eff), B,
                                               max_doublings)
    d2 = _verify_tier(tindex.raw, idx, qr.q, valid, opts)
    neg, pos = jax.lax.top_k(-d2, k_eff)                     # ascending d2
    nn_d2 = -neg
    nn_idx = jnp.take_along_axis(idx, pos, axis=-1)
    nn_idx = jnp.where(jnp.isfinite(nn_d2), nn_idx, -1)
    return nn_idx, nn_d2, ~overflow


def quantized_mixed_query(
    tindex: TieredIndex, qr: QueryReprDev, epsilon, is_knn, k: int,
    options: SearchOptions | None = None, **legacy,
):
    """Mixed range/k-NN batch over the tiered index, serving-layer layout.

    The tiered twin of :func:`mixed_query`: range rows screen at the
    caller's ε (the widening happens inside the screen), k-NN rows at
    their slacked seeded radius; one shared compaction + raw-tier exact
    verify serves both.  Returns ``(idx, answer, d2, overflow)`` with
    ``overflow`` all-False (the capacity is chosen from the survivor
    count, as in :func:`quantized_range_query`) — for k-NN rows ``answer``
    marks valid candidate slots (a verified superset of the true top-k),
    extracted per row via :func:`mixed_topk` exactly like the other
    serving backends.  Knobs ride in ``options``
    (:class:`SearchOptions`); old kwargs shim through with a
    :class:`DeprecationWarning`.
    """
    options = _coerce_quant_options(options, legacy)
    opts, rest = resolve_options(options, legacy, "quantized_mixed_query")
    if rest:
        raise TypeError(f"quantized_mixed_query: unexpected kwargs "
                        f"{sorted(rest)}")
    capacity, max_doublings = opts.capacity, opts.max_doublings
    Q, B = qr.q.shape[0], tindex.size
    k_eff = min(int(k), B)
    knn_col = jnp.asarray(is_knn, dtype=bool).reshape(Q, 1)
    eps_req = _eps_qcol(epsilon, Q)
    eps = jnp.where(knn_col, _slacked(_tiered_seed_eps(tindex, qr, k_eff)),
                    eps_req)

    def screen():
        keep, d2hat = _quantized_screen_backend(tindex, qr, eps,
                                                opts.backend)
        if np.asarray(is_knn).any():      # range-only batches keep ε as is
            keep = _tighten_tiered_keep(tindex.dev, qr.q, keep, d2hat, eps,
                                        knn_col, k_eff)
        return keep

    cap = min(B, max(4 * k_eff, 64) if capacity is None else int(capacity))
    idx, valid, overflow = _screen_and_compact(screen, max(cap, k_eff), B,
                                               max_doublings)
    d2 = _verify_tier(tindex.raw, idx, qr.q, valid, opts)
    answer = jnp.where(knn_col, valid, valid & (d2 <= eps_req * eps_req))
    return idx, answer, jnp.where(answer, d2, jnp.inf), overflow


# ---------------------------------------------------------------------------
# Observability: traced twins of the query entry points (DESIGN.md §10).
#
# Design law: tracing never touches the untraced functions.  Each traced
# twin (a) runs the UNCHANGED engine call for the answers and (b) runs a
# separate cheap counting pass that duplicates the cascade expressions
# term for term.  Disabled tracing is therefore literally the old call
# path — same jitted callables, same cache entries, same jaxprs (tested
# in tests/test_obs.py) — and enabled tracing cannot change answers
# because the answer arrays come from the same functions as before.  The
# counting pass reads only the screen columns (words + residuals — never
# the series), so its cost is a small fraction of the verify matmul.
# ---------------------------------------------------------------------------


def _count_alive(mask: jnp.ndarray) -> jnp.ndarray:
    """(…, B) bool -> (…,) int32 survivor count."""
    return jnp.sum(mask, axis=-1, dtype=jnp.int32)


def _cascade_counting(index: DeviceIndex, qr: QueryReprDev, eps, valid_mask):
    """:func:`cascade_mask`, line for line, recording per-level counts.

    The per-level expressions are the same jnp terms as
    :func:`cascade_mask`, applied in the same C9-then-C10 order to the
    same running alive set as the host engine's sequential scan
    (``core/search.py``) — so the survivor counts bit-agree with the
    op-counted host accounting.  ``valid_mask`` (shard padding) is folded
    into the INITIAL alive set, so pad rows never inflate the level-0 C9
    kill count.
    """
    n = index.n
    Q = qr.q.shape[0]
    eps2 = eps * eps
    alive = jnp.ones((Q, index.series.shape[0]), dtype=bool)
    if valid_mask is not None:
        alive &= valid_mask[None, :]
    tab = _mindist_sq_tab(index.alphabet)
    gap_extras, word_extras = _extra_reps(index)
    after_c9, after_c10 = [], []
    for li, N in enumerate(index.levels):
        gap = jnp.abs(index.residuals[li][None, :] - qr.residuals[li][:, None])
        alive &= gap <= eps
        for rep in gap_extras:    # extra gap kills count under after_c9
            alive &= rep.dev_gap(index.extra[li][rep.name],
                                 qr.extra[li][rep.name]) <= eps
        after_c9.append(_count_alive(alive))
        cell = tab[index.words[li][None, :, :], qr.words[li][:, None, :]]
        md_sq = (n / N) * jnp.sum(cell * cell, axis=-1)
        alive &= md_sq <= eps2
        for rep in word_extras:   # extra word kills count under after_c10
            alive &= rep.dev_bound_sq(index.extra[li][rep.name],
                                      qr.extra[li][rep.name],
                                      n=n, N=N, tab=tab) <= eps2
        after_c10.append(_count_alive(alive))
    return alive, jnp.stack(after_c9, axis=-1), jnp.stack(after_c10, axis=-1)


@jax.jit
def cascade_trace(
    index: DeviceIndex, qr: QueryReprDev, epsilon,
    valid_mask: jnp.ndarray | None = None,
) -> QueryTrace:
    """:class:`QueryTrace` of the cascade at radius ``epsilon``.

    ``verified``/``screen_survivors`` default to the candidate count (the
    rows a verify must touch; there is no series screen on the
    full-precision path); ``answers`` is zero — callers that know the
    answer set patch it via ``dataclasses.replace``.  Safe inside
    ``shard_map`` (pure dataflow, no host sync).
    """
    Q = qr.q.shape[0]
    _, a9, a10 = _cascade_counting(index, qr, _eps_qcol(epsilon, Q),
                                   valid_mask)
    cand = a10[:, -1]
    return QueryTrace(after_c9=a9, after_c10=a10, screen_survivors=cand,
                      verified=cand, answers=jnp.zeros_like(cand))


def range_query_traced(
    index: DeviceIndex, qr: QueryReprDev, epsilon, backend: str = "xla",
    valid_mask: jnp.ndarray | None = None, **pallas_kw,
):
    """Range query + :class:`QueryTrace`: ``(answers, d2, trace)``.

    Answers are bit-identical to the untraced backend call (they ARE the
    untraced backend call); the trace comes from the separate counting
    pass at the same radius.  On the Pallas backend the counters come
    from the XLA counting pass over the identical cascade expressions —
    the fused kernel is bit-identical to the XLA cascade by construction
    (tests/test_kernels.py), so the counts describe it exactly.
    """
    if resolve_backend(backend) == "pallas":
        ans, d2 = range_query_pallas(index, qr, epsilon,
                                     valid_mask=valid_mask, **pallas_kw)
    else:
        ans, d2 = range_query(index, qr, epsilon)
        ans, d2 = _mask_dense(ans, d2, valid_mask)
    trace = cascade_trace(index, qr, epsilon, valid_mask)
    return ans, d2, dataclasses.replace(trace, answers=_count_alive(ans))


@functools.partial(jax.jit, static_argnames=("k",))
def knn_radius_trace(
    index: DeviceIndex, qr: QueryReprDev, nn_d2, k: int,
    valid_mask: jnp.ndarray | None = None,
) -> QueryTrace:
    """Cascade counters at the final verified k-NN radius ``d_k``.

    The adaptive k-NN engines visit levels in a probe-dependent order
    with a shrinking radius, so their *internal* counts are not
    comparable across engines; the counters at the final radius are —
    they equal the host ``fastsax_range_query`` accounting at
    ``ε = d_k`` exactly (the k-th neighbour's own lower bounds sit
    strictly inside its distance, so the boundary row always survives
    both conditions on both engines).
    """
    eps = jnp.sqrt(jnp.maximum(nn_d2[:, k - 1:k], 0.0))       # (Q, 1)
    eps = jnp.where(jnp.isfinite(eps), eps, _SEED_EPS_MAX)
    _, a9, a10 = _cascade_counting(index, qr, eps, valid_mask)
    cand = a10[:, -1]
    answers = jnp.sum(jnp.isfinite(nn_d2[:, :k]), axis=-1, dtype=jnp.int32)
    return QueryTrace(after_c9=a9, after_c10=a10, screen_survivors=cand,
                      verified=cand, answers=answers)


def knn_query_traced(
    index: DeviceIndex, qr: QueryReprDev, k: int, backend: str = "xla",
    capacity: int | None = None, n_iters: int = 2,
    valid_mask: jnp.ndarray | None = None, **pallas_kw,
):
    """Exact k-NN + :class:`QueryTrace` at the final verified radius:
    ``(nn_idx, nn_d2, exact, trace)`` — the first three outputs are the
    unchanged :func:`knn_query_backend` results."""
    if resolve_knn_backend(backend, k) == "pallas":
        nn_idx, nn_d2, exact = knn_query_pallas(
            index, qr, k, n_iters=n_iters, valid_mask=valid_mask,
            **pallas_kw)
    else:
        nn_idx, nn_d2, exact = knn_query_auto(
            index, qr, k, capacity=capacity, n_iters=n_iters,
            valid_mask=valid_mask)
    k_eff = min(int(k), index.series.shape[0])
    trace = knn_radius_trace(index, qr, nn_d2, k_eff, valid_mask)
    return nn_idx, nn_d2, exact, trace


@functools.partial(jax.jit, static_argnames=("k",))
def mixed_trace(
    index: DeviceIndex, qr: QueryReprDev, epsilon, is_knn, k: int,
    answer, d2, valid_mask: jnp.ndarray | None = None,
) -> QueryTrace:
    """Trace for a served mixed batch at each row's FINAL radius.

    Range rows count at the request ε; k-NN rows at their verified k-th
    candidate distance, recovered from the returned buffers (compact or
    dense layout both work — non-answer slots carry +inf).  ``answers``
    is the per-row answer-set size: in-range rows for range requests,
    ``min(k, finite candidates)`` for k-NN requests.
    """
    Q = qr.q.shape[0]
    eps_req = _eps_qcol(epsilon, Q)
    knn_col = jnp.asarray(is_knn, dtype=bool).reshape(Q, 1)
    d2a = jnp.where(answer, d2, jnp.inf)
    k_eff = max(1, min(int(k), d2a.shape[-1]))
    eps_knn = jnp.sqrt(jnp.maximum(_kth_smallest_rounds(d2a, k_eff), 0.0))
    eps_knn = jnp.where(jnp.isfinite(eps_knn), eps_knn, _SEED_EPS_MAX)
    eps = jnp.where(knn_col, eps_knn, eps_req)
    _, a9, a10 = _cascade_counting(index, qr, eps, valid_mask)
    cand = a10[:, -1]
    n_ans = jnp.sum(jnp.isfinite(d2a), axis=-1, dtype=jnp.int32)
    answers = jnp.where(knn_col[:, 0], jnp.minimum(n_ans, k_eff), n_ans)
    return QueryTrace(after_c9=a9, after_c10=a10, screen_survivors=cand,
                      verified=cand, answers=answers)


@functools.partial(jax.jit, static_argnames=("k", "capacity", "n_iters"))
def mixed_query_and_trace(
    index: DeviceIndex, qr: QueryReprDev, epsilon, is_knn, k: int,
    capacity: int, n_iters: int = 2,
    valid_mask: jnp.ndarray | None = None,
):
    """:func:`mixed_query` + :func:`mixed_trace` fused into ONE jit call.

    The serving layer's traced dispatch uses this instead of two separate
    calls because the counting pass shares its expensive terms with the
    answer pass — the residual gaps and MINDIST² panels depend on the
    index and queries but NOT on the radius — so inside one compilation
    XLA CSEs them and the trace's marginal cost collapses to the per-level
    comparisons and survivor sums (the overhead contract: traced qps ≥
    0.95× untraced, gated by ``benchmarks/obs_overhead.py``).  The answer
    arrays come from the same jaxpr as the standalone call and remain
    bit-identical to it (tested in tests/test_obs.py).

    Both bodies are traced through their ``__wrapped__`` form: a nested
    ``jax.jit`` call lowers to a separate computation that XLA will not
    CSE across, which is precisely the sharing this wrapper exists for.
    """
    idx, answer, d2, overflow = mixed_query.__wrapped__(
        index, qr, epsilon, is_knn, k, capacity, n_iters,
        valid_mask)
    trace = mixed_trace.__wrapped__(index, qr, epsilon, is_knn, k, answer,
                                    d2, valid_mask)
    return idx, answer, d2, overflow, trace


@functools.partial(jax.jit, static_argnames=("k",))
def mixed_query_dense_and_trace(
    index: DeviceIndex, qr: QueryReprDev, epsilon, is_knn, k: int,
    valid_mask: jnp.ndarray | None = None,
):
    """Dense-dispatch twin of :func:`mixed_query_and_trace`.

    Runs ONE cascade chain — the counting chain at the request ε, the
    radius the untraced :func:`mixed_query_dense` itself uses — so the
    alive mask is bitwise the untraced chain's and the answer arrays
    are bit-identical to ``mixed_query_dense`` (asserted in
    tests/test_obs.py) at the cost of the per-level comparisons and
    survivor sums alone.

    Counter semantics follow the *work the dense path actually does*:
    range rows report cascade survivors at ε like every other traced
    path, but k-NN rows are answered by dense brute force — the
    cascade is never consulted for them, every valid candidate is
    distance-verified — so their counters report exactly that
    (``after_c9 = after_c10 = screen_survivors = verified =`` the
    valid row count, ``answers = min(k, valid)``).  This differs from
    the compaction twin (:func:`mixed_trace` counts k-NN rows at the
    verified k-th radius) because the execution strategy differs;
    telemetry describes the strategy, not a hypothetical one.
    Recovering the k-th radius here would need a full-row order
    statistic inside the fused graph, which is exactly the overhead
    the ge95 serving gate exists to forbid.
    """
    Q, B = qr.q.shape[0], index.series.shape[0]
    knn_col = jnp.asarray(is_knn, dtype=bool).reshape(Q, 1)
    eps_req = _eps_qcol(epsilon, Q)
    d2 = verify_distances(index, qr)
    valid = jnp.ones((Q, B), dtype=bool)
    if valid_mask is not None:
        valid &= valid_mask[None, :]
    alive, a9, a10 = _cascade_counting(index, qr, eps_req, valid_mask)
    in_range = alive & (d2 <= eps_req * eps_req)
    answer = jnp.where(knn_col, valid, in_range)
    idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None, :], (Q, B))
    overflow = jnp.zeros((Q,), dtype=bool)
    k_eff = max(1, min(int(k), B))
    n_valid = jnp.sum(valid, axis=-1, dtype=jnp.int32)
    n_ans = jnp.sum(answer, axis=-1, dtype=jnp.int32)
    a9 = jnp.where(knn_col, n_valid[:, None], a9)
    a10 = jnp.where(knn_col, n_valid[:, None], a10)
    cand = a10[:, -1]
    answers = jnp.where(knn_col[:, 0], jnp.minimum(n_ans, k_eff), n_ans)
    trace = QueryTrace(after_c9=a9, after_c10=a10, screen_survivors=cand,
                       verified=cand, answers=answers)
    return idx, answer, jnp.where(answer, d2, jnp.inf), overflow, trace


@jax.jit
def quantized_cascade_trace(
    qindex: QuantizedDeviceIndex, qr: QueryReprDev, epsilon,
) -> QueryTrace:
    """:func:`quantized_screen`, line for line, with counts.

    Per level: widened-C9 survivors then unwidened-C10 survivors (the
    same expressions over the same running alive set as the widened host
    oracle ``search.quantized_fastsax_range_query`` — bit-agreement
    tested); then the series-screen survivor count, which has no host
    counterpart (the host oracle verifies every cascade survivor) and is
    the quantized tier's own pruning figure.  ``verified`` equals the
    screen survivors: exactly the rows the raw mmap tier gathers.
    """
    n = qindex.n
    Q = qr.q.shape[0]
    eps = _eps_qcol(epsilon, Q)
    eps2 = eps * eps
    B = qindex.series.shape[0]
    alive = jnp.ones((Q, B), dtype=bool)
    tab = _mindist_sq_tab(qindex.alphabet)
    _, word_extras = _extra_reps(qindex)
    after_c9, after_c10 = [], []
    for li, N in enumerate(qindex.levels):
        res = _dequant_residuals_dev(qindex, li)
        err = _expand_block_col(qindex.resid_err[li], B)
        gap = jnp.abs(res[None, :] - qr.residuals[li][:, None])
        alive &= gap <= eps + err[None, :]
        after_c9.append(_count_alive(alive))
        cell = tab[qindex.words[li].astype(jnp.int32)[None, :, :],
                   qr.words[li][:, None, :]]
        md_sq = (n / N) * jnp.sum(cell * cell, axis=-1)
        alive &= md_sq <= eps2
        for rep in word_extras:   # extra word kills count under after_c10
            col = qindex.extra[li][rep.name].astype(jnp.int32)
            alive &= rep.dev_bound_sq(col, qr.extra[li][rep.name],
                                      n=n, N=N, tab=tab) <= eps2
        after_c10.append(_count_alive(alive))
    u = _dequant_series_dev(qindex)
    qn = jnp.sum(qr.q * qr.q, axis=-1)
    cross = jnp.dot(qr.q, u.T, precision=_F32,
                    preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qn[:, None] - 2.0 * cross + qindex.norms_sq[None, :],
                     0.0)
    thresh = (eps + qindex.series_err[None, :]) * \
        (1.0 + QUANT_SCREEN_REL) + QUANT_SCREEN_ABS
    keep = alive & (d2 <= thresh * thresh)
    kept = _count_alive(keep)
    return QueryTrace(after_c9=jnp.stack(after_c9, axis=-1),
                      after_c10=jnp.stack(after_c10, axis=-1),
                      screen_survivors=kept, verified=kept,
                      answers=jnp.zeros_like(kept))


@functools.partial(jax.jit, static_argnames=("k",))
def quantized_mixed_trace(
    qindex: QuantizedDeviceIndex, qr: QueryReprDev, epsilon, is_knn, k: int,
    answer, d2,
) -> QueryTrace:
    """:func:`mixed_trace` for the tiered backend: the same final-radius
    recovery from the returned buffers, counted through the widened
    quantized screen."""
    Q = qr.q.shape[0]
    eps_req = _eps_qcol(epsilon, Q)
    knn_col = jnp.asarray(is_knn, dtype=bool).reshape(Q, 1)
    d2a = jnp.where(answer, d2, jnp.inf)
    k_eff = max(1, min(int(k), d2a.shape[-1]))
    eps_knn = jnp.sqrt(jnp.maximum(_kth_smallest_rounds(d2a, k_eff), 0.0))
    eps_knn = jnp.where(jnp.isfinite(eps_knn), eps_knn, _SEED_EPS_MAX)
    eps = jnp.where(knn_col, eps_knn, eps_req)
    trace = quantized_cascade_trace(qindex, qr, eps)
    n_ans = jnp.sum(jnp.isfinite(d2a), axis=-1, dtype=jnp.int32)
    answers = jnp.where(knn_col[:, 0], jnp.minimum(n_ans, k_eff), n_ans)
    return dataclasses.replace(trace, answers=answers)


def quantized_range_query_traced(
    tindex: TieredIndex, qr: QueryReprDev, epsilon,
    capacity: int | None = None, backend: str = "auto",
    max_doublings: int = 8,
):
    """:func:`quantized_range_query` + trace: ``(idx, answer, d2, exact,
    trace)``."""
    idx, answer, d2, exact = quantized_range_query(
        tindex, qr, epsilon,
        options=SearchOptions(capacity=capacity, backend=backend,
                              max_doublings=max_doublings))
    trace = quantized_cascade_trace(tindex.dev, qr, epsilon)
    trace = dataclasses.replace(trace, answers=_count_alive(answer))
    return idx, answer, d2, exact, trace


def quantized_knn_query_traced(
    tindex: TieredIndex, qr: QueryReprDev, k: int,
    capacity: int | None = None, backend: str = "auto",
    max_doublings: int = 8,
):
    """:func:`quantized_knn_query` + trace at the final verified radius:
    ``(nn_idx, nn_d2, exact, trace)``."""
    nn_idx, nn_d2, exact = quantized_knn_query(
        tindex, qr, k,
        options=SearchOptions(capacity=capacity, backend=backend,
                              max_doublings=max_doublings))
    k_eff = min(int(k), tindex.size)
    eps = jnp.sqrt(jnp.maximum(nn_d2[:, k_eff - 1:k_eff], 0.0))
    eps = jnp.where(jnp.isfinite(eps), eps, _SEED_EPS_MAX)
    trace = quantized_cascade_trace(tindex.dev, qr, eps)
    answers = jnp.sum(jnp.isfinite(nn_d2[:, :k_eff]), axis=-1,
                      dtype=jnp.int32)
    return nn_idx, nn_d2, exact, dataclasses.replace(trace, answers=answers)


def device_trace_bytes(index: DeviceIndex, trace: QueryTrace) -> dict:
    """Per-tier bytes for a traced pass over a full-precision index: the
    screen tier streams every row's f32 residual + int32 word columns
    once per query; the verify tier is charged the candidate rows (the
    compact-verify contract — the dense path deliberately streams all
    rows, a dense>sparse tradeoff, so this figure is the *information*
    cost the trace reports, not a dense-path byte meter)."""
    rb = screen_row_bytes(index.levels, index.alphabet)
    return tier_bytes(trace, index.series.shape[0], rb, index.n,
                      verify_itemsize=index.series.dtype.itemsize)


def tiered_trace_bytes(tindex: TieredIndex, trace: QueryTrace) -> dict:
    """Per-tier bytes for a traced quantized pass: the resident screen
    streams the QUANTIZED columns (int8/bf16 itemsizes — the tier's whole
    point) including the dequantized-series screen row; the verify tier
    is charged at the raw mmap tier's itemsize for exactly the rows the
    screen could not exclude."""
    qdev = tindex.dev
    rb = screen_row_bytes(
        qdev.levels, qdev.alphabet,
        resid_itemsize=qdev.residuals[0].dtype.itemsize,
        word_itemsize=qdev.words[0].dtype.itemsize)
    rb += qdev.series.shape[1] * qdev.series.dtype.itemsize
    return tier_bytes(trace, tindex.size, rb, qdev.series.shape[1],
                      verify_itemsize=np.asarray(tindex.raw).dtype.itemsize)
