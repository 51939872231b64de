"""Distributed FAST_SAX: the database sharded over a mesh axis (shard_map).

The paper's sequential database scan becomes, on a TPU pod:

  * the series database (and every per-level representation) is sharded over
    the mesh ``data`` axis — each device owns B/P contiguous rows;
  * queries are replicated; each shard runs the vectorised masked cascade of
    ``core/engine.py`` on its rows (embarrassingly parallel — zero
    collectives in the hot path);
  * each shard compacts its survivors into a fixed-capacity (idx, d²) buffer;
    the buffers concatenate across shards via the output sharding (an
    all-gather only when the caller materialises the replicated result);
  * a global survivor count (``psum``) drives the host-side early-exit
    across cascade levels (two-phase: cheap count, then compaction).

Padding rows (added to make B divisible by the shard count) carry a huge
sentinel residual at level 0, so exclusion condition C9 kills them for any
finite ε — they can never reach the answer set.
"""
from __future__ import annotations

import collections
import dataclasses
import statistics
import time
from concurrent import futures as _futures
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import representation as repr_registry
from .engine import (_KNN_SEED_SAMPLE, _SEED_EPS_MAX, DeviceIndex,
                     QuantizedDeviceIndex, QueryReprDev, _compact_mask,
                     _eps_qcol, _kth_smallest, _sample_eps,
                     _screen_upper_bounds, _slacked, _tighten_keep,
                     _verify_tier,
                     build_device_index, cascade_mask, cascade_trace,
                     compact_answers, knn_query, knn_query_pallas,
                     mixed_query, mixed_query_pallas, quantized_mixed_query,
                     quantized_screen, range_query_compact,
                     range_query_pallas, represent_queries, resolve_backend,
                     resolve_knn_backend, stack_backend)
from .options import SearchOptions, resolve_options
from .representation import DEFAULT_STACK
from ..runtime import chaos
from ..runtime.fault_tolerance import StepWatchdog

_PAD_RESIDUAL = 1e30  # sentinel: C9 kills padded rows for any finite epsilon


def _stack_of(index) -> tuple:
    return tuple(getattr(index, "stack", DEFAULT_STACK))


def _extra_specs(stack: tuple, levels: tuple, axis: str):
    """shard_map spec trees for the stack's extra columns, (index-side,
    query-side): word columns are (B, N) → ``P(axis, None)``, gap columns
    (B,) → ``P(axis)``; the query side is replicated.  Both are ``()``
    for the default paper stack (matching the empty ``extra`` tuples)."""
    reps = [repr_registry.get(nm)
            for nm in repr_registry.extra_names(stack)]
    if not reps:
        return (), ()
    lvl_ix = {r.name: (P(axis) if r.kind == "gap" else P(axis, None))
              for r in reps}
    lvl_q = {r.name: P() for r in reps}
    return (tuple(dict(lvl_ix) for _ in levels),
            tuple(dict(lvl_q) for _ in levels))


def _coerce_dist_options(options, legacy: dict):
    """Legacy positional ``capacity_per_shard`` (int) in the ``options``
    slot routes through the deprecation shim."""
    if isinstance(options, int):
        legacy["capacity_per_shard"] = options
        return None
    return options


def pad_database(series: np.ndarray, shards: int):
    """Pad B up to a multiple of ``shards``.  Returns (padded, n_valid)."""
    B = series.shape[0]
    Bp = (B + shards - 1) // shards * shards
    if Bp == B:
        return series, B
    pad = np.zeros((Bp - B, series.shape[1]), dtype=series.dtype)
    # Any finite content works — the sentinel residual guarantees exclusion.
    pad[:] = np.linspace(-1.0, 1.0, series.shape[1])[None, :]
    return np.concatenate([series, pad], axis=0), B


def distributed_build(
    series,
    levels: Sequence[int],
    alphabet: int,
    mesh: Mesh,
    axis: str = "data",
    n_valid: int | None = None,
    stack: tuple = DEFAULT_STACK,
) -> DeviceIndex:
    """Offline phase on the mesh: every shard indexes its own rows.

    ``stack`` names the representation stack (``core/representation.py``);
    extra columns are computed shard-locally and sharded like the
    canonical ones."""
    levels = tuple(int(N) for N in levels)
    stack = repr_registry.validate_stack(stack)
    P_sh = mesh.shape[axis]
    B = series.shape[0]
    if B % P_sh != 0:
        raise ValueError(f"pad first: B={B} not divisible by shards={P_sh}")
    n_valid = B if n_valid is None else int(n_valid)
    b_loc = B // P_sh

    def build_local(s):
        idx = build_device_index(s, levels, alphabet, stack=stack)
        shard = jax.lax.axis_index(axis)
        rows = shard * b_loc + jnp.arange(b_loc)
        res0 = jnp.where(rows < n_valid, idx.residuals[0], _PAD_RESIDUAL)
        return (idx.series, idx.norms_sq,
                (res0,) + tuple(idx.residuals[1:]), idx.words, idx.extra)

    ex_ix, _ = _extra_specs(stack, levels, axis)
    out_specs = (P(axis, None), P(axis),
                 tuple(P(axis) for _ in levels),
                 tuple(P(axis, None) for _ in levels), ex_ix)
    built = jax.shard_map(
        build_local, mesh=mesh,
        in_specs=P(axis, None), out_specs=out_specs, check_vma=False,
    )(jnp.asarray(series, dtype=jnp.float32))
    s, norms, residuals, words, extra = built
    return DeviceIndex(series=s, norms_sq=norms, words=words,
                       residuals=residuals, extra=extra, levels=levels,
                       alphabet=alphabet, stack=stack)


def distributed_range_query(
    index: DeviceIndex,
    queries,
    epsilon,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    **legacy,
):
    """Range query over the sharded database.

    Returns (global_idx (Q, P·C), is_answer (Q, P·C), d2 (Q, P·C),
    overflow (Q, P)): every shard contributes ``options.capacity``
    candidate slots (default 128); ``overflow[q, p]`` flags a shard whose
    survivors did not fit (re-run with larger capacity — soundness is
    never silently lost).

    Knobs ride in ``options`` (:class:`SearchOptions`) — ``backend``
    selects the per-shard engine (``engine.resolve_backend``; extended
    stacks demote Pallas to XLA via ``engine.stack_backend``): the XLA
    cascade or the fused Pallas megakernel, whose dense answers are
    compacted into the same per-shard buffer convention by the
    ``compact_answers`` epilogue.  The old ``capacity_per_shard=`` /
    ``normalize_queries=`` / ``backend=`` kwargs shim through with a
    :class:`DeprecationWarning`.
    """
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy, "distributed_range_query")
    if rest:
        raise TypeError(f"distributed_range_query: unexpected kwargs "
                        f"{sorted(rest)}")
    capacity_per_shard = 128 if opts.capacity is None else int(opts.capacity)
    levels, alphabet = index.levels, index.alphabet
    stack = _stack_of(index)
    P_sh = mesh.shape[axis]
    b_loc = index.series.shape[0] // P_sh
    be = stack_backend(index, resolve_backend(opts.backend))
    qr = represent_queries(jnp.asarray(queries, dtype=jnp.float32),
                           levels, alphabet, normalize=opts.normalize_queries,
                           stack=stack)
    eps = jnp.asarray(epsilon, dtype=jnp.float32)

    def local(series, norms, residuals, words, extra, q, qws, qrs, qex, eps_):
        lidx = DeviceIndex(series=series, norms_sq=norms, words=words,
                           residuals=residuals, extra=extra, levels=levels,
                           alphabet=alphabet, stack=stack)
        lqr = QueryReprDev(q=q, words=qws, residuals=qrs, extra=qex)
        if be == "pallas":
            dense_ans, dense_d2 = range_query_pallas(lidx, lqr, eps_)
            idx, ans, d2, overflow = compact_answers(
                dense_ans, dense_d2, capacity_per_shard)
        else:
            idx, ans, d2, overflow = range_query_compact(
                lidx, lqr, eps_, capacity_per_shard)
        gidx = idx + jax.lax.axis_index(axis) * b_loc
        return gidx, ans, d2, overflow[:, None]

    ex_ix, ex_q = _extra_specs(stack, levels, axis)
    in_specs = (P(axis, None), P(axis),
                tuple(P(axis) for _ in levels),
                tuple(P(axis, None) for _ in levels), ex_ix,
                P(), (P(),) * len(levels), (P(),) * len(levels), ex_q, P())
    out_specs = (P(None, axis), P(None, axis), P(None, axis), P(None, axis))
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(index.series, index.norms_sq, index.residuals, index.words, index.extra,
      qr.q, qr.words, qr.residuals, qr.extra, eps)


def distributed_range_query_auto(
    index: DeviceIndex,
    queries,
    epsilon,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    **legacy,
):
    """Range query with the engine's capacity auto-escalation contract.

    Runs :func:`distributed_range_query`; while any shard reports overflow
    (its survivors did not fit in the per-shard capacity slots — served
    answers would be silently truncated), re-runs with 4× the per-shard
    capacity, capped at the shard size where compaction can never overflow.
    Mirrors ``engine.range_query_auto`` for the sharded database; each
    distinct capacity compiles once and is cached by jit.  Old kwargs
    shim through with a :class:`DeprecationWarning`.
    """
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy,
                                 "distributed_range_query_auto")
    if rest:
        raise TypeError(f"distributed_range_query_auto: unexpected kwargs "
                        f"{sorted(rest)}")
    P_sh = mesh.shape[axis]
    b_loc = index.series.shape[0] // P_sh
    cap = min(128 if opts.capacity is None else int(opts.capacity), b_loc)
    for _ in range(opts.max_doublings + 1):
        gidx, ans, d2, overflow = distributed_range_query(
            index, queries, epsilon, mesh, axis=axis,
            options=dataclasses.replace(opts, capacity=cap))
        if cap >= b_loc or not bool(np.asarray(overflow).any()):
            return gidx, ans, d2, overflow
        cap = min(b_loc, cap * 4)
    return gidx, ans, d2, overflow


def distributed_mixed_query(
    index: DeviceIndex,
    queries,
    epsilon,
    is_knn,
    k: int,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    n_valid: int | None = None,
    **legacy,
):
    """Batched mixed-workload dispatch over the sharded database.

    The serving layer's one device round-trip per micro-batch: every shard
    runs ``engine.mixed_query`` on its rows (range rows prune at the
    caller's ε, k-NN rows self-tighten on shard-local data — zero
    collectives in the cascade, exactly the dedicated paths' physics) and
    contributes a ``capacity_per_shard``-slot candidate buffer.  The
    buffers concatenate through the output sharding; the k-NN merge over
    P·C candidates happens on the host side of the materialised result
    (``mixed_topk``), identical to ``distributed_knn_query``'s merge
    argument: each shard's buffer contains its local top-k, and the global
    top-k is a subset of the union of local top-k sets.

    Returns ``(gidx (Q, P·C), answer (Q, P·C), d2 (Q, P·C), overflow
    (Q, P))``.  For range rows ``answer`` marks verified in-range slots;
    for k-NN rows it marks candidate slots — finish with
    ``mixed_topk(gidx, d2, k)``.  Any True in ``overflow[q]`` means row q's
    buffer truncated on that shard (range: answers may be missing; k-NN:
    certificate failed) — escalate the per-shard capacity and re-dispatch.
    Knobs ride in ``options`` (:class:`SearchOptions`); old kwargs shim
    through with a :class:`DeprecationWarning`.
    """
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy, "distributed_mixed_query")
    if rest:
        raise TypeError(f"distributed_mixed_query: unexpected kwargs "
                        f"{sorted(rest)}")
    n_iters = opts.n_iters
    levels, alphabet = index.levels, index.alphabet
    stack = _stack_of(index)
    P_sh = mesh.shape[axis]
    B = index.series.shape[0]
    b_loc = B // P_sh
    n_valid = B if n_valid is None else int(n_valid)
    k_loc = min(int(k), b_loc)
    cap = min(128 if opts.capacity is None else int(opts.capacity), b_loc)
    # The mixed pallas path's tightening passes unroll the k-NN selection,
    # so large k demotes per shard exactly like distributed_knn_query;
    # extended stacks demote likewise (engine.stack_backend).
    be = stack_backend(index, resolve_knn_backend(opts.backend, k_loc))
    qr = represent_queries(jnp.asarray(queries, dtype=jnp.float32),
                           levels, alphabet, normalize=opts.normalize_queries,
                           stack=stack)
    eps = jnp.asarray(epsilon, dtype=jnp.float32)
    knn_mask = jnp.asarray(is_knn, dtype=bool)

    def local(series, norms, residuals, words, extra, q, qws, qrs, qex,
              eps_, knn_):
        lidx = DeviceIndex(series=series, norms_sq=norms, words=words,
                           residuals=residuals, extra=extra, levels=levels,
                           alphabet=alphabet, stack=stack)
        lqr = QueryReprDev(q=q, words=qws, residuals=qrs, extra=qex)
        shard = jax.lax.axis_index(axis)
        rows = shard * b_loc + jnp.arange(b_loc, dtype=jnp.int32)
        vmask = (rows < n_valid) & (residuals[0] < 0.5 * _PAD_RESIDUAL)
        if be == "pallas":
            _, dense_ans, dense_d2, _ = mixed_query_pallas(
                lidx, lqr, eps_, knn_, k_loc, n_iters=n_iters,
                valid_mask=vmask)
            idx, answer, d2, overflow = compact_answers(
                dense_ans, dense_d2, cap)
        else:
            idx, answer, d2, overflow = mixed_query(
                lidx, lqr, eps_, knn_, k_loc, capacity=cap, n_iters=n_iters,
                valid_mask=vmask)
        gidx = jnp.where(answer, idx + shard * b_loc, -1)
        return gidx, answer, d2, overflow[:, None]

    ex_ix, ex_q = _extra_specs(stack, levels, axis)
    in_specs = (P(axis, None), P(axis),
                tuple(P(axis) for _ in levels),
                tuple(P(axis, None) for _ in levels), ex_ix,
                P(), (P(),) * len(levels), (P(),) * len(levels), ex_q,
                P(), P())
    out_specs = (P(None, axis), P(None, axis), P(None, axis), P(None, axis))
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(index.series, index.norms_sq, index.residuals, index.words, index.extra,
      qr.q, qr.words, qr.residuals, qr.extra, eps, knn_mask)


def distributed_mixed_query_auto(
    index: DeviceIndex,
    queries,
    epsilon,
    is_knn,
    k: int,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    n_valid: int | None = None,
    **legacy,
):
    """:func:`distributed_mixed_query` under the capacity auto-escalation
    contract: 4× the per-shard capacity while any shard overflows, capped
    at the shard size (guaranteed sound there).  Old kwargs shim through
    with a :class:`DeprecationWarning`."""
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy,
                                 "distributed_mixed_query_auto")
    if rest:
        raise TypeError(f"distributed_mixed_query_auto: unexpected kwargs "
                        f"{sorted(rest)}")
    P_sh = mesh.shape[axis]
    b_loc = index.series.shape[0] // P_sh
    cap = min(128 if opts.capacity is None else int(opts.capacity), b_loc)
    for _ in range(opts.max_doublings + 1):
        out = distributed_mixed_query(
            index, queries, epsilon, is_knn, k, mesh, axis=axis,
            options=dataclasses.replace(opts, capacity=cap), n_valid=n_valid)
        if cap >= b_loc or not bool(np.asarray(out[3]).any()):
            return out
        cap = min(b_loc, cap * 4)
    return out


def distributed_knn_query(
    index: DeviceIndex,
    queries,
    k: int,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    n_valid: int | None = None,
    **legacy,
):
    """Exact k-NN over the sharded database: local top-k, cross-shard merge.

    Each shard runs the batched best-so-far engine (``engine.knn_query``)
    over its own rows — zero collectives in the cascade hot path — and
    emits its local top-k as (global index, d²) pairs sorted ascending by
    distance.  The per-shard buffers concatenate through the output
    sharding (the only cross-device movement, an all-gather of Q·P·k pairs
    when the result is materialised) and a final top-k over the P·k merged
    pairs yields the exact global answer: the global top-k is always a
    subset of the union of per-shard top-k sets.

    Padded rows (``pad_database``) are excluded via the per-shard valid
    mask, so they can never enter an answer even at huge radii; shards
    holding fewer than k valid rows contribute ``+inf`` slots that lose
    every merge comparison.

    Returns (nn_idx (Q, k'), nn_d2 (Q, k'), exact (Q,)) with
    ``k' = min(k, B_local)·P ≥ min(k, B)`` entries merged down to
    ``min(k, n_valid)`` — callers read the first min(k, n_valid) columns;
    slots beyond the valid count carry d² = +inf and index −1.  ``exact``
    is the AND of every shard's exactness certificate; on False, re-run
    with a larger ``capacity_per_shard`` (``None`` defaults to the full
    shard size, which can never overflow — always exact).  On the pallas
    backend the certificate instead comes from the block-boundary
    near-tie detector (``engine.knn_query_pallas``); on a rare False,
    re-run with ``backend="xla"``.

    ``n_valid`` is optional: padded rows are *always* recognised by the
    sentinel residual ``distributed_build`` stamps on them (the range path
    relies on the same sentinel), so the k-NN seed sample can never pick
    one up even when the caller does not pass ``n_valid``.

    Knobs ride in ``options`` (:class:`SearchOptions`); the old
    ``capacity_per_shard=`` / ``n_iters=`` / ``backend=`` kwargs shim
    through with a :class:`DeprecationWarning`.
    """
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy, "distributed_knn_query")
    if rest:
        raise TypeError(f"distributed_knn_query: unexpected kwargs "
                        f"{sorted(rest)}")
    n_iters = opts.n_iters
    levels, alphabet = index.levels, index.alphabet
    stack = _stack_of(index)
    P_sh = mesh.shape[axis]
    B = index.series.shape[0]
    b_loc = B // P_sh
    n_valid = B if n_valid is None else int(n_valid)
    k_loc = min(int(k), b_loc)
    cap = b_loc if opts.capacity is None else min(int(opts.capacity), b_loc)
    # Large k demotes the per-shard engine to XLA (engine.resolve_knn_backend)
    # rather than compiling an ever-longer unrolled selection kernel;
    # extended stacks demote likewise (engine.stack_backend).
    be = stack_backend(index, resolve_knn_backend(opts.backend, k_loc))
    qr = represent_queries(jnp.asarray(queries, dtype=jnp.float32),
                           levels, alphabet, normalize=opts.normalize_queries,
                           stack=stack)

    def local(series, norms, residuals, words, extra, q, qws, qrs, qex):
        lidx = DeviceIndex(series=series, norms_sq=norms, words=words,
                           residuals=residuals, extra=extra, levels=levels,
                           alphabet=alphabet, stack=stack)
        lqr = QueryReprDev(q=q, words=qws, residuals=qrs, extra=qex)
        shard = jax.lax.axis_index(axis)
        rows = shard * b_loc + jnp.arange(b_loc, dtype=jnp.int32)
        # Padded rows carry the _PAD_RESIDUAL sentinel at level 0 — the
        # authoritative marker (n_valid merely narrows it further).  The
        # range path is safe on the sentinel alone (C9 kills pads at any
        # finite ε); k-NN must ALSO keep pads out of its seed sample,
        # where no ε exists yet.
        vmask = (rows < n_valid) & (residuals[0] < 0.5 * _PAD_RESIDUAL)
        if be == "pallas":
            nn_idx, nn_d2, exact = knn_query_pallas(
                lidx, lqr, k_loc, n_iters=n_iters, valid_mask=vmask)
        else:
            nn_idx, nn_d2, exact = knn_query(
                lidx, lqr, k_loc, capacity=cap, n_iters=n_iters,
                valid_mask=vmask)
        finite = jnp.isfinite(nn_d2)
        gidx = jnp.where(finite, nn_idx + shard * b_loc, -1)
        return gidx, nn_d2, exact[:, None]

    ex_ix, ex_q = _extra_specs(stack, levels, axis)
    in_specs = (P(axis, None), P(axis),
                tuple(P(axis) for _ in levels),
                tuple(P(axis, None) for _ in levels), ex_ix,
                P(), (P(),) * len(levels), (P(),) * len(levels), ex_q)
    out_specs = (P(None, axis), P(None, axis), P(None, axis))
    gidx, d2, certs = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(index.series, index.norms_sq, index.residuals, index.words, index.extra,
      qr.q, qr.words, qr.residuals, qr.extra)

    # Cross-shard merge: stable top-k over the concatenated (d², idx) pairs.
    # Slot order is shard-major with each shard ascending by (d², index), so
    # equal distances resolve to the lowest global index — the same
    # deterministic tie-break as every other engine.
    k_out = min(int(k), gidx.shape[-1])
    neg, pos = jax.lax.top_k(-d2, k_out)
    nn_d2 = -neg
    nn_idx = jnp.take_along_axis(gidx, pos, axis=-1)
    return nn_idx, nn_d2, jnp.all(certs, axis=-1)


def distributed_survivor_count(
    index: DeviceIndex,
    queries,
    epsilon,
    mesh: Mesh,
    axis: str = "data",
    normalize_queries: bool = True,
):
    """Phase-1 global survivor count per query (one psum) — used to size the
    compaction capacity and for the host-side level early-exit."""
    levels, alphabet = index.levels, index.alphabet
    stack = _stack_of(index)
    qr = represent_queries(jnp.asarray(queries, dtype=jnp.float32),
                           levels, alphabet, normalize=normalize_queries,
                           stack=stack)
    eps = jnp.asarray(epsilon, dtype=jnp.float32)

    def local(series, norms, residuals, words, extra, q, qws, qrs, qex, eps_):
        lidx = DeviceIndex(series=series, norms_sq=norms, words=words,
                           residuals=residuals, extra=extra, levels=levels,
                           alphabet=alphabet, stack=stack)
        lqr = QueryReprDev(q=q, words=qws, residuals=qrs, extra=qex)
        alive = cascade_mask(lidx, lqr, eps_)
        return jax.lax.psum(alive.sum(axis=-1), axis)

    ex_ix, ex_q = _extra_specs(stack, levels, axis)
    in_specs = (P(axis, None), P(axis),
                tuple(P(axis) for _ in levels),
                tuple(P(axis, None) for _ in levels), ex_ix,
                P(), (P(),) * len(levels), (P(),) * len(levels), ex_q, P())
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False,
    )(index.series, index.norms_sq, index.residuals, index.words, index.extra,
      qr.q, qr.words, qr.residuals, qr.extra, eps)


def distributed_cascade_trace(
    index: DeviceIndex,
    queries,
    epsilon,
    mesh: Mesh,
    axis: str = "data",
    normalize_queries: bool = True,
    n_valid: int | None = None,
):
    """Cascade telemetry over the sharded database (DESIGN.md §10).

    Each shard runs ``engine.cascade_trace`` on its own rows with the pad
    sentinel folded into the INITIAL alive set (pad rows never count as
    C9 exclusions), then every counter field psums over the mesh axis.
    The cascade is row-independent, so the per-level sums equal the
    single-host trace over the unsharded database exactly — the merged
    trace bit-agrees with the op-counted host engine the same way the
    single-device trace does (tests/test_obs.py).

    ``epsilon`` may be scalar or per-query (Q,).  ``answers`` comes back
    zero (the trace pass never verifies); the traced query wrappers below
    patch it from their answer buffers.
    """
    levels, alphabet = index.levels, index.alphabet
    stack = _stack_of(index)
    P_sh = mesh.shape[axis]
    B = index.series.shape[0]
    b_loc = B // P_sh
    n_valid = B if n_valid is None else int(n_valid)
    qr = represent_queries(jnp.asarray(queries, dtype=jnp.float32),
                           levels, alphabet, normalize=normalize_queries,
                           stack=stack)
    eps = jnp.asarray(epsilon, dtype=jnp.float32)

    def local(series, norms, residuals, words, extra, q, qws, qrs, qex, eps_):
        lidx = DeviceIndex(series=series, norms_sq=norms, words=words,
                           residuals=residuals, extra=extra, levels=levels,
                           alphabet=alphabet, stack=stack)
        lqr = QueryReprDev(q=q, words=qws, residuals=qrs, extra=qex)
        shard = jax.lax.axis_index(axis)
        rows = shard * b_loc + jnp.arange(b_loc, dtype=jnp.int32)
        vmask = (rows < n_valid) & (residuals[0] < 0.5 * _PAD_RESIDUAL)
        tr = cascade_trace(lidx, lqr, eps_, vmask)
        return jax.tree_util.tree_map(lambda c: jax.lax.psum(c, axis), tr)

    ex_ix, ex_q = _extra_specs(stack, levels, axis)
    in_specs = (P(axis, None), P(axis),
                tuple(P(axis) for _ in levels),
                tuple(P(axis, None) for _ in levels), ex_ix,
                P(), (P(),) * len(levels), (P(),) * len(levels), ex_q, P())
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False,
    )(index.series, index.norms_sq, index.residuals, index.words, index.extra,
      qr.q, qr.words, qr.residuals, qr.extra, eps)


def distributed_range_query_traced(
    index: DeviceIndex,
    queries,
    epsilon,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    n_valid: int | None = None,
    **legacy,
):
    """:func:`distributed_range_query_auto` + merged trace: ``(gidx, ans,
    d2, overflow, trace)`` — the first four outputs are the unchanged
    untraced call.  Old kwargs shim through with a
    :class:`DeprecationWarning`."""
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy,
                                 "distributed_range_query_traced")
    if rest:
        raise TypeError(f"distributed_range_query_traced: unexpected kwargs "
                        f"{sorted(rest)}")
    gidx, ans, d2, overflow = distributed_range_query_auto(
        index, queries, epsilon, mesh, axis=axis, options=opts)
    trace = distributed_cascade_trace(
        index, queries, epsilon, mesh, axis=axis,
        normalize_queries=opts.normalize_queries, n_valid=n_valid)
    answers = jnp.sum(ans, axis=-1, dtype=jnp.int32)
    return gidx, ans, d2, overflow, dataclasses.replace(trace,
                                                        answers=answers)


def distributed_knn_query_traced(
    index: DeviceIndex,
    queries,
    k: int,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    n_valid: int | None = None,
    **legacy,
):
    """:func:`distributed_knn_query` + merged trace at each query's final
    verified radius: ``(nn_idx, nn_d2, exact, trace)``.

    The radius is the k-th distance of the CROSS-SHARD merged answer (the
    same radius the single-host traced engine reports), so the merged
    counters are comparable across shard counts — and equal the host
    engine's accounting at ``ε = d_k`` exactly.  Old kwargs shim through
    with a :class:`DeprecationWarning`.
    """
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy,
                                 "distributed_knn_query_traced")
    if rest:
        raise TypeError(f"distributed_knn_query_traced: unexpected kwargs "
                        f"{sorted(rest)}")
    nn_idx, nn_d2, exact = distributed_knn_query(
        index, queries, k, mesh, axis=axis, options=opts, n_valid=n_valid)
    B = index.series.shape[0]
    k_eff = min(int(k), nn_d2.shape[-1],
                B if n_valid is None else int(n_valid))
    eps = jnp.sqrt(jnp.maximum(nn_d2[:, k_eff - 1], 0.0))       # (Q,)
    eps = jnp.where(jnp.isfinite(eps), eps, _SEED_EPS_MAX)
    trace = distributed_cascade_trace(
        index, queries, eps, mesh, axis=axis,
        normalize_queries=opts.normalize_queries, n_valid=n_valid)
    answers = jnp.sum(jnp.isfinite(nn_d2[:, :k_eff]), axis=-1,
                      dtype=jnp.int32)
    return nn_idx, nn_d2, exact, dataclasses.replace(trace, answers=answers)


def make_data_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    """A 1-D device mesh over the available devices (CPU test helper)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.asarray(devs[:n]), (axis,))


# ---------------------------------------------------------------------------
# Stream-sharded subsequence dispatch (DESIGN.md §8).
#
# The subsequence workload shards over *streams*: each device owns S/P
# contiguous streams and derives its own windows locally (the shared f32
# materialisation of ``core/subseq.device_windows`` runs inside
# shard_map, so no host ever assembles the global (W, w) window matrix).
# Because windows are numbered stream-major, the per-shard window rows
# are contiguous in the global window id space and the result is an
# ordinary sharded DeviceIndex over windows — every distributed engine
# above consumes it unchanged, padding killed by the same C9 sentinel.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DistSubseqIndex:
    """Sharded windows-as-rows index + the subsequence geometry needed to
    map window ids back to (stream, start) and to size exclusion zones.
    ``n_valid`` counts real windows (padded streams sort last, so valid
    window ids coincide with the single-device canonical layout)."""

    index: DeviceIndex
    window: int
    stride: int
    windows_per_stream: int
    n_valid: int


def distributed_subseq_index(
    hidx,
    mesh: Mesh,
    axis: str = "data",
) -> DistSubseqIndex:
    """Build the stream-sharded subsequence index from a host
    ``core/subseq.SubseqHostIndex``: pad the stream batch to a multiple
    of the shard count (padded streams' windows carry the sentinel
    residual), shard streams and their window features contiguously, and
    materialise each shard's z windows on its own device."""
    from .subseq import device_windows

    P_sh = mesh.shape[axis]
    S, n_stream = hidx.streams.shape
    W_s = hidx.windows_per_stream
    S_p = (S + P_sh - 1) // P_sh * P_sh
    window, stride = hidx.window, hidx.stride
    levels = tuple(lv.n_segments for lv in hidx.levels)
    alphabet = hidx.config.alphabet

    stack = tuple(getattr(hidx.config, "stack", DEFAULT_STACK))

    pad_s = S_p - S
    pad_w = pad_s * W_s
    streams_p = np.concatenate(
        [hidx.streams,
         np.broadcast_to(np.linspace(-1.0, 1.0, n_stream), (pad_s, n_stream))],
        axis=0) if pad_s else hidx.streams
    mu_p = np.concatenate([hidx.mu, np.zeros(pad_w)])
    sd_p = np.concatenate([hidx.sd, np.ones(pad_w)])
    res_p, words_p, extra_p = [], [], []
    for li, lv in enumerate(hidx.levels):
        fill = _PAD_RESIDUAL if li == 0 else 0.0
        res_p.append(np.concatenate(
            [lv.residuals, np.full(pad_w, fill)]).astype(np.float32))
        words_p.append(np.concatenate(
            [lv.words, np.zeros((pad_w, lv.n_segments), np.int32)]).astype(
                np.int32))
        # Extra columns pad with zeros — the level-0 sentinel residual
        # kills padded windows before any extra bound is consulted.
        d = {}
        for name, arr in getattr(lv, "extra", {}).items():
            rep = repr_registry.get(name)
            pad_shape = (pad_w,) + arr.shape[1:]
            dt = np.int32 if rep.kind == "word" else np.float32
            d[name] = np.concatenate(
                [arr, np.zeros(pad_shape, arr.dtype)]).astype(dt)
        extra_p.append(d)
    extra_p = tuple(extra_p) if repr_registry.extra_names(stack) else ()

    def local(streams_loc, mu_loc, sd_loc, residuals_loc, words_loc,
              extra_loc):
        series = device_windows(streams_loc, window, stride, mu_loc, sd_loc)
        return (series, jnp.sum(series * series, axis=-1),
                residuals_loc, words_loc, extra_loc)

    ex_ix, _ = _extra_specs(stack, levels, axis)
    in_specs = (P(axis, None), P(axis), P(axis),
                tuple(P(axis) for _ in levels),
                tuple(P(axis, None) for _ in levels), ex_ix)
    out_specs = (P(axis, None), P(axis),
                 tuple(P(axis) for _ in levels),
                 tuple(P(axis, None) for _ in levels), ex_ix)
    series, norms, residuals, words, extra = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(jnp.asarray(streams_p, jnp.float32), jnp.asarray(mu_p, jnp.float32),
      jnp.asarray(sd_p, jnp.float32), tuple(jnp.asarray(r) for r in res_p),
      tuple(jnp.asarray(w) for w in words_p),
      jax.tree_util.tree_map(jnp.asarray, extra_p))
    index = DeviceIndex(series=series, norms_sq=norms, words=words,
                        residuals=residuals, extra=extra, levels=levels,
                        alphabet=alphabet, stack=stack)
    return DistSubseqIndex(index=index, window=window, stride=stride,
                           windows_per_stream=W_s, n_valid=S * W_s)


def distributed_subseq_range_query(
    dsx: DistSubseqIndex,
    queries,
    epsilon,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    **legacy,
):
    """Stream-sharded subsequence range query — exactly
    :func:`distributed_range_query_auto` over the windows-as-rows index
    (the sentinel residual keeps padded-stream windows out at any finite
    ε).  Answers are global window ids; map through
    ``(wid // windows_per_stream, (wid % windows_per_stream) · stride)``.
    Old kwargs shim through with a :class:`DeprecationWarning`.
    """
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy,
                                 "distributed_subseq_range_query")
    if rest:
        raise TypeError(f"distributed_subseq_range_query: unexpected kwargs "
                        f"{sorted(rest)}")
    return distributed_range_query_auto(
        dsx.index, queries, epsilon, mesh, axis=axis, options=opts)


def distributed_subseq_knn_query(
    dsx: DistSubseqIndex,
    queries,
    k: int,
    mesh: Mesh,
    excl: int | None = None,
    axis: str = "data",
    options: SearchOptions | None = None,
    **legacy,
):
    """Exact exclusion-zone k-NN over the stream-sharded windows.

    Fetches the provably sufficient ``subseq.knn_fetch_count`` candidates
    through :func:`distributed_knn_query` (local top-k per shard, merged
    ascending by (d², global index) — the order the greedy suppression
    needs) and applies the trivial-match suppression on the host, exactly
    like the single-device ``subseq.subseq_knn_query``.  Returns
    ``(sel_idx (Q, k), sel_d2 (Q, k), exact (Q,))`` host arrays.  Old
    kwargs shim through with a :class:`DeprecationWarning`.
    """
    from .subseq import knn_fetch_count, suppress_trivial_matches

    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy,
                                 "distributed_subseq_knn_query")
    if rest:
        raise TypeError(f"distributed_subseq_knn_query: unexpected kwargs "
                        f"{sorted(rest)}")
    excl = (dsx.window // 2) if excl is None else int(excl)
    kf = knn_fetch_count(k, excl, dsx.stride, dsx.n_valid)
    nn_idx, nn_d2, exact = distributed_knn_query(
        dsx.index, queries, kf, mesh, axis=axis, options=opts,
        n_valid=dsx.n_valid)
    W_s = dsx.windows_per_stream
    wid = np.arange(dsx.index.series.shape[0])
    sel_idx, sel_d2 = suppress_trivial_matches(
        np.asarray(nn_idx), np.asarray(nn_d2), wid // W_s,
        (wid % W_s) * dsx.stride, int(k), excl)
    return sel_idx, sel_d2, np.asarray(exact)


# ---------------------------------------------------------------------------
# Persistence: the sharded index as a long-lived on-disk artifact.
# ---------------------------------------------------------------------------

def store_sharded(index: DeviceIndex, path, n_valid: int | None = None):
    """Persist the sharded index, one store dir per mesh shard — each
    device's rows are written from its own addressable shard, with no
    host-side gather of the global arrays (``repro.index.sharded``)."""
    from ..index.sharded import store_sharded as _store
    return _store(index, path, n_valid=n_valid)


def load_sharded(path, mesh: Mesh, axis: str = "data", verify: bool = False):
    """Warm-start the distributed engine from a sharded store: generation
    file *i* maps directly onto mesh shard *i* (mmap → device_put →
    ``make_array_from_single_device_arrays``).  Returns
    ``(DeviceIndex, n_valid)``; the stored shard count must match the mesh
    axis size."""
    from ..index.sharded import load_sharded as _load
    return _load(path, mesh, axis=axis, verify=verify)


# ---------------------------------------------------------------------------
# Distributed quantized screen — PR 10, DESIGN.md §13.
#
# The quantized resident tier (DESIGN.md §9) runs *inside* shard_map:
# every shard holds its own slice of the int8/bf16 screen columns and
# evaluates the widened C9/series bounds shard-locally, then compacts its
# survivors into a fixed-capacity (global id, valid) buffer.  Only those
# survivor ids cross shards — 5 bytes/slot (int32 id + bool) against the
# full-precision distributed screen's 9 bytes/slot (id + bool + f32 d²),
# and no screen column ever leaves its device.  The raw verify tier stays
# on the host (per-shard mmaps — never concatenated), and the final exact
# verify gathers only the surviving rows, optionally double-buffered
# (``SearchOptions.verify_prefetch``).  Certificates are always exact on
# return: per-shard capacity escalates 4× on overflow up to the shard
# size, where compaction cannot overflow.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DistTieredIndex:
    """Mesh-resident tiered index: quantized screen sharded, raw on host.

    ``dev`` is a :class:`engine.QuantizedDeviceIndex` whose leaves are
    global arrays sharded row-wise over the mesh axis (block-scale
    columns shard per block — row counts are padded to a multiple of
    ``shards × RESID_BLOCK`` so blocks never straddle a shard boundary).
    ``raw`` is the host-side full-precision verify tier — an ndarray,
    ``np.memmap``, or ``index.sharded.ShardedRaw`` — holding ONLY real
    rows (no padding): pad rows carry the level-0 sentinel code, the
    shard-local screen provably kills them, and the verify gather clamps
    ids, so they can never be fetched as answers.
    """

    dev: QuantizedDeviceIndex
    raw: object
    n_valid: int

    @property
    def size(self) -> int:
        return int(self.dev.series.shape[0])

    @property
    def mode(self) -> str:
        return self.dev.mode


def _pad_rows(a, rows: int, fill=0) -> np.ndarray:
    """Pad the leading axis of a host copy of ``a`` up to ``rows``."""
    a = np.asarray(a)
    if a.shape[0] >= rows:
        return a
    pad = np.full((rows - a.shape[0],) + a.shape[1:], fill, dtype=a.dtype)
    return np.concatenate([a, pad], axis=0)


def distributed_tiered_index(
    tindex,
    mesh: Mesh,
    axis: str = "data",
    n_valid: int | None = None,
) -> DistTieredIndex:
    """Reshard a single-host ``engine.TieredIndex`` onto a mesh.

    Rows pad to a multiple of ``shards × RESID_BLOCK`` so (a) every
    shard owns whole scale blocks (the per-block (nb, 1) columns shard
    cleanly) and (b) shard sizes are equal.  Pad rows — and rows at or
    past ``n_valid`` — are stamped with the level-0 sentinel residual
    code, so condition C9 kills them inside the shard-local screen for
    any finite radius; the raw tier is NOT padded (ids clamp at the
    verify gather, and dead slots are masked).
    """
    from ..index import quantized as _q

    qdev = tindex.dev
    int8 = qdev.mode == "int8"
    B = int(qdev.series.shape[0])
    R = int(tindex.raw.shape[0])
    n_valid = min(B, R) if n_valid is None else int(n_valid)
    P_sh = mesh.shape[axis]
    quantum = P_sh * _q.RESID_BLOCK
    Bp = -(-B // quantum) * quantum
    nbp = Bp // _q.RESID_BLOCK
    live = np.arange(Bp) < n_valid

    def put(a, spec):
        return jax.device_put(np.asarray(a), NamedSharding(mesh, spec))

    def rows2(a, fill=0):
        return put(_pad_rows(a, Bp, fill), P(axis, None))

    def rows1(a, fill=0):
        return put(_pad_rows(a, Bp, fill), P(axis))

    def blocks(a, fill=0):
        return put(_pad_rows(a, nbp, fill), P(axis, None))

    res0 = np.array(_pad_rows(qdev.residuals[0], Bp))   # writable copy
    if int8:
        res0[~live] = _q.SENTINEL_CODE
    else:
        res0[~live] = res0.dtype.type(_q.PAD_RESIDUAL)
    residuals = (put(res0, P(axis)),) + tuple(
        rows1(r) for r in qdev.residuals[1:])
    none_t = tuple(None for _ in qdev.levels)
    dev = QuantizedDeviceIndex(
        series=rows2(qdev.series),
        series_scale=rows2(qdev.series_scale, 1.0) if int8 else None,
        series_zero=rows2(qdev.series_zero, 0.0) if int8 else None,
        series_err=rows1(qdev.series_err),
        norms_sq=rows1(qdev.norms_sq),
        words=tuple(rows2(w) for w in qdev.words),
        residuals=residuals,
        resid_scale=tuple(blocks(s, 1.0) for s in qdev.resid_scale)
        if int8 else none_t,
        resid_zero=tuple(blocks(z, 0.0) for z in qdev.resid_zero)
        if int8 else none_t,
        resid_err=tuple(blocks(e) for e in qdev.resid_err),
        extra=tuple({name: rows2(col) for name, col in lvl.items()}
                    for lvl in qdev.extra),
        levels=qdev.levels, alphabet=qdev.alphabet, mode=qdev.mode,
        stack=qdev.stack)
    return DistTieredIndex(dev=dev, raw=tindex.raw, n_valid=n_valid)


def store_sharded_tiered(dti: DistTieredIndex, path):
    """Persist the mesh-resident tiered index, one store dir per shard —
    quantized columns written from device-local shards, the raw tier
    sliced per shard (``index.sharded.store_sharded_quantized``)."""
    from ..index.sharded import store_sharded_quantized as _store
    return _store(dti, path, n_valid=dti.n_valid)


def load_sharded_tiered(path, mesh: Mesh, axis: str = "data",
                        verify: bool = False) -> DistTieredIndex:
    """Warm-start the distributed quantized engine from a tiered sharded
    store: shard file *i*'s quantized columns map onto mesh shard *i*
    with no host-side concatenation, and the raw verify tier stays a set
    of per-shard host mmaps (``index.sharded.load_sharded_tiered``)."""
    from ..index.sharded import load_sharded_tiered as _load
    dev, raw, n_valid = _load(path, mesh, axis=axis, verify=verify)
    return DistTieredIndex(dev=dev, raw=raw, n_valid=n_valid)


def _shard_tree_specs(tree, axis: str):
    """Leafwise shard_map specs: 1-D leaves shard rows (``P(axis)``),
    2-D leaves shard rows and replicate columns (``P(axis, None)``)."""
    return jax.tree_util.tree_map(
        lambda a: P(axis) if a.ndim == 1 else P(axis, None), tree)


def _replicated_specs(tree):
    return jax.tree_util.tree_map(lambda a: P(), tree)


def _dist_quantized_screen(dti: DistTieredIndex, qr, eps_col,
                           mesh: Mesh, axis: str, capacity: int,
                           knn_col=None, k: int = 0):
    """One shard_map round of the quantized screen: every shard runs the
    widened screen on its own resident columns (``engine.quantized_screen``
    — the same jitted oracle as the single-host tier, so the kept set is
    identical by construction) and compacts survivors into a
    ``capacity``-slot (global id, valid) buffer.  With ``k`` the k-NN rows
    (``knn_col``) then shrink their radius to the k-th smallest screen
    upper bound over the whole mesh — each shard's k smallest bounds are
    all-gathered, k values per query per shard — before compaction (the
    distributed twin of ``engine._tighten_tiered_keep``).  Returns
    ``(gidx (Q, P·C), valid (Q, P·C), overflow (Q, P))`` — the only
    arrays that cross shards besides those bounds.
    """
    qdev = dti.dev
    b_loc = dti.size // mesh.shape[axis]
    cap = int(capacity)
    children, aux = qdev.tree_flatten()
    qleaves = (qr.q, qr.words, qr.residuals, qr.extra)
    if knn_col is None:
        knn_col = jnp.zeros((qr.q.shape[0], 1), bool)

    def local(ix_children, ql, eps_, knn_):
        lq = QuantizedDeviceIndex.tree_unflatten(aux, ix_children)
        lqr = QueryReprDev(q=ql[0], words=ql[1], residuals=ql[2],
                           extra=ql[3])
        keep, d2hat = quantized_screen(lq, lqr, eps_)
        if k:
            ub = _screen_upper_bounds(lq, lqr.q, d2hat)
            mine = -jax.lax.top_k(-ub, min(k, b_loc))[0]
            pool = jax.lax.all_gather(mine, axis, axis=1, tiled=True)
            keep = _tighten_keep(lq, keep, d2hat, eps_,
                                 _slacked(_kth_smallest(pool, k)), knn_)
        idx, valid, overflow = _compact_mask(keep, cap)
        gidx = idx + jax.lax.axis_index(axis) * b_loc
        return gidx, valid, overflow[:, None]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(_shard_tree_specs(children, axis),
                  _replicated_specs(qleaves), P(), P()),
        out_specs=(P(None, axis), P(None, axis), P(None, axis)),
        check_vma=False,
    )(children, qleaves, eps_col, knn_col)


def _dist_quant_candidates(dti, qr, eps_col, mesh, axis, opts,
                           cap0: int, knn_col=None, k: int = 0):
    """Escalating screen rounds: re-run with 4× per-shard capacity while
    any shard overflows, capped at the shard size where compaction cannot
    overflow — so the certificate is always exact on return."""
    b_loc = dti.size // mesh.shape[axis]
    cap = min(b_loc, max(1, int(cap0)))
    for _ in range(opts.max_doublings + 1):
        gidx, valid, overflow = _dist_quantized_screen(
            dti, qr, eps_col, mesh, axis, cap, knn_col, k)
        if cap >= b_loc or not bool(np.asarray(overflow).any()):
            break
        cap = min(b_loc, cap * 4)
    return gidx, valid, overflow


def _dist_qr(dti, queries, opts):
    return represent_queries(jnp.asarray(queries, dtype=jnp.float32),
                             dti.dev.levels, dti.dev.alphabet,
                             normalize=opts.normalize_queries,
                             stack=dti.dev.stack)


def _dist_seed_eps(dti: DistTieredIndex, qr, k: int) -> jnp.ndarray:
    """k-NN seed radius: strided verified sample from the host raw tier.
    The stride runs over the raw tier's own (unpadded, real) rows, so the
    sampled k-th distance is a true upper bound of the global k-th."""
    R = int(dti.raw.shape[0])
    S = min(R, max(k, _KNN_SEED_SAMPLE))
    sample = (np.arange(S) * R) // S
    rows = jnp.asarray(np.asarray(dti.raw[sample]), jnp.float32)
    return _sample_eps(rows, qr.q, k)


def distributed_quantized_range_query(
    dti: DistTieredIndex,
    queries,
    epsilon,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    **legacy,
):
    """Exact range query with the quantized screen inside shard_map.

    Returns ``(gidx (Q, P·C), answer (Q, P·C), d2 (Q, P·C), exact (Q,))``
    — set-identical to ``engine.quantized_range_query`` on the same data
    and to the f64 brute-force oracle (tests/test_dist_quantized.py).
    ``exact`` is always True after escalation.  Knobs ride in ``options``
    (:class:`SearchOptions`, including ``verify_prefetch``); the old
    ``capacity_per_shard=`` kwarg shims through with a
    :class:`DeprecationWarning`.
    """
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy,
                                 "distributed_quantized_range_query")
    if rest:
        raise TypeError(f"distributed_quantized_range_query: unexpected "
                        f"kwargs {sorted(rest)}")
    qr = _dist_qr(dti, queries, opts)
    Q = qr.q.shape[0]
    eps = _eps_qcol(epsilon, Q)
    cap0 = 64 if opts.capacity is None else int(opts.capacity)
    gidx, valid, overflow = _dist_quant_candidates(
        dti, qr, eps, mesh, axis, opts, cap0)
    d2 = _verify_tier(dti.raw, gidx, qr.q, valid, opts)
    answer = valid & (d2 <= eps * eps)
    exact = ~jnp.any(overflow, axis=-1)
    return gidx, answer, jnp.where(answer, d2, jnp.inf), exact


def distributed_quantized_knn_query(
    dti: DistTieredIndex,
    queries,
    k: int,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    **legacy,
):
    """Exact k-NN with the quantized screen inside shard_map.

    Seeds a verified radius from the host raw tier, screens every shard
    at the slacked radius, gathers only surviving ids cross-shard,
    exact-verifies them against the raw tier, and takes the global top-k
    (ties to the lowest global index — the engine-wide order).  Returns
    ``(nn_idx (Q, k), nn_d2 (Q, k), exact (Q,))``; ``exact`` is always
    True after escalation.
    """
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy,
                                 "distributed_quantized_knn_query")
    if rest:
        raise TypeError(f"distributed_quantized_knn_query: unexpected "
                        f"kwargs {sorted(rest)}")
    qr = _dist_qr(dti, queries, opts)
    k_eff = max(1, min(int(k), dti.n_valid))
    eps = _dist_seed_eps(dti, qr, k_eff)                     # (Q, 1)
    cap0 = max(4 * k_eff, 64) if opts.capacity is None else int(opts.capacity)
    gidx, valid, overflow = _dist_quant_candidates(
        dti, qr, _slacked(eps), mesh, axis, opts, max(cap0, k_eff),
        jnp.ones((qr.q.shape[0], 1), bool), k_eff)
    d2 = _verify_tier(dti.raw, gidx, qr.q, valid, opts)
    neg, pos = jax.lax.top_k(-d2, k_eff)                     # ascending d2
    nn_d2 = -neg
    nn_idx = jnp.take_along_axis(gidx, pos, axis=-1)
    nn_idx = jnp.where(jnp.isfinite(nn_d2), nn_idx, -1)
    return nn_idx, nn_d2, ~jnp.any(overflow, axis=-1)


def distributed_quantized_mixed_query(
    dti: DistTieredIndex,
    queries,
    epsilon,
    is_knn,
    k: int,
    mesh: Mesh,
    axis: str = "data",
    options: SearchOptions | None = None,
    **legacy,
):
    """Mixed range/k-NN batch over the mesh-resident tiered index —
    serving-layer layout, the distributed twin of
    ``engine.quantized_mixed_query``.

    Returns ``(gidx (Q, P·C), answer (Q, P·C), d2 (Q, P·C), overflow
    (Q,))`` with ``overflow`` all-False after escalation; k-NN rows'
    ``answer`` marks verified candidate slots (a superset of the true
    top-k) — finish with ``engine.mixed_topk(gidx, d2, k)`` exactly like
    the other serving backends.
    """
    options = _coerce_dist_options(options, legacy)
    opts, rest = resolve_options(options, legacy,
                                 "distributed_quantized_mixed_query")
    if rest:
        raise TypeError(f"distributed_quantized_mixed_query: unexpected "
                        f"kwargs {sorted(rest)}")
    qr = _dist_qr(dti, queries, opts)
    Q = qr.q.shape[0]
    k_eff = max(1, min(int(k), dti.n_valid))
    knn_col = jnp.asarray(is_knn, dtype=bool).reshape(Q, 1)
    eps_req = _eps_qcol(epsilon, Q)
    eps = jnp.where(knn_col, _slacked(_dist_seed_eps(dti, qr, k_eff)),
                    eps_req)
    cap0 = max(4 * k_eff, 64) if opts.capacity is None else int(opts.capacity)
    k_tight = k_eff if np.asarray(is_knn).any() else 0   # 0: range only
    gidx, valid, overflow = _dist_quant_candidates(
        dti, qr, eps, mesh, axis, opts, max(cap0, k_eff), knn_col, k_tight)
    d2 = _verify_tier(dti.raw, gidx, qr.q, valid, opts)
    answer = jnp.where(knn_col, valid, valid & (d2 <= eps_req * eps_req))
    gidx = jnp.where(answer, gidx, -1)
    return (gidx, answer, jnp.where(answer, d2, jnp.inf),
            jnp.any(overflow, axis=-1))


# ---------------------------------------------------------------------------
# Failover serving engine — PR 9, DESIGN.md §12.
#
# ``shard_map`` is the right execution model when every device is healthy:
# one collective jit, zero per-shard overhead.  It is exactly the wrong
# model for fault tolerance — the global array couples the shards, so one
# dead device poisons the whole dispatch.  ``FailoverShards`` trades the
# collective for independence: each shard is its own single-device
# ``DeviceIndex`` queried on its own thread with its own timeout, retry
# budget, and health state, and the cross-shard merge happens on the host.
# When every shard answers, the merged result is bit-identical to the
# single-index engines (same per-shard ``mixed_query``, same shard-major
# ascending tie-break as ``distributed_knn_query``); when a shard is lost,
# the survivors still merge into a *certified-partial* answer whose
# ``ShardCoverage`` says exactly what fraction of the database it covers.
# ---------------------------------------------------------------------------


class FailoverError(RuntimeError):
    """No shard produced an answer for a dispatch (all down/failed)."""


def _screen_of(shard):
    """The screen-tier index of a failover shard: a full-precision shard
    IS its screen (``DeviceIndex``); a quantized tiered shard
    (``engine.TieredIndex``) screens through ``.dev``."""
    return shard.dev if hasattr(shard, "dev") else shard


@dataclasses.dataclass(frozen=True)
class ShardCoverage:
    """The degraded-answer certificate: which part of the database this
    answer actually covers.  ``exact`` iff every shard answered — the
    serve layer propagates it onto each request (DESIGN.md §12)."""

    shards_ok: int
    shards_total: int
    rows_ok: int
    rows_total: int

    @property
    def exact(self) -> bool:
        return self.shards_ok == self.shards_total

    def as_dict(self) -> dict:
        return {"exact": self.exact,
                "shards_ok": self.shards_ok,
                "shards_total": self.shards_total,
                "rows_ok": self.rows_ok,
                "rows_total": self.rows_total}


class FailoverShards:
    """Per-shard query execution with timeouts, retries, and failover.

    Health model (all counting is in dispatches/attempts, never wall
    clock, so chaos replays are deterministic):

      * every live shard is queried concurrently (thread pool); a shard's
        attempt is bounded by a per-shard timeout — the base ``timeout_s``
        until the shard's ``StepWatchdog`` rolling-median latency window
        has ``min_samples``, then ``slow_factor × median`` (straggler
        hedging: a slow shard is re-dispatched rather than awaited);
      * a failed/timed-out attempt is retried up to ``retries`` times
        with exponential backoff (``backoff_s · 2^attempt``) — transient
        faults (``chaos.FaultInjected``, flaky reads) heal here;
      * ``down_threshold`` consecutive exhausted dispatches mark the
        shard **down**: it is skipped (not awaited) until every
        ``probe_every``-th dispatch sends a single probe; a probe success
        marks it up again — recovery back to ``exact=True`` answers;
      * the surviving shards' ``(gidx, answer, d2)`` buffers concatenate
        shard-major ascending (the same (d², lowest-index) tie-break as
        the collective engine), and the dispatch returns a
        :class:`ShardCoverage` naming what was covered.  Zero survivors
        raises :class:`FailoverError` — the serve layer's circuit breaker
        counts those.

    Per-shard capacity defaults to the full shard size, so a surviving
    shard's rows are answered *exactly* (no overflow, no escalation) and
    the partial answer equals brute force restricted to covered rows.
    """

    def __init__(
        self,
        shards: Sequence,
        offsets: Optional[Sequence[int]] = None,
        n_valid: Optional[int] = None,
        *,
        timeout_s: float = 30.0,
        retries: int = 2,
        backoff_s: float = 0.02,
        slow_factor: float = 4.0,
        down_threshold: int = 3,
        probe_every: int = 4,
        capacity: Optional[int] = None,
        n_iters: int = 2,
        normalize_queries: bool = False,
        on_event: Optional[Callable[[str, int], None]] = None,
    ):
        if not shards:
            raise ValueError("need at least one shard")
        self.shards = list(shards)
        P_sh = len(self.shards)
        sizes = [int(_screen_of(s).series.shape[0]) for s in self.shards]
        if offsets is None:
            offsets = list(np.cumsum([0] + sizes[:-1]))
        self.offsets = [int(o) for o in offsets]
        self.n_valid = int(sum(sizes) if n_valid is None else n_valid)
        ref = _screen_of(self.shards[0])
        self.levels = tuple(ref.levels)
        self.alphabet = int(ref.alphabet)
        self.stack = tuple(getattr(ref, "stack", DEFAULT_STACK))
        for s in map(_screen_of, self.shards[1:]):
            if (tuple(s.levels) != self.levels
                    or int(s.alphabet) != self.alphabet
                    or tuple(getattr(s, "stack", DEFAULT_STACK))
                    != self.stack):
                raise ValueError("shards disagree on (levels, alphabet, "
                                 "stack) — not one index")
        self.timeout_s = float(timeout_s)
        self.retries = int(retries)
        self.backoff_s = float(backoff_s)
        self.down_threshold = int(down_threshold)
        self.probe_every = max(1, int(probe_every))
        self.capacity = capacity
        self.n_iters = int(n_iters)
        self.normalize_queries = bool(normalize_queries)
        self.on_event = on_event
        self.events: collections.Counter = collections.Counter()

        # Valid-row masks: rows past n_valid or carrying the pad sentinel
        # must never answer (same rule as the collective engines).  None
        # when every row is real — keeps the unmasked jit signature.
        self._vmask, self._rows = [], []
        for si, s in enumerate(self.shards):
            B_s = sizes[si]
            hi = max(0, min(B_s, self.n_valid - self.offsets[si]))
            if hasattr(s, "dev"):
                # Quantized tiered shard: pad rows carry the level-0
                # sentinel CODE and the tiered engine's screen kills them
                # internally — no host-side mask.  Live rows = raw-tier
                # rows within n_valid (the raw slice is trimmed to the
                # live range at load, so the k-NN seed never samples a
                # pad row).
                self._rows.append(int(min(hi, int(s.raw.shape[0]))))
                self._vmask.append(None)
                continue
            live = np.arange(B_s) < hi
            live &= np.asarray(s.residuals[0]) < 0.5 * _PAD_RESIDUAL
            self._rows.append(int(live.sum()))
            self._vmask.append(None if live.all() else jnp.asarray(live))

        self._wd = [StepWatchdog(slow_factor=slow_factor, window=64,
                                 min_samples=5) for _ in range(P_sh)]
        self._fail_streak = [0] * P_sh
        self._down = [False] * P_sh
        self._down_at = [0] * P_sh
        self._dispatch_no = 0
        self._pool = _futures.ThreadPoolExecutor(
            max_workers=max(2, 2 * P_sh),
            thread_name_prefix="repro-failover")

    # --- construction -------------------------------------------------------

    @classmethod
    def from_series(cls, series: np.ndarray, shards: int,
                    levels: Sequence[int], alphabet: int,
                    normalize: bool = False, stack: tuple = DEFAULT_STACK,
                    **kw) -> "FailoverShards":
        """Build per-shard indexes from contiguous row splits of a host
        database (shards may be unequal — no padding rows needed)."""
        series = np.asarray(series, np.float32)
        parts = np.array_split(series, int(shards))
        offsets = list(np.cumsum([0] + [p.shape[0] for p in parts[:-1]]))
        devs = [build_device_index(jnp.asarray(p), levels, alphabet,
                                   normalize=normalize, stack=stack)
                for p in parts]
        return cls(devs, offsets=offsets, **kw)

    @classmethod
    def from_store(cls, path, verify: bool = False,
                   **kw) -> "FailoverShards":
        """Warm-start from a sharded store, keeping each ``shard_*/`` a
        separately-queryable index (``index.sharded.load_shard_indexes``)."""
        from ..index.sharded import load_shard_indexes
        devs, offsets, n_valid = load_shard_indexes(path, verify=verify)
        return cls(devs, offsets=offsets, n_valid=n_valid, **kw)

    # --- introspection ------------------------------------------------------

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def size(self) -> int:
        return self.n_valid

    @property
    def n(self) -> int:
        return int(_screen_of(self.shards[0]).series.shape[-1])

    def shard_states(self) -> list:
        return ["down" if d else "up" for d in self._down]

    def close(self):
        self._pool.shutdown(wait=False)

    # --- health bookkeeping -------------------------------------------------

    def _emit(self, kind: str, n: int = 1):
        self.events[kind] += n
        if self.on_event is not None:
            self.on_event(kind, n)

    def _on_shard_ok(self, si: int):
        self._fail_streak[si] = 0
        if self._down[si]:
            self._down[si] = False
            self._emit("shard_up")

    def _on_shard_fail(self, si: int):
        self._fail_streak[si] += 1
        if (not self._down[si]
                and self._fail_streak[si] >= self.down_threshold):
            self._down[si] = True
            self._down_at[si] = self._dispatch_no
            self._emit("shard_down")

    def _timeout(self, si: int) -> float:
        wd = self._wd[si]
        if len(wd.window) >= wd.min_samples:
            return max(0.05, wd.slow_factor * statistics.median(wd.window))
        return self.timeout_s

    # --- per-shard execution ------------------------------------------------

    def _query_shard(self, si: int, qr, eps_j, knn_j, k: int):
        chaos.maybe_fire("shard_query", key=str(si))
        wd = self._wd[si]
        wd.start(self._dispatch_no)
        idx = self.shards[si]
        B_s = int(_screen_of(idx).series.shape[0])
        k_s = max(1, min(int(k), B_s))
        cap = B_s if self.capacity is None else int(self.capacity)
        cap = max(min(cap, B_s), k_s)
        if hasattr(idx, "dev"):
            # Quantized tiered shard (PR 6 × PR 9): the same per-shard
            # exactness story — quantized_mixed_query escalates until no
            # overflow and exact-verifies survivors against the shard's
            # raw mmap slice, so a surviving shard's rows are answered
            # exactly and the partial-answer certificate holds unchanged.
            ridx, answer, d2, overflow = quantized_mixed_query(
                idx, qr, eps_j, knn_j, k_s,
                options=SearchOptions(capacity=cap))
        else:
            ridx, answer, d2, overflow = mixed_query(
                idx, qr, eps_j, knn_j, k_s, capacity=cap,
                n_iters=self.n_iters, valid_mask=self._vmask[si])
        answer = np.asarray(answer)
        gidx = np.where(answer, np.asarray(ridx) + self.offsets[si], -1)
        out = (gidx, answer, np.asarray(d2), np.asarray(overflow))
        wd.stop()
        return out

    def _collect(self, si: int, fut, probe: bool, qr, eps_j, knn_j,
                 k: int):
        """Await one shard with its timeout; retry transient failures
        with exponential backoff.  Returns the shard result or None."""
        attempts = 1 if probe else self.retries + 1
        for a in range(attempts):
            try:
                out = fut.result(timeout=self._timeout(si))
                self._on_shard_ok(si)
                return out
            except _futures.TimeoutError:
                fut.cancel()
                self._emit("hedges")   # straggler: re-dispatch, don't wait
            except Exception:          # noqa: BLE001 — any shard-local
                pass                   # failure is survivable by design
            if a + 1 < attempts:
                self._emit("retries")
                time.sleep(self.backoff_s * (2 ** a))
                fut = self._pool.submit(self._query_shard, si, qr, eps_j,
                                        knn_j, k)
        self._on_shard_fail(si)
        return None

    # --- the dispatch -------------------------------------------------------

    def query(self, q: np.ndarray, eps: np.ndarray, is_knn: np.ndarray,
              k: int):
        """One batch over every live shard.

        Returns ``(gidx, answer, d2, overflow, coverage)`` — the merged
        host buffers ((Q, ΣC_s) over surviving shards, global row ids,
        -1 in dead slots), the per-query overflow OR across survivors,
        and the :class:`ShardCoverage` certificate.
        """
        self._dispatch_no += 1
        qr = represent_queries(jnp.asarray(q, jnp.float32), self.levels,
                               self.alphabet,
                               normalize=self.normalize_queries,
                               stack=self.stack)
        eps_j = jnp.asarray(eps, jnp.float32)
        knn_j = jnp.asarray(is_knn)

        plan = []   # (shard, is_probe)
        for si in range(self.n_shards):
            if not self._down[si]:
                plan.append((si, False))
            elif (self._dispatch_no - self._down_at[si]) \
                    % self.probe_every == 0:
                plan.append((si, True))
        futs = {si: self._pool.submit(self._query_shard, si, qr, eps_j,
                                      knn_j, k)
                for si, _probe in plan}
        results = {}
        for si, probe in plan:
            out = self._collect(si, futs[si], probe, qr, eps_j, knn_j, k)
            if out is not None:
                results[si] = out

        ok = sorted(results)
        if not ok:
            raise FailoverError(
                f"no shard answered dispatch {self._dispatch_no} "
                f"({self.n_shards} total, "
                f"{sum(self._down)} marked down)")
        gidx = np.concatenate([results[si][0] for si in ok], axis=-1)
        answer = np.concatenate([results[si][1] for si in ok], axis=-1)
        d2 = np.concatenate([results[si][2] for si in ok], axis=-1)
        overflow = np.logical_or.reduce([results[si][3] for si in ok])
        coverage = ShardCoverage(
            shards_ok=len(ok), shards_total=self.n_shards,
            rows_ok=int(sum(self._rows[si] for si in ok)),
            rows_total=int(sum(self._rows)))
        return gidx, answer, d2, overflow, coverage
