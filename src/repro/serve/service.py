"""The online FAST_SAX query service (DESIGN.md §6).

Layered strictly on the existing engines — the service owns no search
logic.  Request flow:

    submit → bounded queue (admission control, deadlines)
           → micro-batch  (MicroBatcher drains + coalesces)
           → bucket       (pad Q to a power of two, k to a power of two —
                           jit compiles once per bucket, never per request)
           → dispatch     (one mixed-workload device pass:
                           engine.mixed_query_auto, or the sharded
                           distributed_mixed_query_auto — capacity
                           auto-escalation keeps every answer exact)
           → respond      (per-request id/distance extraction, external-id
                           mapping, latency accounting)

Warm start: ``SearchService.from_store`` accepts any committed
``repro.index`` artifact — a plain single store, a ``MutableIndex`` root
(which also enables live ingest), or a sharded store (mapped onto a mesh
over the available devices).

Live ingest: ``insert``/``delete`` route through the ``MutableIndex``
(durable, crash-safe); the commit-refresh hook marks the device copy
stale, and the dispatcher swaps in a freshly-uploaded live view at the
next batch boundary once ``refresh_min_interval_s`` has passed — queries
never observe a half-updated index, because the swap is a whole-reference
replacement between device calls.
"""
from __future__ import annotations

import dataclasses
import functools
import pathlib
import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.engine import (DeviceIndex, build_device_index,
                           device_index_from_host, device_trace_bytes,
                           mixed_query, mixed_query_and_trace,
                           mixed_query_dense, mixed_query_dense_and_trace,
                           mixed_query_pallas, mixed_trace,
                           represent_queries, resolve_backend,
                           resolve_knn_backend, stack_backend)
from ..core.options import SearchOptions
from ..core.representation import DEFAULT_STACK
from ..obs.calibration import CalibrationLog
from ..obs.spans import SpanRecorder, span
from ..obs.trace import select_queries, trace_totals
from ..runtime import chaos
from .batcher import (BREAKER_OPEN, FAILED, KIND_KNN, KIND_RANGE, OK,
                      REJECTED_SHED, CircuitBreaker, MicroBatcher, Request)
from .stats import StatsTracker


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving knobs.  ``levels``/``alphabet`` matter only when the service
    builds its own index (``from_series``); a warm start inherits them from
    the store."""

    levels: Sequence[int] = (8, 16)
    alphabet: int = 10
    stack: Sequence[str] = DEFAULT_STACK   # registered representation stack
    normalize_queries: bool = True
    backend: str = "auto"          # auto|xla|pallas (engine.resolve_backend)
    quantization: str = "none"     # none|bf16|int8 — tiered resident index
    verify_prefetch: bool = False  # overlap raw-tier verify fetch with
    #                                device compute (DESIGN.md §13);
    #                                bit-identical answers
    max_batch: int = 32            # micro-batch ceiling (and top Q bucket)
    max_queue: int = 256           # admission-control bound
    max_wait_ms: float = 2.0       # coalescing window after first request
    default_deadline_ms: Optional[float] = None   # None = no deadline
    n_iters: int = 2               # k-NN tightening passes
    capacity0: Optional[int] = None  # first candidate capacity (None: auto)
    dense_fallback_frac: float = 0.125   # capacity > frac·B → dense dispatch
    refresh_min_interval_s: float = 0.0   # live-ingest refresh throttle
    warmup_ks: Sequence[int] = (8,)       # k buckets to precompile
    # --- fault tolerance (DESIGN.md §12) — defaults keep today's behavior
    # except the breaker (pure win: sheds instead of FAILED-storming) and
    # the non-blocking generation swap (commit-refresh no longer stalls
    # the dispatch loop; refresh(force=True) stays synchronous).
    failover_shards: int = 0       # >0: serve through FailoverShards
    #                                (from_series splits into this many;
    #                                from_store uses the store's count)
    shard_timeout_s: float = 30.0  # per-shard attempt timeout floor
    shard_retries: int = 2         # transient-fault retries per shard
    shard_backoff_s: float = 0.02  # exponential-backoff base
    breaker_threshold: int = 5     # consecutive dispatch failures → open
    breaker_cooldown: int = 8      # shed batches before half-open probe
    async_refresh: bool = True     # background device upload on commit
    # --- observability (DESIGN.md §10) — all OFF by default: the untraced
    # hot path is byte-for-byte the pre-observability code path.
    trace: bool = False            # cascade counters + spans + calibration
    trace_ring: int = 4096         # span ring capacity (bounded memory)
    calibration_ring: int = 2048   # dispatch-record ring capacity
    profile_dir: str = ""          # jax.profiler trace dir: one session
    #                                from start() to stop() ("" = off)

    @classmethod
    def from_options(cls, options: SearchOptions, **overrides):
        """Build a ServeConfig from the unified query-options surface:
        the :class:`SearchOptions` fields that have a serving-level
        counterpart map across, everything else keeps its default (or the
        explicit ``overrides``)."""
        mapped = dict(backend=options.backend,
                      quantization=options.quantization,
                      verify_prefetch=options.verify_prefetch,
                      trace=options.trace,
                      n_iters=options.n_iters,
                      capacity0=options.capacity,
                      normalize_queries=options.normalize_queries)
        mapped.update(overrides)
        return cls(**mapped)


def _pow2_at_least(n: int, cap: int) -> int:
    b = 1
    while b < n and b < cap:
        b *= 2
    return min(b, cap)


_DENSE = -1   # capacity-hint sentinel: this k bucket dispatches densely


def _to_host(*outs):
    """The one device→host copy every backend's outputs go through: wait
    for the device first, so the ``repro.serve.d2h`` span times the copy
    alone."""
    jax.block_until_ready(outs)
    with span("repro.serve.d2h", bytes=sum(int(a.nbytes) for a in outs)):
        return tuple(np.asarray(a) for a in outs)


def _counted(trace):
    """A cascade-counter result already in hand, as the zero-argument
    callable a backend's dispatch returns for it."""
    return lambda: trace


class _SingleBackend:
    """Single-process engine: one DeviceIndex, escalating ``mixed_query``.

    Capacity escalation is *sticky*: once a batch overflows and re-runs at
    4× capacity, later batches start at the learned capacity — under
    steady traffic the double pass (and any jit compile beyond the first)
    happens once, not per batch.  When the learned capacity crosses
    ``dense_fallback_frac``·B the backend switches to
    ``mixed_query_dense`` permanently: gather-compaction over a large
    fraction of the database costs more than the dense matmul verify it
    exists to avoid.  The policy is backend-global (not per bucket) so a
    direct replay of any served request takes the same dispatch mode —
    and therefore the same float path — as the batch that served it.
    """

    def __init__(self, index: DeviceIndex, cfg: ServeConfig):
        self.index = index
        self.cfg = cfg
        # Extended representation stacks demote the fused Pallas path to
        # XLA (the megakernels hard-code the canonical level pair).
        self.backend = stack_backend(index, resolve_backend(cfg.backend))
        self._cap: Optional[int] = None   # learned capacity or _DENSE
        self.stats: Optional[StatsTracker] = None   # set by SearchService

    @property
    def n(self) -> int:
        return self.index.n

    @property
    def size(self) -> int:
        return self.index.series.shape[0]

    def prepare_from_host(self, host):
        """Heavy half of a generation swap: build + upload the device
        index and block until the transfer lands.  Runs off the dispatch
        thread (the non-blocking swap, DESIGN.md §12) — nothing here
        touches the serving state."""
        index = device_index_from_host(host)
        jax.block_until_ready(index.series)
        return index

    def install(self, prepared):
        """Cheap half: whole-reference swap (caller holds the refresh
        lock; in-flight batches finished on the old index)."""
        self.index = prepared
        self.backend = stack_backend(self.index,
                                     resolve_backend(self.cfg.backend))

    def reload_from_host(self, host, ids=None):
        """Live-ingest refresh hook: synchronous prepare + install."""
        self.install(self.prepare_from_host(host))

    def _note_demotion(self, k: int):
        if (self.stats is not None and self.backend == "pallas"
                and resolve_knn_backend(self.backend, k) != "pallas"):
            self.stats.on_demotion()

    def _note_certificates(self, overflow):
        if self.stats is not None:
            bad = int(np.asarray(overflow).sum())
            total = int(np.asarray(overflow).size)
            self.stats.on_certificates(total - bad, total)

    def trace_bytes(self, trace) -> dict:
        return device_trace_bytes(self.index, trace)

    def cost_estimate(self, Q: int, k: int) -> dict:
        from ..core.cost_model import fused_pass_estimate

        return fused_pass_estimate(Q, self.size, self.n, self.index.levels,
                                   self.index.alphabet, k=int(k))

    def fused_call(self, k: int):
        """The fused-megakernel device call :meth:`dispatch` makes for the
        k bucket ``k``, as ``f(index, qr, eps, is_knn)``, or None where
        the bucket runs the XLA engine.  Large k buckets demote the fused
        path to XLA (the unrolled in-kernel selection grows linearly in k,
        DESIGN.md §7); the decision is a pure function of (backend, k
        bucket), so every batch — and every direct replay — of a bucket
        takes the same float path."""
        if resolve_knn_backend(self.backend, k) != "pallas":
            return None
        return functools.partial(mixed_query_pallas, k=k,
                                 n_iters=self.cfg.n_iters)

    def dispatch(self, q: np.ndarray, eps: np.ndarray, is_knn: np.ndarray,
                 k: int, want_trace: bool = False):
        """One padded micro-batch: host ``(idx, answer, d2)`` and, when
        ``want_trace``, a zero-argument callable that returns the batch's
        ``QueryTrace`` (where counting is a device pass of its own, the
        call runs it, so the caller can time it apart), else None."""
        B = self.size
        with span("repro.serve.represent"):
            qr = represent_queries(jnp.asarray(q, jnp.float32),
                                   self.index.levels, self.index.alphabet,
                                   normalize=self.cfg.normalize_queries,
                                   stack=tuple(getattr(self.index, "stack",
                                                       DEFAULT_STACK)))
            eps_j = jnp.asarray(eps, jnp.float32)
            knn_j = jnp.asarray(is_knn)
        self._note_demotion(k)
        trace = None
        fused = self.fused_call(k)
        if fused is not None:
            # One fused megakernel pass per micro-batch: dense layout,
            # no candidate buffer, no capacity escalation (DESIGN.md §7).
            # The jit cache stays keyed on the (Q, k) bucket exactly like
            # the XLA path.
            idx, answer, d2, overflow = fused(self.index, qr, eps_j, knn_j)
            if want_trace:
                trace = functools.partial(mixed_trace, self.index, qr, eps_j,
                                          knn_j, k, answer, d2)
        else:
            idx = answer = d2 = overflow = None
            cap_limit = max(64, int(self.cfg.dense_fallback_frac * B))
            cap = self._cap
            if cap is None:
                cap = self.cfg.capacity0 or max(4 * k, 64)
            while cap != _DENSE:
                cap = max(min(int(cap), B), min(k, B))
                # Traced dispatch fuses the counting pass into the same
                # jit call (mixed_query_and_trace) so XLA shares the
                # radius-independent screen terms — the untraced call
                # path and its jit cache entries are untouched.
                if want_trace:
                    idx, answer, d2, overflow, trace = mixed_query_and_trace(
                        self.index, qr, eps_j, knn_j, k, capacity=cap,
                        n_iters=self.cfg.n_iters)
                    trace = _counted(trace)
                else:
                    idx, answer, d2, overflow = mixed_query(
                        self.index, qr, eps_j, knn_j, k, capacity=cap,
                        n_iters=self.cfg.n_iters)
                if cap >= B or not bool(np.asarray(overflow).any()):
                    self._cap = max(cap, self._cap or 0)
                    break
                if self.stats is not None:
                    self.stats.on_escalation()
                cap = cap * 4 if cap * 4 <= cap_limit else _DENSE
            else:
                self._cap = _DENSE
                if want_trace:
                    idx, answer, d2, overflow, trace = \
                        mixed_query_dense_and_trace(
                            self.index, qr, eps_j, knn_j, k)
                    trace = _counted(trace)
                else:
                    idx, answer, d2, overflow = mixed_query_dense(
                        self.index, qr, eps_j, knn_j, k)
        self._note_certificates(overflow)
        return (*_to_host(idx, answer, d2), trace)


class _QuantizedBackend:
    """Tiered serving backend (DESIGN.md §9): the quantized screen stays
    device-resident, the full-precision rows stay in the mmap tier and
    are gathered only for the survivors' exact verify.

    The capacity choice lives inside ``engine.quantized_mixed_query``
    (counted before its one compaction), so the dispatch here is a
    single call.
    Answers are set-identical to the full-precision backends — the
    widened screen is a provable superset and the verify is exact
    (tested in tests/test_serve.py's quantized cases).
    """

    def __init__(self, tindex, cfg: ServeConfig):
        self.tindex = tindex
        self.cfg = cfg
        # The screen's engine (the exact verify is XLA either way).
        self.backend = stack_backend(tindex.dev, resolve_backend(cfg.backend))
        self.stats: Optional[StatsTracker] = None   # set by SearchService

    @property
    def n(self) -> int:
        return int(self.tindex.dev.n)

    @property
    def size(self) -> int:
        return int(self.tindex.size)

    def prepare_from_host(self, host):
        from ..core.engine import TieredIndex

        tiered = TieredIndex.from_host(host, self.tindex.mode)
        jax.block_until_ready(tiered.dev.series)
        return tiered

    def install(self, prepared):
        self.tindex = prepared

    def reload_from_host(self, host, ids=None):
        self.install(self.prepare_from_host(host))

    def trace_bytes(self, trace) -> dict:
        from ..core.engine import tiered_trace_bytes

        return tiered_trace_bytes(self.tindex, trace)

    def cost_estimate(self, Q: int, k: int) -> dict:
        from ..core.cost_model import fused_pass_estimate

        return fused_pass_estimate(Q, self.size, self.n,
                                   self.tindex.dev.levels,
                                   self.tindex.dev.alphabet, k=int(k))

    def dispatch(self, q: np.ndarray, eps: np.ndarray, is_knn: np.ndarray,
                 k: int, want_trace: bool = False):
        from ..core.engine import quantized_mixed_query, quantized_mixed_trace

        with span("repro.serve.represent"):
            qr = represent_queries(jnp.asarray(q, jnp.float32),
                                   self.tindex.dev.levels,
                                   self.tindex.dev.alphabet,
                                   normalize=self.cfg.normalize_queries,
                                   stack=tuple(getattr(self.tindex.dev,
                                                       "stack",
                                                       DEFAULT_STACK)))
            eps_j = jnp.asarray(eps, jnp.float32)
            knn_j = jnp.asarray(is_knn)
        cap = self.cfg.capacity0 or max(4 * k, 64)
        idx, answer, d2, overflow = quantized_mixed_query(
            self.tindex, qr, eps_j, knn_j, k,
            options=SearchOptions(backend=self.backend, capacity=cap,
                                  verify_prefetch=self.cfg.verify_prefetch))
        if self.stats is not None:
            bad = int(np.asarray(overflow).sum())
            total = int(np.asarray(overflow).size)
            self.stats.on_certificates(total - bad, total)
        trace = (functools.partial(quantized_mixed_trace, self.tindex.dev,
                                   qr, eps_j, knn_j, k, answer, d2)
                 if want_trace else None)
        return (*_to_host(idx, answer, d2), trace)


class _DistQuantizedBackend:
    """Distributed tiered serving (DESIGN.md §13): each mesh device holds
    its own shard's quantized screen columns, the widened screen runs
    shard-locally inside ``shard_map``, and only the surviving row ids
    cross hosts — the raw-tier exact verify then gathers just those rows
    from the host mmap tier (optionally double-buffered against the next
    chunk's device compute via ``cfg.verify_prefetch``).

    Capacity escalation lives inside
    ``dist_search.distributed_quantized_mixed_query`` (escalates to the
    per-shard row count, where compaction cannot overflow), so answers
    carry an always-exact certificate and are set-identical to the
    single-host tiered backend."""

    def __init__(self, dti, mesh, cfg: ServeConfig, axis: str = "data"):
        self.dti = dti
        self.mesh = mesh
        self.axis = axis
        self.cfg = cfg
        self._cap: Optional[int] = None
        self.stats: Optional[StatsTracker] = None   # set by SearchService

    @property
    def n(self) -> int:
        return int(self.dti.dev.n)

    @property
    def size(self) -> int:
        return int(self.dti.n_valid)

    def cost_estimate(self, Q: int, k: int) -> dict:
        from ..core.cost_model import fused_pass_estimate

        b_loc = (int(self.dti.dev.series.shape[0])
                 // self.mesh.shape[self.axis])
        return fused_pass_estimate(Q, b_loc, self.n, self.dti.dev.levels,
                                   self.dti.dev.alphabet, k=int(k))

    def trace_bytes(self, trace) -> dict:
        from ..core.engine import tiered_trace_bytes

        return tiered_trace_bytes(self.dti, trace)

    def dispatch(self, q: np.ndarray, eps: np.ndarray, is_knn: np.ndarray,
                 k: int, want_trace: bool = False):
        from ..core.dist_search import distributed_quantized_mixed_query

        cap = self._cap or self.cfg.capacity0 or max(4 * k, 64)
        gidx, answer, d2, overflow = distributed_quantized_mixed_query(
            self.dti, q, eps, is_knn, k, self.mesh, axis=self.axis,
            options=SearchOptions(
                backend=self.cfg.backend, capacity=cap,
                normalize_queries=self.cfg.normalize_queries,
                verify_prefetch=self.cfg.verify_prefetch))
        self._cap = max(cap, self._cap or 0)
        if self.stats is not None:
            bad = int(np.asarray(overflow).sum())
            total = int(np.asarray(overflow).size)
            self.stats.on_certificates(total - bad, total)
        return (*_to_host(gidx, answer, d2), None)


class _ShardedBackend:
    """Distributed engine: database sharded over a mesh,
    ``distributed_mixed_query_auto`` per micro-batch."""

    def __init__(self, index: DeviceIndex, mesh, n_valid: int,
                 cfg: ServeConfig, axis: str = "data"):
        self.index = index
        self.mesh = mesh
        self.axis = axis
        self.n_valid = int(n_valid)
        self.cfg = cfg
        self._cap: Optional[int] = None   # learned per-shard capacity
        self.stats: Optional[StatsTracker] = None   # set by SearchService

    @property
    def n(self) -> int:
        return self.index.n

    @property
    def size(self) -> int:
        return self.n_valid

    def trace_bytes(self, trace) -> dict:
        from ..obs.trace import screen_row_bytes, tier_bytes

        rb = screen_row_bytes(self.index.levels, self.index.alphabet)
        return tier_bytes(trace, self.n_valid, rb, self.n,
                          verify_itemsize=self.index.series.dtype.itemsize)

    def cost_estimate(self, Q: int, k: int) -> dict:
        from ..core.cost_model import fused_pass_estimate

        # Per-chip figure: each shard screens its own rows concurrently.
        b_loc = self.index.series.shape[0] // self.mesh.shape[self.axis]
        return fused_pass_estimate(Q, b_loc, self.n, self.index.levels,
                                   self.index.alphabet, k=int(k))

    def dispatch(self, q: np.ndarray, eps: np.ndarray, is_knn: np.ndarray,
                 k: int, want_trace: bool = False):
        from ..core.dist_search import (distributed_cascade_trace,
                                        distributed_mixed_query)
        from ..core.engine import _SEED_EPS_MAX

        b_loc = self.index.series.shape[0] // self.mesh.shape[self.axis]
        cap = self._cap
        if cap is None:
            cap = self.cfg.capacity0 or max(4 * k, 64)
        cap = min(int(cap), b_loc)
        while True:
            gidx, answer, d2, overflow = distributed_mixed_query(
                self.index, q, eps, is_knn, k, self.mesh, axis=self.axis,
                options=SearchOptions(
                    backend=self.cfg.backend, capacity=cap,
                    n_iters=self.cfg.n_iters,
                    normalize_queries=self.cfg.normalize_queries),
                n_valid=self.n_valid)
            if cap >= b_loc or not bool(np.asarray(overflow).any()):
                break
            if self.stats is not None:
                self.stats.on_escalation()
            cap = min(b_loc, cap * 4)
        self._cap = max(cap, self._cap or 0)
        gidx, answer, d2 = _to_host(gidx, answer, d2)
        if self.stats is not None:
            # Per-query certificate: no shard's buffer truncated.
            bad = int(np.asarray(overflow).any(axis=-1).sum())
            self.stats.on_certificates(gidx.shape[0] - bad, gidx.shape[0])
        if not want_trace:
            return gidx, answer, d2, None
        index = self.index

        def count():
            # Each row's FINAL radius, recovered from the merged buffers
            # exactly like engine.mixed_trace (host arithmetic here; the
            # counting pass itself runs sharded with a psum merge).
            d2a = np.where(answer, d2, np.inf)
            k_eff = max(1, min(int(k), d2a.shape[-1]))
            kth = np.partition(d2a, k_eff - 1, axis=-1)[:, k_eff - 1]
            eps_knn = np.sqrt(np.maximum(kth, 0.0))
            eps_knn = np.where(np.isfinite(eps_knn), eps_knn, _SEED_EPS_MAX)
            eps_f = np.where(is_knn, eps_knn, eps).astype(np.float32)
            trace = distributed_cascade_trace(
                index, q, eps_f, self.mesh, axis=self.axis,
                normalize_queries=self.cfg.normalize_queries,
                n_valid=self.n_valid)
            n_ans = np.isfinite(d2a).sum(axis=-1).astype(np.int32)
            answers = np.where(is_knn, np.minimum(n_ans, k_eff), n_ans)
            return dataclasses.replace(trace,
                                       answers=answers.astype(np.int32))

        return gidx, answer, d2, count


class _FailoverBackend:
    """Fault-tolerant sharded serving (DESIGN.md §12): wraps
    ``core.dist_search.FailoverShards`` — per-shard timeouts, retries,
    down-marking and probing — behind the backend dispatch interface.

    Unlike the collective ``_ShardedBackend``, a dispatch here can
    *partially* succeed: the merged answer covers only the surviving
    shards, and ``last_coverage`` carries the ShardCoverage certificate
    the service attaches to every request of the batch (``exact=False``
    + coverage fields when any shard was lost)."""

    def __init__(self, engine, cfg: ServeConfig):
        self.engine = engine
        self.cfg = cfg
        self._stats: Optional[StatsTracker] = None
        self.last_coverage = None

    @property
    def stats(self):
        return self._stats

    @stats.setter
    def stats(self, tracker):
        self._stats = tracker
        if tracker is not None:
            def _on_event(kind, n=1):
                if kind == "retries":
                    tracker.on_retry(n)
                elif kind == "hedges":
                    tracker.on_hedge(n)
            self.engine.on_event = _on_event

    @property
    def n(self) -> int:
        return self.engine.n

    @property
    def size(self) -> int:
        return self.engine.size

    def cost_estimate(self, Q: int, k: int) -> dict:
        from ..core.cost_model import fused_pass_estimate
        from ..core.dist_search import _screen_of

        b_max = max(int(_screen_of(s).series.shape[0])
                    for s in self.engine.shards)
        return fused_pass_estimate(Q, b_max, self.n, self.engine.levels,
                                   self.engine.alphabet, k=int(k))

    def trace_bytes(self, trace) -> dict:
        from ..obs.trace import screen_row_bytes, tier_bytes

        rb = screen_row_bytes(self.engine.levels, self.engine.alphabet)
        return tier_bytes(trace, self.size, rb, self.n)

    def dispatch(self, q: np.ndarray, eps: np.ndarray, is_knn: np.ndarray,
                 k: int, want_trace: bool = False):
        gidx, answer, d2, overflow, cov = self.engine.query(
            q, eps, np.asarray(is_knn), k)
        self.last_coverage = cov
        if self._stats is not None:
            # Per-query certificate: capacity covers each full shard, so
            # overflow is structurally False — a query is exact iff every
            # shard answered.
            bad = int(np.asarray(overflow).sum()) if cov.exact \
                else gidx.shape[0]
            self._stats.on_certificates(gidx.shape[0] - bad, gidx.shape[0])
        return (*_to_host(gidx, answer, d2), None)


class SearchService:
    """Online range/k-NN service with dynamic micro-batching."""

    def __init__(self, backend, cfg: ServeConfig = ServeConfig(),
                 ids: Optional[np.ndarray] = None, mutable=None):
        self.cfg = cfg
        self.backend = backend
        self._ids = None if ids is None else np.asarray(ids, dtype=np.int64)
        self.mutable = mutable
        self.stats = StatsTracker()
        # Backends report host-side events (escalations, demotions,
        # certificate outcomes) into the shared tracker — cheap counter
        # bumps, recorded whether or not tracing is on.
        backend.stats = self.stats
        # Tracing surfaces (DESIGN.md §10): a bounded span ring and the
        # cost-model calibration log, allocated only when cfg.trace — the
        # untraced service carries no observability state beyond counters.
        self.tracer = SpanRecorder(cfg.trace_ring) if cfg.trace else None
        self.calibration = (CalibrationLog(cfg.calibration_ring)
                            if cfg.trace else None)
        self._batcher = MicroBatcher(
            self._dispatch, max_batch=cfg.max_batch, max_queue=cfg.max_queue,
            max_wait_ms=cfg.max_wait_ms, stats=self.stats,
            tracer=self.tracer)
        # Dispatch circuit breaker (DESIGN.md §12): driven only by the
        # dispatcher thread; read by /healthz and the metrics snapshot.
        self.breaker = CircuitBreaker(threshold=cfg.breaker_threshold,
                                      cooldown=cfg.breaker_cooldown)
        self._refresh_thread: Optional[threading.Thread] = None
        # Serializes the (index, ids) swap against in-flight dispatches so
        # a batch never maps one generation's row positions through
        # another generation's ids (see _dispatch / refresh).
        self._refresh_lock = threading.Lock()
        # Range-only batches still bucket k at the warmed floor, so they
        # can never hit a cold (Q, k=1) jit entry at serve time.
        self._k_floor = _pow2_at_least(
            min(cfg.warmup_ks) if cfg.warmup_ks else 1, self.backend.size)
        self._batch_seq = 0        # dispatches so far (repro.serve.batch seq)
        self._profiling = False    # a cfg.profile_dir session is open
        self._loaded_gen = mutable.generation if mutable is not None else -1
        self._last_refresh = time.perf_counter()
        self._stale = False
        self._unsubscribe = None
        if mutable is not None:
            self._unsubscribe = mutable.subscribe(self._on_commit)

    # --- construction -------------------------------------------------------

    @classmethod
    def from_series(cls, series: np.ndarray, cfg: ServeConfig = ServeConfig(),
                    mesh=None, normalize: bool = True) -> "SearchService":
        """Cold start: build the device index from raw series."""
        if mesh is not None:
            from ..core.dist_search import distributed_build, pad_database
            if cfg.quantization != "none":
                from ..core.dist_search import distributed_tiered_index
                from ..core.engine import TieredIndex
                from ..core.fastsax import FastSAXConfig, build_index

                host = build_index(
                    np.asarray(series),
                    FastSAXConfig(n_segments=tuple(cfg.levels),
                                  alphabet=cfg.alphabet,
                                  stack=tuple(cfg.stack)),
                    normalize=normalize)
                tiered = TieredIndex.from_host(host, cfg.quantization)
                dti = distributed_tiered_index(tiered, mesh)
                return cls(_DistQuantizedBackend(dti, mesh, cfg), cfg)
            padded, n_valid = pad_database(np.asarray(series),
                                           mesh.shape["data"])
            index = distributed_build(padded, tuple(cfg.levels), cfg.alphabet,
                                      mesh, n_valid=n_valid,
                                      stack=tuple(cfg.stack))
            return cls(_ShardedBackend(index, mesh, n_valid, cfg), cfg)
        if cfg.failover_shards:
            if cfg.quantization != "none":
                raise ValueError("failover serving is full-precision — "
                                 "set quantization='none'")
            from ..core.dist_search import FailoverShards
            engine = FailoverShards.from_series(
                np.asarray(series), cfg.failover_shards,
                tuple(cfg.levels), cfg.alphabet, normalize=normalize,
                stack=tuple(cfg.stack), timeout_s=cfg.shard_timeout_s,
                retries=cfg.shard_retries, backoff_s=cfg.shard_backoff_s,
                n_iters=cfg.n_iters,
                normalize_queries=cfg.normalize_queries)
            return cls(_FailoverBackend(engine, cfg), cfg)
        if cfg.quantization != "none":
            from ..core.engine import TieredIndex
            from ..core.fastsax import FastSAXConfig, build_index

            host = build_index(
                np.asarray(series),
                FastSAXConfig(n_segments=tuple(cfg.levels),
                              alphabet=cfg.alphabet,
                              stack=tuple(cfg.stack)),
                normalize=normalize)
            tiered = TieredIndex.from_host(host, cfg.quantization)
            return cls(_QuantizedBackend(tiered, cfg), cfg)
        index = build_device_index(jnp.asarray(series, jnp.float32),
                                   tuple(cfg.levels), cfg.alphabet,
                                   normalize=normalize,
                                   stack=tuple(cfg.stack))
        return cls(_SingleBackend(index, cfg), cfg)

    @classmethod
    def from_store(cls, path, cfg: ServeConfig = ServeConfig(),
                   mesh=None) -> "SearchService":
        """Warm start from any committed ``repro.index`` artifact:

        * ``MutableIndex`` root (``CURRENT`` present) — live ingest enabled;
        * sharded store — mapped onto ``mesh`` (default: a 1-D mesh over
          all devices; the stored shard count must match);
        * tiered sharded store (``store_sharded_quantized``) — served
          quantized (it holds no full-precision screen columns): through
          ``FailoverShards`` when ``cfg.failover_shards`` is set, the
          distributed quantized screen when a ``mesh`` is passed
          (DESIGN.md §13), and the single-host tiered backend otherwise;
        * plain single store — mmap-opened, uploaded once.

        With ``cfg.quantization != "none"`` the single-host cases serve
        through the tiered :class:`_QuantizedBackend`: a plain store with
        a matching stored quantized tier warm-starts zero-copy, anything
        else quantizes the loaded live view in memory.
        """
        from ..index import mutable as _mutable
        from ..index import sharded as _sharded
        from ..index import store as _store

        path = pathlib.Path(path)
        quant = cfg.quantization != "none"
        if (path / _mutable.CURRENT).exists():
            mi = _mutable.MutableIndex.open(path)
            host, ids = mi.live_index()
            if quant:
                from ..core.engine import TieredIndex

                tiered = TieredIndex.from_host(host, cfg.quantization)
                return cls(_QuantizedBackend(tiered, cfg), cfg,
                           ids=np.asarray(ids), mutable=mi)
            index = device_index_from_host(host)
            return cls(_SingleBackend(index, cfg), cfg, ids=np.asarray(ids),
                       mutable=mi)
        manifest = _store.read_manifest(path)
        if manifest.get("kind") == _sharded._TIERED_KIND:
            if cfg.failover_shards:
                from ..core.dist_search import FailoverShards
                engine = FailoverShards.from_store(
                    path, timeout_s=cfg.shard_timeout_s,
                    retries=cfg.shard_retries,
                    backoff_s=cfg.shard_backoff_s, n_iters=cfg.n_iters,
                    normalize_queries=cfg.normalize_queries)
                return cls(_FailoverBackend(engine, cfg), cfg)
            if mesh is not None:
                from ..core.dist_search import load_sharded_tiered
                dti = load_sharded_tiered(path, mesh)
                return cls(_DistQuantizedBackend(dti, mesh, cfg), cfg)
            tiered, _n_valid = _sharded.load_sharded_quantized(path)
            return cls(_QuantizedBackend(tiered, cfg), cfg)
        if manifest.get("kind") == _sharded._KIND:
            if quant:
                raise ValueError(
                    "quantized serving of a full-precision sharded store "
                    "is not supported — restore it with "
                    "store_sharded_quantized, or set quantization='none'")
            if cfg.failover_shards:
                from ..core.dist_search import FailoverShards
                engine = FailoverShards.from_store(
                    path, timeout_s=cfg.shard_timeout_s,
                    retries=cfg.shard_retries,
                    backoff_s=cfg.shard_backoff_s, n_iters=cfg.n_iters,
                    normalize_queries=cfg.normalize_queries)
                return cls(_FailoverBackend(engine, cfg), cfg)
            from ..core.dist_search import load_sharded, make_data_mesh
            mesh = mesh or make_data_mesh()
            index, n_valid = load_sharded(path, mesh)
            return cls(_ShardedBackend(index, mesh, n_valid, cfg), cfg)
        if quant:
            from ..core.engine import TieredIndex

            tiered = TieredIndex.from_store(path,
                                            quantization=cfg.quantization)
            return cls(_QuantizedBackend(tiered, cfg), cfg)
        host = _store.load_index(path, mmap=True)
        return cls(_SingleBackend(device_index_from_host(host), cfg), cfg)

    # --- lifecycle ----------------------------------------------------------

    def start(self) -> "SearchService":
        if self.cfg.profile_dir and not self._profiling:
            # One profiler session for the service's run: the trace holds
            # every repro.* span beside the device operations.
            jax.profiler.start_trace(self.cfg.profile_dir)
            self._profiling = True
        self._batcher.start()
        return self

    def _stop_profiler(self):
        if self._profiling:
            self._profiling = False
            jax.profiler.stop_trace()

    def stop(self):
        try:
            self._batcher.stop()
        finally:
            self._stop_profiler()
        if self._unsubscribe is not None:
            self._unsubscribe()
            self._unsubscribe = None

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown: refuse new work (submits resolve
        REJECTED_SHED), let queued and in-flight batches finish, then
        stop the dispatcher.  The SIGTERM path in ``launch/serve.py``
        calls this so preemption never drops an accepted request.
        Returns False if in-flight work did not finish in time."""
        try:
            drained = self._batcher.drain(timeout_s=timeout_s)
        finally:
            self._stop_profiler()
        if self._unsubscribe is not None:
            self._unsubscribe()
            self._unsubscribe = None
        return drained

    def health(self):
        """Readiness probe body for ``/healthz``: ``(ready, detail)``.
        Not ready while the dispatcher is down, a drain is in progress,
        or the circuit breaker is open — the signal a load balancer uses
        to route around this replica while it sheds."""
        detail = {
            "running": self._batcher.running,
            "draining": self._batcher.draining,
            "breaker": self.breaker.state,
            "generation": self._loaded_gen,
            "stale": self._stale,
        }
        cov = getattr(self.backend, "last_coverage", None)
        if cov is not None:
            detail["coverage"] = cov.as_dict()
        ready = (self._batcher.running and not self._batcher.draining
                 and self.breaker.state != BREAKER_OPEN)
        return ready, detail

    def __enter__(self) -> "SearchService":
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def warmup(self, qs: Optional[Sequence[int]] = None,
               ks: Optional[Sequence[int]] = None):
        """Precompile the bucket ladder so no request pays jit latency.
        Compiles every (Q bucket ≤ max_batch) × (k bucket) combination —
        each is one cache entry that every future batch in the bucket
        reuses."""
        q_buckets = list(qs) if qs is not None else []
        if not q_buckets:
            b = 1
            while b <= self.cfg.max_batch:
                q_buckets.append(b)
                b *= 2
        k_buckets = [
            _pow2_at_least(int(k), self.backend.size)
            for k in (ks if ks is not None else self.cfg.warmup_ks)]
        # Not a constant: a constant probe z-normalises to zeros, which sit
        # at the same distance from every z-normalised row, and the tiered
        # backend's k-NN warmup would then verify the whole database.
        probe = np.sin(np.linspace(0.0, 6.0 * np.pi, self.backend.n,
                                   dtype=np.float32))[None, :]
        for qb in q_buckets:
            q = np.repeat(probe, qb, axis=0)
            eps = np.full(qb, 1.0, np.float32)
            for kb in sorted(set(k_buckets)):
                is_knn = np.zeros(qb, dtype=bool)
                is_knn[: max(1, qb // 2)] = True
                count = self.backend.dispatch(
                    q, eps, is_knn, kb, want_trace=bool(self.cfg.trace))[3]
                if count is not None:
                    count()
        return self

    # --- submission ---------------------------------------------------------

    def _deadline(self, deadline_ms) -> Optional[float]:
        ms = self.cfg.default_deadline_ms if deadline_ms is None else deadline_ms
        return None if ms is None else time.perf_counter() + float(ms) / 1e3

    def submit_range(self, query: np.ndarray, epsilon: float,
                     deadline_ms: Optional[float] = None) -> Request:
        return self._batcher.submit(Request(
            kind=KIND_RANGE, query=np.asarray(query, dtype=np.float32),
            epsilon=float(epsilon), deadline=self._deadline(deadline_ms)))

    def submit_knn(self, query: np.ndarray, k: int,
                   deadline_ms: Optional[float] = None) -> Request:
        return self._batcher.submit(Request(
            kind=KIND_KNN, query=np.asarray(query, dtype=np.float32),
            k=int(k), deadline=self._deadline(deadline_ms)))

    def range_query(self, query, epsilon, deadline_ms=None, timeout=60.0):
        """Synchronous range query; raises on rejection."""
        req = self.submit_range(query, epsilon, deadline_ms)
        if req.wait(timeout) != OK:
            raise RuntimeError(f"range request {req.status}")
        return req.ids, req.distances

    def knn(self, query, k, deadline_ms=None, timeout=60.0):
        """Synchronous exact k-NN; raises on rejection."""
        req = self.submit_knn(query, k, deadline_ms)
        if req.wait(timeout) != OK:
            raise RuntimeError(f"knn request {req.status}")
        return req.ids, req.distances

    # --- live ingest --------------------------------------------------------

    def _require_mutable(self):
        if self.mutable is None:
            raise RuntimeError(
                "live ingest needs a MutableIndex-backed service "
                "(SearchService.from_store on an index root)")
        return self.mutable

    def insert(self, series: np.ndarray) -> np.ndarray:
        """Durably insert rows; returns their external ids.  Served answers
        include them after the next refresh (at most
        ``refresh_min_interval_s`` later)."""
        return self._require_mutable().insert(np.asarray(series))

    def delete(self, ids) -> int:
        """Durably tombstone rows by external id."""
        return self._require_mutable().delete(ids)

    def _on_commit(self, _mi):
        # Commit-refresh hook (MutableIndex.subscribe): runs on the mutating
        # thread after CURRENT swaps.  Just a staleness marker — the actual
        # device upload happens on the dispatcher at a batch boundary, so
        # in-flight batches finish on a consistent index.
        self._stale = True

    def _maybe_refresh(self, force: bool = False):
        mi = self.mutable
        if mi is None or not (self._stale or force):
            return
        if not force and self.cfg.async_refresh \
                and hasattr(self.backend, "prepare_from_host"):
            # Non-blocking generation swap (DESIGN.md §12): the
            # dispatcher only *kicks* the background upload and keeps
            # serving the current generation; _refresh_bg installs the
            # prepared index under the lock when the transfer is done.
            if self._refresh_thread is not None \
                    and self._refresh_thread.is_alive():
                return
            if (time.perf_counter() - self._last_refresh
                    < self.cfg.refresh_min_interval_s):
                return
            if mi.generation == self._loaded_gen:
                self._stale = False
                return
            self._refresh_thread = threading.Thread(
                target=self._refresh_bg, name="repro-serve-refresh",
                daemon=True)
            self._refresh_thread.start()
            return
        with self._refresh_lock:
            if mi.generation == self._loaded_gen:
                self._stale = False
                return
            now = time.perf_counter()
            if not force and (now - self._last_refresh
                              < self.cfg.refresh_min_interval_s):
                return
            gen = mi.generation
            try:
                host, ids = mi.live_index()
                chaos.maybe_fire("device_upload", key=str(gen))
                self.backend.reload_from_host(host)
            except BaseException:
                self.stats.on_refresh_failure()
                self._stale = True
                raise
            self._ids = np.asarray(ids, dtype=np.int64)
            self._loaded_gen = gen
            self._last_refresh = now
            # A commit racing with the upload re-flags via the hook; only
            # clear staleness if the generation we loaded is still current.
            self._stale = mi.generation != gen
        self.stats.on_refresh_swap()

    def _refresh_bg(self):
        """Background half of the non-blocking swap: snapshot + upload
        happen here with NO lock held (the dispatch loop keeps serving);
        only the final whole-reference install takes the refresh lock.
        A failed upload (e.g. an injected ``device_upload`` fault) keeps
        serving the old generation and re-flags staleness — the next
        batch boundary kicks a fresh attempt."""
        mi = self.mutable
        gen = mi.generation
        try:
            host, ids = mi.live_index()
            chaos.maybe_fire("device_upload", key=str(gen))
            prepared = self.backend.prepare_from_host(host)
        except BaseException:   # noqa: BLE001 — serving must survive
            self.stats.on_refresh_failure()
            self._stale = True
            return
        with self._refresh_lock:
            if gen <= self._loaded_gen:
                return   # a forced refresh() overtook this upload
            self.backend.install(prepared)
            self._ids = np.asarray(ids, dtype=np.int64)
            self._loaded_gen = gen
            self._last_refresh = time.perf_counter()
            self._stale = mi.generation != gen
        self.stats.on_refresh_swap()

    def refresh(self):
        """Force the device index to the committed epoch right now
        (synchronous — returns only once served answers reflect it)."""
        self._maybe_refresh(force=True)

    # --- dispatch -----------------------------------------------------------

    def _dispatch(self, batch: list):
        """MicroBatcher callback: one padded, bucketed device pass."""
        t0 = time.perf_counter()
        self._batch_seq += 1
        with span("repro.serve.batch", self.tracer,
                  seq=self._batch_seq) as batch_span:
            self._dispatch_batch(batch, t0, batch_span)

    def _dispatch_batch(self, batch: list, t0: float, batch_span):
        self._maybe_refresh()
        if not self.breaker.allow():
            # Breaker open: shed the whole batch with a *rejected* status
            # — controlled backpressure, not a FAILED storm against a
            # backend we already know is down (DESIGN.md §12).
            n_shed = 0
            for req in batch:
                if not req._done.is_set():
                    req._resolve(REJECTED_SHED)
                    n_shed += 1
            self.stats.on_shed(n_shed)
            self.stats.set_breaker(self.breaker.state,
                                   self.breaker.state_code)
            return
        tracer = self.tracer
        with span("repro.serve.assemble", tracer):
            Q = len(batch)
            qb = _pow2_at_least(Q, self.cfg.max_batch)
            n = self.backend.n
            q = np.empty((qb, n), dtype=np.float32)
            eps = np.zeros(qb, dtype=np.float32)
            is_knn = np.zeros(qb, dtype=bool)
            max_k = 1
            for i, req in enumerate(batch):
                if req.query.shape != (n,):
                    req._resolve(FAILED, error=ValueError(
                        f"query must be ({n},), got {req.query.shape}"))
                    self.stats.on_failed()
                    continue
                q[i] = req.query
                if req.kind == KIND_KNN:
                    is_knn[i] = True
                    max_k = max(max_k, req.k)
                else:
                    eps[i] = req.epsilon
            live = [(i, r) for i, r in enumerate(batch)
                    if not r._done.is_set()]
            if not live:
                return
            # Padding rows replay the first live query as a range query at
            # ε = 0 — same shapes, negligible extra work, no effect on
            # answers.
            for j in range(Q, qb):
                q[j] = q[live[0][0]]
            k_bucket = _pow2_at_least(max(max_k, self._k_floor),
                                      self.backend.size)
            for _, req in live:
                req.t_dispatch = t0
                req.batch_seq = self._batch_seq
        waits = [t0 - req.t_submit for _, req in live]
        batch_span.set(live=len(live), qb=qb, kb=k_bucket,
                       wait_ms_max=round(max(waits) * 1e3, 3),
                       wait_ms_sum=round(sum(waits) * 1e3, 3))
        self.stats.on_batch(len(live), qb, self._batcher.depth)
        tracing = tracer is not None
        # Hold the refresh lock across dispatch + ids snapshot: a
        # concurrent refresh() must not swap in a new generation's ids
        # between the device pass and the id mapping.
        try:
            with self._refresh_lock:
                chaos.maybe_fire("serve_dispatch")
                with span("repro.serve.device", tracer, qb=qb,
                          kb=k_bucket):
                    t_dev = time.perf_counter()
                    idx, answer, d2, count = self.backend.dispatch(
                        q, eps, is_knn, k_bucket, want_trace=tracing)
                    t_dev = time.perf_counter() - t_dev
                ids = self._ids
                coverage = getattr(self.backend, "last_coverage", None)
        except BaseException:
            # The batcher resolves the batch FAILED; here we only feed
            # the breaker so a persistent backend failure opens it.
            self.breaker.on_failure()
            self.stats.set_breaker(self.breaker.state,
                                   self.breaker.state_code)
            raise
        self.breaker.on_success()
        self.stats.set_breaker(self.breaker.state, self.breaker.state_code)
        if tracing:
            # The dispatch outputs are host numpy (every backend returns
            # through _to_host), so t_dev covers the full device pass and
            # the copy — no extra sync was added to measure it.
            self.calibration.record(
                batch=len(live), k=k_bucket,
                backend=type(self.backend).__name__,
                measured_s=t_dev,
                estimate=self.backend.cost_estimate(qb, k_bucket))
        if count is not None:
            with span("repro.serve.cascade_count", tracer,
                      batch=len(live)):
                live_trace = select_queries(count(), [i for i, _ in live])
                totals = trace_totals(live_trace, self.backend.size)
                totals.update(self.backend.trace_bytes(live_trace))
                self.stats.on_cascade(totals)
        n_knn = sum(req.kind == KIND_KNN for _, req in live)
        with span("repro.serve.reply", tracer, knn=n_knn,
                  range=len(live) - n_knn):
            for i, req in live:
                self._finish(req, idx[i], answer[i], d2[i], ids, coverage)

    def _finish(self, req: Request, idx_row, answer_row, d2_row, ids_map,
                coverage=None):
        if req.kind == KIND_KNN:
            finite = np.isfinite(d2_row)
            # Ascending (d², slot); slots are low-index compacted, so ties
            # resolve to the lowest database row — identical ordering to
            # engine.knn_query / mixed_topk (tested).
            order = np.lexsort((np.arange(d2_row.size), d2_row))
            order = order[finite[order]][: req.k]
            rows = idx_row[order]
            dist = np.sqrt(d2_row[order])
        else:
            mask = answer_row & np.isfinite(d2_row)
            rows = idx_row[mask]
            dist = np.sqrt(d2_row[mask])
        rows, dist = self._postprocess(req, rows, dist)
        ids = rows if ids_map is None else ids_map[rows]
        if coverage is not None:
            # Certified-partial answer: the result is exact over the
            # surviving shards only; the caller sees the gap instead of a
            # silently-wrong "exact" answer (DESIGN.md §12).
            req.exact = bool(coverage.exact)
            req.coverage = coverage.as_dict()
            if not req.exact:
                self.stats.on_degraded()
        req._resolve(OK, ids=np.asarray(ids, dtype=np.int64),
                     distances=dist.astype(np.float64))

    def _postprocess(self, req: Request, rows, dist):
        """Answer-shaping hook between the device pass and the response —
        the base service returns candidates verbatim; subclasses (the
        subsequence service's exclusion-zone suppression) override.  Runs
        identically on the batched and direct paths, so the serving
        exactness contract (replay bit-equality) is preserved."""
        return rows, dist

    # --- observability surface ----------------------------------------------

    def metrics_text(self) -> str:
        """The live Prometheus text exposition for this service — the
        render function ``launch/serve.py --metrics`` serves and the CI
        smoke job scrapes.  Rebuilt per call from the stats snapshot
        (plus calibration/span aggregates when tracing): zero hot-path
        work."""
        from ..obs.metrics import build_registry

        cal = self.calibration.summary() if self.calibration else None
        spans = self.tracer.counts() if self.tracer else None
        return build_registry(self.stats.snapshot(), cal, spans).render()

    # --- unbatched reference path -------------------------------------------

    def direct_query(self, kind: str, query, epsilon: float = 0.0,
                     k: int = 0, meta: Optional[dict] = None):
        """One request, one device pass, no queue/bucketing — the
        per-request sequential baseline the benchmarks compare against,
        and the reference the exactness checks trust.  ``meta`` carries
        the same answer-shaping hints a batched submit would attach, so
        the replay runs the identical :meth:`_postprocess`."""
        self._maybe_refresh()
        n = self.backend.n
        q = np.asarray(query, dtype=np.float32).reshape(1, n)
        is_knn = np.asarray([kind == KIND_KNN])
        eps = np.asarray([0.0 if is_knn[0] else epsilon], np.float32)
        # Bucket k exactly like _dispatch (including the warmed floor), so
        # a direct replay hits the same jit entry and backend policy as
        # the batch that served it — the exactness check compares answers
        # bit-for-bit.
        kk = _pow2_at_least(max(int(k), 1, self._k_floor),
                            self.backend.size)
        with self._refresh_lock:
            idx, answer, d2, _ = self.backend.dispatch(q, eps, is_knn, kk)
            ids = self._ids
            coverage = getattr(self.backend, "last_coverage", None)
        req = Request(kind=kind, query=q[0], epsilon=epsilon,
                      k=max(int(k), 1), meta=meta)
        self._finish(req, idx[0], answer[0], d2[0], ids, coverage)
        return req.ids, req.distances


class SubseqSearchService(SearchService):
    """Online *subsequence* search: every window of the indexed streams is
    a database row (DESIGN.md §8), served through the unchanged
    queue → bucket → mixed-dispatch machinery above.

    Two request families:

      * ``submit_subseq_range(query, ε)`` — every window within ε, ids
        are window ids (map through :meth:`window_meta`);
      * ``submit_subseq_knn(query, k, excl)`` — the k nearest windows
        under trivial-match suppression: the request is batched as an
        ordinary k-NN at the provably sufficient fetch count
        (``core/subseq.knn_fetch_count``) and the exclusion-zone greedy
        runs in the :meth:`_postprocess` hook — identically on the
        batched and direct paths, so replay exactness holds verbatim.

    The device pass itself is the windows-as-rows mixed engine (the
    micro-batch path shares jit buckets with every other request); the
    streaming Pallas kernel remains the engine-level serving form for
    dedicated subsequence fleets (``core/subseq.subseq_range_query``).
    """

    def __init__(self, sidx, cfg: ServeConfig = ServeConfig(),
                 excl: Optional[int] = None):
        self.sidx = sidx
        self.excl = (sidx.window // 2) if excl is None else int(excl)
        super().__init__(_SingleBackend(sidx.index, cfg), cfg)

    # --- construction -------------------------------------------------------

    @classmethod
    def from_streams(cls, streams, window: int, stride: int = 1,
                     cfg: ServeConfig = ServeConfig(),
                     excl: Optional[int] = None) -> "SubseqSearchService":
        """Cold start: amortised window-feature build over raw streams."""
        from ..core.fastsax import FastSAXConfig
        from ..core.subseq import build_subseq_index, subseq_device_index

        hidx = build_subseq_index(
            np.asarray(streams),
            FastSAXConfig(n_segments=tuple(cfg.levels),
                          alphabet=cfg.alphabet, stack=tuple(cfg.stack)),
            window, stride)
        return cls(subseq_device_index(hidx), cfg, excl=excl)

    @classmethod
    def from_store(cls, path, cfg: ServeConfig = ServeConfig(),
                   excl: Optional[int] = None) -> "SubseqSearchService":
        """Warm start from a committed ``core/subseq.save_subseq_index``
        store (a standard index store with the stream columns riding
        along — O(ms) mmap open, like every other warm start)."""
        from ..core.subseq import load_subseq_index, subseq_device_index

        return cls(subseq_device_index(load_subseq_index(path)), cfg,
                   excl=excl)

    # --- submission ---------------------------------------------------------

    def _fetch_k(self, k: int, excl: int) -> int:
        from ..core.subseq import knn_fetch_count
        return knn_fetch_count(int(k), excl, self.sidx.stride,
                               self.sidx.n_windows)

    def submit_subseq_range(self, query, epsilon: float,
                            deadline_ms: Optional[float] = None) -> Request:
        """Range answers need no suppression — this is a plain range
        submit whose ids happen to be window ids."""
        return self.submit_range(query, epsilon, deadline_ms)

    def submit_subseq_knn(self, query, k: int, excl: Optional[int] = None,
                          deadline_ms: Optional[float] = None) -> Request:
        excl = self.excl if excl is None else int(excl)
        return self._batcher.submit(Request(
            kind=KIND_KNN, query=np.asarray(query, dtype=np.float32),
            k=self._fetch_k(k, excl), deadline=self._deadline(deadline_ms),
            meta={"subseq_k": int(k), "excl": excl}))

    def subseq_range(self, query, epsilon, deadline_ms=None, timeout=60.0):
        return self.range_query(query, epsilon, deadline_ms, timeout)

    def subseq_knn(self, query, k, excl=None, deadline_ms=None,
                   timeout=60.0):
        """Synchronous exclusion-zone k-NN; raises on rejection."""
        req = self.submit_subseq_knn(query, k, excl, deadline_ms)
        if req.wait(timeout) != OK:
            raise RuntimeError(f"subseq knn request {req.status}")
        return req.ids, req.distances

    # --- direct replay (the exactness reference) ----------------------------

    def direct_subseq_range(self, query, epsilon: float):
        return self.direct_query(KIND_RANGE, query, epsilon=epsilon)

    def direct_subseq_knn(self, query, k: int, excl: Optional[int] = None):
        excl = self.excl if excl is None else int(excl)
        return self.direct_query(
            KIND_KNN, query, k=self._fetch_k(k, excl),
            meta={"subseq_k": int(k), "excl": excl})

    # --- answer shaping -----------------------------------------------------

    def _postprocess(self, req: Request, rows, dist):
        """Exclusion-zone suppression, delegated to THE defining greedy
        (``core/subseq.suppress_trivial_matches`` — the same code the
        engine and distributed paths run, so the served answers cannot
        drift from them).  The candidate list is already ascending by
        (d², id), so scan *positions* stand in for the distance column:
        the returned "d2" values are then the kept positions, letting
        the untouched ``dist`` values pass straight through."""
        from ..core.subseq import suppress_trivial_matches

        meta = req.meta or {}
        if req.kind != KIND_KNN or "subseq_k" not in meta:
            return rows, dist
        k, excl = int(meta["subseq_k"]), int(meta["excl"])
        rows = np.asarray(rows)
        wid = np.arange(self.sidx.n_windows)
        stream_of, start_of = self.sidx.window_meta(wid)
        sel_idx, sel_pos = suppress_trivial_matches(
            rows[None, :],
            np.arange(rows.size, dtype=np.float64)[None, :],
            stream_of, start_of, k, excl)
        pos = sel_pos[0][sel_idx[0] >= 0].astype(int)
        return rows[pos], dist[pos]

    def window_meta(self, ids):
        """Window ids -> (stream index, start position) host arrays."""
        return self.sidx.window_meta(ids)
