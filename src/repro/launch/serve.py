"""Serving launcher — a thin driver over three serving modes:

  * LM decode loop (the model-stack smoke):
      PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
          --batch 4 --prompt-len 32 --gen 16
  * one-shot FAST_SAX search (range / k-NN over a sharded database):
      PYTHONPATH=src python -m repro.launch.serve --search --db-size 4096
      PYTHONPATH=src python -m repro.launch.serve --search --index-dir idx/
  * the online query service (``repro.serve``: dynamic micro-batching,
    admission control, deadlines, live ingest — DESIGN.md §6):
      PYTHONPATH=src python -m repro.launch.serve --serve --index-dir idx/ \
          --bench-requests 256 --clients 16 --verify-exact

``--serve`` runs the event loop in-process and drives it with the
closed-loop load generator (``--bench-requests``); the final line is a
machine-readable JSON summary (the CI serving smoke parses it).
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..models.transformer import decode_step, init_params, prefill
from ..runtime.compile_cache import use_compile_cache
from ..runtime.sharding import single_device
from .mesh import make_test_parallelism


def serve_lm(args):
    par = single_device()
    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    B = args.batch
    toks = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab_size)
    memory = None
    if cfg.kind == "encdec":
        memory = jax.random.normal(key, (B, cfg.enc_seq, cfg.d_model),
                                   cfg.jdtype)
    if cfg.kind == "vlm":
        memory = jax.random.normal(key, (B, cfg.img_tokens, cfg.d_model),
                                   cfg.jdtype)
    max_seq = args.prompt_len + args.gen

    prefill_fn = jax.jit(functools.partial(
        prefill, cfg, par, max_seq=max_seq))
    decode_fn = jax.jit(functools.partial(decode_step, cfg, par))

    t0 = time.perf_counter()
    logits, cache = prefill_fn(params, toks, memory=memory)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    out_tokens = []
    nxt = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    t0 = time.perf_counter()
    for _ in range(args.gen):
        out_tokens.append(np.asarray(nxt))
        logits, cache = decode_fn(params, cache, nxt)
        nxt = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = (time.perf_counter() - t0) / args.gen
    gen = np.concatenate(out_tokens, axis=1)
    print(f"[serve] arch={cfg.name} batch={B} prompt={args.prompt_len}")
    print(f"[serve] prefill {t_prefill*1e3:.1f} ms; "
          f"decode {t_decode*1e3:.1f} ms/token "
          f"({B/t_decode:.1f} tok/s aggregate)")
    print(f"[serve] sample generation (first row): {gen[0][:16].tolist()}")


def serve_subseq_search(args):
    """One-shot stream-sharded *subsequence* search (DESIGN.md §8):
    index every window of a stream batch across the mesh, then answer
    windowed range or exclusion-zone k-NN queries.

        PYTHONPATH=src python -m repro.launch.serve --search --subseq \\
            --streams 8 --stream-len 1024 --stride 4 --knn 3
    """
    from ..core.dist_search import (distributed_subseq_index,
                                    distributed_subseq_knn_query,
                                    distributed_subseq_range_query,
                                    make_data_mesh)
    from ..core.fastsax import FastSAXConfig
    from ..core.options import SearchOptions
    from ..core.subseq import build_subseq_index
    from ..data.timeseries import make_subseq_queries, make_wafer_like

    mesh = make_data_mesh()
    n_dev = len(jax.devices())
    streams = make_wafer_like(args.streams, args.stream_len, seed=0,
                              normalize=False)
    t0 = time.perf_counter()
    hidx = build_subseq_index(
        streams, FastSAXConfig(n_segments=(8, 16), alphabet=args.alphabet),
        args.window, args.stride)
    dsx = distributed_subseq_index(hidx, mesh)
    jax.block_until_ready(dsx.index.series)
    print(f"[subseq] indexed {dsx.n_valid} windows "
          f"({args.streams}x{args.stream_len}, w={args.window}, "
          f"s={args.stride}) on {n_dev} shard(s) "
          f"in {time.perf_counter()-t0:.2f}s")
    queries = make_subseq_queries(streams, args.queries, args.window, seed=1)
    excl = None if args.excl < 0 else args.excl
    if args.knn:
        t0 = time.perf_counter()
        sel_idx, sel_d2, exact = distributed_subseq_knn_query(
            dsx, queries, args.knn, mesh, excl=excl,
            options=SearchOptions(backend=args.backend))
        dt = time.perf_counter() - t0
        W_s = dsx.windows_per_stream
        for qi in range(min(4, args.queries)):
            pairs = [f"s{w // W_s}@{(w % W_s) * dsx.stride}:{d:.3f}"
                     for w, d in zip(sel_idx[qi], np.sqrt(sel_d2[qi]))
                     if w >= 0]
            print(f"[subseq-knn] q{qi}: {' '.join(pairs)}")
        print(f"[subseq-knn] k={args.knn} "
              f"excl={dsx.window // 2 if excl is None else excl}: "
              f"{args.queries} queries in {dt*1e3:.1f} ms; "
              f"exact={bool(exact.all())}")
        return
    t0 = time.perf_counter()
    gidx, ans, d2, overflow = distributed_subseq_range_query(
        dsx, queries, args.epsilon, mesh,
        options=SearchOptions(backend=args.backend))
    jax.block_until_ready(ans)
    dt = time.perf_counter() - t0
    ans = np.asarray(ans)
    gidx = np.asarray(gidx)
    for qi in range(min(4, args.queries)):
        hits = sorted(gidx[qi][ans[qi]].tolist())
        print(f"[subseq] q{qi}: {ans[qi].sum()} windows within "
              f"eps={args.epsilon} (first: {hits[:6]})")
    print(f"[subseq] {args.queries} queries in {dt*1e3:.1f} ms "
          f"({args.queries/dt:.0f} qps); "
          f"overflow={bool(np.asarray(overflow).any())}")


def serve_search(args):
    """FAST_SAX range-query / k-NN service over a sharded database.

    With ``--index-dir``, the offline artifact outlives the process: a
    matching sharded store warm-starts the service (O(ms) mmap load per
    shard instead of an O(B) rebuild), and a cold build persists its index
    for the next restart (DESIGN.md §5).
    """
    from ..core.dist_search import (distributed_build, distributed_knn_query,
                                    distributed_range_query_auto,
                                    load_sharded, make_data_mesh,
                                    pad_database, store_sharded)
    from ..core.options import SearchOptions
    from ..data.timeseries import make_queries, make_wafer_like

    n_dev = len(jax.devices())
    mesh = make_data_mesh()

    index = None
    store_after_build = False
    if args.index_dir:
        import os
        try:
            t0 = time.perf_counter()
            index, n_valid = load_sharded(args.index_dir, mesh)
            jax.block_until_ready(index.series)
            print(f"[search] warm start: {n_valid} series from "
                  f"{args.index_dir} on {n_dev} shard(s) "
                  f"in {time.perf_counter()-t0:.3f}s")
        except (FileNotFoundError, ValueError, IOError) as e:
            print(f"[search] cold start ({e})")
            index = None
            # Persist after the build ONLY into an empty/absent dir —
            # never clobber an existing store that merely failed to load
            # (wrong kind, mesh-size mismatch, corruption): that data may
            # be someone's only copy.
            store_after_build = (not os.path.exists(args.index_dir)
                                 or (os.path.isdir(args.index_dir)
                                     and not os.listdir(args.index_dir)))
            if not store_after_build:
                print(f"[search] NOT overwriting existing {args.index_dir}; "
                      f"remove it or pick a fresh --index-dir to persist")
    if index is None:
        # The database is only needed on the cold path — a warm start must
        # not pay O(B) host-side regeneration just to derive queries.
        db = make_wafer_like(args.db_size, 128, seed=0)
        padded, n_valid = pad_database(db, n_dev)
        t0 = time.perf_counter()
        index = distributed_build(padded, (8, 16), args.alphabet, mesh,
                                  n_valid=n_valid)
        jax.block_until_ready(index.series)
        print(f"[search] indexed {n_valid} series on {n_dev} shard(s) "
              f"in {time.perf_counter()-t0:.2f}s")
        if store_after_build:
            t0 = time.perf_counter()
            store_sharded(index, args.index_dir, n_valid=n_valid)
            print(f"[search] stored sharded index -> {args.index_dir} "
                  f"in {time.perf_counter()-t0:.2f}s")
    else:
        # Warm path: synthesise a small query-source batch instead of the
        # whole database (queries are wafer-like rows + noise either way).
        db = make_wafer_like(max(4 * args.queries, 64), 128, seed=0)
    queries = make_queries(db, args.queries, seed=1)
    if args.knn:
        k = args.knn
        t0 = time.perf_counter()
        nn_idx, nn_d2, exact = distributed_knn_query(
            index, queries, k, mesh, n_valid=n_valid,
            options=SearchOptions(backend=args.backend,
                                  normalize_queries=False))
        jax.block_until_ready(nn_d2)
        dt = time.perf_counter() - t0
        nn_idx = np.asarray(nn_idx)[:, :k]
        nn_d = np.sqrt(np.asarray(nn_d2))[:, :k]
        for qi in range(min(4, args.queries)):
            pairs = [f"{i}:{d:.3f}" for i, d in zip(nn_idx[qi], nn_d[qi])]
            print(f"[knn] q{qi}: {' '.join(pairs[:6])}")
        print(f"[knn] k={k}: {args.queries} queries in {dt*1e3:.1f} ms "
              f"({args.queries/dt:.0f} qps); "
              f"exact={bool(np.asarray(exact).all())}")
        return
    t0 = time.perf_counter()
    # Auto-escalating capacity: a shard whose survivors overflow the
    # candidate buffer is re-queried at 4x capacity (up to the shard size),
    # so served answers are never silently truncated.
    gidx, ans, d2, overflow = distributed_range_query_auto(
        index, queries, args.epsilon, mesh,
        options=SearchOptions(backend=args.backend, capacity=128,
                              normalize_queries=False))
    jax.block_until_ready(ans)
    dt = time.perf_counter() - t0
    ans = np.asarray(ans)
    gidx = np.asarray(gidx)
    for qi in range(min(4, args.queries)):
        hits = gidx[qi][ans[qi]]
        print(f"[search] q{qi}: {ans[qi].sum()} answers "
              f"(first: {sorted(hits.tolist())[:6]})")
    print(f"[search] {args.queries} queries in {dt*1e3:.1f} ms "
          f"({args.queries/dt:.0f} qps); overflow={bool(np.asarray(overflow).any())}")


def _obs_start(args, service):
    """Start the metrics endpoint when ``--metrics`` is set (port 0 lets
    the OS pick).  Returns the server (or None) for :func:`_obs_finish`."""
    if args.metrics < 0:
        return None
    from ..obs.metrics import start_metrics_server

    server = start_metrics_server(service.metrics_text, args.metrics,
                                  health_fn=getattr(service, "health",
                                                    None))
    print(f"[serve] metrics at "
          f"http://127.0.0.1:{server.server_address[1]}/metrics "
          f"(readiness at /healthz)")
    return server


def _drain_on_preempt(ph, service):
    """Arm a watcher that gracefully drains the service when the
    :class:`~repro.runtime.fault_tolerance.PreemptionHandler` catches
    SIGTERM: new submits shed, accepted requests finish, then the
    dispatcher stops — preemption never drops an accepted request."""
    import threading

    def watch():
        ph.requested.wait()
        print("[serve] SIGTERM: draining (new submits shed)")
        ok = service.drain(timeout_s=30.0)
        print(f"[serve] drain {'complete' if ok else 'TIMED OUT'}")

    t = threading.Thread(target=watch, name="repro-drain-watch",
                         daemon=True)
    t.start()
    return t


def _obs_finish(args, service, server):
    """Export trace artifacts, hold the metrics endpoint open for external
    scrapers (the CI smoke), then shut it down."""
    tracer = getattr(service, "tracer", None)
    if tracer is not None and args.trace_jsonl:
        n = tracer.to_jsonl(args.trace_jsonl)
        print(f"[serve] wrote {n} spans -> {args.trace_jsonl}")
    if tracer is not None and args.chrome_trace:
        n = tracer.to_chrome_trace(args.chrome_trace)
        print(f"[serve] wrote {n} chrome trace events -> {args.chrome_trace}")
    calibration = getattr(service, "calibration", None)
    if calibration is not None and args.calibration_out:
        n = calibration.to_jsonl(args.calibration_out)
        print(f"[serve] wrote {n} calibration records -> "
              f"{args.calibration_out} (render: python -m "
              f"benchmarks.roofline --calibration {args.calibration_out})")
    if server is not None:
        if args.metrics_hold_s > 0:
            print(f"[serve] holding metrics endpoint for "
                  f"{args.metrics_hold_s:g}s")
            time.sleep(args.metrics_hold_s)
        server.shutdown()


class _SubseqLoadShim:
    """Adapts a ``SubseqSearchService`` to the load generator's
    submit_knn/submit_range/direct_query surface, so ``run_closed_loop``
    and ``check_exactness`` drive the subsequence request family through
    the same closed-loop + replay machinery as the whole-series service."""

    def __init__(self, svc):
        self.svc = svc

    def submit_knn(self, q, k, deadline_ms=None):
        return self.svc.submit_subseq_knn(q, k, deadline_ms=deadline_ms)

    def submit_range(self, q, eps, deadline_ms=None):
        return self.svc.submit_subseq_range(q, eps, deadline_ms=deadline_ms)

    def direct_query(self, kind, q, epsilon=0.0, k=0):
        if kind == "knn":
            return self.svc.direct_subseq_knn(q, k)
        return self.svc.direct_subseq_range(q, epsilon)


def serve_subseq_service(args):
    """The online *subsequence* query service: windows-as-rows micro-batch
    dispatch with exclusion-zone k-NN shaping, driven by the closed-loop
    load generator with per-request replay verification.

        PYTHONPATH=src python -m repro.launch.serve --serve --subseq \\
            --streams 8 --stream-len 512 --bench-requests 128 --verify-exact
    """
    import json

    from ..data.timeseries import make_subseq_queries, make_wafer_like
    from ..runtime.fault_tolerance import PreemptionHandler
    from ..serve import (ServeConfig, SubseqSearchService, WorkloadSpec,
                         check_exactness, make_workload, run_closed_loop)

    cfg = ServeConfig(max_batch=args.max_batch, max_queue=args.max_queue,
                      max_wait_ms=args.max_wait_ms, alphabet=args.alphabet,
                      default_deadline_ms=args.deadline_ms or None,
                      backend=args.backend, trace=args.trace,
                      profile_dir=args.profile_dir)
    streams = make_wafer_like(args.streams, args.stream_len, seed=0,
                              normalize=False)
    excl = None if args.excl < 0 else args.excl
    t0 = time.perf_counter()
    service = SubseqSearchService.from_streams(
        streams, args.window, args.stride, cfg, excl=excl)
    print(f"[subseq-serve] indexed {service.sidx.n_windows} windows in "
          f"{time.perf_counter()-t0:.2f}s (excl={service.excl})")
    queries = make_subseq_queries(streams, max(args.queries, 16),
                                  args.window, seed=1)
    k = args.knn or 3
    t0 = time.perf_counter()
    service.warmup(ks=(service._fetch_k(k, service.excl),))
    print(f"[subseq-serve] warmup {time.perf_counter()-t0:.1f}s")
    spec = WorkloadSpec(n_requests=args.bench_requests,
                        knn_frac=args.knn_frac, k=k, epsilon=args.epsilon,
                        deadline_ms=args.deadline_ms or None)
    workload = make_workload(queries, spec)
    shim = _SubseqLoadShim(service)
    with PreemptionHandler() as ph, service:
        _drain_on_preempt(ph, service)
        server = _obs_start(args, service)
        result = run_closed_loop(shim, workload, clients=args.clients,
                                 deadline_ms=spec.deadline_ms,
                                 jsonl_path=args.request_log or None)
        mismatches = -1
        if args.verify_exact:
            mismatches = check_exactness(shim, workload, result)
        _obs_finish(args, service, server)
    snap = service.stats.snapshot()
    summary = result.summary(snap)
    summary["exact_mismatches"] = mismatches
    print(f"[subseq-serve] {summary['served']}/{summary['requests']} "
          f"served at {summary['qps']} qps; "
          f"mean batch {snap.get('mean_batch_size')}")
    print(f"[serve] summary {json.dumps(summary, sort_keys=True)}")


def serve_service(args):
    """The online query service event loop (``repro.serve``), driven by the
    closed-loop load generator.  Prints per-request samples, the stats
    snapshot, and a final machine-readable JSON summary line::

        [serve] summary {...}

    The CI serving smoke parses that line and asserts exactness and zero
    dropped in-deadline requests.
    """
    import json

    from ..data.timeseries import make_queries, make_wafer_like
    from ..runtime.fault_tolerance import PreemptionHandler
    from ..serve import (SearchService, ServeConfig, WorkloadSpec,
                         check_exactness, make_workload, run_closed_loop)

    cfg = ServeConfig(max_batch=args.max_batch, max_queue=args.max_queue,
                      max_wait_ms=args.max_wait_ms, alphabet=args.alphabet,
                      default_deadline_ms=args.deadline_ms or None,
                      backend=args.backend, quantization=args.quantization,
                      verify_prefetch=args.verify_prefetch,
                      trace=args.trace, profile_dir=args.profile_dir,
                      failover_shards=args.failover_shards)
    if args.index_dir:
        t0 = time.perf_counter()
        service = SearchService.from_store(args.index_dir, cfg)
        print(f"[serve] warm start: {service.backend.size} rows from "
              f"{args.index_dir} in {time.perf_counter()-t0:.3f}s "
              f"(live ingest: {'on' if service.mutable else 'off'})")
        # The query pool only needs series-shaped rows near the database
        # distribution; the warm path must not regenerate the database.
        pool_src = make_wafer_like(max(64, 4 * args.queries),
                                   service.backend.n, seed=0)
    else:
        db = make_wafer_like(args.db_size, 128, seed=0)
        t0 = time.perf_counter()
        service = SearchService.from_series(db, cfg)
        print(f"[serve] cold build: {args.db_size} rows in "
              f"{time.perf_counter()-t0:.2f}s")
        pool_src = db
    queries = make_queries(pool_src, max(args.queries, 16), seed=1)

    t0 = time.perf_counter()
    service.warmup(ks=(args.knn or 8,))
    print(f"[serve] warmup (bucket ladder precompile) "
          f"{time.perf_counter()-t0:.1f}s")

    spec = WorkloadSpec(n_requests=args.bench_requests,
                        knn_frac=args.knn_frac, k=args.knn or 5,
                        epsilon=args.epsilon,
                        deadline_ms=args.deadline_ms or None)
    workload = make_workload(queries, spec)
    with PreemptionHandler() as ph, service:
        _drain_on_preempt(ph, service)
        server = _obs_start(args, service)
        result = run_closed_loop(service, workload, clients=args.clients,
                                 deadline_ms=spec.deadline_ms,
                                 jsonl_path=args.request_log or None)
        mismatches = -1
        if args.verify_exact:
            mismatches = check_exactness(service, workload, result)
        _obs_finish(args, service, server)
    snap = service.stats.snapshot()
    summary = result.summary(snap)
    summary["exact_mismatches"] = mismatches
    lat = snap.get("latency_ms", {})
    print(f"[serve] {summary['served']}/{summary['requests']} served at "
          f"{summary['qps']} qps; p50/p95/p99 = {lat.get('p50')}/"
          f"{lat.get('p95')}/{lat.get('p99')} ms; "
          f"mean batch {snap.get('mean_batch_size')} "
          f"(occupancy {snap.get('batch_occupancy')})")
    print(f"[serve] summary {json.dumps(summary, sort_keys=True)}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    choices=configs.list_archs())
    # BooleanOptionalAction so --no-smoke can actually disable it (a bare
    # store_true with default=True was impossible to turn off).
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="use the smoke-sized arch config (--no-smoke for "
                         "the full config)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--search", action="store_true",
                    help="one-shot FAST_SAX search instead of an LM")
    ap.add_argument("--serve", action="store_true",
                    help="run the online query service event loop "
                         "(repro.serve) and drive it with the load "
                         "generator")
    ap.add_argument("--knn", type=int, default=0, metavar="K",
                    help="with --search: serve exact k-NN queries instead "
                         "of ε-range queries; with --serve: the workload's "
                         "k (default 5)")
    ap.add_argument("--db-size", type=int, default=4096)
    ap.add_argument("--index-dir", default="",
                    help="warm-start from this index store (--search: "
                         "sharded store, persisted after a cold build; "
                         "--serve: any repro.index artifact)")
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--epsilon", type=float, default=2.0)
    ap.add_argument("--alphabet", type=int, default=10)
    # Subsequence request family (DESIGN.md §8)
    ap.add_argument("--subseq", action="store_true",
                    help="with --search/--serve: subsequence workload — "
                         "index every window of a stream batch; k-NN "
                         "answers apply the exclusion zone")
    ap.add_argument("--streams", type=int, default=8,
                    help="with --subseq: number of streams")
    ap.add_argument("--stream-len", type=int, default=1024,
                    help="with --subseq: samples per stream")
    ap.add_argument("--window", type=int, default=128,
                    help="with --subseq: window length w")
    ap.add_argument("--stride", type=int, default=4,
                    help="with --subseq: window stride")
    ap.add_argument("--excl", type=int, default=-1,
                    help="with --subseq: exclusion-zone radius in start "
                         "positions (-1 = window // 2, 0 = off)")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "xla", "pallas"),
                    help="search engine backend (--search/--serve): "
                         "'auto' compiles the fused Pallas megakernel on "
                         "TPU and uses the XLA engine elsewhere; 'pallas' "
                         "off-TPU runs the kernels in interpret mode "
                         "(slow — parity/debug only)")
    ap.add_argument("--failover-shards", type=int, default=0, metavar="P",
                    help="with --serve: split the database over P "
                         "independently-queried shards with timeout/retry "
                         "failover — shard loss degrades to a certified-"
                         "partial answer (exact=False + coverage) instead "
                         "of an outage (0 = off; a warm start from a "
                         "quantized sharded store serves tiered shards)")
    ap.add_argument("--quantization", default="none",
                    choices=("none", "bf16", "int8"),
                    help="with --serve: quantized resident tier for the "
                         "screen columns; survivors verify against the "
                         "full-precision mmap tier (DESIGN.md §9)")
    ap.add_argument("--verify-prefetch", action="store_true",
                    help="with --serve + --quantization: double-buffer the "
                         "raw-tier verify fetch against device compute "
                         "(DESIGN.md §13) — answers stay bit-identical")
    # --serve knobs
    ap.add_argument("--bench-requests", type=int, default=256,
                    help="with --serve: closed-loop load-generator request "
                         "count")
    ap.add_argument("--clients", type=int, default=16,
                    help="with --serve: concurrent closed-loop clients")
    ap.add_argument("--knn-frac", type=float, default=0.5,
                    help="with --serve: fraction of k-NN requests in the "
                         "mixed workload")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="with --serve: per-request deadline (0 = none)")
    ap.add_argument("--verify-exact", action="store_true",
                    help="with --serve: replay every served request "
                         "through the direct path and count mismatches")
    # Observability (DESIGN.md §10) — all off by default.
    ap.add_argument("--trace", action="store_true",
                    help="with --serve: enable query-path tracing "
                         "(cascade counters into the stats surface, span "
                         "ring, per-dispatch cost-model calibration)")
    ap.add_argument("--metrics", type=int, default=-1, metavar="PORT",
                    help="with --serve: expose Prometheus metrics at "
                         "http://127.0.0.1:PORT/metrics (0 = OS-picked "
                         "port, -1 = off)")
    ap.add_argument("--metrics-hold-s", type=float, default=0.0,
                    help="with --metrics: keep the endpoint up this many "
                         "seconds after the workload, for external "
                         "scrapers (the CI smoke)")
    ap.add_argument("--trace-jsonl", default="",
                    help="with --trace: write the span ring to this JSONL "
                         "file after the run")
    ap.add_argument("--chrome-trace", default="",
                    help="with --trace: write Chrome trace-event JSON "
                         "(chrome://tracing / Perfetto) after the run")
    ap.add_argument("--calibration-out", default="",
                    help="with --trace: write the cost-model calibration "
                         "log to this JSONL file after the run (render "
                         "with benchmarks.roofline --calibration)")
    ap.add_argument("--request-log", default="",
                    help="with --serve: write the load generator's "
                         "per-request JSONL to this file")
    ap.add_argument("--profile-dir", default="",
                    help="jax.profiler trace directory: one session from "
                         "service start to stop, holding every repro.* "
                         "span beside the device operations")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.serve:
        serve_subseq_service(args) if args.subseq else serve_service(args)
    elif args.search:
        serve_subseq_search(args) if args.subseq else serve_search(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
