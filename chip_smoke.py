#!/usr/bin/env python3
"""Bring-up check of the served exact search on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded services, 4-chip host

One process, the one that holds the chip(s).  It refuses to run without a
TPU: there is no CPU fallback.

* Data: z-normalised random walks of length 256, the shape of the Hydra
  benchmark (Echihabi et al., "The Lernaean Hydra of Data Series
  Similarity Search", PVLDB 12(2), 2018), made on the host from a fixed
  seed: 2^21 rows, 2 GiB of float32.  32 queries are database
  rows plus Gaussian noise of three sizes.
* Reference: plain brute force in float64 on the host, in blocks,
  independent of the engine (its own z-normalisation, its own distances).
* Services, built through the public entry points:
  one chip — ``SearchService.from_series(db, ServeConfig())`` (full
  precision, ``backend="auto"``) and the int8 tier,
  ``SearchService.from_store`` on an index store that ``repro.index``
  writes (the raw tier stays on a host mmap);
  ``--chips 4`` — the same two configurations through
  ``from_series(..., mesh=make_data_mesh())``.
* Traffic: ``warmup()`` (one chip: the service's default bucket ladder,
  as ``launch/serve.py --serve`` runs it; four chips: the buckets the
  traffic forms), then 32 requests through the micro-batcher:
  16 exact k-NN (k = 1 and 10) and 16 ε-range, each ε halfway (in d²)
  between the query's 20th and 21st reference distances.
* Checks: every request ends OK; k-NN ids equal the reference's, ties
  broken by id; range sets equal the reference's except rows whose f64 d²
  lies within ``n·2⁻²³·(‖q‖² + ‖u‖²)`` of ε² (the worst-case f32 error of
  an n-term dot, counted and printed); every exactness certificate is
  true; and the dispatch ran compiled Pallas (one chip: the backend's
  engine is "pallas" and the device call it makes for the traffic's k
  bucket lowers to a ``tpu_custom_call``;
  four chips: every index array spans all four devices).

Each phase prints one line; the last line is the JSON device record.
Any failed check exits non-zero before that line is printed.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE / "src"))

from repro.runtime.compile_cache import use_compile_cache  # noqa: E402

ROWS = 1 << 21            # database rows: 2 GiB of float32 series
SEED = 0                  # data and query seed
N = 256                   # series length (Hydra)
LEVELS = (8, 16)          # ServeConfig's default cascade
N_QUERIES = 32
KNN_KS = (1, 10)
RANGE_RANK = 20           # ε sits between the 20th and 21st neighbour
NOISE = (0.1, 0.3, 1.0)   # query noise σ, cycled: easy to hard pruning
GEN_BLOCK = 1 << 16       # rows per host generation / reference block


def log(msg: str) -> None:
    print(msg, flush=True)


def random_walks(rows: int, seed: int) -> np.ndarray:
    """(rows, N) float32 z-normalised random walks, made in blocks."""
    rng = np.random.default_rng(seed)
    out = np.empty((rows, N), np.float32)
    for lo in range(0, rows, GEN_BLOCK):
        w = rng.standard_normal((min(GEN_BLOCK, rows - lo), N),
                                dtype=np.float32).cumsum(axis=1)
        w -= w.mean(axis=1, keepdims=True)
        w /= w.std(axis=1, keepdims=True)
        out[lo:lo + w.shape[0]] = w
    return out


def make_queries(db: np.ndarray, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 1)
    rows = rng.integers(0, db.shape[0], N_QUERIES)
    sigma = np.resize(np.asarray(NOISE, np.float32), N_QUERIES)[:, None]
    noise = rng.standard_normal((N_QUERIES, N)).astype(np.float32)
    return db[rows] + sigma * noise


def znorm64(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    x = x - x.mean(axis=1, keepdims=True)
    return x / np.maximum(x.std(axis=1, keepdims=True), 1e-8)


def reference_d2(db: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """(Q, B) float64 squared distances of the z-normalised rows."""
    q = znorm64(queries)
    qn = (q * q).sum(axis=1)[:, None]
    out = np.empty((q.shape[0], db.shape[0]))
    for lo in range(0, db.shape[0], GEN_BLOCK):
        x = znorm64(db[lo:lo + GEN_BLOCK])
        out[:, lo:lo + x.shape[0]] = qn - 2.0 * (q @ x.T) + \
            (x * x).sum(axis=1)[None, :]
    return np.maximum(out, 0.0)


def reference_knn(d2_row: np.ndarray, k: int) -> np.ndarray:
    """The k nearest row ids, ties broken by the lower id."""
    kth = np.partition(d2_row, k - 1)[k - 1]
    cand = np.flatnonzero(d2_row <= kth)
    return cand[np.lexsort((cand, d2_row[cand]))][:k]


def make_workload(d2: np.ndarray):
    """(kind, k, ε) per query: even queries k-NN, odd queries ε-range."""
    work = []
    for i in range(d2.shape[0]):
        if i % 2 == 0:
            work.append(("knn", KNN_KS[(i // 2) % len(KNN_KS)], 0.0))
        else:
            lo, hi = np.partition(d2[i], RANGE_RANK)[RANGE_RANK - 1:
                                                    RANGE_RANK + 1]
            work.append(("range", 0, float(np.sqrt(0.5 * (lo + hi)))))
    return work


#: Half-width of the float32 band around ε² that the range comparison
#: leaves out: n·2⁻²³·(‖q‖² + ‖u‖²), with ‖q‖² = ‖u‖² = n for z-normalised
#: rows — the worst-case f32 error of an n-term dot.
BAND_D2 = N * 2.0 ** -23 * (2.0 * N)


def serve_and_check(name: str, svc, queries, d2, work, failures: list,
                    ladder: bool = True):
    from repro.serve.batcher import OK

    t0 = time.perf_counter()
    if ladder:
        # The default ladder, as ``launch/serve.py --serve`` warms it.  It
        # holds k bucket 8 only: a batch holding k=10 compiles its k
        # bucket 16 on first use, inside serve_s.
        svc.warmup()
    else:
        # Only the buckets this traffic forms (one full batch, k raised to
        # the warmed floor): the sharded services compile every bucket
        # across the mesh, and the one-chip run covers the ladder.
        floor = min(svc.cfg.warmup_ks)
        svc.warmup(qs=[min(N_QUERIES, svc.cfg.max_batch)],
                   ks=sorted({max(k, floor) for k in KNN_KS}))
    t_warm = time.perf_counter() - t0
    svc.start()
    try:
        t0 = time.perf_counter()
        reqs = [svc.submit_knn(q, k) if kind == "knn"
                else svc.submit_range(q, eps)
                for q, (kind, k, eps) in zip(queries, work)]
        statuses = []
        for r in reqs:
            try:
                statuses.append(r.wait(timeout=900))
            except Exception as e:           # a FAILED dispatch re-raises
                statuses.append(f"FAILED: {type(e).__name__}: {e}")
        t_serve = time.perf_counter() - t0
    finally:
        svc.stop()
    snap = svc.stats.snapshot()
    n_failed = sum(s != OK for s in statuses)
    knn_bad = range_bad = in_band = 0
    for i, (r, (kind, k, eps)) in enumerate(zip(reqs, work)):
        if r.status != OK:
            continue
        if kind == "knn":
            want = reference_knn(d2[i], k)
            if not np.array_equal(np.asarray(r.ids), want):
                knn_bad += 1
                log(f"[{name}] knn mismatch q{i} k={k}: got "
                    f"{np.asarray(r.ids).tolist()} want {want.tolist()}")
        else:
            got = set(np.asarray(r.ids).tolist())
            want = set(np.flatnonzero(d2[i] <= eps * eps).tolist())
            near = np.abs(d2[i] - eps * eps) <= BAND_D2
            in_band += int(near.sum())
            off = [j for j in got ^ want if not near[j]]
            range_bad += len(off)
            if off:
                log(f"[{name}] range mismatch q{i} eps={eps!r}: rows "
                    f"{off[:8]} (got {len(got)}, want {len(want)})")
    certified = snap["events"]["certified_exact"]
    cert_total = snap["events"]["certified_total"]
    not_exact = sum(1 for r in reqs if r.status == OK and not r.exact)
    log(f"[{name}] warmup_compile_s={t_warm!r} serve_s={t_serve!r} "
        f"requests={len(reqs)} failed={n_failed} batches={snap['batches']} "
        f"knn_mismatches={knn_bad} range_mismatches={range_bad} "
        f"range_rows_in_f32_band={in_band} band_d2={BAND_D2!r} "
        f"certified={certified}/{cert_total} not_exact={not_exact}")
    for s in statuses:
        if s != OK:
            log(f"[{name}] request status {s}")
    if n_failed:
        failures.append(f"{name}: {n_failed} requests not OK")
    if knn_bad:
        failures.append(f"{name}: {knn_bad} k-NN answers differ from f64")
    if range_bad:
        failures.append(f"{name}: {range_bad} range rows differ from f64 "
                        "outside the f32 band")
    if certified != cert_total or cert_total == 0 or not_exact:
        failures.append(f"{name}: certificates {certified}/{cert_total}, "
                        f"{not_exact} answers not exact")


def check_pallas_single(name: str, svc, queries, failures: list):
    """The backend resolved to compiled Pallas and its dispatch lowers to
    a Mosaic kernel (``tpu_custom_call``), not interpret mode."""
    import jax
    import jax.numpy as jnp

    from repro.core import engine

    be = svc.backend
    engine_name = be.backend
    q = jnp.asarray(queries[:8])
    eps = jnp.ones((8,), jnp.float32)
    if hasattr(be, "tindex"):
        # The screen call engine.quantized_mixed_query makes.
        dev = be.tindex.dev
        qr = engine.represent_queries(q, dev.levels, dev.alphabet)
        lowered = jax.jit(lambda d, r, e: engine._quantized_screen_backend(
            engine.TieredIndex(dev=d, raw=None), r, e.reshape(-1, 1),
            engine_name)).lower(dev, qr, eps)
    else:
        # The device call the backend makes for the traffic's k bucket.
        kb = 1 << (max(max(KNN_KS), *svc.cfg.warmup_ks) - 1).bit_length()
        fused = be.fused_call(kb)
        if fused is None:
            failures.append(f"{name}: k bucket {kb} dispatches to XLA")
            return
        ix = be.index
        qr = engine.represent_queries(q, ix.levels, ix.alphabet)
        knn = jnp.arange(8) % 2 == 0
        lowered = jax.jit(fused).lower(ix, qr, eps, knn)
    kernel = "tpu_custom_call" in lowered.as_text()
    log(f"[{name}] engine={engine_name} tpu_custom_call={kernel}")
    if engine_name != "pallas" or not kernel:
        failures.append(f"{name}: dispatch is not compiled Pallas "
                        f"(engine={engine_name}, kernel={kernel})")


def check_pallas_sharded(name: str, svc, queries, failures: list):
    """Each shard of the sharded full-precision dispatch runs the Mosaic
    kernel (the lowered ``shard_map`` body holds a ``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp

    from repro.core.dist_search import distributed_mixed_query
    from repro.core.options import SearchOptions

    be = svc.backend
    knn = jnp.arange(8) % 2 == 0
    lowered = jax.jit(lambda d, q, e, m: distributed_mixed_query(
        d, q, e, m, 16, be.mesh, options=SearchOptions(capacity=64),
        n_valid=be.n_valid)).lower(be.index, jnp.asarray(queries[:8]),
                                   jnp.ones((8,), jnp.float32), knn)
    kernel = "tpu_custom_call" in lowered.as_text()
    log(f"[{name}] tpu_custom_call={kernel}")
    if not kernel:
        failures.append(f"{name}: sharded dispatch is not compiled Pallas")


def check_spread(name: str, tree, n_dev: int, failures: list):
    """Every index array spans all ``n_dev`` devices (none sits on one)."""
    import jax

    narrow = [a.shape for a in jax.tree_util.tree_leaves(tree)
              if hasattr(a, "sharding")
              and len(a.sharding.device_set) != n_dev]
    log(f"[{name}] index arrays on fewer than {n_dev} devices: "
        f"{len(narrow)}")
    if narrow:
        failures.append(f"{name}: arrays not spread over the mesh: "
                        f"{narrow[:4]}")


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform={devs[0].platform!r}); "
              "this check runs on the chip only", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 2

    from repro.core.dist_search import make_data_mesh
    from repro.core.fastsax import FastSAXConfig, build_index
    from repro.index.store import save_index
    from repro.serve.service import SearchService, ServeConfig

    log(f"[setup] device={devs[0].device_kind!r} count={len(devs)} "
        f"chips={args.chips} rows={ROWS} n={N} "
        f"compile_cache={cache_dir}")
    t0 = time.perf_counter()
    db = random_walks(ROWS, SEED)
    queries = make_queries(db, SEED)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    d2 = reference_d2(db, queries)
    work = make_workload(d2)
    log(f"[reference] generate_s={t_gen!r} f64_reference_s="
        f"{time.perf_counter() - t0!r} db_bytes={db.nbytes}")

    failures: list = []
    mesh = make_data_mesh(args.chips) if args.chips > 1 else None
    scratch = pathlib.Path(tempfile.mkdtemp(prefix=".chip_smoke_",
                                            dir=HERE))
    try:
        # Full precision.
        t0 = time.perf_counter()
        svc = SearchService.from_series(db, ServeConfig(), mesh=mesh)
        jax.block_until_ready(jax.tree_util.tree_leaves(
            getattr(svc.backend, "index", None)))
        name = "f32" if mesh is None else "f32_sharded"
        log(f"[{name}] build_s={time.perf_counter() - t0!r} "
            f"backend={type(svc.backend).__name__}")
        if mesh is None:
            check_pallas_single(name, svc, queries, failures)
        else:
            check_spread(name, svc.backend.index, args.chips, failures)
            check_pallas_sharded(name, svc, queries, failures)
        serve_and_check(name, svc, queries, d2, work, failures,
                        ladder=mesh is None)
        del svc
        gc.collect()

        # int8 tier, raw rows on a host mmap.
        cfg8 = ServeConfig(quantization="int8")
        t0 = time.perf_counter()
        if mesh is None:
            host = build_index(db, FastSAXConfig(n_segments=LEVELS,
                                                 alphabet=cfg8.alphabet))
            save_index(host, scratch / "store", quantization="int8")
            del host
            t_store = time.perf_counter() - t0
            t0 = time.perf_counter()
            svc = SearchService.from_store(scratch / "store", cfg8)
            name = "int8"
            log(f"[{name}] store_build_s={t_store!r} warm_start_s="
                f"{time.perf_counter() - t0!r} "
                f"backend={type(svc.backend).__name__}")
            check_pallas_single(name, svc, queries, failures)
        else:
            svc = SearchService.from_series(db, cfg8, mesh=mesh)
            name = "int8_sharded"
            log(f"[{name}] build_s={time.perf_counter() - t0!r} "
                f"backend={type(svc.backend).__name__}")
            check_spread(name, svc.backend.dti.dev, args.chips, failures)
        serve_and_check(name, svc, queries, d2, work, failures,
                        ladder=mesh is None)
        del svc
        gc.collect()
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    log(f"[memory] peak_bytes_in_use="
        f"{[peak_bytes(d) for d in devs[:args.chips]]}")
    if failures:
        for f in failures:
            log(f"FAIL {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
