"""One run of one cell: set up, drive the window, compare, report.

Everything that belongs to one configuration, traffic mix, metric or
kernel is a file of its own, found by the name ``BENCHMARK.json`` gives:

    bench/configs/<config>.json    sizes, ServeConfig fields, source
    bench/traffic/<traffic>.json   the mix's parameters (bench/loadgen.py)
    bench/metrics/<metric>.py      read(run) -> number or None
    bench/kernels/<kernel>.py      PATTERN, cost(hlo) -> (bytes, ops)
    bench/peaks.json               chip peaks by device_kind

The service is the program's own, built and called through its public
entry points.  The benchmark only wraps timestamps (and, with
``--trace 1``, profiler annotations) around three of its calls: the
batcher's dispatch callback, the backend's dispatch and the per-request
``_finish``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

import data          # noqa: E402
import loadgen       # noqa: E402
import reference     # noqa: E402
import trace as tr   # noqa: E402

ANSWER_GRACE_S = 60.0     # how long past the close late answers are awaited


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernels() -> dict:
    return {p.stem: load_module("kernels", p.stem)
            for p in sorted((BENCH / "kernels").glob("*.py"))
            if not p.stem.startswith("_")}


def process_start() -> float:
    """Wall-clock instant this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration):
        return time.time()


def quantile(x, q: float) -> float:
    """Linear-interpolation quantile (numpy's default) that stays +inf,
    rather than nan, where it falls among infinite values."""
    x = np.sort(np.asarray(x, np.float64))
    pos = q * (len(x) - 1)
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if np.isinf(x[hi]):
        return float(x[hi]) if hi > lo or np.isinf(x[lo]) else float(x[lo])
    return float(x[lo] + (x[hi] - x[lo]) * (pos - lo))


@dataclasses.dataclass
class Batch:
    t0: float                # dispatch callback start (perf_counter)
    live: int = 0
    qb: int = 0              # Q bucket the backend was called with
    kb: int = 0              # k bucket
    out_bytes: int = 0       # bytes of the dispatch outputs on the host
    slots: int = 0           # candidate slots per query in the outputs
    tiered: bool = False


class Hooks:
    """Timestamps around the service's calls; profiler annotations too
    when ``annotate``.  ``fault`` (tests only) wraps the backend's
    dispatch to break what it returns."""

    def __init__(self, svc, annotate: bool, fault=None):
        import jax

        self.start, self.done, self.batches = {}, {}, []
        ann = jax.profiler.TraceAnnotation if annotate else None
        tiered = hasattr(svc.backend, "tindex")
        cb, finish = svc._batcher._dispatch_fn, svc._finish
        backend = svc.backend.dispatch
        if fault is not None:
            backend = fault(backend)

        def on_batch(batch):
            b = Batch(t0=time.perf_counter(),
                      live=sum(not r._done.is_set() for r in batch))
            for r in batch:
                self.start[id(r)] = b.t0
            self.batches.append(b)
            if ann:
                with ann("bench.dispatch_cb", live=b.live):
                    cb(batch)
            else:
                cb(batch)

        def on_backend(q, eps, is_knn, k, want_trace=False):
            if ann:
                with ann("bench.backend_dispatch", qb=int(q.shape[0]),
                         kb=int(k)):
                    out = backend(q, eps, is_knn, k, want_trace)
            else:
                out = backend(q, eps, is_knn, k, want_trace)
            if self.batches:
                b = self.batches[-1]
                b.qb, b.kb, b.tiered = int(q.shape[0]), int(k), tiered
                b.out_bytes = sum(int(np.asarray(a).nbytes) for a in out[:3])
                b.slots = int(np.asarray(out[0]).shape[-1])
            return out

        def on_finish(req, *args, **kw):
            if ann:
                with ann("bench.finish"):
                    finish(req, *args, **kw)
            else:
                finish(req, *args, **kw)
            self.done[id(req)] = time.perf_counter()

        svc._batcher._dispatch_fn = on_batch
        svc.backend.dispatch = on_backend
        svc._finish = on_finish

    def reset(self):
        self.start.clear()
        self.done.clear()
        self.batches.clear()


class CompileCounter:
    """While ``on``: executables built (compiled, or loaded from the
    persistent cache: ``hits``) and the seconds that took."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax

        self.on = False
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def reset(self):
        self.count, self.hits, self.seconds = 0, 0, 0.0

    def _seen(self, event, duration, **kw):
        if not self.on:
            return
        if event == self.BUILD:
            self.count += 1
            self.seconds += duration
        elif event == self.HIT:
            self.hits += 1


@dataclasses.dataclass
class RunRecord:
    """What a metric reader sees."""

    cfg: dict
    mix: dict
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    due: np.ndarray          # per request, perf_counter instants
    submit: np.ndarray
    start: np.ndarray        # dispatch callback start (nan: never)
    done: np.ndarray         # answer time (nan: never)
    ok: np.ndarray
    batches: list            # [Batch] of the window
    trace: object = None     # trace.Trace with --trace 1
    peaks: dict = None
    kernels: dict = None

    @property
    def latency_s(self) -> np.ndarray:
        """Due time to answer, +inf for a request never answered OK."""
        return np.where(self.ok, self.done - self.due, np.inf)


class Log:
    def __init__(self, device: str):
        self.device = device

    def __call__(self, tag: str, **kv):
        body = " ".join(f"{k}={v!r}" for k, v in kv.items())
        print(f"[{tag}] {self.device} {body}", flush=True)


def serve_config(cfg: dict):
    from repro.serve.service import ServeConfig

    return ServeConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in cfg["serve"].items()})


def range_eps(db, pool: np.ndarray, rank: int) -> np.ndarray:
    """ε of each pool query: halfway, in d², between its ``rank``-th and
    next nearest rows (float32 on the device)."""
    d2, _ = reference.nearest(db, pool, rank + 1)
    return np.sqrt(0.5 * (d2[:, rank - 1] + d2[:, rank])).astype(np.float32)


def _wait_all(reqs: list, until: float):
    for r in reqs:
        r._done.wait(max(0.0, until - time.perf_counter()))


def cache_everything():
    """Keep every program in the persistent cache, however fast it
    compiled, and never evict: the cache is the checkout's own."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_info():
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    return devs, dev, Log(f"platform={dev['platform']} "
                          f"device_kind={dev['kind']!r} "
                          f"count={dev['count']}")


class Session:
    """Set-up of one process: the collection, the query pool and its ε,
    the service built through ``SearchService.from_series``, the hooks,
    and the warm-up that compiles every shape the cell's traffic forms."""

    def __init__(self, cfg: dict, mix: dict, seed: int, traced: bool, log,
                 fault=None):
        import jax

        from repro.serve.service import SearchService

        self.cfg, self.mix, self.seed, self.log = cfg, mix, seed, log
        self.counter = CompileCounter()
        t0 = time.perf_counter()
        db = data.random_walks(seed, cfg["rows"], cfg["n"])
        db.block_until_ready()
        t_data = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.pool, self.sigma = data.make_pool(db, seed, int(mix["pool"]),
                                               mix["noise_sigma"])
        self.eps = range_eps(db, self.pool, int(mix["range_rank"]))
        t_pool = time.perf_counter() - t0
        t0 = time.perf_counter()
        scfg = serve_config(cfg)
        series = db if scfg.quantization == "none" else \
            np.asarray(jax.device_get(db))
        del db
        svc = SearchService.from_series(series, scfg)
        del series
        jax.block_until_ready(jax.tree_util.tree_leaves(
            getattr(svc.backend, "index", None) or svc.backend.tindex.dev))
        t_build = time.perf_counter() - t0

        self.svc = svc
        self.hooks = Hooks(svc, annotate=traced, fault=fault)
        svc.start()
        t0 = time.perf_counter()
        self.counter.on = True
        # A tiered service compiles one program per output width, and the
        # width follows the candidates a batch keeps: its warm-up replays
        # every noise level and the queries that reach the widest outputs.
        # A dense service's shapes do not depend on the query values, so
        # one level (the noisiest) reaches every program it builds.
        tiered = hasattr(svc.backend, "tindex")
        levels = sorted(set(mix["noise_sigma"])) if tiered \
            else [max(mix["noise_sigma"])]
        hard = self._probe(mix, scfg.max_batch) if tiered else None
        t_probe = time.perf_counter() - t0
        steps = []
        for burst in loadgen.warmup_bursts(mix, self.sigma, scfg.max_batch,
                                           levels, hard):
            c1 = self.counter.count
            secs, slots = self._burst(burst)
            steps.append((len(burst), sum(kn for _, kn, _ in burst),
                          max(k for _, _, k in burst), slots, round(secs, 3),
                          self.counter.count - c1))
        self.counter.on = False
        log("warmup", levels=levels, probe_s=t_probe, hard=hard,
            bursts_size_knn_kmax_slots_s_builds=steps)
        log("setup", data_s=t_data, pool_and_eps_s=t_pool, build_s=t_build,
            warmup_s=time.perf_counter() - t0,
            warmup_batches=len(self.hooks.batches),
            warmup_executables=self.counter.count,
            warmup_cache_hits=self.counter.hits,
            warmup_build_s=self.counter.seconds,
            warmup_buckets=sorted({(b.qb, b.kb, b.slots)
                                   for b in self.hooks.batches}),
            backend=type(svc.backend).__name__,
            engine=getattr(svc.backend, "backend", None))

    def _burst(self, burst: list):
        """Send ``burst`` at once and wait for it: (seconds, widest output
        slots of the batches it formed)."""
        t0, b0 = time.perf_counter(), len(self.hooks.batches)
        reqs = [loadgen.submit(self.svc, self.pool[p], knn, k, self.eps[p])
                for p, knn, k in burst]
        _wait_all(reqs, time.perf_counter() + 900.0)
        bad = [(r.status, str(r.error)) for r in reqs if r.status != "ok"]
        if bad:
            raise RuntimeError(f"warm-up request not answered: {bad[:2]}")
        return (time.perf_counter() - t0,
                max((b.slots for b in self.hooks.batches[b0:]), default=0))

    def _probe(self, mix: dict, qmax: int) -> dict:
        """The queries that form the widest outputs, for range and for k-NN
        at the largest k: the noisiest queries are sent in full batches,
        then the members of the batches with the widest output one by one.
        A service whose outputs grow with the candidates it keeps compiles
        one program per width, and these queries reach the widest."""
        noisy = np.flatnonzero(self.sigma == self.sigma.max())
        kmax = max(int(k) for k in mix["knn_k"])
        hard = {}
        for kind, knn, k in (("range", False, 0), ("knn", True, kmax)):
            groups = [noisy[i:i + qmax] for i in range(0, len(noisy), qmax)]
            widths = [self._burst([(int(p), knn, k) for p in g])[1]
                      for g in groups]
            top = max(widths)
            cand = [int(p) for g, w in zip(groups, widths) if w == top
                    for p in g][:2 * qmax]
            singles = [p for p in cand
                       if self._burst([(p, knn, k)])[1] == top]
            hard[kind] = singles or cand
        return hard

    def window(self, mix: dict, seconds: float, logdir=None):
        """Drive one window of ``mix``; with ``logdir``, under the
        profiler.  Returns (schedule, sent, t_open, t_close, batches)."""
        import jax

        self.hooks.reset()
        self.counter.reset()
        sched = loadgen.schedule(mix, self.seed, seconds)
        if logdir:
            jax.profiler.start_trace(logdir)
        self.counter.on = True
        t_open = time.perf_counter() + 0.05
        t_close = t_open + seconds
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                sent = loadgen.drive(self.svc, sched, self.pool, self.eps,
                                     t_open)
                _wait_all(sent.requests, t_close + ANSWER_GRACE_S)
        finally:
            if logdir:
                jax.profiler.stop_trace()
            self.counter.on = False
        self.t_last = time.perf_counter()
        return sched, sent, t_open, t_close, list(self.hooks.batches)

    def times(self, sent):
        """(dispatch start, answer) instants per request, nan where none."""
        nan = float("nan")
        return (np.array([self.hooks.start.get(id(r), nan)
                          for r in sent.requests]),
                np.array([self.hooks.done.get(id(r), nan)
                          for r in sent.requests]))

    def close(self):
        self.svc.stop()
        del self.svc, self.hooks
        gc.collect()


def window_line(log, sess, sent, t_close, batches):
    ok = np.array([r.status == "ok" for r in sent.requests])
    _, done = sess.times(sent)
    statuses = {}
    for r in sent.requests:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    lat = np.where(ok, done - sent.due, np.inf) * 1e3
    log("window", requests=len(sent.requests), statuses=statuses,
        executables_built_in_window=sess.counter.count,
        batches=len(batches),
        answered_after_close=int(np.sum(ok & (done > t_close))),
        last_answer_after_close_s=sess.t_last - t_close,
        p50_ms=quantile(lat, 0.5) if lat.size else None,
        p95_ms=quantile(lat, 0.95) if lat.size else None,
        gen_late_p95_ms=float(np.quantile(
            (sent.submit - sent.due) * 1e3, 0.95)) if lat.size else None,
        mean_live_per_batch=float(np.mean([b.live for b in batches]))
        if batches else 0.0,
        buckets=sorted({(b.qb, b.kb, b.slots) for b in batches}))
    return ok


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        traced: bool, metrics: list, device_check=None, fault=None,
        keep_trace: str | None = None) -> dict:
    """Run one cell; returns the result object (the last line).  Prints
    phase lines on stdout and the compared numbers on stderr.
    ``metrics`` are the cell's metric entries of ``BENCHMARK.json``;
    ``device_check(device)`` refuses a device or returns its peaks."""
    t_proc = process_start()
    import jax

    from repro.runtime.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    cache_everything()
    devs, dev, log = device_info()
    peaks = device_check(dev) if device_check else None
    log("bench", cell=cell["name"], seed=seed, seconds=seconds,
        trace=int(traced), rows=cfg["rows"], n=cfg["n"],
        compile_cache=cache_dir)
    sess = Session(cfg, mix, seed, traced, log, fault=fault)
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    wall, now = time.time(), time.perf_counter()
    sched, sent, t_open, t_close, batches = sess.window(mix, seconds, logdir)
    setup_s = wall + (t_open - now) - t_proc
    peak = int((devs[0].memory_stats() or {}).get("peak_bytes_in_use", -1))
    ok = window_line(log, sess, sent, t_close, batches)
    start, done = sess.times(sent)
    log("memory", peak_bytes_in_use=peak)
    rec = RunRecord(
        cfg=cfg, mix=mix, seconds=seconds, setup_s=setup_s, t_open=t_open,
        t_close=t_close, due=sent.due, submit=sent.submit, start=start,
        done=done, ok=ok, batches=batches, peaks=peaks, kernels=kernels())
    answers = [(r.ids, r.distances) if r.status == "ok" else (None, None)
               for r in sent.requests]
    asks_raw = [(int(sched.query[i]), "knn" if sched.knn[i] else "range",
                 int(sched.k[i]), float(sess.eps[sched.query[i]]))
                for i in range(len(sent.requests))]
    pool = sess.pool
    sess.close()
    del sess

    if traced:
        rec.trace = tr.load(logdir)
        if keep_trace:
            tr.describe(logdir, os.path.join(keep_trace, "describe.json"))
            with open(os.path.join(keep_trace, "events.json"), "w") as f:
                json.dump(rec.trace.to_json(), f)
        shutil.rmtree(logdir, ignore_errors=True)

    t0 = time.perf_counter()
    numbers = compare(cfg, seed, pool, asks_raw, answers,
                      unanswered=int((~ok).sum()))
    log("reference", reference_s=time.perf_counter() - t0,
        compared=int(ok.sum()), band_d2=reference.band_d2(cfg["n"]))

    values = {}
    for name in (m["name"] for m in metrics):
        mod = load_module("metrics", name)
        v = mod.read(rec)
        if v is not None:
            # JSON has no infinity: a never-answered request's latency
            # reads as 1e30 ms.
            values[name] = float(v) if np.isfinite(v) else 1e30
        note = mod.describe(rec) if hasattr(mod, "describe") else ""
        if note:
            log(f"metric {name}", note=note)
    units = {m["name"]: m["unit"] for m in metrics}
    result = {
        "correct": reference.passes(numbers),
        "attempted": len(sent.requests),
        "failed": int((~ok).sum()),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": dict(dev, memory_peak_bytes=peak),
    }
    if traced:
        result["device"]["busy_s"] = tr.busy_s(rec.trace)
        result["device"]["window_s"] = rec.trace.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(rec.trace),
                               "idle_gaps": tr.idle_gaps(rec.trace)}
    result["checks"] = numbers
    for name, v in numbers.items():
        print(f"check {name} value={v['value']!r} limit={v['limit']!r} "
              f"{'ok' if v['value'] <= v['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    return result


def compare(cfg: dict, seed: int, pool: np.ndarray, asks_raw: list,
            answers: list, unanswered: int) -> dict:
    """The numbers compared: every answered request of the window against
    the float64 reference, on the collection made anew from the seed."""
    db = data.random_walks(seed, cfg["rows"], cfg["n"])
    used = sorted({a[0] for a, (ids, _) in zip(asks_raw, answers)
                   if ids is not None})
    slot = {p: i for i, p in enumerate(used)}
    served = [[] for _ in used]
    asks, got = [], []
    for (p, kind, k, e), (ids, dist) in zip(asks_raw, answers):
        if ids is None:
            continue
        served[slot[p]].extend(np.asarray(ids).tolist())
        asks.append((slot[p], kind, k, e))
        got.append((ids, dist))
    if used:
        ref = reference.build_reference(db, pool[used], served)
        numbers = reference.check(ref, asks, got, cfg["rows"], unanswered)
    else:
        numbers = reference.check(None, [], [], cfg["rows"], unanswered)
    del db
    return numbers
