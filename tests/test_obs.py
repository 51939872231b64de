"""Observability subsystem tests (DESIGN.md §10).

The load-bearing claims, each asserted here:

  * **counter bit-agreement** — the device ``QueryTrace`` counters equal
    the op-counted host engine (``core/search.py``) EXACTLY, for the
    range, k-NN (final-radius) and quantized (widened-oracle) paths;
  * **traced == untraced answers** — the fused query+trace twins return
    bit-identical answer arrays to the untraced engines they shadow;
  * **exact order statistic** — ``_kth_smallest_rounds`` (the sort-free
    k-th used inside traced graphs) equals ``lax.top_k`` on adversarial
    grids: ties, +inf rows, duplicates, non-multiple widths;
  * **jit-cache stability** — running traced twins never retraces the
    untraced engines (tracing off costs zero compilations);
  * **bounded memory** — the span ring and calibration log never grow
    past capacity, and their exports round-trip;
  * **metrics surface** — every REQUIRED_FAMILIES family renders, with
    clean zeros before traffic;
  * **traced serving** — a ``trace=True`` service answers identically to
    the direct path and populates the cascade/span/calibration surfaces;
  * **program spans** — the ``repro.serve.*`` / ``repro.engine.*`` spans
    land in a ``jax.profiler`` trace, nested where the work happens, with
    the batch's shapes and counts as attributes.
"""
import glob
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import engine as eng
from repro.core.engine import (cascade_trace, device_index_from_host,
                               knn_query_traced, mixed_query,
                               mixed_query_and_trace, mixed_query_dense,
                               mixed_query_dense_and_trace,
                               range_query_traced, represent_queries)
from repro.core.fastsax import FastSAXConfig, build_index, represent_query
from repro.core.search import fastsax_range_query
from repro.data.timeseries import make_queries, make_wafer_like
from repro.obs.calibration import CalibrationLog
from repro.obs.metrics import REQUIRED_FAMILIES, build_registry
from repro.obs.spans import SpanRecorder
from repro.obs.trace import (QueryTrace, excluded_c9, excluded_c10,
                             merge_traces, select_queries, trace_totals)
from repro.serve import (OK, SearchService, ServeConfig, WorkloadSpec,
                         make_workload, run_saturated)

B, N, LEVELS, ALPHA = 256, 128, (8, 16), 10


@pytest.fixture(scope="module")
def hidx():
    db = make_wafer_like(B, N, seed=3, normalize=False)
    return db, build_index(db, FastSAXConfig(n_segments=LEVELS,
                                             alphabet=ALPHA),
                           normalize=False)


@pytest.fixture(scope="module")
def didx(hidx):
    return device_index_from_host(hidx[1])


@pytest.fixture(scope="module")
def queries(hidx):
    db, _ = hidx
    qs = make_queries(db, 8, seed=4)
    qr = represent_queries(jnp.asarray(qs, jnp.float32), LEVELS, ALPHA,
                           normalize=False)
    return np.asarray(qs), qr


# ---------------------------------------------------------------------------
# Counter bit-agreement with the op-counted host engine.
# ---------------------------------------------------------------------------

def host_counts(hidx, q, eps):
    cfg = FastSAXConfig(n_segments=LEVELS, alphabet=ALPHA)
    r = fastsax_range_query(hidx, represent_query(q, cfg, normalize=False),
                            eps)
    return (r.excluded_c9, r.excluded_c10, r.candidates, r.answers.size)


@pytest.mark.parametrize("eps", [0.5, 1.0, 2.0, 3.0])
def test_range_trace_bit_agrees_with_host(hidx, didx, queries, eps):
    db, host = hidx
    qs, qr = queries
    ans, _d2, tr = range_query_traced(didx, qr, np.float32(eps))
    c9 = excluded_c9(tr, B).sum(axis=-1)
    c10 = excluded_c10(tr).sum(axis=-1)
    n_ans = np.asarray(ans).sum(axis=-1)
    for qi in range(qs.shape[0]):
        assert (int(c9[qi]), int(c10[qi]), int(tr.candidates[qi]),
                int(n_ans[qi])) == host_counts(host, qs[qi], eps)


def test_knn_trace_bit_agrees_with_host_at_final_radius(hidx, didx, queries):
    db, host = hidx
    qs, qr = queries
    k = 5
    nn_idx, nn_d2, exact, tr = knn_query_traced(didx, qr, k)
    assert bool(np.asarray(exact).all())
    c9 = excluded_c9(tr, B).sum(axis=-1)
    c10 = excluded_c10(tr).sum(axis=-1)
    for qi in range(qs.shape[0]):
        d_k = float(np.sqrt(max(np.asarray(nn_d2)[qi, k - 1], 0.0)))
        hc9, hc10, hcand, _ = host_counts(host, qs[qi], d_k)
        assert (int(c9[qi]), int(c10[qi]),
                int(tr.candidates[qi])) == (hc9, hc10, hcand)
        assert int(np.asarray(tr.answers)[qi]) == k


def test_quantized_trace_bit_agrees_with_widened_host_oracle(hidx):
    from repro.core.engine import TieredIndex, quantized_range_query_traced
    from repro.core.search import quantized_fastsax_range_query
    from repro.index.quantized import quantize_host_index

    db, host = hidx
    tidx = TieredIndex.from_host(host, "int8")
    qhost = quantize_host_index(host, "int8")
    qs = make_queries(db, 4, seed=9)
    qr = represent_queries(jnp.asarray(qs, jnp.float32), LEVELS, ALPHA,
                           normalize=False)
    cfg = FastSAXConfig(n_segments=LEVELS, alphabet=ALPHA)
    for eps in (1.0, 2.0):
        _idx, _ans, _d2, _exact, tr = quantized_range_query_traced(
            tidx, qr, np.float32(eps))
        c9 = excluded_c9(tr, B).sum(axis=-1)
        c10 = excluded_c10(tr).sum(axis=-1)
        for qi in range(qs.shape[0]):
            r = quantized_fastsax_range_query(
                qhost, host.series,
                represent_query(qs[qi], cfg, normalize=False), eps)
            assert (int(c9[qi]), int(c10[qi])) == (r.excluded_c9,
                                                   r.excluded_c10)


def test_subseq_trace_self_consistent():
    from repro.core.subseq import (build_subseq_index,
                                   represent_subseq_queries,
                                   subseq_device_index,
                                   subseq_range_query_traced)

    rng = np.random.default_rng(11)
    streams = rng.standard_normal((4, 512)).astype(np.float32)
    cfg = FastSAXConfig(n_segments=LEVELS, alphabet=ALPHA)
    sidx = subseq_device_index(
        build_subseq_index(streams, cfg, window=128, stride=4))
    qr = represent_subseq_queries(sidx, streams[:1, 37:37 + 128])
    ans, d2, tr = subseq_range_query_traced(sidx, qr, 1.0)
    a9 = np.asarray(tr.after_c9)
    a10 = np.asarray(tr.after_c10)
    # per level: C10 never resurrects a C9 kill, next level only shrinks
    assert (a10 <= a9).all()
    assert (a9[:, 1:] <= a10[:, :-1]).all()
    assert int(np.asarray(tr.answers).sum()) == int(np.asarray(ans).sum())
    assert (np.asarray(tr.answers) <= tr.candidates).all()


# ---------------------------------------------------------------------------
# Traced twins: answers bit-identical to the untraced engines.
# ---------------------------------------------------------------------------

def _mixed_args(queries, pat):
    qs, qr = queries
    Q = qs.shape[0]
    eps = jnp.asarray(np.linspace(0.5, 3.0, Q), jnp.float32)
    is_knn = jnp.asarray(np.arange(Q) % 3 == 0) if pat == 0 else \
        jnp.asarray(np.arange(Q) % 2 == 1)
    return qr, eps, is_knn


@pytest.mark.parametrize("pat", [0, 1])
@pytest.mark.parametrize("k", [1, 5, 8])
def test_dense_twin_bit_identical_and_counters(didx, queries, pat, k):
    qr, eps, is_knn = _mixed_args(queries, pat)
    u = mixed_query_dense(didx, qr, eps, is_knn, k)
    t = mixed_query_dense_and_trace(didx, qr, eps, is_knn, k)
    for a, b in zip(u, t[:4]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    tr, knn, ans = t[4], np.asarray(is_knn), np.asarray(t[1])
    a9, a10 = np.asarray(tr.after_c9), np.asarray(tr.after_c10)
    ref = cascade_trace(didx, qr, eps)
    for qi in range(ans.shape[0]):
        if knn[qi]:
            # dense k-NN rows are brute-forced: every valid candidate is
            # screened-through and verified, the answer trims to k on host
            assert (a9[qi] == B).all() and (a10[qi] == B).all()
            assert int(np.asarray(tr.verified)[qi]) == B
            assert int(np.asarray(tr.answers)[qi]) == min(k, B)
        else:
            assert np.array_equal(a9[qi], np.asarray(ref.after_c9)[qi])
            assert np.array_equal(a10[qi], np.asarray(ref.after_c10)[qi])
            assert int(np.asarray(tr.answers)[qi]) == int(ans[qi].sum())


@pytest.mark.parametrize("k", [1, 5])
def test_compact_twin_bit_identical(didx, queries, k):
    qr, eps, is_knn = _mixed_args(queries, 0)
    u = mixed_query(didx, qr, eps, is_knn, k, 64)
    t = mixed_query_and_trace(didx, qr, eps, is_knn, k, 64)
    for a, b in zip(u, t[:4]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_dense_twin_with_valid_mask(didx, queries):
    qr, eps, is_knn = _mixed_args(queries, 1)
    vm = jnp.asarray(np.arange(B) % 5 != 0)
    nv = int(np.asarray(vm).sum())
    u = mixed_query_dense(didx, qr, eps, is_knn, 5, vm)
    t = mixed_query_dense_and_trace(didx, qr, eps, is_knn, 5, vm)
    for a, b in zip(u, t[:4]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    knn = np.asarray(is_knn)
    assert (np.asarray(t[4].verified)[knn] == nv).all()


# ---------------------------------------------------------------------------
# The sort-free k-th order statistic.
# ---------------------------------------------------------------------------

def test_kth_smallest_rounds_exact_adversarial_grid():
    rng = np.random.default_rng(17)
    kth = jax.jit(eng._kth_smallest_rounds, static_argnames=("k", "block"))
    for width in (33, 97, 256, 320, 2048):
        for k in (1, 2, 5, 8, 31):
            a = np.where(rng.random((16, width)) < 0.7,
                         rng.random((16, width)), np.inf).astype(np.float32)
            a[0] = 0.5                       # all-tie row
            a[1] = np.inf                    # no finite entries
            a[2, : min(9, width)] = 0.25     # duplicate cluster at the front
            if width > 140:
                a[3, 5] = a[3, 77] = a[3, 139] = 1e-6   # cross-block ties
            got = np.asarray(kth(jnp.asarray(a), k))
            want = np.asarray(eng._kth_smallest(jnp.asarray(a), k))
            assert np.array_equal(got, want), (width, k)


# ---------------------------------------------------------------------------
# Tracing off = zero extra compilations of the untraced engines.
# ---------------------------------------------------------------------------

def test_traced_twins_never_retrace_untraced_engines(didx, queries):
    qr, eps, is_knn = _mixed_args(queries, 0)
    mixed_query_dense(didx, qr, eps, is_knn, 5)          # warm untraced
    before = mixed_query_dense._cache_size()
    mixed_query_dense_and_trace(didx, qr, eps, is_knn, 5)
    range_query_traced(didx, qr, np.float32(1.0))
    assert mixed_query_dense._cache_size() == before
    # and the untraced call afterwards hits the same cache entry
    mixed_query_dense(didx, qr, eps, is_knn, 5)
    assert mixed_query_dense._cache_size() == before


# ---------------------------------------------------------------------------
# Trace pytree helpers.
# ---------------------------------------------------------------------------

def _toy_trace(q=4):
    a10 = np.arange(q * 2).reshape(q, 2).astype(np.int32)
    return QueryTrace(after_c9=a10 + 1, after_c10=a10,
                      screen_survivors=a10[:, -1], verified=a10[:, -1],
                      answers=np.ones(q, np.int32))


def test_merge_select_totals_roundtrip():
    t = _toy_trace()
    merged = merge_traces([t, t])
    assert np.array_equal(np.asarray(merged.after_c10),
                          2 * np.asarray(t.after_c10))
    sel = select_queries(t, [0, 2])
    assert np.asarray(sel.after_c9).shape == (2, 2)
    totals = trace_totals(t, n_rows=100)
    assert totals["queries"] == 4 and totals["rows_screened"] == 400
    assert totals["answers"] == 4
    with pytest.raises(ValueError):
        merge_traces([])


# ---------------------------------------------------------------------------
# Span ring + calibration log: bounded, exportable.
# ---------------------------------------------------------------------------

def test_span_ring_bounded_and_exports(tmp_path):
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        rec.record("dispatch", float(i), float(i) + 0.5, batch=i)
    assert len(rec) == 8 and rec.recorded == 20
    jl = tmp_path / "spans.jsonl"
    ct = tmp_path / "chrome.json"
    assert rec.to_jsonl(jl) == 8
    lines = [json.loads(line) for line in jl.read_text().splitlines()]
    assert lines[0]["name"] == "dispatch"
    assert lines[0]["duration_ms"] == pytest.approx(500.0)
    assert rec.to_chrome_trace(ct) == 8
    events = json.loads(ct.read_text())
    assert all(e["ph"] == "X" for e in events)
    assert rec.counts() == {"dispatch": 8}


def test_span_records_into_ring_with_late_attributes():
    from repro.obs.spans import span

    rec = SpanRecorder(capacity=8)
    with span("repro.test.outer", rec, seq=1) as sp:
        with span("repro.test.unrecorded"):
            pass
        sp.set(live=3)
    with rec.span("repro.test.method", n=2):
        pass
    got = {s.name: s.attrs for s in rec.snapshot()}
    assert got == {"repro.test.outer": {"seq": 1, "live": 3},
                   "repro.test.method": {"n": 2}}
    assert all(s.t1 >= s.t0 for s in rec.snapshot())


def test_calibration_log_bounded_and_summary(tmp_path):
    log = CalibrationLog(capacity=4)
    assert log.summary()["n"] == 0            # clean zeros before traffic
    for i in range(10):
        log.record(batch=16, k=5, backend="xla", measured_s=2e-3,
                   estimate={"t_est_s": 1e-3, "bytes_hbm": 1e6,
                             "flops_mxu": 1e7})
    assert len(log) == 4 and log.recorded == 10
    s = log.summary()
    assert s["n"] == 4
    assert s["mean_rel_err"] == pytest.approx(0.5)
    out = tmp_path / "cal.jsonl"
    assert log.to_jsonl(out) == 4
    rec = json.loads(out.read_text().splitlines()[0])
    assert rec["rel_err"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Metrics surface.
# ---------------------------------------------------------------------------

def test_metrics_registry_renders_required_families():
    from repro.serve.stats import StatsTracker

    text = build_registry(StatsTracker().snapshot(), None, None).render()
    for fam in REQUIRED_FAMILIES:
        assert f"# TYPE {fam}" in text, fam
    # clean zeros before any traffic — never NaN
    assert "nan" not in text.lower()


# ---------------------------------------------------------------------------
# Traced serving end to end.
# ---------------------------------------------------------------------------

def test_traced_service_exact_and_surfaces_populated(hidx):
    db, _ = hidx
    cfg = ServeConfig(max_batch=8, max_queue=64, max_wait_ms=1.0,
                      normalize_queries=False, trace=True)
    svc = SearchService.from_series(db, cfg, normalize=False)
    qs = make_queries(db, 8, seed=6)
    workload = make_workload(qs, WorkloadSpec(n_requests=32, knn_frac=0.5,
                                              k=3, epsilon=2.0))
    with svc:
        res = run_saturated(svc, workload)
        assert res.statuses.count(OK) == len(workload)
        for (kind, q, eps, k), req in zip(workload, res.requests):
            ids, dist = svc.direct_query(kind, q, epsilon=eps, k=k)
            assert np.array_equal(ids, req.ids)
            assert np.allclose(dist, req.distances, rtol=1e-6, atol=1e-9)
        snap = svc.stats.snapshot()
        cascade = snap["cascade"]
        assert cascade["queries"] == len(workload)
        assert cascade["rows_screened"] == len(workload) * B
        assert cascade["verified"] > 0 and cascade["answers"] > 0
        assert cascade["bytes_screen"] > 0 and cascade["bytes_verify"] > 0
        assert svc.tracer is not None and svc.tracer.recorded > 0
        names = set(svc.tracer.counts())
        assert {"repro.batcher.enqueue", "repro.batcher.form",
                "repro.serve.device", "repro.serve.reply"} <= names
        assert svc.calibration.recorded > 0
        text = svc.metrics_text()
    for fam in REQUIRED_FAMILIES:
        assert f"# TYPE {fam}" in text, fam


def test_untraced_service_allocates_no_obs_state(hidx):
    db, _ = hidx
    with SearchService.from_series(
            db, ServeConfig(max_batch=8, normalize_queries=False),
            normalize=False) as svc:
        assert svc.tracer is None and svc.calibration is None


def test_saturated_loadgen_jsonl(hidx, tmp_path):
    db, _ = hidx
    cfg = ServeConfig(max_batch=8, max_queue=64, max_wait_ms=1.0,
                      normalize_queries=False)
    svc = SearchService.from_series(db, cfg, normalize=False)
    qs = make_queries(db, 4, seed=7)
    workload = make_workload(qs, WorkloadSpec(n_requests=16, knn_frac=0.5,
                                              k=3, epsilon=2.0))
    out = tmp_path / "requests.jsonl"
    with svc:
        res = run_saturated(svc, workload, jsonl_path=out)
    assert res.qps > 0 and res.dropped_in_deadline == 0
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    assert len(recs) == len(workload)
    for rec in recs:
        assert rec["status"] == OK
        assert rec["latency_ms"] is not None and rec["latency_ms"] >= 0
        assert rec["kind"] in ("knn", "range")


def test_cli_info_stats_key_only_with_flag(tmp_path, capsys):
    from repro.index import cli

    rows = make_wafer_like(64, 64, seed=2, normalize=False)
    np.save(tmp_path / "rows.npy", rows)
    idx = str(tmp_path / "idx")
    cli.main(["build", "--dir", idx, "--input", str(tmp_path / "rows.npy"),
              "--levels", "4,8"])
    capsys.readouterr()
    cli.main(["info", "--dir", idx])
    plain = json.loads(capsys.readouterr().out)
    assert "stats" not in plain
    cli.main(["info", "--dir", idx, "--stats", "--stats-queries", "4"])
    info = json.loads(capsys.readouterr().out)
    stats = info["stats"]
    assert stats["queries"] == 4 and stats["rows"] == 64
    assert stats["rows_screened"] == 4 * 64
    for key in ("candidates", "excluded_c9", "excluded_c10", "answers",
                "ops", "model_latency"):
        assert key in stats


# ---------------------------------------------------------------------------
# Program spans in the profiler's trace.
# ---------------------------------------------------------------------------

def _profiled(run, logdir):
    """Run ``run()`` under one ``jax.profiler`` session and return the
    ``repro.*`` host spans it recorded: [(name, start_ns, end_ns, stats)]
    in start order."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(logdir)):
        run()
    (path,) = glob.glob(os.path.join(str(logdir), "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = ProfileData.from_file(path)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in data.planes if plane.name.startswith("/host")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return sorted(spans, key=lambda sp: sp[1])


def _named(spans, name):
    return [sp for sp in spans if sp[0] == name]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _serve_one_batch(svc, reqs):
    """Start ``svc`` with ``reqs`` already queued, so they form one batch,
    and wait for every answer; the backend's outputs are recorded."""
    outs = []
    dispatch = svc.backend.dispatch

    def recording(*args, **kw):
        out = dispatch(*args, **kw)
        outs.append(out)
        return out

    svc.backend.dispatch = recording

    def run():
        with svc:
            for r in reqs:
                assert r.wait(120) == OK
    return run, outs


def test_serve_spans_nest_with_batch_attributes(hidx, tmp_path):
    db, _ = hidx
    svc = SearchService.from_series(
        db, ServeConfig(max_batch=8, max_wait_ms=1.0,
                        normalize_queries=False), normalize=False)
    svc.warmup(qs=[4])
    qs = make_queries(db, 3, seed=21)
    reqs = [svc.submit_knn(qs[0], 3), svc.submit_knn(qs[1], 3),
            svc.submit_range(qs[2], 2.0)]
    run, outs = _serve_one_batch(svc, reqs)
    spans = _profiled(run, tmp_path)
    (batch,) = _named(spans, "repro.serve.batch")
    assert batch[3]["live"] == 3 and batch[3]["qb"] == 4
    assert batch[3]["kb"] == 8            # k 3 bucketed at the warmed floor
    assert batch[3]["wait_ms_max"] >= 0.0
    assert batch[3]["wait_ms_sum"] >= batch[3]["wait_ms_max"]
    (assemble,) = _named(spans, "repro.serve.assemble")
    (device,) = _named(spans, "repro.serve.device")
    (reply,) = _named(spans, "repro.serve.reply")
    for child in (assemble, device, reply):
        assert _inside(child, batch)
    assert assemble[2] <= device[1] and device[2] <= reply[1]
    assert (device[3]["qb"], device[3]["kb"]) == (4, 8)
    assert (reply[3]["knn"], reply[3]["range"]) == (2, 1)
    (represent,) = _named(spans, "repro.serve.represent")
    (d2h,) = _named(spans, "repro.serve.d2h")
    assert _inside(represent, device) and _inside(d2h, device)
    assert d2h[3]["bytes"] == sum(a.nbytes for a in outs[0][:3])
    assert not _named(spans, "repro.serve.cascade_count")   # untraced
    for r in reqs:
        assert r.batch_seq == batch[3]["seq"]
        assert r.t_submit <= r.t_dispatch <= r.t_done


def test_tiered_spans_show_escalation_and_gather(hidx, tmp_path):
    db, _ = hidx
    svc = SearchService.from_series(
        db, ServeConfig(quantization="int8", capacity0=4, max_batch=8,
                        max_wait_ms=1.0, normalize_queries=False),
        normalize=False)
    qs = make_queries(db, 2, seed=22)
    # ε far beyond every row: the screen keeps the whole database, so the
    # survivor count is B and the capacity is the ladder's rung at B.
    reqs = [svc.submit_range(qs[0], 1e3), svc.submit_knn(qs[1], 3)]
    run, outs = _serve_one_batch(svc, reqs)
    spans = _profiled(run, tmp_path)
    (batch,) = _named(spans, "repro.serve.batch")
    (device,) = _named(spans, "repro.serve.device")
    qb, slots = batch[3]["qb"], outs[0][0].shape[-1]
    (seed,) = _named(spans, "repro.engine.seed")
    (screen,) = _named(spans, "repro.engine.screen")
    assert not _named(spans, "repro.engine.escalate")
    assert screen[3]["survivors_max"] == B
    ladder = [max(4, batch[3]["kb"])]
    while ladder[-1] < screen[3]["survivors_max"]:
        ladder.append(min(B, 4 * ladder[-1]))
    assert screen[3]["cap"] == ladder[-1] == slots == B
    (gather,) = _named(spans, "repro.engine.gather")
    assert gather[3]["rows"] == qb * slots
    assert gather[3]["bytes"] == qb * slots * N * 4
    (verify,) = _named(spans, "repro.engine.verify")
    order = [seed, screen, gather, verify]
    assert all(_inside(sp, device) for sp in order)
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))


@pytest.mark.parametrize("quantization", ["none", "int8"])
def test_cascade_count_runs_outside_device_span(hidx, tmp_path,
                                                quantization):
    db, _ = hidx
    svc = SearchService.from_series(
        db, ServeConfig(quantization=quantization, max_batch=8,
                        max_wait_ms=1.0, normalize_queries=False,
                        trace=True), normalize=False)
    qs = make_queries(db, 2, seed=23)
    reqs = [svc.submit_range(qs[0], 2.0), svc.submit_knn(qs[1], 3)]
    run, _ = _serve_one_batch(svc, reqs)
    spans = _profiled(run, tmp_path)
    (device,) = _named(spans, "repro.serve.device")
    (count,) = _named(spans, "repro.serve.cascade_count")
    (reply,) = _named(spans, "repro.serve.reply")
    (batch,) = _named(spans, "repro.serve.batch")
    assert device[2] <= count[1] and count[2] <= reply[1]
    assert _inside(count, batch)
    assert svc.stats.snapshot()["cascade"]["queries"] == len(reqs)
    ring = [sp.name for sp in svc.tracer.snapshot()]
    assert ring.index("repro.serve.device") < \
        ring.index("repro.serve.cascade_count") < \
        ring.index("repro.serve.reply")


def test_profile_dir_is_one_session_per_service_run(hidx, tmp_path):
    from jax.profiler import ProfileData

    db, _ = hidx
    svc = SearchService.from_series(
        db, ServeConfig(max_batch=8, max_wait_ms=1.0,
                        normalize_queries=False,
                        profile_dir=str(tmp_path)), normalize=False)
    qs = make_queries(db, 2, seed=24)
    with svc:
        for q in qs:                    # one batch after the other
            svc.knn(q, 3)
    paths = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1
    data = ProfileData.from_file(paths[0])
    seqs = sorted(dict(e.stats)["seq"] for plane in data.planes
                  for line in plane.lines for e in line.events
                  if e.name == "repro.serve.batch")
    assert seqs == [1, 2]


def test_stats_qps_counts_a_trailing_window():
    from repro.serve.stats import QPS_WINDOW_S, StatsTracker

    now = [0.0]
    st = StatsTracker(clock=lambda: now[0])
    for t in (0.1, 0.2, 0.3, 0.4, 0.5):
        st.on_served(0.01, t)
    now[0] = 1.0
    assert st.snapshot()["qps"] == 5.0          # uptime shorter than window
    for t in np.arange(1.0, 30.0, 0.5):         # 2 answers a second
        st.on_served(0.01, float(t))
    now[0] = 30.0
    snap = st.snapshot()
    assert snap["qps"] == 2.0                   # the last window only
    assert snap["served"] == 5 + 58
    now[0] = 30.0 + 2 * QPS_WINDOW_S            # idle since: no answers
    assert st.snapshot()["qps"] == 0.0
