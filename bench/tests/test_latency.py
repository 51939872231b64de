"""Due-time latency arithmetic of the end-to-end and generator metrics."""
import numpy as np
import pytest

import harness


def record(due, submit, start, done, ok, seconds=10.0, t_open=100.0):
    return harness.RunRecord(
        cfg={}, mix={}, seconds=seconds, setup_s=12.5, t_open=t_open,
        t_close=t_open + seconds, due=np.asarray(due, float),
        submit=np.asarray(submit, float), start=np.asarray(start, float),
        done=np.asarray(done, float), ok=np.asarray(ok, bool), batches=[])


def read(name, rec):
    return harness.load_module("metrics", name).read(rec)


def test_latency_counts_from_due_time():
    due = 100.0 + np.arange(100) * 0.1
    submit = due + 0.002              # the generator ran 2 ms late
    start = due + 0.010
    done = due + np.linspace(0.05, 0.149, 100)
    rec = record(due, submit, start, done, np.ones(100))
    lat = (done - due) * 1e3
    assert read("p50_ms", rec) == pytest.approx(np.quantile(lat, 0.5))
    assert read("p95_ms", rec) == pytest.approx(np.quantile(lat, 0.95))
    assert read("gen_late_ms", rec) == pytest.approx(2.0)
    assert read("queue_wait_ms", rec) == pytest.approx(10.0)
    assert read("setup_s", rec) == 12.5


def test_quantile_matches_numpy_and_keeps_inf():
    x = np.random.default_rng(0).random(37)
    for q in (0.5, 0.95):
        assert harness.quantile(x, q) == pytest.approx(np.quantile(x, q))
    assert harness.quantile([1.0, 2.0, np.inf], 0.5) == 2.0
    assert harness.quantile([1.0, 2.0, np.inf], 0.95) == np.inf


def test_unanswered_requests_are_infinitely_late():
    due = 100.0 + np.arange(20) * 0.5
    done = due + 0.1
    ok = np.ones(20, bool)
    ok[[3, 7]] = False
    done[[3, 7]] = np.nan
    rec = record(due, due, due, done, ok)
    assert read("p50_ms", rec) == pytest.approx(100.0)
    assert read("p95_ms", rec) == np.inf
    assert read("qps", rec) == pytest.approx(18 / 10.0)


def test_qps_counts_only_answers_inside_the_window():
    due = 100.0 + np.arange(10)
    done = due + np.array([0.1] * 8 + [2.5, 3.0])   # last two after 110
    rec = record(due, due, due, done, np.ones(10))
    assert read("qps", rec) == pytest.approx(0.8)


def test_batch_counters():
    b = [harness.Batch(t0=0, live=3, qb=4, kb=8, out_bytes=900, slots=64,
                       tiered=True),
         harness.Batch(t0=1, live=1, qb=1, kb=16, out_bytes=100, slots=256,
                       tiered=True),
         harness.Batch(t0=2, live=0)]     # breaker shed: no dispatch
    rec = record([], [], [], [], [])
    rec.batches = b
    assert read("d2h_bytes_per_query", rec) == pytest.approx(1000 / 4)
    assert read("verify_rows_per_query", rec) == pytest.approx(
        (4 * 64 + 1 * 256) / 4)
    for x in b:
        x.tiered = False
    assert read("verify_rows_per_query", rec) is None
