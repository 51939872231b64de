"""The open-loop schedule: the same work and arrival times for every
seed, in another order, filling the window."""
import numpy as np
import pytest

import loadgen

MIX = {"arrival": "poisson", "rate_qps": 40.0, "knn_share": 0.5,
       "knn_k": [1, 10], "range_rank": 20, "noise_sigma": [0.1, 0.3, 1.0],
       "pool": 64}


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**40 + 3])
def test_schedule_fills_window(seed):
    s = loadgen.schedule(MIX, seed, 10.0)
    assert len(s) == 400
    assert s.due[0] == 0.0 and (np.diff(s.due) > 0).all()
    assert s.due[-1] < 10.0
    assert s.knn.sum() == 200
    assert sorted(s.k[s.knn].tolist()) == [1] * 100 + [10] * 100
    assert (s.k[~s.knn] == 0).all()
    # every pool query equally often (400 = 6 x 64 + 16)
    counts = np.bincount(s.query, minlength=64)
    assert counts.min() >= 6 and counts.max() <= 7


def test_same_work_other_order():
    a = loadgen.schedule(MIX, 1, 10.0)
    b = loadgen.schedule(MIX, 2, 10.0)
    assert np.array_equal(a.due, b.due)
    assert not np.array_equal(a.query, b.query)
    assert not np.array_equal(a.knn, b.knn)
    assert np.array_equal(np.sort(a.k), np.sort(b.k))
    c = loadgen.schedule(MIX, 1, 10.0)
    assert np.array_equal(a.due, c.due) and np.array_equal(a.query, c.query)
    assert np.array_equal(a.k, c.k)


def test_gaps_are_exponential():
    s = loadgen.schedule(dict(MIX, rate_qps=1000.0), 5, 10.0)
    gaps = np.diff(s.due)
    assert gaps.mean() == pytest.approx(1e-3, rel=0.01)
    # exponential: the standard deviation equals the mean
    assert gaps.std() == pytest.approx(1e-3, rel=0.1)


def test_warmup_covers_every_bucket_and_noise_level():
    sigma = np.resize(np.array([0.1, 0.3, 1.0], np.float32), 64)
    sizes = [1, 2, 4, 8, 16, 32]
    bursts = loadgen.warmup_bursts(MIX, sigma, 32, [0.1, 0.3, 1.0])
    # per noise level and size: range only, k-NN only and mixed per k
    assert [len(b) for b in bursts] == [n for _ in range(3) for n in sizes
                                         for _ in range(5)]
    assert sorted({float(sigma[b[0][0]]) for b in bursts}) == \
        pytest.approx([0.1, 0.3, 1.0])
    assert all(len({float(sigma[p]) for p, _, _ in b}) == 1 for b in bursts)
    kinds = {(sum(kn for _, kn, _ in b) * 2 // len(b),
              max(k for _, _, k in b)) for b in bursts if len(b) > 1}
    assert kinds == {(0, 0), (1, 1), (1, 10), (2, 1), (2, 10)}
    hard = loadgen.warmup_bursts(MIX, sigma, 32, [1.0],
                                 hard={"range": [5], "knn": [7, 9]})
    assert len(hard) == 60
    assert all(sigma[p] == 1.0 for b in hard[:30] for p, _, _ in b)
    assert {p for b in hard[30:] for p, kn, _ in b if not kn} == {5}
    assert {p for b in hard[30:] for p, kn, _ in b if kn} == {7, 9}


class FakeService:
    """Answers each request ``delay`` seconds after it is submitted."""

    def __init__(self, delay):
        self.delay, self.sent = delay, []

    def _submit(self):
        import threading
        import types
        req = types.SimpleNamespace(_done=threading.Event())
        threading.Timer(self.delay, req._done.set).start()
        self.sent.append(req)
        return req

    def submit_knn(self, q, k):
        return self._submit()

    def submit_range(self, q, eps):
        return self._submit()


def test_open_loop_sends_on_schedule_whatever_the_service_does():
    import time
    mix = dict(MIX, rate_qps=50.0)
    s = loadgen.schedule(mix, 1, 0.4)
    svc = FakeService(delay=1.0)             # slower than the arrivals
    t0 = time.perf_counter() + 0.01
    sent = loadgen.drive(svc, s, np.zeros((64, 4)), np.ones(64), t0)
    assert len(sent.requests) == 20
    assert np.allclose(sent.due, t0 + s.due)
    assert (sent.submit - sent.due).max() < 0.05


def test_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError, match="arrival process"):
        loadgen.schedule(dict(MIX, arrival="onoff"), 1, 1.0)
