"""A whole run on the CPU at a small size, without the harness's look for
a chip: sound, it is correct; with the timed path broken underneath the
service, it is not."""
import numpy as np
import pytest

import harness
from test_control import small


def altered(dispatch):
    """Every batch answers its first query with ids off by one."""
    def f(q, eps, is_knn, k, want_trace=False):
        idx, answer, d2, tr = dispatch(q, eps, is_knn, k, want_trace)
        idx = np.array(idx)
        idx[0] = (idx[0] + 1) % harness_rows
        return idx, answer, d2, tr
    return f


def half_left_out(dispatch):
    """Only the first half of each batch (rounded down) is answered; the
    rest come back empty."""
    def f(q, eps, is_knn, k, want_trace=False):
        idx, answer, d2, tr = dispatch(q, eps, is_knn, k, want_trace)
        answer, d2 = np.array(answer), np.array(d2)
        h = len(q) // 2
        answer[h:] = False
        d2[h:] = np.inf
        return idx, answer, d2, tr
    return f


def unchanged(dispatch):
    """Each call returns the previous call's outputs: stale state."""
    last = []

    def f(q, eps, is_knn, k, want_trace=False):
        out = dispatch(q, eps, is_knn, k, want_trace)
        prev = last[0] if last and last[0][0].shape[0] == len(q) else out
        last[:] = [out]
        return prev
    return f


harness_rows = 1 << 13


def run(fault, seed):
    cfg, mix = small("int8-verify-steady" if seed % 2 else
                     "f32-mixed-steady")
    cfg = dict(cfg, rows=harness_rows)
    mix = dict(mix, pool=48, rate_qps=40.0)
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    metrics = [m for m in spec["end_to_end"]]
    return harness.run({"name": "small"}, cfg, mix, seed, 2.0, False,
                       metrics, fault=fault)


@pytest.mark.parametrize("seed", [4, 2**31 + 1])
def test_sound_run_is_correct(seed):
    res = run(None, seed)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 80
    assert list(res)[-1] == "checks"
    assert {"setup_s", "qps", "p50_ms", "p95_ms"} <= set(res["metrics"])


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged])
@pytest.mark.parametrize("seed", [6, 2**31 + 3])
def test_broken_path_is_not_correct(fault, seed):
    res = run(fault, seed)
    assert not res["correct"]
