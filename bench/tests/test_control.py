"""The comparison that decides ``correct``, on a small collection: exact
answers pass, the bfloat16 control fails."""
import json

import numpy as np
import pytest

import control
import data
import harness
import reference

SMALL_ROWS = 1 << 15


def small(name):
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = harness.load_json(harness.ROOT / conf["file"])
    mix = harness.load_json(harness.BENCH / "traffic" /
                            f"{cell['traffic']}.json")
    return dict(cfg, rows=SMALL_ROWS), dict(mix, pool=96, rate_qps=10.0)


@pytest.mark.parametrize("cell", ["f32-mixed-steady", "int8-verify-steady"])
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 77])
def test_bf16_control_fails(cell, seed):
    cfg, mix = small(cell)
    nums = control.control_numbers(cfg, mix, seed, 10.0)
    assert not reference.passes(nums)
    assert nums["dist_err_d2"]["value"] > 3 * nums["dist_err_d2"]["limit"]


def test_exact_answers_pass_and_faults_read_high():
    cfg, mix = small("f32-mixed-steady")
    db = data.random_walks(9, cfg["rows"], cfg["n"])
    pool, _ = data.make_pool(db, 9, 16, mix["noise_sigma"])
    d2, ids = reference.nearest(db, pool, 21)
    eps = np.sqrt(0.5 * (d2[:, 19] + d2[:, 20]))
    ref = reference.build_reference(db, pool, [[] for _ in pool])
    asks, got = [], []
    for i in range(len(pool)):
        asks.append((i, "knn", 10, 0.0))
        got.append((ref.ids[i][:10], np.sqrt(ref.d2[i][:10])))
        want = ref.d2[i] <= eps[i] ** 2
        asks.append((i, "range", 0, float(eps[i])))
        got.append((ref.ids[i][want], np.sqrt(ref.d2[i][want])))
    ok = reference.check(ref, asks, got, cfg["rows"], 0)
    assert reference.passes(ok)
    assert ok["answer_gap_d2"]["value"] == 0.0
    # one neighbour swapped for the (k+1)-th, one range row dropped,
    # one distance off by 0.1 in d²
    bad = list(got)
    ids, dist = bad[0]
    bad[0] = (np.append(ids[:9], ref.ids[0][10]),
              np.append(dist[:9], np.sqrt(ref.d2[0][10])))
    ids, dist = bad[1]
    bad[1] = (ids[1:], dist[1:])
    for fault in ([bad[0]] + got[1:], got[:1] + [bad[1]] + got[2:]):
        assert not reference.passes(
            reference.check(ref, asks, fault, cfg["rows"], 0))
    ids, dist = got[2]
    moved = got[:2] + [(ids, np.sqrt(dist ** 2 + 0.1))] + got[3:]
    nums = reference.check(ref, asks, moved, cfg["rows"], 0)
    assert nums["dist_err_d2"]["value"] == pytest.approx(0.1)
    assert not reference.passes(
        reference.check(ref, asks, got, cfg["rows"], 1))
