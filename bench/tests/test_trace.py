"""The reduction from a trace to numbers, on a small trace: two batches,
each a dispatch callback holding a backend dispatch and device work."""
import json
import pathlib

import pytest

import harness
import trace as tr

HERE = pathlib.Path(__file__).resolve().parent
MS = 1e6


def ev(name, start_ms, dur_ms, **args):
    return tr.Event(name, start_ms * MS, dur_ms * MS, args)


# HLO text as the TPU profiler names the calls (layouts shortened).
TOPK = ("%fused_topk_pallas.1 = (f32[2048,8,12]{2,1,0}, s32[2048,8,12]"
        "{2,1,0:T(8,128)S(1)}) custom-call(f32[8,256]{1,0} %q, "
        "f32[8,1]{1,0} %qn, f32[2097152,256]{1,0:T(8,128)} %s, "
        "s32[2097152,8]{1,0} %w), custom_call_target=\"tpu_custom_call\"")
RANGE = ("%fused_range_pallas.1 = (s32[8,2097152]{1,0}, f32[8,2097152]"
         "{1,0}) custom-call(f32[8,256]{1,0} %q, f32[8,1]{1,0} %qn, "
         "f32[2097152,256]{1,0} %s, s32[2097152,8]{1,0} %w), "
         "custom_call_target=\"tpu_custom_call\"")


@pytest.fixture
def small():
    device = {0: [
        ev("%fusion.1 = f32[8,256]{1,0} fusion(f32[8,256] %a)", 10, 2),
        ev(TOPK, 12, 3),                         # batch 1
        ev(RANGE, 14, 4),                        # overlaps the one before
        ev("%copy.2 = s32[2097152,8]{1,0} copy(s32[2097152,8] %w)", 30, 1),
        ev(RANGE, 31, 2),                        # batch 2
        ev("%late.3 = f32[] fusion()", 200, 5),  # outside the window
    ]}
    spans = [
        ev("bench.window", 0, 100),
        ev("bench.dispatch_cb", 9, 20, live=3),
        ev("bench.backend_dispatch", 9.5, 10, qb=4, kb=8),
        ev("bench.finish", 20, 8),
        ev("bench.dispatch_cb", 29, 11, live=1),
        ev("bench.backend_dispatch", 29.5, 5, qb=1, kb=16),
        ev("bench.finish", 35, 4),
    ]
    return tr.Trace(device, spans, (0.0, 100 * MS))


def test_merge_and_busy(small):
    assert tr.merge([(3, 5), (1, 2), (4, 8), (8, 9), (10, 10)]) == \
        [[1, 2], [3, 9]]
    assert tr.busy(small, 0) == [[10 * MS, 18 * MS], [30 * MS, 33 * MS]]
    assert tr.busy_s(small) == pytest.approx(0.011)
    assert tr.busy(small, 0, 15 * MS, 31 * MS) == \
        [[15 * MS, 18 * MS], [30 * MS, 31 * MS]]


def test_idle_gaps_labelled_by_innermost_span(small):
    gaps = tr.idle_gaps(small, top=3)
    assert gaps[0] == ["idle", pytest.approx(0.067)]        # 33..100 ms
    assert gaps[1] == ["bench.finish", pytest.approx(0.012)]  # 18..30 ms
    assert gaps[2] == ["idle", pytest.approx(0.010)]        # 0..10 ms


def test_top_ops_and_kernel_time(small):
    ops = dict(tr.top_ops(small))
    assert ops["fused_range_pallas (s32[8,2097152], f32[8,2097152])"] == \
        pytest.approx(0.006)
    assert ops["copy s32[2097152,8]"] == pytest.approx(0.001)
    assert not any(n.startswith("late") for n in ops)
    rng = harness.kernels()["fused_range"]
    assert [e.dur for e in tr.ops_matching(small, rng.PATTERN)] \
        == [4 * MS, 2 * MS]


def test_json_round_trip(small, tmp_path):
    path = tmp_path / "t.json"
    path.write_text(json.dumps(small.to_json()))
    back = tr.Trace.from_json(json.loads(path.read_text()))
    assert tr.busy(back, 0) == tr.busy(small, 0)
    assert back.window_s == pytest.approx(0.1)


def _record(trace):
    cfg = harness.load_json(harness.BENCH / "configs" / "hydra256-f32.json")
    peaks = harness.load_json(harness.BENCH / "peaks.json")["chips"]
    return harness.RunRecord(
        cfg=cfg, mix={}, seconds=0.1, setup_s=1.0, t_open=0.0, t_close=0.1,
        due=None, submit=None, start=None, done=None, ok=None, batches=[],
        trace=trace, peaks=peaks["TPU v5 lite"], kernels=harness.kernels())


def test_trace_metrics(small):
    rec = _record(small)
    idle = harness.load_module("metrics", "device_idle").read(rec)
    assert idle == pytest.approx(89.0)
    host = harness.load_module("metrics", "host_ms_per_batch").read(rec)
    # batch 1: 20 ms callback, 8 ms busy; batch 2: 11 ms, 3 ms busy
    assert host == pytest.approx((12 + 8) / 2)
    roof = harness.load_module("metrics", "screen_roofline")
    reads = 4 * (8 * 256 + 8 + 2097152 * 256 + 2097152 * 8)
    range_bytes = 2 * 4 * 8 * 2097152 + reads
    topk_bytes = 2 * 4 * 2048 * 8 * 12 + reads
    ops = 6 * 2 * 8 * 2097152 * 256
    assert rec.kernels["fused_range"].cost(RANGE) == (range_bytes, ops)
    assert rec.kernels["fused_topk"].cost(TOPK) == (topk_bytes, ops)
    least = (topk_bytes + 2 * range_bytes) / 819e9
    assert least > 3 * ops / 197e12          # bound by bytes
    assert roof.read(rec) == pytest.approx(100 * least / 0.009)
    assert "fused_range: calls=2" in roof.describe(rec)
    assert "bound=bytes" in roof.describe(rec)


def test_trace_metrics_silent_without_trace(small):
    rec = _record(None)
    for name in ("device_idle", "host_ms_per_batch", "screen_roofline"):
        assert harness.load_module("metrics", name).read(rec) is None


def test_recorded_chip_trace():
    """One batch of two requests of ``f32-mixed-steady`` as the chip's
    profiler recorded it on one v5e chip: the reduction
    against a plain sweep over the same events."""
    t = tr.Trace.from_json(json.loads(
        (HERE / "recorded_trace.json").read_text()))
    lo, hi = t.window
    evs = [(max(e.start, lo), min(e.end, hi)) for e in t.device[0]
           if min(e.end, hi) > max(e.start, lo)]
    points = sorted([(s, 1) for s, _ in evs] + [(e, -1) for _, e in evs])
    depth, busy, last = 0, 0.0, None
    for x, d in points:
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    assert tr.busy_s(t) == pytest.approx(busy * 1e-9)
    assert 0 < tr.busy_s(t) < t.window_s
    ks = harness.kernels()
    for name, prefix in (("fused_range", "%fused_range_pallas"),
                         ("fused_topk", "%fused_topk_pallas")):
        got = tr.ops_matching(t, ks[name].PATTERN)
        want = [e for e in t.device[0] if e.name.startswith(prefix + ".")
                or e.name.startswith(prefix + " ")]
        assert len(got) == len(want) == 1
        nbytes, ops = ks[name].cost(got[0].name)
        assert nbytes > 2 * 2**30 and ops == 6 * 2 * 8 * 2**21 * 256
    gaps = tr.idle_gaps(t)
    assert sum(g for _, g in gaps) <= t.window_s - tr.busy_s(t) + 1e-9
    cb = [s for s in t.spans if s.name == "bench.dispatch_cb"][0]
    host = harness.load_module("metrics", "host_ms_per_batch").read(
        _record(t))
    inside = tr.length(tr.busy(t, 0, cb.start, cb.end))
    assert host == pytest.approx((cb.dur - inside) * 1e-6)
