"""Property battery for the quantized memory tier (DESIGN.md §9).

Three guarantees, in increasing order of integration:

  1. the codec contract — quantize→dequantize error never exceeds the
     stored per-block worst-case bound, including adversarial inputs
     (constant, all-zero, huge dynamic range, single-outlier-per-block);
  2. the soundness lemma — every widened screen bound (C9 + per-block
     error, lossless C10 MINDIST, series screen + per-row L2 error)
     lower-bounds the true Euclidean distance, so no kill can lose a
     true answer;
  3. set-identity — int8 AND bf16 quantized range/k-NN answers equal the
     full-precision engine exactly, with the exactness certificates
     intact (the PR acceptance criterion), on both the device tiered
     engine and the host op-counting engine.

Property sampling uses ``hypothesis`` when installed, else the seeded
shim (same fallback as test_sax_invariants.py).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from _mini_hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import engine
from repro.core.fastsax import FastSAXConfig, build_index, represent_query
from repro.core.sax import mindist_table
from repro.core.search import (fastsax_knn_query, fastsax_range_query,
                               quantized_fastsax_range_query)
from repro.data.timeseries import make_queries, make_wafer_like
from repro.index import quantized as q

MODES = ("bf16", "int8")
SETTINGS = dict(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# 1. Codec contract: realized error never exceeds the stored bound
# ---------------------------------------------------------------------------

def _column(seed: int, size: int, log_scale: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal(size) * 10.0 ** log_scale


@settings(**SETTINGS)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 400),
       st.floats(-6.0, 6.0), st.sampled_from(MODES))
def test_residual_dequant_error_within_stored_bound(seed, size, log_s, mode):
    x = np.abs(_column(seed, size, log_s))          # residuals are >= 0
    codes, scale, zero, err = q.quantize_residuals(x, mode)
    if mode == "int8":
        deq = q.int8_decode(codes, scale, zero, q.RESID_BLOCK)
        assert int(codes.max(initial=-127)) < q.SENTINEL_CODE, \
            "data codes must never collide with the padding sentinel"
    else:
        deq = q.bf16_decode(codes)
    row_err = np.repeat(err, q.RESID_BLOCK)[:size]
    realized = np.abs(deq.astype(np.float64) - x)
    assert (realized <= row_err.astype(np.float64)).all()


@settings(**SETTINGS)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 64),
       st.integers(2, 96), st.floats(-6.0, 6.0), st.sampled_from(MODES))
def test_series_dequant_error_within_stored_bound(seed, B, n, log_s, mode):
    x = _column(seed, B * n, log_s).reshape(B, n)
    codes, scale, zero, err, norms = q.quantize_series(x, mode)
    if mode == "int8":
        deq = q.int8_decode(codes, scale, zero, 1)
    else:
        deq = q.bf16_decode(codes)
    realized = np.sqrt(((deq.astype(np.float64) - x) ** 2).sum(axis=1))
    assert (realized <= err.astype(np.float64)).all()
    # norms_sq is the norm of the DEQUANTIZED rows (screen exactness).
    np.testing.assert_allclose(
        norms, (deq.astype(np.float32) ** 2).sum(axis=1), rtol=1e-6)


# Adversarial inputs the affine per-block codec historically gets wrong:
# span-zero blocks (scale degenerates), exact zeros, ranges that overflow
# one scale, and a lone outlier that flattens every other code in its
# block to the same value.
_ADVERSARIAL = {
    "constant": np.full(300, 3.14159),
    "all_zero": np.zeros(300),
    "huge_dynamic_range": np.concatenate(
        [np.logspace(-30, 30, 150), -np.logspace(-30, 28, 150)]),
    "single_outlier_per_block": np.where(
        np.arange(300) % q.RESID_BLOCK == 7, 1e6, 1e-3),
}


@pytest.mark.parametrize("name", sorted(_ADVERSARIAL))
@pytest.mark.parametrize("mode", MODES)
def test_adversarial_columns_respect_bound(name, mode):
    x = np.abs(_ADVERSARIAL[name])
    codes, scale, zero, err = q.quantize_residuals(x, mode)
    deq = (q.int8_decode(codes, scale, zero, q.RESID_BLOCK)
           if mode == "int8" else q.bf16_decode(codes))
    row_err = np.repeat(err, q.RESID_BLOCK)[:x.size]
    assert (np.abs(deq.astype(np.float64) - x) <= row_err).all()
    if mode == "int8" and name in ("constant", "all_zero"):
        # Span-zero blocks degenerate to scale=1/code=0: the value is
        # stored as the f32 zero-point, so the only error left is the f32
        # rounding of the zero-point itself.
        ulp = np.nextafter(np.float32(np.abs(np.float32(x[0]) - x[0])),
                           np.float32(np.inf))
        assert (err <= ulp).all()


@pytest.mark.parametrize("mode", MODES)
def test_adversarial_series_respect_bound(mode):
    rows = np.stack([np.resize(v, 128) for v in _ADVERSARIAL.values()])
    codes, scale, zero, err, _ = q.quantize_series(rows, mode)
    deq = (q.int8_decode(codes, scale, zero, 1)
           if mode == "int8" else q.bf16_decode(codes))
    realized = np.sqrt(((deq.astype(np.float64) - rows) ** 2).sum(axis=1))
    assert (realized <= err.astype(np.float64)).all()


def test_narrow_words_lossless_and_guarded():
    w = np.random.default_rng(0).integers(0, 127, (50, 8))
    assert np.array_equal(q.narrow_words(w), w)
    with pytest.raises(q.QuantizationError, match="int8 range"):
        q.narrow_words(np.array([[127]]))
    with pytest.raises(q.QuantizationError, match="int8 range"):
        q.narrow_words(np.array([[-1]]))


def test_mode_validation():
    with pytest.raises(q.QuantizationError, match="quantization"):
        q.check_mode("fp8")
    with pytest.raises(q.QuantizationError, match="none"):
        q.quantize_residuals(np.ones(4), "none")


# ---------------------------------------------------------------------------
# 2. Soundness: every widened bound lower-bounds the true distance
# ---------------------------------------------------------------------------

def _small_index(seed: int, B: int = 96, n: int = 64,
                 levels=(4, 8), alphabet: int = 8):
    db = make_wafer_like(B, n, seed=seed, normalize=False)
    cfg = FastSAXConfig(n_segments=levels, alphabet=alphabet)
    return db, build_index(db, cfg, normalize=False), cfg


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(MODES))
def test_widened_bounds_never_exceed_true_distance(seed, mode):
    """The lemma every pruning decision rests on: for all rows u and any
    query qv,  widened-bound(u, qv) ≤ d(u, qv)  at every cascade stage."""
    db, idx, cfg = _small_index(seed)
    qhost = q.quantize_host_index(idx, mode)
    qv = make_queries(db, 1, seed=seed % 97)[0]
    qr = represent_query(qv, cfg, normalize=False)
    true_d = np.sqrt(((db.astype(np.float64)
                       - np.asarray(qr.q, np.float64)[None, :]) ** 2).sum(-1))
    n = db.shape[1]
    for li, lv in enumerate(qhost.levels):
        # Widened C9: |r̂(u) − r(q)| − e_blk ≤ |r(u) − r(q)| ≤ d(u, q).
        gap = np.abs(lv.dequant_residuals().astype(np.float64)
                     - qr.residuals[li])
        assert (gap - lv.row_err().astype(np.float64)
                <= true_d + 1e-9).all()
        # C10 is unwidened: the int8 symbols must be lossless, so MINDIST
        # computed from them is the exact full-precision lower bound.
        assert np.array_equal(lv.words.astype(np.int64),
                              idx.levels[li].words.astype(np.int64))
        tab = mindist_table(cfg.alphabet)
        cell = tab[lv.words.astype(np.int64),
                   np.asarray(qr.words[li])[None, :]]
        md = np.sqrt(n / lv.n_segments) * np.sqrt((cell * cell).sum(-1))
        assert (md <= true_d + 1e-6).all()
    # Series screen: d(û, q) − e_u ≤ d(u, q) (triangle inequality).
    deq = qhost.dequant_series().astype(np.float64)
    d_hat = np.sqrt(((deq - np.asarray(qr.q, np.float64)[None, :]) ** 2)
                    .sum(-1))
    assert (d_hat - qhost.series_err.astype(np.float64)
            <= true_d + 1e-9).all()


@pytest.mark.parametrize("mode", MODES)
def test_sentinel_code_dequantizes_to_padding(mode):
    if mode == "bf16":
        # bf16 represents the sentinel value natively above the detection
        # threshold (0.5 · PAD_RESIDUAL).
        deq = q.bf16_decode(q.bf16_encode(np.array([q.PAD_RESIDUAL])))
        assert deq[0] > 0.5 * q.PAD_RESIDUAL
        return
    codes = np.array([0, q.SENTINEL_CODE], np.int8)
    lv = q.QuantizedLevel(n_segments=4, words=np.zeros((2, 4), np.int8),
                          residuals=codes,
                          scale=np.array([2.0], np.float32),
                          zero=np.array([1.0], np.float32),
                          err=np.array([0.0], np.float32))
    deq = lv.dequant_residuals()
    assert deq[0] == 1.0                      # zero + scale·0
    assert deq[1] == np.float32(q.PAD_RESIDUAL)


# ---------------------------------------------------------------------------
# 3. Set-identity with the full-precision engine (acceptance criterion)
# ---------------------------------------------------------------------------

# (B, n, levels, alphabet): covers single/multi level, B below / above /
# straddling the RESID_BLOCK scale-block boundary, small/large alphabet.
GRID = [
    (64, 64, (4,), 5),
    (200, 128, (8, 16), 10),
    (257, 96, (8, 16), 20),
]


@pytest.fixture(scope="module", params=GRID, ids=lambda c: f"B{c[0]}")
def case(request):
    B, n, levels, alphabet = request.param
    db = make_wafer_like(B, n, seed=11, normalize=False)
    cfg = FastSAXConfig(n_segments=levels, alphabet=alphabet)
    idx = build_index(db, cfg, normalize=False)
    dev = engine.device_index_from_host(idx)
    qs = make_queries(db, 4, seed=3)
    qr = engine.represent_queries(jnp.asarray(qs, jnp.float32), levels,
                                  alphabet, normalize=False)
    return db, idx, cfg, dev, qs, qr


@pytest.mark.parametrize("mode", MODES)
def test_tiered_range_set_identical(case, mode):
    db, idx, cfg, dev, qs, qr = case
    tindex = engine.TieredIndex.from_host(idx, mode)
    eps = jnp.asarray(np.linspace(0.8, 4.0, qs.shape[0]), jnp.float32)
    want_m, want_d = engine.range_query(dev, qr, eps)
    got_i, got_a, got_d, exact = engine.quantized_range_query(
        tindex, qr, eps, capacity=8)          # tiny capacity: escalates
    assert bool(np.asarray(exact).all()), \
        "capacity escalation must end with an exactness certificate"
    wm, gi, ga = (np.asarray(x) for x in (want_m, got_i, got_a))
    for qi in range(qs.shape[0]):
        want_set = set(np.flatnonzero(wm[qi]).tolist())
        got_set = set(gi[qi][ga[qi]].tolist())
        assert got_set == want_set, (mode, qi)
    # Reported distances are the exact diff²-form raw-tier distances.
    d2 = np.asarray(got_d)
    for qi in range(qs.shape[0]):
        rows = gi[qi][ga[qi]]
        ref = ((db[rows].astype(np.float64)
                - np.asarray(qr.q, np.float64)[qi][None, :]) ** 2).sum(-1)
        np.testing.assert_allclose(np.sort(d2[qi][ga[qi]]), np.sort(ref),
                                   rtol=1e-4)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("k", [1, 5])
def test_tiered_knn_set_identical(case, mode, k):
    db, idx, cfg, dev, qs, qr = case
    tindex = engine.TieredIndex.from_host(idx, mode)
    want_i, want_d, want_e = engine.knn_query_auto(dev, qr, k)
    got_i, got_d, got_e = engine.quantized_knn_query(tindex, qr, k,
                                                     capacity=k)
    assert bool(np.asarray(want_e).all()) and bool(np.asarray(got_e).all())
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", MODES)
def test_tiered_mixed_set_identical(case, mode):
    db, idx, cfg, dev, qs, qr = case
    tindex = engine.TieredIndex.from_host(idx, mode)
    Q = qs.shape[0]
    k = 3
    eps = jnp.asarray(np.linspace(1.0, 3.0, Q), jnp.float32)
    is_knn = jnp.asarray([i % 2 == 0 for i in range(Q)])
    want = engine.mixed_query_dense(dev, qr, eps, is_knn, k)
    got = engine.quantized_mixed_query(tindex, qr, eps, is_knn, k,
                                       capacity=4)
    assert not bool(np.asarray(got[3]).any())
    wki, _ = engine.mixed_topk(want[0], want[2], k)
    gki, _ = engine.mixed_topk(got[0], got[2], k)
    wm = np.asarray(want[1])
    gi, ga = np.asarray(got[0]), np.asarray(got[1])
    for qi in range(Q):
        if bool(is_knn[qi]):
            np.testing.assert_array_equal(np.asarray(gki)[qi],
                                          np.asarray(wki)[qi])
        else:
            # The dense backend's answer mask is (Q, B) over positions.
            want_rows = set(np.flatnonzero(wm[qi]).tolist())
            assert set(gi[qi][ga[qi]].tolist()) == want_rows


def _random_walks(B: int, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((B, n)).cumsum(axis=1)
    w -= w.mean(axis=1, keepdims=True)
    return (w / w.std(axis=1, keepdims=True)).astype(np.float32)


@pytest.mark.parametrize("mode", MODES)
def test_tiered_knn_survivors_bounded(mode):
    """The tiered k-NN radius shrinks to the screen's own k-th upper bound,
    so a k-NN row verifies a few times k raw rows, not the ~k/64 of the
    database its 64-row sample seed radius admits (~320 rows here): the
    raw-tier gather stays small at millions of rows."""
    B, n, Q, k = 4096, 64, 8, 5
    db = _random_walks(B, n, seed=0)
    rng = np.random.default_rng(1)
    sigma = np.resize([0.1, 0.3, 1.0], Q)[:, None]
    qs = (db[rng.integers(0, B, Q)]
          + sigma * rng.standard_normal((Q, n))).astype(np.float32)
    levels, alpha = (4, 8), 8
    host = build_index(db, FastSAXConfig(n_segments=levels, alphabet=alpha))
    tindex = engine.TieredIndex.from_host(host, mode)
    qr = engine.represent_queries(jnp.asarray(qs), levels, alpha,
                                  stack=tindex.dev.stack)
    is_knn = np.arange(Q) % 2 == 0
    idx, answer, d2, overflow = engine.quantized_mixed_query(
        tindex, qr, jnp.full((Q,), 1.0, jnp.float32), is_knn, k)
    assert not bool(np.asarray(overflow).any())
    survivors = np.asarray(answer).sum(axis=1)[is_knn]
    assert (survivors >= k).all() and (survivors <= 4 * k).all(), survivors
    # ... and the tightened set still holds the exact top-k.
    d2o = ((db[None].astype(np.float64)
            - np.asarray(qr.q, np.float64)[:, None]) ** 2).sum(-1)
    got, _ = engine.mixed_topk(idx, d2, k)
    for qi in np.flatnonzero(is_knn):
        want = np.lexsort((np.arange(B), d2o[qi]))[:k]
        np.testing.assert_array_equal(np.asarray(got)[qi], want)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(MODES),
       st.sampled_from([0.8, 1.5, 3.0, 50.0]))
def test_host_engine_set_identical(seed, mode, eps):
    """The op-counting host engine: widened cascade + raw verify answers
    exactly like the full-precision reference, and the counter charges
    the per-candidate dequantization extra."""
    db, idx, cfg = _small_index(seed, B=80)
    qhost = q.quantize_host_index(idx, mode)
    qv = make_queries(db, 1, seed=seed % 89)[0]
    qr = represent_query(qv, cfg, normalize=False)
    ref = fastsax_range_query(idx, qr, eps)
    got = quantized_fastsax_range_query(qhost, idx.series, qr, eps)
    assert np.array_equal(got.answers, ref.answers)
    np.testing.assert_allclose(np.sort(got.distances),
                               np.sort(ref.distances), rtol=1e-9)


def test_host_engine_requires_config_for_raw_queries():
    db, idx, cfg = _small_index(0, B=32)
    qhost = q.quantize_host_index(idx, "int8")
    with pytest.raises(ValueError, match="config"):
        quantized_fastsax_range_query(qhost, idx.series, db[0], 2.0)
    # A raw query goes through the same default representation (incl.
    # normalization) on both engines.
    got = quantized_fastsax_range_query(qhost, idx.series, db[0], 2.0,
                                        config=cfg)
    ref = fastsax_range_query(idx, db[0], 2.0)
    assert np.array_equal(got.answers, ref.answers)


# ---------------------------------------------------------------------------
# Layout accounting (the 2x memory claim rests on these two functions)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_resident_bytes_accounting(mode):
    db, idx, cfg = _small_index(1, B=200, n=128, levels=(8, 16))
    qhost = q.quantize_host_index(idx, mode)
    full = q.full_precision_resident_bytes(idx.size, db.shape[1],
                                           [8, 16])
    assert full == idx.size * (4 * 128 + 4 + (4 * 8 + 4) + (4 * 16 + 4))
    ratio = full / qhost.resident_bytes()
    # int8 ≈ 4x on the dominant series column; bf16 ≈ 2x.
    assert ratio >= (3.0 if mode == "int8" else 1.9)


def test_alphabet_guard():
    db = make_wafer_like(16, 32, seed=0, normalize=False)
    idx = build_index(db, FastSAXConfig(n_segments=(4,), alphabet=3),
                      normalize=False)
    big = idx.config.alphabet
    object.__setattr__(idx.config, "alphabet", 127)
    try:
        with pytest.raises(q.QuantizationError, match="alphabet"):
            q.quantize_host_index(idx, "int8")
    finally:
        object.__setattr__(idx.config, "alphabet", big)
