"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret=True on
CPU) vs the pure-jnp oracles in kernels/ref.py."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (device_index_from_host, knn_query_auto,
                               knn_query_pallas, mixed_query_dense,
                               mixed_query_pallas, mixed_topk, range_query,
                               range_query_pallas, represent_queries,
                               resolve_backend)
from repro.core.fastsax import FastSAXConfig, build_index
from repro.core.paa import paa_np
from repro.core.sax import discretize_np
from repro.data.timeseries import make_wafer_like
from repro.kernels import ops, ref

SHAPES = [(64, 64), (200, 128), (513, 256)]   # includes non-multiple-of-block
DTYPES = [jnp.float32, jnp.bfloat16]


def _data(B, n, dtype, seed=0):
    x = make_wafer_like(B, n, seed=seed)
    return jnp.asarray(x, dtype=dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("N", [4, 8, 16])
def test_paa_kernel(shape, dtype, N):
    B, n = shape
    x = _data(B, n, dtype)
    got = ops.paa(x, N, block_b=128)
    want = ref.paa_ref(x.astype(jnp.float32), N)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("N", [4, 8, 16])
def test_linfit_kernel(shape, dtype, N):
    B, n = shape
    x = _data(B, n, dtype)
    got = ops.linfit_residual_sq(x, N, block_b=128)
    want = ref.linfit_residual_sq_ref(x.astype(jnp.float32), N)
    tol = 5e-4 if dtype == jnp.float32 else 0.35   # bf16: catastrophic-cancel prone
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("shape", [(64, 64), (513, 128)])
@pytest.mark.parametrize("alphabet", [3, 10, 20])
@pytest.mark.parametrize("N", [8, 16])
def test_mindist_kernel(shape, alphabet, N):
    B, n = shape
    x = np.asarray(_data(B, n, jnp.float32), np.float64)
    words = discretize_np(paa_np(x, N), alphabet)
    qword = words[B // 2]
    got = ops.mindist_sq(jnp.asarray(words), jnp.asarray(qword), n, alphabet,
                         block_b=128)
    tq = jnp.asarray(ref.query_table(qword, alphabet))
    want = ref.mindist_sq_ref(jnp.asarray(words), tq, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # self-distance must be 0 (adjacent-symbol cells are 0)
    assert float(np.asarray(got)[B // 2]) == 0.0


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sqdist_kernel(shape, dtype):
    B, n = shape
    x = _data(B, n, dtype)
    q = x[B // 3]
    got = ops.sqdist(x, q, block_b=128)
    want = ref.sqdist_ref(x.astype(jnp.float32), q.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 0.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_prune_level_respects_incoming_mask():
    B, n, N, alphabet = 128, 64, 8, 10
    db = make_wafer_like(B, n, seed=3)
    idx = build_index(db, FastSAXConfig(n_segments=(N,), alphabet=alphabet),
                      normalize=False)
    dev = device_index_from_host(idx)
    qr = represent_queries(jnp.asarray(db[:1], jnp.float32), (N,), alphabet,
                           normalize=False)
    dead = jnp.zeros((B,), dtype=bool)
    out = ops.prune_level(dead, dev.residuals[0], dev.words[0],
                          qr.words[0][0], qr.residuals[0][0],
                          jnp.float32(100.0), n, alphabet, block_b=128)
    assert not bool(np.asarray(out).any()), "dead lanes must stay dead"


def test_vmem_budget_guard():
    x = jnp.zeros((256, 100_000), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        ops.sqdist(x, x[0], block_b=256)


def test_fused_prune_rejects_non_multiple_batch():
    # A ValueError (never a bare assert — stripped under python -O) naming
    # both the batch and the block size.
    from repro.kernels.fused_prune import fused_prune_level_pallas
    B, N, alphabet = 100, 8, 3
    with pytest.raises(ValueError, match=r"B=100.*block_b=64"):
        fused_prune_level_pallas(
            jnp.ones((B,), jnp.int32), jnp.zeros((B,), jnp.float32),
            jnp.zeros((B, N), jnp.int32), jnp.zeros((alphabet, N)),
            jnp.float32(0.0), jnp.float32(1.0), 64, alphabet, block_b=64)


def test_mindist_table_cache_and_panels():
    tab1 = ops.mindist_table_cached(10)
    tab2 = ops.mindist_table_cached(10)
    np.testing.assert_array_equal(np.asarray(tab1), np.asarray(tab2))
    qwords = jnp.asarray(np.random.default_rng(0).integers(0, 10, (5, 8)),
                         jnp.int32)
    panels = np.asarray(ops.query_panels(qwords, 10))
    tab = np.asarray(tab1)
    for qi in range(5):
        np.testing.assert_array_equal(
            panels[qi], np.asarray(ops.query_table(qwords[qi], 10)))
        np.testing.assert_array_equal(panels[qi],
                                      tab[:, np.asarray(qwords[qi])])


# ---------------------------------------------------------------------------
# Fused megakernel (kernels/fused_query.py) — interpret-mode parity with
# the XLA engine oracle, bit for bit (ISSUE 4 acceptance criterion).
# ---------------------------------------------------------------------------

# (Q, B, levels, alphabet): covers single/multi level, small/large alphabet,
# B not a multiple of block_b (padding path) and Q not a multiple of block_q.
FUSED_GRID = [
    (1, 64, (8,), 3),
    (4, 200, (8, 16), 10),
    (7, 513, (8, 16), 20),
]


def _fused_case(Q, B, levels, alphabet, seed=2):
    n = 128
    db = make_wafer_like(B, n, seed=seed)
    idx = build_index(db, FastSAXConfig(n_segments=levels, alphabet=alphabet),
                      normalize=False)
    dev = device_index_from_host(idx)
    rng = np.random.default_rng(seed)
    q = db[rng.integers(0, B, Q)] + 0.05 * rng.standard_normal((Q, n))
    qr = represent_queries(jnp.asarray(q, jnp.float32), levels, alphabet,
                           normalize=False)
    return dev, qr


@pytest.mark.parametrize("case", FUSED_GRID)
def test_fused_range_bit_identical(case):
    Q, B, levels, alphabet = case
    dev, qr = _fused_case(Q, B, levels, alphabet)
    # Per-query epsilon column — every row prunes at its own radius.
    eps = jnp.asarray(np.linspace(0.5, 3.0, Q), jnp.float32)
    want_m, want_d = range_query(dev, qr, eps)
    got_m, got_d = range_query_pallas(dev, qr, eps, block_q=8, block_b=128,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


def test_fused_range_scalar_epsilon():
    dev, qr = _fused_case(4, 200, (8, 16), 10)
    want_m, want_d = range_query(dev, qr, jnp.float32(2.0))
    got_m, got_d = range_query_pallas(dev, qr, jnp.float32(2.0),
                                      block_q=8, block_b=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


@pytest.mark.parametrize("case", FUSED_GRID)
@pytest.mark.parametrize("k", [1, 5])
def test_fused_knn_bit_identical(case, k):
    Q, B, levels, alphabet = case
    dev, qr = _fused_case(Q, B, levels, alphabet)
    want_i, want_d, want_e = knn_query_auto(dev, qr, k)
    got_i, got_d, got_e = knn_query_pallas(dev, qr, k, block_q=8,
                                           block_b=128, interpret=True)
    assert bool(np.asarray(want_e).all()) and bool(np.asarray(got_e).all())
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    # Candidates are re-verified in the engine's diff² form, so distances
    # are bit-identical, not merely close.
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


def test_fused_topk_partials_merge():
    # The block-local partial top-k union must contain the global top-k,
    # and the merge epilogue must reproduce it with the engine tie-break.
    from repro.kernels.fused_query import (fused_topk_pallas,
                                           merge_topk_partials)
    from repro.kernels.ops import query_panels
    dev, qr = _fused_case(3, 513, (8, 16), 10)
    k = 5
    eps = jnp.full((3,), 100.0, jnp.float32)   # everything survives
    panels = tuple(query_panels(w, dev.alphabet) for w in qr.words)
    idxp, d2p = fused_topk_pallas(
        dev.series, dev.norms_sq, dev.words, dev.residuals,
        qr.q, panels, qr.residuals, eps,
        levels=dev.levels, alphabet=dev.alphabet, n=dev.n, k=k,
        block_q=8, block_b=128, interpret=True)
    assert idxp.shape == (3, (513 + 127) // 128 * k)
    nn_idx, nn_d2 = merge_topk_partials(idxp, d2p, k)
    # Brute-force oracle in the same (matmul) distance form.
    from repro.core.engine import verify_distances
    dense = np.asarray(verify_distances(dev, qr))
    for qi in range(3):
        order = np.lexsort((np.arange(513), dense[qi]))[:k]
        np.testing.assert_array_equal(np.asarray(nn_idx)[qi], order)


@pytest.mark.parametrize("case", FUSED_GRID[1:])
def test_fused_mixed_dispatch_parity(case):
    Q, B, levels, alphabet = case
    dev, qr = _fused_case(Q, B, levels, alphabet)
    k = 3
    eps = jnp.asarray(np.linspace(1.0, 3.0, Q), jnp.float32)
    is_knn = jnp.asarray([i % 2 == 0 for i in range(Q)])
    want = mixed_query_dense(dev, qr, eps, is_knn, k)
    got = mixed_query_pallas(dev, qr, eps, is_knn, k, block_q=8,
                             block_b=128, interpret=True)
    wi, wa, wd = (np.asarray(x) for x in want[:3])
    gi, ga, gd = (np.asarray(x) for x in got[:3])
    wki, wkd = (np.asarray(x) for x in mixed_topk(want[0], want[2], k))
    gki, gkd = (np.asarray(x) for x in mixed_topk(got[0], got[2], k))
    for i in range(Q):
        if bool(is_knn[i]):
            # k-NN rows: identical neighbours and identical (matmul-form)
            # distances vs the dense oracle.
            np.testing.assert_array_equal(gki[i], wki[i])
            np.testing.assert_array_equal(gkd[i], wkd[i])
        else:
            # Range rows: bit-identical dense answer mask and distances.
            np.testing.assert_array_equal(ga[i], wa[i])
            np.testing.assert_array_equal(gd[i], wd[i])
    assert not bool(np.asarray(got[3]).any())   # fused path never overflows


def test_fused_knn_valid_mask_excludes_rows():
    dev, qr = _fused_case(2, 200, (8, 16), 10)
    # Invalidate the unmasked winners; they must vanish from the answers.
    base_i, _, _ = knn_query_pallas(dev, qr, 3, block_q=8, block_b=128,
                                    interpret=True)
    banned = np.unique(np.asarray(base_i).ravel())
    vmask = np.ones(200, dtype=bool)
    vmask[banned] = False
    got_i, got_d, _ = knn_query_pallas(dev, qr, 3,
                                       valid_mask=jnp.asarray(vmask),
                                       block_q=8, block_b=128,
                                       interpret=True)
    want_i, want_d, _ = knn_query_auto(dev, qr, 3,
                                       valid_mask=jnp.asarray(vmask))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    assert not np.isin(np.asarray(got_i), banned).any()


def test_fused_knn_mostly_padding_shard_exact():
    # REVIEW regression (high): when the strided seed sample holds fewer
    # than k valid rows, the seed radius used to come out +inf, which let
    # the sentinel-residual (masked/padded) rows through the fused cascade
    # ("1e30 <= inf" passes C9); their finite distances then tightened the
    # radius below the true k-th VALID distance and the final pass dropped
    # true neighbours (e.g. [3, -1] instead of [3, 7]) while still
    # certifying exact=True.  Reachable via distributed_knn_query on a
    # mostly-padding shard.
    dev, qr = _fused_case(2, 200, (8, 16), 10)
    vmask = np.zeros(200, dtype=bool)
    vmask[[5, 7]] = True          # neither row is in the strided seed sample
    vm = jnp.asarray(vmask)
    series = np.asarray(dev.series, np.float32)
    qs = np.asarray(qr.q, np.float32)
    for k in (1, 2):
        got_i, got_d, got_e = knn_query_pallas(
            dev, qr, k, valid_mask=vm, block_q=8, block_b=128,
            interpret=True)
        want_i, want_d, want_e = knn_query_auto(dev, qr, k, valid_mask=vm)
        assert bool(np.asarray(got_e).all()) and bool(np.asarray(want_e).all())
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
        np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
        # Brute force over the valid rows only.
        d2 = ((series[None, :, :] - qs[:, None, :]) ** 2).sum(-1)
        d2[:, ~vmask] = np.inf
        for qi in range(2):
            order = np.lexsort((np.arange(200), d2[qi]))[:k]
            np.testing.assert_array_equal(np.asarray(got_i)[qi], order)

    # Same scenario through the mixed dispatch (k-NN rows only).
    is_knn = jnp.asarray([True, True])
    eps0 = jnp.zeros((2,), jnp.float32)
    got = mixed_query_pallas(dev, qr, eps0, is_knn, 2, valid_mask=vm,
                             block_q=8, block_b=128, interpret=True)
    want = mixed_query_dense(dev, qr, eps0, is_knn, 2, valid_mask=vm)
    gki, _ = mixed_topk(got[0], got[2], 2)
    wki, _ = mixed_topk(want[0], want[2], 2)
    np.testing.assert_array_equal(np.asarray(gki), np.asarray(wki))
    assert not np.asarray(got[1])[:, ~vmask].any(), \
        "masked rows must never enter the dense answer mask"


def test_fused_knn_huge_scale_finite_seed_radius():
    # Follow-up regression: the seed-radius guard substitutes a finite
    # stand-in ONLY for a non-finite (no-information) radius.  On
    # un-normalised data whose distances exceed any fixed small ceiling, a
    # legitimately finite sampled radius must pass through untouched on
    # both backends — an unconditional clamp here would silently exclude
    # true neighbours while certifying exact=True.
    from repro.core.engine import build_device_index
    rng = np.random.default_rng(1)
    big = (rng.standard_normal((64, 128)) * 1e16).astype(np.float32)
    dev = build_device_index(jnp.asarray(big), (8,), 10, normalize=False)
    qr = represent_queries(jnp.asarray(big[:2] + 1e15), (8,), 10,
                           normalize=False)
    want_i, _, want_e = knn_query_auto(dev, qr, 3)
    got_i, _, got_e = knn_query_pallas(dev, qr, 3, block_q=8, block_b=128,
                                       interpret=True)
    d2 = ((big[None, :, :].astype(np.float64)
           - np.asarray(qr.q)[:, None, :].astype(np.float64)) ** 2).sum(-1)
    bf = np.stack([np.lexsort((np.arange(64), d2[i]))[:3] for i in range(2)])
    np.testing.assert_array_equal(np.asarray(want_i), bf)
    np.testing.assert_array_equal(np.asarray(got_i), bf)
    assert bool(np.asarray(want_e).all()) and bool(np.asarray(got_e).all())


def test_reverify_rows_discards_out_of_range_and_invalid():
    # REVIEW regression (low): indices >= B (padded kernel rows) used to be
    # gather-clamped to row B-1, yielding finite bogus distances that could
    # survive the merge.  They must re-verify to +inf, as must rows an
    # explicit valid_mask excludes.
    from repro.core.engine import _reverify_rows
    dev, qr = _fused_case(1, 64, (8,), 3)
    idx = jnp.asarray([[0, 5, -1, 63, 64, 200]], jnp.int32)
    d2 = np.asarray(_reverify_rows(dev, qr, idx))
    assert np.isfinite(d2[0, [0, 1, 3]]).all()
    assert np.isinf(d2[0, [2, 4, 5]]).all()
    ref_d2 = ((np.asarray(dev.series)[[0, 5, 63]]
               - np.asarray(qr.q)[0][None, :]) ** 2).sum(-1)
    np.testing.assert_allclose(d2[0, [0, 1, 3]], ref_d2, rtol=1e-6)
    vmask = np.ones(64, dtype=bool)
    vmask[5] = False
    d2m = np.asarray(_reverify_rows(dev, qr, idx, jnp.asarray(vmask)))
    assert np.isinf(d2m[0, 1]) and np.isfinite(d2m[0, [0, 3]]).all()


def test_fused_knn_certificate_flags_boundary_ties():
    # REVIEW regression (low): > _TOPK_GUARD rows of one block inside the
    # same noise window at the partial-list boundary — the certificate must
    # not claim exactness there (the conservative direction; here the ties
    # are exact duplicates, so the answer itself is still correct).
    n, alphabet, levels = 128, 10, (8,)
    rng = np.random.default_rng(7)
    base = rng.standard_normal(n)
    rest = base[None, :] + 5.0 * rng.standard_normal((48, n))
    db = np.concatenate([np.repeat(base[None, :], 16, axis=0), rest])
    idx = build_index(db, FastSAXConfig(n_segments=levels, alphabet=alphabet),
                      normalize=False)
    dev = device_index_from_host(idx)
    qr = represent_queries(jnp.asarray(base[None, :], jnp.float32), levels,
                           alphabet, normalize=False)
    got_i, got_d, got_e = knn_query_pallas(dev, qr, 1, block_q=8,
                                           block_b=128, interpret=True)
    # 16 zero-distance rows share one block: the full partial list's worst
    # re-verified distance ties the merged k-th, so no exactness claim...
    assert not bool(np.asarray(got_e).any())
    # ...even though the answer (lowest-index duplicate) is in fact right.
    assert int(np.asarray(got_i)[0, 0]) == 0
    assert float(np.asarray(got_d)[0, 0]) == 0.0


def test_choose_fused_blocks_respects_vmem():
    bq, bb = ops.choose_fused_blocks(32, 4096, 128, (8, 16), 10)
    assert bq in ops.FUSED_BLOCK_Q and bb in ops.FUSED_BLOCK_B
    assert ops.fused_vmem_bytes(bq, bb, 128, (8, 16), 10) <= ops.vmem_limit()
    with pytest.raises(ValueError, match="VMEM"):
        ops.choose_fused_blocks(32, 4096, 10 ** 7, (8, 16), 10)


def test_resolve_backend():
    assert resolve_backend("xla") == "xla"
    assert resolve_backend("pallas") == "pallas"
    assert resolve_backend("auto") in ("xla", "pallas")
    with pytest.raises(ValueError, match="backend"):
        resolve_backend("cuda")


# ---------------------------------------------------------------------------
# Quantized megakernels (DESIGN.md §9) — dequantize-in-kernel Pallas loads
# vs the XLA quantized-screen oracle, bit for bit, int8 AND bf16.
# ---------------------------------------------------------------------------

QUANT_MODES = ("bf16", "int8")


def _quant_case(Q, B, levels, alphabet, mode, seed=2):
    from repro.core import engine
    n = 128
    db = make_wafer_like(B, n, seed=seed)
    idx = build_index(db, FastSAXConfig(n_segments=levels, alphabet=alphabet),
                      normalize=False)
    tindex = engine.TieredIndex.from_host(idx, mode)
    rng = np.random.default_rng(seed)
    q = db[rng.integers(0, B, Q)] + 0.05 * rng.standard_normal((Q, n))
    qr = represent_queries(jnp.asarray(q, jnp.float32), levels, alphabet,
                           normalize=False)
    return tindex, qr


def _assert_screen_d2_close(got, want, q, norms_sq):
    """Screen distances of the kernel vs the XLA oracle.

    Both evaluate d̂² = ‖q‖² − 2·q·û + ‖û‖² with the same operands, but the
    kernel's dot runs on (block_q, n)·(n, block_b) tiles and the oracle's
    on the whole (Q, n)·(n, B) product, and XLA's CPU dot sums in an order
    that depends on the operand shapes (and the host CPU).  Two summation
    orders of an n-term f32 dot differ by at most 2·γ_{n−1}·Σ|q_i·û_i|, so
    the bound is n ulps of ‖q‖² + ‖û‖²; +inf lanes must coincide exactly.
    """
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want))
    fin = np.isfinite(want)
    q = np.asarray(q, np.float64)
    scale = (q * q).sum(-1)[:, None] + np.asarray(norms_sq)[None, :]
    tol = q.shape[-1] * 2.0 ** -23 * scale
    assert (np.abs(got - want)[fin] <= tol[fin]).all()


@pytest.mark.parametrize("case", FUSED_GRID)
@pytest.mark.parametrize("mode", QUANT_MODES)
def test_fused_quant_range_bit_identical(case, mode):
    from repro.core import engine
    from repro.kernels.fused_query import fused_quant_range_pallas

    Q, B, levels, alphabet = case
    tindex, qr = _quant_case(Q, B, levels, alphabet, mode)
    eps = jnp.asarray(np.linspace(0.5, 3.0, Q), jnp.float32).reshape(Q, 1)
    want_k, want_d = engine.quantized_screen(tindex.dev, qr, eps)
    got_k, got_d = fused_quant_range_pallas(
        tindex.dev, qr.q, tuple(ops.query_panels(w, alphabet)
                                for w in qr.words),
        qr.residuals, eps, block_q=8, block_b=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    _assert_screen_d2_close(got_d, want_d, qr.q, tindex.dev.norms_sq)


@pytest.mark.parametrize("mode", QUANT_MODES)
def test_fused_quant_range_mostly_padding_block(mode):
    # A 5-row database inside one 128-lane kernel block: the sentinel-coded
    # padding lanes must neither survive the screen nor poison the real
    # lanes' distances (the PR-4 padding regression, quantized edition).
    from repro.core import engine
    from repro.kernels.fused_query import fused_quant_range_pallas

    tindex, qr = _quant_case(2, 5, (8,), 10, mode)
    eps = jnp.full((2, 1), 1e6, jnp.float32)    # keep everything real
    want_k, want_d = engine.quantized_screen(tindex.dev, qr, eps)
    got_k, got_d = fused_quant_range_pallas(
        tindex.dev, qr.q, tuple(ops.query_panels(w, 10) for w in qr.words),
        qr.residuals, eps, block_q=8, block_b=128, interpret=True)
    assert got_k.shape == (2, 5)
    assert bool(np.asarray(got_k).all())
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    _assert_screen_d2_close(got_d, want_d, qr.q, tindex.dev.norms_sq)
    assert np.isfinite(np.asarray(got_d)).all()


@pytest.mark.parametrize("case", FUSED_GRID[1:])
@pytest.mark.parametrize("mode", QUANT_MODES)
def test_fused_quant_topk_partials_contain_global(case, mode):
    from repro.core import engine
    from repro.kernels.fused_query import (fused_quant_topk_pallas,
                                           merge_topk_partials)

    Q, B, levels, alphabet = case
    k = 5
    tindex, qr = _quant_case(Q, B, levels, alphabet, mode)
    eps = jnp.full((Q, 1), 100.0, jnp.float32)   # everything survives
    panels = tuple(ops.query_panels(w, alphabet) for w in qr.words)
    idxp, d2p = fused_quant_topk_pallas(
        tindex.dev, qr.q, panels, qr.residuals, eps, k,
        block_q=8, block_b=128, interpret=True)
    nb = (B + 127) // 128
    assert idxp.shape == (Q, nb * k)
    nn_idx, nn_d2 = merge_topk_partials(idxp, d2p, k)
    # Oracle: the dense XLA screen distances, same tie-break.
    _, dense = engine.quantized_screen(tindex.dev, qr, eps)
    dense = np.asarray(dense)
    norms = np.asarray(tindex.dev.norms_sq)
    for qi in range(Q):
        order = np.lexsort((np.arange(B), dense[qi]))[:k]
        np.testing.assert_array_equal(np.asarray(nn_idx)[qi], order)
        _assert_screen_d2_close(np.asarray(nn_d2)[qi:qi + 1],
                                dense[qi:qi + 1, order], qr.q[qi:qi + 1],
                                norms[order])


@pytest.mark.parametrize("mode", QUANT_MODES)
def test_quantized_backend_dispatch_parity(mode):
    # End-to-end tiered range query: the Pallas screen backend and the XLA
    # oracle produce identical verified answers.
    from repro.core import engine

    tindex, qr = _quant_case(4, 200, (8, 16), 10, mode)
    eps = jnp.asarray(np.linspace(0.8, 2.5, 4), jnp.float32)
    wi, wa, wd, we = engine.quantized_range_query(tindex, qr, eps,
                                                  backend="xla")
    gi, ga, gd, ge = engine.quantized_range_query(tindex, qr, eps,
                                                  backend="pallas")
    assert bool(np.asarray(we).all()) and bool(np.asarray(ge).all())
    for qi in range(4):
        w = set(np.asarray(wi)[qi][np.asarray(wa)[qi]].tolist())
        g = set(np.asarray(gi)[qi][np.asarray(ga)[qi]].tolist())
        assert g == w


@pytest.mark.parametrize("mode", QUANT_MODES)
@pytest.mark.parametrize("stride", [1, 4])
def test_fused_quant_subseq_bit_identical(mode, stride):
    # Streaming subsequence form: quantized screen metadata + exact
    # in-kernel verify — answers bit-identical to the full-precision
    # subsequence kernel (the screen is a provable superset, the epsilon
    # cut happens on the same exact streamed distances).
    from repro.core import subseq as ss
    from repro.data.timeseries import make_subseq_queries

    streams = make_wafer_like(2, 384, seed=5, normalize=False)
    cfg = FastSAXConfig(n_segments=(8, 16), alphabet=10)
    hidx = ss.build_subseq_index(streams, cfg, 128, stride)
    sidx = ss.subseq_device_index(hidx)
    qmeta = ss.quantize_subseq_meta(hidx, mode)
    qs = make_subseq_queries(streams, 3, 128, seed=7)
    qr = represent_queries(jnp.asarray(qs, jnp.float32), (8, 16), 10,
                           normalize=False)
    eps = jnp.asarray([1.0, 2.0, 4.0], jnp.float32)
    want_m, want_d = ss.subseq_range_query(sidx, qr, eps, backend="xla")
    got_m, got_d = ss.subseq_range_query_quantized(sidx, qmeta, qr, eps,
                                                   block_q=8, block_w=128,
                                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
