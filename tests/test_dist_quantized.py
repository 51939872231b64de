"""Differential battery for the distributed quantized screen (PR 10,
DESIGN.md §13).

The tentpole claim under test: running the int8/bf16 screen *inside*
``shard_map`` — per-shard quantized columns resident per device, widened
bounds evaluated shard-locally, only surviving row ids gathered
cross-host — answers every query SET-IDENTICALLY to the single-host
tiered engine AND the f64 brute-force oracle, with an always-exact
certificate, across shard counts, codecs, representation stacks, and
pad-heavy splits.

Multi-device cases run in a subprocess with
``xla_force_host_platform_device_count=8`` (the dry-run isolation rule);
the hypothesis-sampled geometry cases run in-process on a 1-device mesh,
where ``shard_map`` takes the same code path with P=1.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])


def _run(*parts: str):
    """Run the dedented concatenation of ``parts`` (prelude + test body,
    dedented separately — they are indented at different depths) in an
    8-CPU-device subprocess."""
    code = "".join(textwrap.dedent(p) for p in parts)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(pathlib.Path(_REPO_ROOT) / "src"),
               JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, cwd=_REPO_ROOT,
                          env=env, timeout=600)


# Shared subprocess prelude: oracle + reference helpers.
_PRELUDE = """
    import pathlib
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import dist_search as ds
    from repro.core import engine as eng
    from repro.core.engine import TieredIndex, represent_queries
    from repro.core.fastsax import FastSAXConfig, build_index
    from repro.core.options import SearchOptions

    assert len(jax.devices()) == 8

    def oracle_d2(db, qs):
        return ((db[None, :, :].astype(np.float64)
                 - qs[:, None, :].astype(np.float64)) ** 2).sum(-1)

    def answer_sets(gidx, ans):
        gidx, ans = np.asarray(gidx), np.asarray(ans)
        return [set(gidx[i][ans[i]].tolist()) for i in range(gidx.shape[0])]
"""


@pytest.mark.slow
def test_dist_quantized_parity_shard_counts_codecs():
    """Range + k-NN + mixed over shard counts {1, 2, 4, 8} x {int8, bf16}:
    the distributed tiered engine == single-host tiered engine == f64
    oracle, always-exact certificates throughout."""
    r = _run(_PRELUDE, """
        rng = np.random.default_rng(0)
        B, n, Q, k = 330, 64, 6, 5
        db = rng.normal(size=(B, n)).astype(np.float32)
        qs = (db[rng.integers(0, B, Q)]
              + 0.05 * rng.normal(size=(Q, n))).astype(np.float32)
        levels, alpha, eps = (4, 8), 8, 4.0
        host = build_index(db, FastSAXConfig(n_segments=levels,
                                             alphabet=alpha),
                           normalize=False)
        d2o = oracle_d2(db, qs)
        oracle = [set(np.nonzero(d2o[i] <= eps * eps)[0].tolist())
                  for i in range(Q)]
        knn_ref = np.argsort(d2o, axis=1, kind="stable")[:, :k]
        opts = SearchOptions(normalize_queries=False)

        for mode in ("int8", "bf16"):
            tix = TieredIndex.from_host(host, mode)
            qr = represent_queries(jnp.asarray(qs), levels, alpha,
                                   normalize=False, stack=tix.dev.stack)
            si, sa, _sd, _se = eng.quantized_range_query(
                tix, qr, eps, options=SearchOptions())
            single = answer_sets(si, sa)
            assert single == oracle, (mode, "single-host tiered vs oracle")

            for P in (1, 2, 4, 8):
                mesh = ds.make_data_mesh(P)
                dti = ds.distributed_tiered_index(tix, mesh)
                gidx, ans, d2, exact = ds.distributed_quantized_range_query(
                    dti, qs, eps, mesh, options=opts)
                assert bool(np.asarray(exact).all()), (mode, P)
                assert answer_sets(gidx, ans) == oracle, (mode, P, "range")
                for i in range(Q):
                    a = np.asarray(ans[i]); gi = np.asarray(gidx[i])[a]
                    np.testing.assert_allclose(
                        np.asarray(d2[i])[a], d2o[i][gi],
                        rtol=1e-4, atol=1e-4)

                nn, nnd2, kex = ds.distributed_quantized_knn_query(
                    dti, qs, k, mesh, options=opts)
                assert bool(np.asarray(kex).all()), (mode, P, "knn cert")
                assert np.array_equal(np.asarray(nn), knn_ref), (mode, P)

                is_knn = np.arange(Q) % 2 == 0
                mg, ma, md, mo = ds.distributed_quantized_mixed_query(
                    dti, qs, eps, is_knn, k, mesh, options=opts)
                assert not bool(np.asarray(mo).any()), (mode, P, "mixed")
                for i in range(Q):
                    a = np.asarray(ma[i]); gi = np.asarray(mg[i])[a]
                    if is_knn[i]:
                        assert set(knn_ref[i].tolist()) <= set(gi.tolist())
                    else:
                        assert set(gi.tolist()) == oracle[i], (mode, P, i)
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dist_quantized_mostly_padding_shards():
    """Tiny B on 8 shards: most devices hold pure sentinel padding (and
    zero live raw rows), yet answers stay oracle-identical and exact."""
    r = _run(_PRELUDE, """
        rng = np.random.default_rng(1)
        B, n, Q = 40, 32, 5          # pads to 8*128=1024 screen rows
        db = rng.normal(size=(B, n)).astype(np.float32)
        qs = (db[:Q] + 0.05 * rng.normal(size=(Q, n))).astype(np.float32)
        levels, alpha, eps, k = (4,), 6, 3.0, 3
        host = build_index(db, FastSAXConfig(n_segments=levels,
                                             alphabet=alpha),
                           normalize=False)
        d2o = oracle_d2(db, qs)
        oracle = [set(np.nonzero(d2o[i] <= eps * eps)[0].tolist())
                  for i in range(Q)]
        knn_ref = np.argsort(d2o, axis=1, kind="stable")[:, :k]
        mesh = ds.make_data_mesh(8)
        opts = SearchOptions(normalize_queries=False)
        for mode in ("int8", "bf16"):
            tix = TieredIndex.from_host(host, mode)
            dti = ds.distributed_tiered_index(tix, mesh)
            assert dti.size == 8 * 128 and dti.n_valid == B
            assert int(dti.raw.shape[0]) == B     # raw stays unpadded
            gidx, ans, d2, exact = ds.distributed_quantized_range_query(
                dti, qs, eps, mesh, options=opts)
            assert bool(np.asarray(exact).all())
            assert answer_sets(gidx, ans) == oracle, mode
            nn, _d, kex = ds.distributed_quantized_knn_query(
                dti, qs, k, mesh, options=opts)
            assert bool(np.asarray(kex).all())
            assert np.array_equal(np.asarray(nn), knn_ref), mode
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dist_quantized_trend_slope_stack():
    """Extended representation stack (trend_slope) rides through the
    distributed quantized screen: extra columns shard like the canonical
    ones, answers stay oracle-identical."""
    r = _run(_PRELUDE, """
        rng = np.random.default_rng(2)
        B, n, Q = 300, 64, 5
        db = rng.normal(size=(B, n)).astype(np.float32)
        db += np.linspace(-1, 1, n)[None, :] * rng.normal(size=(B, 1))
        db = db.astype(np.float32)
        qs = (db[:Q] + 0.05 * rng.normal(size=(Q, n))).astype(np.float32)
        levels, alpha, eps = (4, 8), 8, 4.0
        stack = ("linfit_residual", "sax_word", "trend_slope")
        host = build_index(db, FastSAXConfig(n_segments=levels,
                                             alphabet=alpha, stack=stack),
                           normalize=False)
        d2o = oracle_d2(db, qs)
        oracle = [set(np.nonzero(d2o[i] <= eps * eps)[0].tolist())
                  for i in range(Q)]
        mesh = ds.make_data_mesh(4)
        for mode in ("int8", "bf16"):
            tix = TieredIndex.from_host(host, mode)
            assert tuple(tix.dev.stack) == stack
            dti = ds.distributed_tiered_index(tix, mesh)
            gidx, ans, d2, exact = ds.distributed_quantized_range_query(
                dti, qs, eps, mesh,
                options=SearchOptions(normalize_queries=False))
            assert bool(np.asarray(exact).all())
            assert answer_sets(gidx, ans) == oracle, mode
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dist_quantized_store_round_trips():
    """store_sharded_tiered -> {load_sharded_tiered (mesh, per-shard
    upload), load_sharded_quantized (single-host concat),
    load_shard_indexes (failover tiered shards)}: all three reloads
    answer oracle-identically; the raw tier survives as a live-row
    prefix (pad shards store empty series)."""
    r = _run(_PRELUDE, """
        import tempfile
        from repro.index import sharded
        rng = np.random.default_rng(3)
        B, n, Q = 300, 64, 5          # pads to 512 on 4 shards
        db = rng.normal(size=(B, n)).astype(np.float32)
        qs = (db[:Q] + 0.05 * rng.normal(size=(Q, n))).astype(np.float32)
        levels, alpha, eps = (4, 8), 8, 4.0
        host = build_index(db, FastSAXConfig(n_segments=levels,
                                             alphabet=alpha),
                           normalize=False)
        d2o = oracle_d2(db, qs)
        oracle = [set(np.nonzero(d2o[i] <= eps * eps)[0].tolist())
                  for i in range(Q)]
        mesh = ds.make_data_mesh(4)
        opts = SearchOptions(normalize_queries=False)
        for mode in ("int8", "bf16"):
            tix = TieredIndex.from_host(host, mode)
            dti = ds.distributed_tiered_index(tix, mesh)
            with tempfile.TemporaryDirectory() as td:
                p = pathlib.Path(td) / "tier"
                ds.store_sharded_tiered(dti, p)

                # last shard's screen rows [384, 512) are all past the
                # 300 live raw rows -> empty stored series slice.
                import repro.index.store as store
                smf = store.read_manifest(p / "shard_00003")
                assert smf and store.read_array(
                    p / "shard_00003", "series").shape[0] == 0

                dti2 = ds.load_sharded_tiered(p, mesh)
                assert dti2.n_valid == dti.n_valid
                g, a, _d, e = ds.distributed_quantized_range_query(
                    dti2, qs, eps, mesh, options=opts)
                assert bool(np.asarray(e).all())
                assert answer_sets(g, a) == oracle, (mode, "mesh reload")

                tix2, nv = sharded.load_sharded_quantized(p)
                assert nv == B and int(tix2.raw.shape[0]) == B
                qr = represent_queries(jnp.asarray(qs), levels, alpha,
                                       normalize=False, stack=tix2.dev.stack)
                si, sa, _sd, _se = eng.quantized_range_query(
                    tix2, qr, eps, options=SearchOptions())
                assert answer_sets(si, sa) == oracle, (mode, "host reload")

                shards, offs, nv2 = sharded.load_shard_indexes(p)
                assert nv2 == B and len(shards) == 4
                assert all(hasattr(s, "dev") for s in shards)
                assert int(shards[-1].raw.shape[0]) == 0
                fo = ds.FailoverShards(shards, offsets=offs, n_valid=nv2)
                gf, af, _df, _of, cov = fo.query(qs, eps,
                                                 np.zeros(Q, bool), 1)
                assert cov.exact
                assert answer_sets(gf, af) == oracle, (mode, "failover")

                # mesh-size mismatch is rejected loudly
                try:
                    ds.load_sharded_tiered(p, ds.make_data_mesh(8))
                    raise AssertionError("mesh mismatch accepted")
                except ValueError as e:
                    assert "re-store" in str(e)
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dist_quantized_verify_prefetch_bit_identity():
    """The double-buffered verify fetch returns bit-identical buffers to
    the synchronous path — distributed and single-host tiered engines."""
    r = _run(_PRELUDE, """
        rng = np.random.default_rng(4)
        B, n, Q = 300, 64, 6
        db = rng.normal(size=(B, n)).astype(np.float32)
        qs = (db[:Q] + 0.05 * rng.normal(size=(Q, n))).astype(np.float32)
        levels, alpha, eps, k = (4, 8), 8, 4.0, 4
        host = build_index(db, FastSAXConfig(n_segments=levels,
                                             alphabet=alpha),
                           normalize=False)
        mesh = ds.make_data_mesh(4)
        sync = SearchOptions(normalize_queries=False)
        pre = SearchOptions(normalize_queries=False, verify_prefetch=True)
        for mode in ("int8", "bf16"):
            tix = TieredIndex.from_host(host, mode)
            dti = ds.distributed_tiered_index(tix, mesh)
            g0, a0, d0, e0 = ds.distributed_quantized_range_query(
                dti, qs, eps, mesh, options=sync)
            g1, a1, d1, e1 = ds.distributed_quantized_range_query(
                dti, qs, eps, mesh, options=pre)
            assert np.array_equal(np.asarray(g0), np.asarray(g1))
            assert np.array_equal(np.asarray(a0), np.asarray(a1))
            assert np.array_equal(np.asarray(d0), np.asarray(d1))

            n0, nd0, _ = ds.distributed_quantized_knn_query(
                dti, qs, k, mesh, options=sync)
            n1, nd1, _ = ds.distributed_quantized_knn_query(
                dti, qs, k, mesh, options=pre)
            assert np.array_equal(np.asarray(n0), np.asarray(n1))
            assert np.array_equal(np.asarray(nd0), np.asarray(nd1))

            qr = represent_queries(jnp.asarray(qs), levels, alpha,
                                   normalize=False, stack=tix.dev.stack)
            s0 = eng.quantized_range_query(tix, qr, eps,
                                           options=SearchOptions())
            s1 = eng.quantized_range_query(
                tix, qr, eps, options=SearchOptions(verify_prefetch=True))
            for x, y in zip(s0, s1):
                assert np.array_equal(np.asarray(x), np.asarray(y))
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dist_quantized_serve_backends():
    """Serve layer routing: from_series(mesh + quantization) dispatches
    through the distributed tiered backend; a tiered sharded store warm-
    starts the failover backend when cfg.failover_shards is set."""
    r = _run(_PRELUDE, """
        import tempfile
        from repro.serve.service import SearchService, ServeConfig
        rng = np.random.default_rng(5)
        db = rng.normal(size=(260, 64)).astype(np.float32)
        q = db[7] + 0.01 * rng.normal(size=64).astype(np.float32)
        d2 = ((db.astype(np.float64) - q.astype(np.float64)) ** 2).sum(-1)
        mesh = ds.make_data_mesh(4)
        cfg = ServeConfig(quantization="int8", verify_prefetch=True,
                          normalize_queries=False)
        svc = SearchService.from_series(db, cfg, mesh=mesh,
                                        normalize=False).start()
        try:
            req = svc.submit_range(q, 2.0); req.wait(120)
            assert req.exact
            assert set(req.ids.tolist()) == set(
                np.nonzero(d2 <= 4.0)[0].tolist())
            req2 = svc.submit_knn(q, 3); req2.wait(120)
            assert req2.ids.tolist() == np.argsort(
                d2, kind="stable")[:3].tolist()
        finally:
            svc.stop()

        host = build_index(db, FastSAXConfig(n_segments=(4, 8), alphabet=8),
                           normalize=False)
        tix = TieredIndex.from_host(host, "bf16")
        dti = ds.distributed_tiered_index(tix, mesh)
        with tempfile.TemporaryDirectory() as td:
            p = pathlib.Path(td) / "tier"
            ds.store_sharded_tiered(dti, p)
            cfg2 = ServeConfig(quantization="bf16", failover_shards=4,
                               normalize_queries=False)
            svc2 = SearchService.from_store(p, cfg2).start()
            try:
                req = svc2.submit_range(q, 2.0); req.wait(120)
                assert set(req.ids.tolist()) == set(
                    np.nonzero(d2 <= 4.0)[0].tolist())
            finally:
                svc2.stop()
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout


def test_dist_quantized_knn_survivors_bounded():
    """The distributed k-NN radius shrinks to the k-th smallest screen
    upper bound over the whole mesh (all-gathered per shard), so a k-NN
    row gathers a few times k raw rows across the four shards — not the
    ~k/64 of the database its sample seed radius admits (~320 here)."""
    r = _run(_PRELUDE, """
        B, n, Q, k = 4096, 64, 8, 5
        rng = np.random.default_rng(0)
        w = rng.standard_normal((B, n)).cumsum(axis=1)
        w -= w.mean(axis=1, keepdims=True)
        db = (w / w.std(axis=1, keepdims=True)).astype(np.float32)
        rng = np.random.default_rng(1)
        sigma = np.resize([0.1, 0.3, 1.0], Q)[:, None]
        qs = (db[rng.integers(0, B, Q)]
              + sigma * rng.standard_normal((Q, n))).astype(np.float32)
        levels, alpha = (4, 8), 8
        host = build_index(db, FastSAXConfig(n_segments=levels,
                                             alphabet=alpha))
        mesh = ds.make_data_mesh(4)
        is_knn = np.arange(Q) % 2 == 0
        for mode in ("int8", "bf16"):
            dti = ds.distributed_tiered_index(
                TieredIndex.from_host(host, mode), mesh)
            gidx, ans, d2, over = ds.distributed_quantized_mixed_query(
                dti, qs, 1.0, is_knn, k, mesh)
            assert not bool(np.asarray(over).any()), mode
            surv = np.asarray(ans).sum(axis=1)[is_knn]
            assert (surv >= k).all() and (surv <= 4 * k).all(), (mode, surv)
            qz = np.asarray(represent_queries(
                jnp.asarray(qs), levels, alpha).q, np.float64)
            d2o = ((db[None].astype(np.float64) - qz[:, None]) ** 2).sum(-1)
            got, _ = eng.mixed_topk(gidx, d2, k)
            for qi in np.flatnonzero(is_knn):
                want = np.lexsort((np.arange(B), d2o[qi]))[:k]
                assert np.array_equal(np.asarray(got)[qi], want), (mode, qi)
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# In-process cases: 1-device mesh (same shard_map code path with P=1),
# hypothesis-sampled geometry.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from _mini_hypothesis import given, settings, strategies as st


def _build_tiered(db, levels, alpha, mode, stack=None):
    from repro.core.engine import TieredIndex
    from repro.core.fastsax import FastSAXConfig, build_index

    kw = {} if stack is None else {"stack": stack}
    host = build_index(db, FastSAXConfig(n_segments=levels, alphabet=alpha,
                                         **kw), normalize=False)
    return TieredIndex.from_host(host, mode)


@settings(max_examples=6, deadline=None)
@given(st.integers(3, 200), st.sampled_from(["int8", "bf16"]),
       st.floats(1.0, 6.0))
def test_dist_quantized_geometry_sampled(B, mode, eps):
    """Hypothesis-sampled database sizes — including RESID_BLOCK-
    straddling B — on a 1-device mesh: the padded distributed screen
    answers exactly like the f64 oracle."""
    from repro.core import dist_search as ds
    from repro.core.options import SearchOptions
    from repro.index import quantized as _q

    rng = np.random.default_rng(B)
    # Nudge B to straddle a RESID_BLOCK boundary half the time.
    if B % 2:
        B = max(3, (B % 3 + 1) * _q.RESID_BLOCK + (B % 5) - 2)
    n, Q = 32, 3
    db = rng.normal(size=(B, n)).astype(np.float32)
    qs = (db[rng.integers(0, B, Q)]
          + 0.05 * rng.normal(size=(Q, n))).astype(np.float32)
    tix = _build_tiered(db, (4,), 6, mode)
    mesh = ds.make_data_mesh(1)
    dti = ds.distributed_tiered_index(tix, mesh)
    assert dti.size % _q.RESID_BLOCK == 0
    d2o = ((db[None, :, :].astype(np.float64)
            - qs[:, None, :].astype(np.float64)) ** 2).sum(-1)
    gidx, ans, d2, exact = ds.distributed_quantized_range_query(
        dti, qs, float(eps), mesh,
        options=SearchOptions(normalize_queries=False))
    assert bool(np.asarray(exact).all())
    for i in range(Q):
        a = np.asarray(ans[i])
        got = set(np.asarray(gidx[i])[a].tolist())
        want = set(np.nonzero(d2o[i] <= eps * eps)[0].tolist())
        assert got == want, (B, mode, eps, i)

    k = min(3, B)
    nn, _nd, kex = ds.distributed_quantized_knn_query(
        dti, qs, k, mesh, options=SearchOptions(normalize_queries=False))
    assert bool(np.asarray(kex).all())
    ref = np.argsort(d2o, axis=1, kind="stable")[:, :k]
    assert np.array_equal(np.asarray(nn), ref), (B, mode)


@settings(max_examples=4)
@given(st.integers(1, 4), st.sampled_from(["int8", "bf16"]))
def test_tiered_store_shard_split_sampled(n_parts, mode):
    """Hypothesis-sampled shard splits of a tiered store: every split
    that store_sharded_quantized accepts reloads identically through the
    per-shard loader; the misaligned split fails loudly at store time."""
    import tempfile

    from repro.core import dist_search as ds
    from repro.index import quantized as _q
    from repro.index import sharded

    rng = np.random.default_rng(n_parts * 17 + len(mode))
    B = n_parts * _q.RESID_BLOCK
    db = rng.normal(size=(B, 32)).astype(np.float32)
    tix = _build_tiered(db, (4,), 6, mode)
    mesh = ds.make_data_mesh(1)
    dti = ds.distributed_tiered_index(tix, mesh)
    with tempfile.TemporaryDirectory() as td:
        p = pathlib.Path(td) / "tier"
        ds.store_sharded_tiered(dti, p)
        tiers, n_valid, _mf = sharded.load_tier_shards(p)
        assert n_valid == B
        assert sum(t.rows for t in tiers) == dti.size
        tix2, nv = sharded.load_sharded_quantized(p)
        assert nv == B
        np.testing.assert_array_equal(np.asarray(tix2.raw)[:B], db)


def test_store_misalignment_fails_loudly(tmp_path):
    """Satellite 3: a store whose shard offsets do not tile the index is
    refused at load with an IOError naming the misalignment — never
    served from silently misaligned per-block scales."""
    import json

    from repro.core import dist_search as ds
    from repro.index import quantized as _q
    from repro.index import sharded
    from repro.index import store

    rng = np.random.default_rng(9)
    db = rng.normal(size=(2 * _q.RESID_BLOCK, 32)).astype(np.float32)
    tix = _build_tiered(db, (4,), 6, "int8")
    mesh = ds.make_data_mesh(1)
    dti = ds.distributed_tiered_index(tix, mesh)
    p = tmp_path / "tier"
    ds.store_sharded_tiered(dti, p)

    # Forge a second shard dir by copying the first and lying about its
    # row offset: offsets now overlap instead of tiling [0, size).
    import shutil
    shutil.copytree(p / "shard_00000", p / "shard_00001")
    for d in (p / "shard_00001",):
        smf = store.read_manifest(d)
        smf["row_offset"] = 64          # not 256: overlaps shard 0
        (d / store.MANIFEST).write_text(json.dumps(smf))
    mf = json.loads((p / store.MANIFEST).read_text())
    mf["shards"] = 2
    (p / store.MANIFEST).write_text(json.dumps(mf))

    with pytest.raises(IOError, match="do not tile|mis-sharded"):
        sharded.load_tier_shards(p)
    with pytest.raises(IOError, match="do not tile|mis-sharded"):
        sharded.load_sharded_quantized(p)
