"""Runtime tests: HLO collective parser (incl. while-trip multiplication),
roofline terms, jaxpr cost walker, sharding rules, fault tolerance."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import roofline as rl
from repro.runtime.fault_tolerance import PreemptionHandler, StepWatchdog
from repro.runtime.hlo import parse_collectives
from repro.runtime.jaxpr_cost import jaxpr_cost
from repro.runtime.sharding import Parallelism, spec_for

_HLO = """
HloModule test

%body.1 (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), replica_groups={}
}

%cond.1 (p: (s32[], f32[128])) -> pred[] {
  %c = pred[] compare(s32[] %i, s32[] %n), direction=LT
}

ENTRY %main (a: f32[1024]) -> f32[1024] {
  %ag = f32[1024]{0} all-gather(f32[256]{0} %a), dimensions={0}
  %w = (s32[], f32[128]) while(%t), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"10"}}
  %rs = f32[64]{0} reduce-scatter(f32[1024]{0} %b), dimensions={0}
}
"""


def test_parse_collectives_with_trip_counts():
    st = parse_collectives(_HLO)
    # all-gather: 1024 f32 = 4096 B; all-reduce in 10-trip body: 128 f32
    # = 512 B × 2 (ring) × 10; reduce-scatter result 64 f32 = 256 B.
    assert st.bytes_by_kind["all-gather"] == 4096
    assert st.bytes_by_kind["all-reduce"] == 512 * 2 * 10
    assert st.bytes_by_kind["reduce-scatter"] == 256
    assert st.counts_by_kind["all-reduce"] == 10


def test_roofline_terms_and_dominance():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    t = rl.terms_from_analysis(cost, collective_bytes=50e9 * 3, chips=4,
                               model_flops=4 * 197e12 * 0.5)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 2.0) < 1e-9
    assert abs(t.collective_s - 3.0) < 1e-9
    assert t.dominant == "collective"
    assert abs(t.roofline_fraction - 0.5 / 3.0) < 1e-9
    assert abs(t.useful_ratio - 0.5) < 1e-9


def test_chip_peaks_table_and_unknown_kind():
    v5e = rl.chip_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.ici_bw) == (197e12, 819e9, 50e9)
    # Off the chip the target's peaks price the model; an unknown TPU
    # kind is an error, never a default.
    assert rl.local_peaks() == rl.CHIP_PEAKS[rl.TARGET_KIND]
    with pytest.raises(ValueError, match="TPU v9 mega"):
        rl.chip_peaks("TPU v9 mega")


def test_compile_cache_dir(monkeypatch):
    import pathlib

    from repro.runtime import compile_cache as cc

    was = jax.config.jax_compilation_cache_dir
    try:
        # The environment variable stands as JAX reads it: nothing is set.
        monkeypatch.setenv(cc.ENV_VAR, "/elsewhere/cache")
        assert cc.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
        # Without it, the fixed <checkout>/.jax_cache.
        monkeypatch.delenv(cc.ENV_VAR)
        got = pathlib.Path(cc.use_compile_cache())
        assert got.name == ".jax_cache"
        assert (got.parent / "pyproject.toml").exists()
        assert jax.config.jax_compilation_cache_dir == str(got)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_jaxpr_cost_matmul_exact():
    M, K, N = 128, 64, 32
    c = jaxpr_cost(lambda a, b: a @ b,
                   jax.ShapeDtypeStruct((M, K), jnp.float32),
                   jax.ShapeDtypeStruct((K, N), jnp.float32))
    assert c.flops == 2 * M * K * N


def test_jaxpr_cost_scan_multiplies():
    M, K = 64, 64

    def scanned(a, ws):
        out, _ = jax.lax.scan(lambda c, w: (c @ w, None), a, ws)
        return out
    c = jaxpr_cost(scanned, jax.ShapeDtypeStruct((M, K), jnp.float32),
                   jax.ShapeDtypeStruct((16, K, K), jnp.float32))
    assert c.flops == 16 * 2 * M * K * K


def test_jaxpr_cost_matches_xla_on_unrolled_smoke():
    """Walker vs XLA cost_analysis on a small single-device train step
    (unrolled for XLA, scanned for the walker — must agree within 15%
    on a dense arch)."""
    import dataclasses
    import functools
    from repro import configs
    from repro.models.transformer import init_params
    from repro.runtime.sharding import single_device
    from repro.training.optimizer import AdamWConfig, init_state
    from repro.training.step import make_train_step
    par = single_device()
    cfg = dataclasses.replace(configs.smoke("granite-3-2b"), remat="none")
    cfgu = dataclasses.replace(cfg, unroll_scans=True, attn_kv_chunk=8192)
    ocfg = AdamWConfig()
    ps = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
    os_ = jax.eval_shape(functools.partial(init_state, ocfg), ps)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32)}
    cw = jaxpr_cost(make_train_step(cfg, par, ocfg), ps, os_, batch)
    comp = jax.jit(make_train_step(cfgu, par, ocfg)).lower(
        ps, os_, batch).compile().cost_analysis()
    if isinstance(comp, (list, tuple)):
        comp = comp[0]
    assert abs(cw.flops - comp["flops"]) / comp["flops"] < 0.15


def test_sharding_rules():
    par = Parallelism(mesh=None, data_axes=("data",), model_axis="model",
                      fsdp_axis="data")
    # stacked leaves carry a leading layer dim
    s = spec_for("layers/attn/wq", (4, 64, 128), par)
    assert tuple(s) == (None, "data", "model")
    s = spec_for("embed/table", (1024, 64), par)
    assert tuple(s) == ("model", "data")
    s = spec_for("layers/moe_ep/w_gate", (2, 8, 64, 128), par)
    assert tuple(s) == (None, "model", "data", None)
    s = spec_for("final_norm/scale", (64,), par)
    assert tuple(s) == (None,)


def test_sharding_rules_divisibility_guard():
    mesh = jax.make_mesh((1,), ("model",))

    class FakePar(Parallelism):
        pass
    par = Parallelism(mesh=mesh, data_axes=(), model_axis="model",
                      fsdp_axis=None)
    # vocab 49155 % 1 == 0 → sharding kept even on this trivial mesh
    s = spec_for("embed/table", (49155, 64), par)
    assert tuple(s)[0] == "model"


def test_watchdog_flags_stragglers():
    events = []
    wd = StepWatchdog(slow_factor=5.0, on_slow=events.append,
                      min_samples=3)
    for i in range(6):
        wd.start(i)
        time.sleep(0.01)
        wd.stop()
    wd.start(6)
    time.sleep(0.2)
    wd.stop()
    assert len(events) == 1 and events[0].step == 6


def test_preemption_handler():
    import os
    import signal
    with PreemptionHandler() as p:
        assert not p.preempted
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)
        assert p.preempted
