"""The main-path Pallas kernels, and the tiered screen's sort-free
selection, compile for a TPU v5e at serving widths.

Nothing runs: each test lowers a kernel with ``interpret=False`` against a
described (not attached) ``v5e:2x2`` topology and compiles it with the TPU
compiler, at n=256, B=2^20 and a 32-query batch, with the block shapes the
kernels' own chooser picks.  This is what interpret mode cannot check:
block shapes Mosaic refuses, layouts it cannot lower, and more VMEM than a
kernel may use.  The topology is described inside a fixture, never while a
module is imported, and the persistent compilation cache is off around the
compiles (a described chip's entries cannot be read back).
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine
from repro.index import quantized as qz
from repro.kernels import fused_query as fq
from repro.kernels import ops

B, N, Q, LEVELS, ALPHABET, K = 1 << 20, 256, 32, (8, 16), 10, 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _query_side(s):
    return (_spec(s, (Q, N)),
            tuple(_spec(s, (Q, ALPHABET, lv)) for lv in LEVELS),
            tuple(_spec(s, (Q,)) for _ in LEVELS),
            _spec(s, (Q,)))


def _full_precision_db(s):
    return (_spec(s, (B, N)), _spec(s, (B,)),
            tuple(_spec(s, (B, lv), jnp.int32) for lv in LEVELS),
            tuple(_spec(s, (B,)) for _ in LEVELS))


def _quantized_db(s, mode):
    int8 = mode == "int8"
    codes = jnp.int8 if int8 else jnp.bfloat16
    nbs = B // qz.RESID_BLOCK
    return engine.QuantizedDeviceIndex(
        series=_spec(s, (B, N), codes),
        series_scale=_spec(s, (B, 1)) if int8 else None,
        series_zero=_spec(s, (B, 1)) if int8 else None,
        series_err=_spec(s, (B,)), norms_sq=_spec(s, (B,)),
        words=tuple(_spec(s, (B, lv), jnp.int8) for lv in LEVELS),
        residuals=tuple(_spec(s, (B,), codes) for _ in LEVELS),
        resid_scale=tuple(_spec(s, (nbs, 1)) if int8 else None
                          for _ in LEVELS),
        resid_zero=tuple(_spec(s, (nbs, 1)) if int8 else None
                         for _ in LEVELS),
        resid_err=tuple(_spec(s, (nbs, 1)) for _ in LEVELS),
        levels=LEVELS, alphabet=ALPHABET, mode=mode)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_fused_range_compiles(one_chip):
    bq, bb = ops.choose_fused_blocks(Q, B, N, LEVELS, ALPHABET)
    _compile(lambda *a: fq.fused_range_pallas(
        *a, levels=LEVELS, alphabet=ALPHABET, n=N, block_q=bq, block_b=bb,
        interpret=False), *_full_precision_db(one_chip),
        *_query_side(one_chip))


def test_fused_topk_compiles(one_chip):
    bq, bb = ops.choose_fused_blocks(Q, B, N, LEVELS, ALPHABET, k=K)
    _compile(lambda *a: fq.fused_topk_pallas(
        *a, levels=LEVELS, alphabet=ALPHABET, n=N, k=K, block_q=bq,
        block_b=bb, interpret=False), *_full_precision_db(one_chip),
        *_query_side(one_chip))


@pytest.mark.parametrize("mode", ["int8", "bf16"])
def test_fused_quant_range_compiles(one_chip, mode):
    qdev = _quantized_db(one_chip, mode)
    bq, bb = engine._fused_blocks_quant(qdev, Q)
    _compile(lambda d, *a: fq.fused_quant_range_pallas(
        d, *a, block_q=bq, block_b=bb, interpret=False),
        qdev, *_query_side(one_chip))


def _sorts(compiled) -> list:
    return re.findall(r"\bsort\(", compiled.as_text())


@pytest.mark.parametrize("part", ["count", "compact", "kth"])
def test_tiered_selection_compiles_without_sort(one_chip, part):
    """The tiered screen's selection epilogue — survivor count, compaction
    at a capacity, k-th smallest screen bound — compiles for the chip at
    a (Q, B) mask with no sort over the database axis in it."""
    if part == "count":
        fn, args = engine._survivor_prefix, (_spec(one_chip, (Q, B),
                                                   jnp.bool_),)
    elif part == "compact":
        fn = functools.partial(engine._compact_counted, capacity=1024)
        args = (_spec(one_chip, (Q, B // 32), jnp.uint32),
                _spec(one_chip, (Q, B // 32), jnp.int32))
    else:
        fn = functools.partial(engine._kth_smallest_bisect, k=K)
        args = (_spec(one_chip, (Q, B)),)
    assert not _sorts(jax.jit(fn).lower(*args).compile())


def test_top_k_compaction_compiles_to_a_sort(one_chip):
    """The check above can see a sort: the ``lax.top_k`` form it replaced
    compiles to one."""
    def top_k_compaction(keep):
        keys = jnp.where(keep, B - jnp.arange(B, dtype=jnp.int32), 0)
        return jax.lax.top_k(keys, 1024)

    assert _sorts(jax.jit(top_k_compaction).lower(
        _spec(one_chip, (Q, B), jnp.bool_)).compile())
