"""Collections and query pools, made from the run's seed.

Rows are z-normalised random walks, the synthetic series of the Hydra
study (Echihabi et al., PVLDB 12(2), 2018).  They are made on the device
in one jitted call, block by block, so the peak is the output plus one
block.  Queries are database rows plus Gaussian noise whose σ cycles over
the traffic mix's list, so pruning runs from easy to hard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GEN_BLOCK = 1 << 15        # rows per generated block


def substream(seed: int, name: str) -> np.random.Generator:
    """An independent host generator for one use of the run's seed.  Any
    whole number works, far beyond 32 bits."""
    tag = int.from_bytes(name.encode(), "little")
    return np.random.default_rng([tag, abs(int(seed)), int(seed < 0)])


def device_key(seed: int, name: str) -> jax.Array:
    return jax.random.key(int(substream(seed, name).integers(0, 2**31 - 1)))


def znorm(x: jnp.ndarray) -> jnp.ndarray:
    x = x - x.mean(axis=-1, keepdims=True)
    return x / jnp.maximum(x.std(axis=-1, keepdims=True), 1e-8)


@functools.partial(jax.jit, static_argnames=("rows", "n", "block"))
def _walks(key, rows: int, n: int, block: int):
    def one(k):
        return znorm(jnp.cumsum(jax.random.normal(k, (block, n),
                                                  jnp.float32), axis=1))

    keys = jax.random.split(key, rows // block)
    return jax.lax.map(one, keys).reshape(rows, n)


def random_walks(seed: int, rows: int, n: int) -> jax.Array:
    """(rows, n) float32 z-normalised random walks, on the default device."""
    block = min(GEN_BLOCK, rows)
    if rows % block:
        raise ValueError(f"rows={rows} is not a multiple of {block}")
    return _walks(device_key(seed, "walks"), rows, n, block)


def make_pool(db: jax.Array, seed: int, size: int, sigmas) -> tuple:
    """``size`` queries: database row ``rows[i]`` plus N(0, σᵢ²) noise,
    σᵢ = ``sigmas[i % len(sigmas)]``.  Returns host float32 queries
    (size, n) and the σ of each."""
    rng = substream(seed, "pool")
    rows = rng.integers(0, db.shape[0], size)
    sigma = np.resize(np.asarray(sigmas, np.float32), size)
    noise = rng.standard_normal((size, db.shape[1]), dtype=np.float32)
    base = np.asarray(jax.device_get(db[jnp.asarray(rows)]))
    return base + sigma[:, None] * noise, sigma
