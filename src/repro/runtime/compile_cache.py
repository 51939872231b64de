"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` before their first compile:
``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m benchmarks.run``.  A directory named by the environment
variable ``JAX_COMPILATION_CACHE_DIR`` stands as JAX reads it; without
it the cache lives at the fixed ``<checkout>/.jax_cache`` (listed in
``.gitignore``).  A fixed path matters: the path is part of the cache
key, so a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` — this file is src/repro/runtime/ in a checkout.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
