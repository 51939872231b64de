"""The benchmark's own tests, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They sit outside the repository's ``testpaths``."""
import os
import pathlib
import sys
import tempfile

# CPU compiles go to a throwaway cache, not the checkout's chip cache.
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="bench_tests_cache_")

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
