"""Puts the benchmark's modules (`bench/`), the program (`src/`) and the
repo root (for `benchmarks`) on the import path of the benchmark's tests."""
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src", BENCH.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"


@pytest.fixture(autouse=True)
def bench_cache_in_tmp(tmp_path, monkeypatch):
    """A run in a test keeps its compile cache and traces under the test's
    own temporary directory, not in the checkout, and JAX's cache settings
    are put back afterwards for the other tests of the process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import run

    monkeypatch.setattr(run, "CACHE", tmp_path / "bench_cache")
    saved_env = os.environ.get(CACHE_VAR)
    saved_dir = jax.config.jax_compilation_cache_dir
    yield
    if saved_env is None:
        os.environ.pop(CACHE_VAR, None)
    else:
        os.environ[CACHE_VAR] = saved_env
    jax.config.update("jax_compilation_cache_dir", saved_dir)
    compilation_cache.reset_cache()
