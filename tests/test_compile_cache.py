"""`repro.compile_cache.enable`: where the persistent compilation cache goes.

With JAX_COMPILATION_CACHE_DIR set the cache lives there; unset, it lives
at the fixed in-checkout path. Every test restores JAX's cache config.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_cache_config():
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_env_dir_stays_in_force(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_unset_env_uses_fixed_checkout_path(monkeypatch,
                                            restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
    # fixed: a second call (a later run) resolves to the same directory
    assert compile_cache.enable() == path


def test_compiled_program_lands_in_env_dir(monkeypatch, tmp_path,
                                           restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    compilation_cache.reset_cache()
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.cos(x) * 3.0 + 1.0)(jnp.arange(7.0)
                                              ).block_until_ready()
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir()), \
        sorted(p.name for p in tmp_path.iterdir())


def test_second_compile_hits_the_cache(monkeypatch, tmp_path,
                                       restore_cache_config):
    """A later run (in-memory caches dropped) reads the program back from
    the directory; `counting` sees the miss, then the hit."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    compilation_cache.reset_cache()
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    f = lambda x: jnp.sin(x) * 5.0 - 2.0
    x = jnp.arange(9.0)
    with compile_cache.counting() as first:
        jax.jit(f)(x).block_until_ready()
    jax.clear_caches()
    with compile_cache.counting() as second:
        jax.jit(f)(x).block_until_ready()
    assert first == {"hits": 0, "misses": 1}, first
    assert second == {"hits": 1, "misses": 0}, second
