"""The persistent compilation-cache rule of the entry points
(``repro.compile_cache``): JAX's own ``JAX_COMPILATION_CACHE_DIR`` wins
untouched; otherwise a fixed ``.jax_cache/`` at the checkout root."""
import os

import pytest

from repro import compile_cache

jax = pytest.importorskip("jax")


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_default_is_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(compile_cache.__file__))))
    assert path == os.path.join(root, ".jax_cache")
    assert compile_cache.use_compile_cache() == path        # stable


def test_import_leaves_jax_config_alone():
    """Importing the package must not touch JAX's configuration: only
    entry points apply the cache rule."""
    import importlib

    before = jax.config.jax_compilation_cache_dir
    importlib.reload(compile_cache)
    import repro.core.backends.pallas  # noqa: F401
    assert jax.config.jax_compilation_cache_dir == before
