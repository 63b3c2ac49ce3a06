"""Placement of the persistent compilation cache (``repro.utils.compile_cache``)."""
from pathlib import Path

import jax
import pytest
from jax._src import compilation_cache

from repro.utils.compile_cache import ENV_VAR, checkout_root, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", prev)
    # JAX opens its cache once per process: drop one these tests opened so
    # later tests in this worker do not write into the checkout
    compilation_cache.reset_cache()


def test_env_var_is_honoured_and_nothing_set_in_code(cache_config, tmp_path):
    cache_config.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_gitignored_path_in_the_checkout(cache_config):
    cache_config.delenv(ENV_VAR, raising=False)
    first, second = enable_compile_cache(), enable_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_checkout_root_is_the_directory_with_pyproject(tmp_path):
    assert checkout_root() == REPO
    pkg = tmp_path / "site-packages" / "repro" / "utils" / "compile_cache.py"
    with pytest.raises(RuntimeError, match="no checkout"):
        checkout_root(pkg)
