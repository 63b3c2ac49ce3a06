"""Placement of JAX's persistent compilation cache.

The cache directory is part of each entry's lookup, so it must not move
between runs: a temp, pid- or time-based path would never hit.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache goes to one fixed directory inside the
checkout (``.jax_cache/`` next to ``pyproject.toml``, gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def checkout_root(start: Path = Path(__file__).resolve()) -> Path:
    """The source checkout holding this package: the nearest ancestor with
    a ``pyproject.toml``.  Raises when there is none (an installed copy),
    rather than placing the cache somewhere outside any checkout."""
    for d in start.parents:
        if (d / "pyproject.toml").is_file():
            return d
    raise RuntimeError(
        f"no checkout (pyproject.toml) above {start}; set {ENV_VAR} to place the cache"
    )


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the first compile.  Entry points (``chip_smoke.py``,
    ``repro.launch.train``, ``repro.launch.serve``) call it once at start.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    cache = checkout_root() / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    return str(cache)
