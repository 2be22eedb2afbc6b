"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``repro.launch.serve_snn``,
``benchmarks.kernel_bench``) call :func:`enable_compile_cache` once,
before their first compile; nothing calls it at import. The cache key
includes the directory, so the default is one fixed path per checkout —
never a temporary name, a pid or a time — and a second run of the same
checkout finds what the first one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "default_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    return str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and this sets no other path. Otherwise the cache goes to
    :func:`default_cache_dir`.
    """
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
