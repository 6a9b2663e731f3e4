"""JAX's persistent compilation cache, one rule for every entry point.

``serve``, ``prefill_serve``, the trainer, ``bench.py``, ``chip_smoke.py``
and the tests all call :func:`enable_compile_cache` before their first
compile.  The directory is part of the cache key, so it must never move:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing is set in code;
- otherwise a fixed, git-ignored directory inside the checkout — never
  ``/tmp``, a pid or a time.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def cache_dir() -> str:
    """Where the cache lives under the rule above (jax-free: the
    ``chip_smoke.py`` parent counts entries here without importing jax)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return cache_dir()
