"""JAX's persistent compilation cache for the command-line entry points.

Called once from ``main()`` of ``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train`` and ``benchmarks/run.py``, before anything compiles;
never at import and never from tests.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the cache goes
  there; nothing here sets another directory.
* unset: the cache goes to ``<checkout>/.jax_cache`` — one fixed path, so a
  later run in the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_ENV_VAR", "enable_compile_cache", "cache_entries"]

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV_VAR)
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of entries in the cache directory (0 if it does not exist)."""
    return len(os.listdir(path)) if os.path.isdir(path) else 0
