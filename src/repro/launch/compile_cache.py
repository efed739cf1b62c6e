"""Where the entry points keep JAX's persistent compilation cache.

A cold process compiles every kernel and jitted step; the persistent
cache lets the next process on the same machine load them instead.  Its
path is part of the cache key, so it must not move between runs: the
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that
variable itself), else at the fixed ``<checkout>/.jax_cache``.

Entry points (``chip_smoke.py``, ``benchmarks.common.BenchRunner``, the
examples) call ``enable_compile_cache`` once, before their first
compile; the library never sets it on import.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; -> the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]      # JAX's own reading of it decides
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
