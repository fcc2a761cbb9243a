"""Where JAX's persistent compilation cache lives.

Entry points that compile (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` before their first
compile.  Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The fixed default location: ``<repo root>/.jax_cache`` (git-ignored).
#: A cache is found again only at the same path, so it never depends on a
#: temp name, a pid or the time.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone: nothing is set in code.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
