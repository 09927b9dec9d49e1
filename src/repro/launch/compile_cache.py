"""JAX's persistent compilation cache for the command-line entry points.

One helper, called by the scripts a user runs (chip_smoke.py,
benchmarks/run.py, benchmarks/availability_sweep.py) before their first
compile — never by a library module at import.  A directory named in
$JAX_COMPILATION_CACHE_DIR wins and JAX reads it itself; otherwise the
cache lives at ``<checkout>/.jax-compilation-cache``, the path CI
restores.  The path is part of the cache key, so it stays fixed.
"""
from __future__ import annotations

import os

CACHE_DIRNAME = ".jax-compilation-cache"


def enable_compile_cache(checkout: str) -> str:
    """Turn the persistent compilation cache on and return its directory:
    $JAX_COMPILATION_CACHE_DIR when set (no other directory is set in
    code), else ``<checkout>/.jax-compilation-cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(os.path.abspath(checkout), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
