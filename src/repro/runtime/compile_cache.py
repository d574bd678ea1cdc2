"""JAX's persistent compilation cache, kept at one fixed place.

`enable()` is the first call of every entry point (`chip_smoke.py`,
`benchmarks/run.py`, the examples).  Where `JAX_COMPILATION_CACHE_DIR`
is set, JAX already keeps its cache there and nothing is changed.
Otherwise the cache goes to `<checkout>/.jax_cache` (git-ignored): a
fixed path, because the cache directory is part of what a later run
must find again, and a temporary or per-process directory never hits.
The tests do not call it.
"""

from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
