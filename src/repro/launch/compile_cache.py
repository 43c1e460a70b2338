"""JAX's persistent compilation cache, placed from outside or at a fixed path.

A cold chip run can spend much of its time compiling; the persistent cache
lets the next process with the same programs load them instead.  Its
location is part of what makes entries hit, so it is never built from a
temporary name, a pid or the time:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory itself
  and this code sets no other;
* otherwise the cache lives at ``<checkout>/.jax_cache``, resolved from this
  package's location.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
