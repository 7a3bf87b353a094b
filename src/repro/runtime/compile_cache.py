"""JAX's persistent compilation cache, kept at one fixed place.

Entry points call `enable()` before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
directory is left as it is; otherwise the cache goes to ``.jax_cache/`` at
the root of the checkout. The directory is part of what a cached entry is
found under, so it never takes a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> Optional[str]:
    """Turn the persistent cache on for every compile, however short, and
    return the directory it uses. On the CPU backend nothing is changed
    (None): XLA:CPU entries are tied to the host's instruction set, and
    loading them on another host only warns and recompiles."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
