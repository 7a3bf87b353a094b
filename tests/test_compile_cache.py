"""The persistent compilation cache helper (runtime/compile_cache.py)."""
import os

import jax

from repro.runtime import compile_cache


def test_cache_dir_is_fixed_under_checkout():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.CHECKOUT_CACHE == os.path.join(root, ".jax_cache")


def test_enable_leaves_cpu_runs_alone():
    """XLA:CPU entries are host-specific: on the CPU backend `enable`
    changes nothing."""
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() is None
    assert jax.config.jax_compilation_cache_dir == before
