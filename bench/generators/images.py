"""Image-like pools for exemplar clustering: a mixture of Gaussian classes,
each point mean-subtracted over its features and L2-normalised, as the
GreedyML paper preprocesses Tiny ImageNet.

Configuration keys: `d`, `classes`, `noise`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.lib.data import key, seed_words


@functools.partial(jax.jit,
                   static_argnames=("pools", "n", "d", "classes", "noise"))
def draw(words, *, pools: int, n: int, d: int, classes: int, noise: float):
    """`pools` pools of n points in d dimensions (class centres N(0, 1),
    within-class noise `noise`). One set of class centres per seed; each
    pool draws its own labels and noise."""
    k = key(words, 0)
    k_c, k = jax.random.split(k)
    centers = jax.random.normal(k_c, (classes, d), jnp.float32)
    out = []
    for p in range(pools):
        k_l, k_x = jax.random.split(jax.random.fold_in(k, p))
        lbl = jax.random.randint(k_l, (n,), 0, classes)
        x = centers[lbl] + noise * jax.random.normal(k_x, (n, d),
                                                     jnp.float32)
        x = x - jnp.mean(x, axis=1, keepdims=True)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True),
                            1e-9)
        out.append(x)
    return tuple(out)


def pools(cfg: dict, n: int, count: int, seed: int):
    return draw(seed_words(seed), pools=count, n=n, d=int(cfg["d"]),
                classes=int(cfg["classes"]), noise=float(cfg["noise"]))


def rows(cfg: dict, n: int) -> int:
    return n
