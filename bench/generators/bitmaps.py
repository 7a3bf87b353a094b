"""Transaction sets as packed uint32 bitmaps, for maximum k-cover.

Configuration keys: `universe` (items), `mean_length` and `max_length`
(distinct items per set), `pareto` (the shape of the lengths' power law)
and `zipf` (the exponent of item popularity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.data import key, seed_words

def _lengths(scale: float, n: int, longest: int, pareto: float):
    p = (np.arange(n) + 0.5) / n
    lomax = (1.0 - p) ** (-1.0 / pareto) - 1.0
    return np.minimum(np.floor(scale * lomax) + 1, longest).astype(np.int64)


def set_sizes(n: int, mean: float, longest: int,
              pareto: float) -> np.ndarray:
    """Set lengths: n evenly spaced quantiles of 1 + floor(s * Lomax(pareto))
    capped at `longest`, with the scale s chosen so that their mean is
    `mean` (to within 1/n). Fixed for every seed; a seed only deals them
    out to the sets."""
    lo, hi = 0.0, float(longest)
    for _ in range(100):
        mid = (lo + hi) / 2
        if _lengths(mid, n, longest, pareto).mean() < mean:
            lo = mid
        else:
            hi = mid
    return _lengths(hi, n, longest, pareto)


def words_of(universe: int) -> int:
    return (universe + 31) // 32


def _distinct(owner, item, longest: int, universe: int):
    """Items of each set made distinct, with the set's draws sorted: the
    i-th item becomes max(its draw, the (i-1)-th item + 1), so a repeat
    moves to the next free rank. One cumulative max over the sorted draws
    does it, since sets are contiguous there. Draws are capped at
    universe - longest, so no item passes the universe."""
    m = universe + longest
    sk = jnp.sort(owner * m + jnp.minimum(item, universe - longest))
    own, it = sk // m, sk % m
    idx = jnp.arange(sk.shape[0])
    start = jnp.concatenate([jnp.ones((1,), bool), own[1:] != own[:-1]])
    pos = idx - jax.lax.cummax(jnp.where(start, idx, 0))
    top = jax.lax.cummax(own * m + it - pos + longest)
    return own, top - own * m - longest + pos


@functools.partial(jax.jit,
                   static_argnames=("pools", "n", "universe", "mean",
                                    "longest", "pareto", "zipf"))
def draw(words, *, pools: int, n: int, universe: int, mean: float,
         longest: int, pareto: float, zipf: float):
    """`pools` pools of n sets over `universe` items, (n, words_of(universe))
    each. Lengths come from `set_sizes`, dealt out by the seed; items are
    drawn with P(rank r) ~ (r+1)^-zipf, and a repeat within a set moves to
    the next free rank (`_distinct`), so each set holds exactly its
    length."""
    sizes_np = set_sizes(n, mean, longest, pareto)
    total = int(sizes_np.sum())
    w = words_of(universe)
    assert n * (universe + longest) < 2 ** 31, "keys must fit int32"
    sizes = jnp.asarray(sizes_np, jnp.int32)
    ranks = jnp.arange(1, universe + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -zipf)
    cdf = cdf / cdf[-1]
    k = key(words, 1)
    out = []
    for p in range(pools):
        k_p, k_u = jax.random.split(jax.random.fold_in(k, p))
        owner = jnp.repeat(jnp.arange(n, dtype=jnp.int32),
                           sizes[jax.random.permutation(k_p, n)],
                           total_repeat_length=total)
        u = jax.random.uniform(k_u, (total,), jnp.float32)
        item = jnp.minimum(jnp.searchsorted(cdf, u), universe - 1)
        own, it = _distinct(owner, item.astype(jnp.int32), longest,
                            universe)
        bit = jnp.left_shift(jnp.uint32(1), (it % 32).astype(jnp.uint32))
        flat = jnp.zeros((n * w,), jnp.uint32).at[own * w + it // 32].add(
            bit)
        out.append(flat.reshape(n, w))
    return tuple(out)


def pools(cfg: dict, n: int, count: int, seed: int):
    return draw(seed_words(seed), pools=count, n=n,
                universe=int(cfg["universe"]),
                mean=float(cfg["mean_length"]),
                longest=int(cfg["max_length"]),
                pareto=float(cfg["pareto"]), zipf=float(cfg["zipf"]))


def rows(cfg: dict, n: int) -> int:
    """Ground rows of coverage's matrix: the universe's words."""
    return words_of(int(cfg["universe"]))
