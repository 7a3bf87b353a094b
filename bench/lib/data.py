"""What every instance generator shares: the run's seed as key material.

A generator is `bench/generators/<name>.py`, named by the configuration's
`generator` key. It gives

    pools(cfg, n, count, seed) -> tuple of `count` device arrays of n rows
    rows(cfg, n) -> ground rows of the leaf greedy, unpadded

and draws each instance on the device in one jitted call. It takes the
seed as two traced uint32 words (`seed_words`), so one compiled program
serves every seed (and the persistent cache finds it), and a seed wider
than 32 bits keeps its high word. Shapes depend on the configuration
alone: every seed gives the same sizes, so seeds change the data and not
the work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_words(seed: int):
    """The seed as (low, high) uint32 words."""
    s = int(seed) % 2 ** 64
    return jnp.asarray([s & 0xFFFFFFFF, s >> 32], jnp.uint32)


def key(words, stream: int):
    """A PRNG key for one stream of draws from the seed's words."""
    k = jax.random.PRNGKey(stream)
    return jax.random.fold_in(jax.random.fold_in(k, words[0]), words[1])
