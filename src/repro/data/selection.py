"""GreedyML-backed training-data selection — the paper's technique as a
first-class pipeline feature (DESIGN §2).

Given per-document embeddings (from the model's own encoder, a proxy
embedder, or precomputed), select a maximally-diverse coreset with
facility-location (or exemplars with k-medoid) via:

  * the **distributed** tree (core.greedyml.LevelDispatcher) when a mesh
    is available — embeddings stay sharded across the data axis exactly as
    training shards documents; the accumulation tree reuses the mesh axes,
    and RandGreedi gathers over all of them in one level;
  * the **simulator** (core.simulate) on a single device;
  * the **streaming engine** (repro.streaming) for ``stream:*`` specs —
    documents arrive in batches through a sieve instead of running an
    offline k-pass greedy over the materialized pool: one pass over the
    stream, O(levels·k) solution slots plus O(levels·N_eval) state over
    the fixed evaluation set (pass a subsampled ground to bound it
    independently of the stream length; DESIGN §Streaming).

``spec`` strings: 'greedyml:facility', 'randgreedi:kmedoid',
'stream:facility', 'stream:kcover', 'none', …
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.functions import make_objective
from repro.core.greedy import greedy
from repro.core.greedyml import LevelDispatcher
from repro.core.simulate import run_tree_dense, run_greedy_dense
from repro.core.tree import AccumulationTree, randgreedi_tree
from repro.launch.mesh import factor_tree_axes, make_tree_mesh


def parse_spec(spec: str) -> Tuple[str, str]:
    if spec in ("none", ""):
        return "none", ""
    algo, _, obj = spec.partition(":")
    return algo, obj or "facility"


def embed_documents(tokens: np.ndarray, dim: int = 256, seed: int = 0
                    ) -> np.ndarray:
    """Cheap deterministic doc embeddings: hashed bag-of-tokens projection
    (a stand-in for model forward features; unit-normalized)."""
    rng = np.random.default_rng(seed)
    vocab_proj = rng.normal(0, 1.0 / np.sqrt(dim),
                            (int(tokens.max()) + 1, dim)).astype(np.float32)
    emb = vocab_proj[tokens.reshape(-1)].reshape(*tokens.shape, dim)
    emb = emb.mean(axis=1)
    emb /= np.maximum(np.linalg.norm(emb, axis=-1, keepdims=True), 1e-9)
    return emb.astype(np.float32)


def select_coreset(embeddings: np.ndarray, k: int, spec: str = "greedyml:facility",
                   mesh: Optional[Mesh] = None,
                   tree_axes: Optional[Sequence[str]] = None,
                   machines: int = 8, branching: int = 2,
                   seed: int = 0, stream_batch: int = 0,
                   stream_order: str = "shuffled",
                   stream_eval: int = 0) -> np.ndarray:
    """Returns selected document indices (≤ k)."""
    from repro.runtime import flags

    algo, obj_name = parse_spec(spec)
    n = embeddings.shape[0]
    if algo == "none":
        return np.arange(n)
    if algo == "stream":
        from repro.data.synthetic import Stream
        from repro.streaming import stream_select
        if obj_name in ("kcover", "kdom", "coverage"):
            raise ValueError("stream:* coreset selection operates on "
                             "embeddings; use launch/stream.py for "
                             "coverage streams")
        rng = np.random.default_rng(seed + 101)
        stream = Stream(np.asarray(embeddings, np.float32),
                        rng.permutation(n) if stream_order == "shuffled"
                        else np.arange(n),
                        stream_batch or flags.stream_batch())
        obj = make_objective(obj_name)
        # evaluation ground: the pool, or a fixed subsample so sieve state
        # stays O(levels·stream_eval) regardless of how long the stream is
        ground = np.asarray(embeddings, np.float32)
        if 0 < stream_eval < n:
            ground = ground[rng.choice(n, stream_eval, replace=False)]
        sol = stream_select(obj, stream, k, ground=jnp.asarray(ground))
        return np.asarray(sol.ids)[np.asarray(sol.valid)]
    if mesh is not None:
        axes = tuple(tree_axes or factor_tree_axes(mesh, mesh.axis_names))
        obj = make_objective(obj_name)
        ids = jnp.arange(n, dtype=jnp.int32)
        pay = jnp.asarray(embeddings)
        valid = jnp.ones((n,), bool)
        radices = tuple(mesh.shape[a] for a in axes)
        if algo == "greedyml":
            sol = LevelDispatcher(obj, k, radices, mesh=mesh,
                                  tree_axes=axes).run(ids, pay, valid)
        elif algo == "randgreedi":
            m = math.prod(radices)
            sol = LevelDispatcher(obj, k, (m,), mesh=make_tree_mesh((m,))
                                  ).run(ids, pay, valid)
        elif algo == "greedy":
            sol = greedy(obj, ids, pay, valid, k)
        else:
            raise KeyError(algo)
        return np.asarray(sol.ids)[np.asarray(sol.valid)]
    # single-device simulation path
    if algo == "greedy":
        return run_greedy_dense(obj_name, embeddings, k).ids
    tree = (randgreedi_tree(machines) if algo == "randgreedi"
            else AccumulationTree(machines, branching))
    return run_tree_dense(obj_name, embeddings, k, tree, seed=seed).ids
