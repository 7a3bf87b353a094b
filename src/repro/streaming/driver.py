"""Streaming selection drivers (DESIGN §Streaming).

Three entry points over an arrival stream (any iterable of
``(ids, payloads, valid)`` batches — data.synthetic.gen_stream is the
canonical deterministic source):

  * ``stream_select`` — single-device sieve over the whole stream, with
    optional checkpoint/resume through checkpoint.manager (the sieve
    state is one fixed-shape pytree, so a stream can stop and resume
    bit-exactly).
  * ``stream_select_continuous`` — the CONTINUOUS DISTRIBUTED mode on one
    device: each of `lanes` simulated mesh lanes runs a local sieve over
    its shard of every batch (one vmapped stream-filter dispatch), and
    every `merge_every` batches the per-lane summaries are merged through
    the GreedyML accumulation tree (sieve-as-leaf-solver: union the child
    summaries, node-local Greedy, argmax{f(S), f(S_prev)}), then
    select_better'd against the last merged solution — the stream's
    current answer only ever improves between merges.
  * ``stream_select_distributed`` — the same continuous mode on a REAL
    mesh via shard_map: lanes are mesh devices, the merge reuses
    core.greedyml.accumulate_levels (the exact Algorithm 3.1 rounds) with
    the fixed evaluation set threaded in as per-level augmentation.

For k-medoid/facility the sieve summarizes the stream against a FIXED
evaluation ground set (`ground`) — the streaming analogue of the paper's
§6.4 local objective; coverage needs none.
"""
from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.checkpoint import manager
from repro.core.greedy import Solution
from repro.core.greedyml import _broadcast_from_root, accumulate_levels
from repro.streaming.sieve import SieveStreamer

F32 = jnp.float32


def _empty_solution(k: int, payload_example: jax.Array) -> Solution:
    pay = jnp.zeros((k,) + payload_example.shape[1:], payload_example.dtype)
    return Solution(jnp.full((k,), -1, jnp.int32), pay,
                    jnp.zeros((k,), bool), jnp.asarray(-jnp.inf, F32),
                    jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# single-device arrival loop
# ---------------------------------------------------------------------------


def stream_select(objective, stream: Iterable, k: int, *, eps: float = 0.1,
                  ground: Optional[jax.Array] = None,
                  ground_valid: Optional[jax.Array] = None,
                  backend: Optional[str] = None,
                  ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                  resume: bool = False) -> Solution:
    """Run the sieve over the whole stream; returns the best level's
    solution. With ``ckpt_dir`` the sieve state is saved every
    ``ckpt_every`` batches (and at the end); ``resume=True`` restores the
    latest checkpoint and skips the already-consumed prefix of the (same,
    deterministic) stream."""
    streamer = SieveStreamer(objective, k, eps, ground=ground,
                             ground_valid=ground_valid, backend=backend)
    step = jax.jit(streamer.process_batch)
    state, done = None, 0
    if resume and ckpt_dir and manager.latest_step(ckpt_dir) is not None:
        # example built from the streamer alone — consuming a batch here
        # would silently desynchronize one-shot iterator streams
        state, manifest = manager.restore(ckpt_dir, streamer.init())
        done = int(manifest["extra"]["batches"])
    for i, (ids, pay, valid) in enumerate(stream):
        if i < done:
            continue
        ids, pay, valid = (jnp.asarray(ids), jnp.asarray(pay),
                           jnp.asarray(valid))
        if state is None:
            state = streamer.init(pay)
        state = step(state, ids, pay, valid)
        done = i + 1
        if ckpt_dir and ckpt_every and done % ckpt_every == 0:
            manager.save(ckpt_dir, done, state,
                         extra={"batches": done})
    if state is None:
        raise ValueError("empty stream")
    if ckpt_dir:
        manager.save(ckpt_dir, done, state, extra={"batches": done})
    return streamer.solution(state)


# ---------------------------------------------------------------------------
# continuous distributed mode — simulated lanes (vmap) + tree merges
# ---------------------------------------------------------------------------


class ContinuousSelector:
    """Push-driven core of the continuous distributed mode: `lanes`
    vmapped local sieves + periodic GreedyML tree merges, packaged as an
    incremental object so callers that do not own the arrival loop — the
    per-tenant sessions of serving/session.py — can ride the exact same
    machinery. `stream_select_continuous` is now a thin loop over it, so
    the batch/merge semantics cannot drift between the one-shot driver
    and the always-on sessions.

    push(ids, payloads, valid) folds one arrival batch into all lanes
    (one vmapped stream-filter dispatch) and runs a tree merge every
    `merge_every` batches; result() returns the current merged Solution,
    merging any unmerged tail first — monotone between calls, since the
    root is select_better'd against the previous merged answer.
    """

    def __init__(self, objective, k: int, *, lanes: int = 4,
                 branching: int = 0, merge_every: int = 4,
                 eps: float = 0.1,
                 ground: Optional[jax.Array] = None,
                 ground_valid: Optional[jax.Array] = None,
                 backend: Optional[str] = None,
                 node_engine: str = "auto", sample_level: int = 0,
                 seed: Optional[int] = None, supervisor=None):
        self.objective, self.k = objective, k
        self.lanes, self.merge_every = lanes, merge_every
        self.node_engine, self.sample_level = node_engine, sample_level
        self.seed, self.supervisor = seed, supervisor
        self.streamer = SieveStreamer(objective, k, eps, ground=ground,
                                      ground_valid=ground_valid,
                                      backend=backend)
        self._step = jax.jit(jax.vmap(self.streamer.process_batch))
        self._extract = jax.jit(jax.vmap(self.streamer.solution))
        b = branching or lanes
        levels = max(1, round(math.log(lanes, b))) if lanes > 1 else 0
        assert b ** levels == lanes, \
            f"lanes ({lanes}) must be branching^levels (b={b})"
        self.branching, self.levels = b, levels
        self._axes = tuple(f"mrg{i}" for i in range(levels))
        self._radices = [b] * levels
        self._aug = None
        if ground is not None and levels:
            self._aug = jnp.broadcast_to(
                self.streamer.ground[None],
                (levels,) + self.streamer.ground.shape)
        self.states, self.merged, self._base = None, None, None
        self.merges, self.batches = [], 0
        self._dirty = False

    def _merge_round(self, states, merged):
        lane_sols = self._extract(states)

        def fn(sol):
            return accumulate_levels(self.objective, sol, self.k,
                                     self._axes, self._radices,
                                     aug_levels=self._aug,
                                     sample_level=self.sample_level,
                                     node_engine=self.node_engine,
                                     carry_prev=merged, seed=self.seed)

        f = fn
        for ax in self._axes:   # innermost level = innermost vmap
            f = jax.vmap(f, axis_name=ax)
        # lane index: level-0 digit is the LOW digit, so the row-major
        # reshape (fastest-varying last axis) matches the tree arithmetic
        grouped = jax.tree.map(
            lambda x: x.reshape((self.branching,) * self.levels
                                + x.shape[1:]), lane_sols)
        out = f(grouped)
        # after the last gather+greedy all lanes hold identical solutions
        return jax.tree.map(lambda x: x[(0,) * self.levels], out)

    def push(self, ids, payloads, valid) -> "ContinuousSelector":
        """Fold one arrival batch (split equally over the lanes) into the
        per-lane sieves; merges fire every `merge_every` pushes."""
        ids, pay, valid = (jnp.asarray(ids), jnp.asarray(payloads),
                           jnp.asarray(valid))
        nb = ids.shape[0]
        assert nb % self.lanes == 0, \
            f"batch {nb} must split over {self.lanes} lanes"
        shp = (self.lanes, nb // self.lanes)
        if self.states is None:
            self._base = self.streamer.init(pay)
            self.states = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None],
                                           (self.lanes,) + x.shape),
                self._base)
        self.states = self._step(self.states, ids.reshape(shp),
                                 pay.reshape(shp + pay.shape[1:]),
                                 valid.reshape(shp))
        self.batches += 1
        self._dirty = True
        if self.batches % self.merge_every == 0:
            self.merge()
        return self

    def merge(self) -> Solution:
        """One accumulation-tree merge round over the current lane
        states (supervised when a supervisor is attached)."""
        if self.supervisor is not None:
            self.merged, self.states = self.supervisor.run_merge(
                self._merge_round, self.states, self.merged,
                len(self.merges), self._base, self.lanes)
        else:
            self.merged = self._merge_round(self.states, self.merged)
        self.merges.append(float(self.merged.value))
        self._dirty = False
        return self.merged

    def result(self) -> Solution:
        """The stream's current answer: the last merged Solution, after
        merging any pushes since the last merge round."""
        if self.states is None:
            raise ValueError("empty stream")
        if self.merged is None or self._dirty:
            self.merge()
        return self.merged

    def info(self) -> dict:
        d = {"merges": self.merges, "batches": self.batches,
             "tree": (self.lanes, self.branching, self.levels)}
        if self.supervisor is not None:
            d["events"] = list(self.supervisor.events)
        return d


def stream_select_continuous(objective, stream: Iterable, k: int, *,
                             lanes: int = 4, branching: int = 0,
                             merge_every: int = 4, eps: float = 0.1,
                             ground: Optional[jax.Array] = None,
                             ground_valid: Optional[jax.Array] = None,
                             backend: Optional[str] = None,
                             node_engine: str = "auto",
                             sample_level: int = 0,
                             seed: Optional[int] = None,
                             supervisor=None
                             ) -> Tuple[Solution, dict]:
    """Continuous mode with `lanes` vmapped lanes (the single-device
    simulation of the mesh — core.simulate style). Returns the final
    merged Solution plus an info dict with the merged-value trajectory.

    Each batch is split equally across lanes (batch % lanes == 0); every
    `merge_every` batches the per-lane sieve summaries run through a
    T(lanes, b=branching or lanes) accumulation tree whose node-local
    ground is the union of child summaries plus (vector objectives) the
    fixed evaluation set — and the root is select_better'd against the
    last merged solution, so the served answer is monotone between rounds.
    The merge IS core.greedyml.accumulate_levels — the same Algorithm 3.1
    rounds `LevelDispatcher` dispatches one at a time — executed under
    nested vmap axes (one named axis per tree level), so continuous and
    distributed modes cannot drift semantically. ``lanes`` must equal
    branching^levels.
    ``sample_level``/``seed`` enable reseedable stochastic greedy at the
    merge nodes (threaded to accumulate_levels; seed None keeps the
    legacy fixed tape).

    ``supervisor``: optional runtime.supervisor.SelectionSupervisor —
    every periodic merge then runs under fault supervision (DESIGN
    §Fault tolerance): a transient WorkerFailure replays the merge from
    the in-memory per-lane sieve states, a repeatedly-failing lane is
    declared lost mid-merge and its sieve state reset so a replacement
    worker joins cold (the merge proceeds without its summary), and lane
    states + the merged solution are checkpointed after every merge.
    The structured recovery log lands in ``supervisor.events`` and is
    echoed in the returned info dict.

    Implemented as a loop over `ContinuousSelector` — the push-driven
    form the serving sessions (serving/session.py) use — so the one-shot
    and always-on paths share every batch/merge decision.
    """
    sel = ContinuousSelector(objective, k, lanes=lanes,
                             branching=branching, merge_every=merge_every,
                             eps=eps, ground=ground,
                             ground_valid=ground_valid, backend=backend,
                             node_engine=node_engine,
                             sample_level=sample_level, seed=seed,
                             supervisor=supervisor)
    for ids, pay, valid in stream:
        sel.push(ids, pay, valid)
    merged = sel.result()
    return merged, sel.info()


# ---------------------------------------------------------------------------
# continuous distributed mode — real mesh (shard_map)
# ---------------------------------------------------------------------------


def stream_select_distributed(objective, stream: Iterable, k: int, mesh,
                              tree_axes: Sequence[str], *,
                              merge_every: int = 4, eps: float = 0.1,
                              ground: Optional[jax.Array] = None,
                              ground_valid: Optional[jax.Array] = None,
                              backend: Optional[str] = None,
                              node_engine: str = "auto",
                              sample_level: int = 0,
                              seed: Optional[int] = None
                              ) -> Tuple[Solution, dict]:
    """Continuous mode over a real mesh: each lane sieves its shard of
    every arrival batch, and merge rounds run the exact
    core.greedyml.accumulate_levels recurrence (sieve-as-leaf-solver)
    with the last merged solution carried as an extra competitor.
    ``sample_level``/``seed`` reseed the merge nodes' stochastic draws
    (seed None keeps the legacy fixed tape)."""
    from jax.sharding import PartitionSpec as P

    radices = [mesh.shape[a] for a in tree_axes]
    lanes = math.prod(radices)
    streamer = SieveStreamer(objective, k, eps, ground=ground,
                             ground_valid=ground_valid, backend=backend)
    lane_spec = P(tuple(reversed(tree_axes)))
    rep = P()

    def step_fn(state, ids, pay, valid):
        state1 = jax.tree.map(lambda x: x[0], state)
        state1 = streamer.process_batch(state1, ids, pay, valid)
        return jax.tree.map(lambda x: x[None], state1)

    aug_levels = None
    if not streamer.rule.is_bitmap:
        aug_levels = jnp.broadcast_to(
            streamer.ground[None], (len(tree_axes),) + streamer.ground.shape)

    def merge_fn(state, carry):
        sol = streamer.solution(jax.tree.map(lambda x: x[0], state))
        out = accumulate_levels(objective, sol, k, tree_axes, radices,
                                aug_levels=aug_levels,
                                sample_level=sample_level,
                                node_engine=node_engine, carry_prev=carry,
                                seed=seed)
        return _broadcast_from_root(out, tree_axes, radices)

    step = jax.shard_map(step_fn, mesh=mesh,
                         in_specs=(lane_spec, lane_spec, lane_spec,
                                   lane_spec),
                         out_specs=lane_spec, check_vma=False)
    merge = jax.shard_map(merge_fn, mesh=mesh, in_specs=(lane_spec, rep),
                          out_specs=Solution(rep, rep, rep, rep, rep),
                          check_vma=False)

    states, merged = None, None
    merges, done = [], 0
    for i, (ids, pay, valid) in enumerate(stream):
        ids, pay, valid = (jnp.asarray(ids), jnp.asarray(pay),
                           jnp.asarray(valid))
        nb = ids.shape[0]
        assert nb % lanes == 0, f"batch {nb} must shard over {lanes} lanes"
        if states is None:
            base = streamer.init(pay)
            states = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (lanes,) + x.shape),
                base)
            merged = _empty_solution(k, pay)
        states = step(states, ids, pay, valid)
        done = i + 1
        if done % merge_every == 0:
            merged = merge(states, merged)
            merges.append(float(merged.value))
    if states is None:
        raise ValueError("empty stream")
    if done % merge_every != 0:
        merged = merge(states, merged)
        merges.append(float(merged.value))
    return merged, {"merges": merges, "batches": done, "lanes": lanes}
