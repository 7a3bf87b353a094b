"""GreedyML, the paper's Algorithm 3.1, driven one stage at a time by
`LevelDispatcher` (DESIGN §4).

The m machines are lanes of an L-level mixed-radix tree (b_1, …, b_L),
innermost level first; machine id digits follow the paper's
``parent(id, ℓ) = b^ℓ·⌊id/b^ℓ⌋`` arithmetic. Stage 0 is the leaf Greedy
on each lane's pool; stage ℓ is one accumulation round
(`accumulate_one_level`):

    lax.all_gather(S_prev, axis=tree_axes[ℓ-1])
    + a local Greedy on the b·k union, redundantly in every group member
    + argmax{f(S), f(S_prev)}, S_prev scored by ``replay_value`` on the
      node-local evaluation set (line 15).

Lane b^ℓ·j then holds node (ℓ, b^ℓ·j), so the next gather collects one
representative per child subtree — the recurrence of Fig. 3 — and the
answer is machine 0's solution.

- ``LevelDispatcher.leaves`` / ``.level`` run the stages over stacked
  (lanes, …) state, through shard_map on a mesh with one device per lane,
  or through nested vmap over the same named axes on one device.
- ``LevelDispatcher.run`` chains them. RandGreedi is its one-level case,
  radices ``(m,)``.
- The supervisor (runtime/supervisor.py) runs the same stages and
  checkpoints the state between them.
- ``core.simulate.run_tree_dense`` deals the paper's random partition and
  runs ragged trees through the dispatcher without a mesh;
  ``run_tree_lazy`` is the call-count reference.

Every Greedy call (leaves AND accumulation nodes) runs through the fastest
fitting engine (greedy(engine='auto'), DESIGN §Perf): the leaf cache is
(n/m)×(n/m), while the accumulation-node working set is only
(b·k + augment)×(b·k), which fits VMEM whole, so internal nodes default
to the RESIDENT megakernel tier — one kernel dispatch per node. Huge leaf
partitions degrade gracefully via the memory gate — the paper's whole
point is respecting per-machine memory limits (§6.1/§6.4).
``node_engine`` overrides the accumulation-node engine independently of
the leaves.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.greedy import Solution, greedy, replay_value, select_better
from repro.kernels import ops as kernel_ops
from repro.kernels.shard_gains import shard_greedy

F32 = jnp.float32


def _machine_flat_id(tree_axes: Sequence[str], radices: Sequence[int]):
    """Mixed-radix machine id of this lane (level-0 digit = innermost)."""
    mid = jnp.zeros((), jnp.int32)
    mult = 1
    for ax, r in zip(tree_axes, radices):
        mid = mid + lax.axis_index(ax).astype(jnp.int32) * mult
        mult *= r
    return mid


def _broadcast_from_root(sol: Solution, tree_axes: Sequence[str],
                         radices: Sequence[int]) -> Solution:
    """Replicate machine-0's solution to every lane (paper returns S_0)."""
    mid = _machine_flat_id(tree_axes, radices)
    mask = (mid == 0)

    def pick(x):
        zero = jnp.zeros_like(x)
        sel = jnp.where(jnp.reshape(mask, (1,) * x.ndim), x, zero)
        out = sel
        for ax in tree_axes:
            out = lax.psum(out, ax)
        return out.astype(x.dtype)

    return Solution(pick(sol.ids),
                    jax.tree.map(pick, sol.payloads),
                    pick(sol.valid.astype(jnp.int32)) > 0,
                    pick(sol.value), pick(sol.evals))


def _level_key(seed: Optional[int], lvl: int) -> jax.Array:
    """Base PRNG key for accumulation level `lvl`: the legacy fixed tape
    when unseeded (bit-compatible with older runs), an independent stream
    per user seed otherwise. `seed` is a static int, so the key is built
    inside the traced SPMD function — no shard_map capture."""
    if seed is None:
        return jax.random.PRNGKey(23 + lvl)
    return jax.random.fold_in(jax.random.PRNGKey(seed), 1 + lvl)


def _leaf_key(seed: Optional[int]) -> jax.Array:
    """Base PRNG key for the leaf Greedy draws (see _level_key)."""
    if seed is None:
        return jax.random.PRNGKey(17)
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0)


def accumulate_one_level(objective, s_prev: Solution, k: int,
                         tree_axes: Sequence[str], radices: Sequence[int],
                         lvl: int, aug: Optional[jax.Array] = None,
                         sample_level: int = 0, node_engine: str = "auto",
                         seed: Optional[int] = None,
                         constraint=None
                         ) -> Tuple[Solution, jax.Array, jax.Array]:
    """ONE accumulation round of Algorithm 3.1: gather the child solutions
    over ``tree_axes[lvl]``, run the node-local Greedy on the b·k union,
    and argmax{f(S), f(S_prev)}. Must be called with ALL of `tree_axes`
    bound (inside shard_map over the mesh, or nested vmap axis_names for
    the single-device simulation) — the per-lane PRNG stream folds in the
    full mixed-radix machine id.

    Returns ``(solution, ground, ground_valid)`` — the node-local
    evaluation set is handed back so callers can replay extra competitors
    (``carry_prev``) against the same ground the level was scored on.

    This is the unit the supervised runtime (runtime/supervisor.py)
    dispatches once per level, checkpointing the per-lane state in
    between; `accumulate_levels` loops over it inside the streaming
    driver's own shard_map.

    ``constraint``: optional hereditary constraint SPEC (e.g.
    core.constraints.KnapsackSpec) — ``constraint.bind(u_ids)`` aligns the
    global-id-indexed spec to this node's gathered union, so the same
    budget binds identically at every tree node (heredity is all Theorem
    4.4 needs, so the α/(L+1) bound carries over unchanged).
    """
    ax = tree_axes[lvl]
    u_ids = lax.all_gather(s_prev.ids, ax, axis=0, tiled=True)
    u_pay = lax.all_gather(s_prev.payloads, ax, axis=0, tiled=True)
    u_val = lax.all_gather(s_prev.valid, ax, axis=0, tiled=True)
    ground, ground_valid = u_pay, u_val
    if aug is not None:
        ground = jnp.concatenate([u_pay, aug], axis=0)
        ground_valid = jnp.concatenate(
            [u_val, jnp.ones(aug.shape[0], bool)], axis=0)
    lvl_key = None
    if sample_level:
        lvl_key = jax.random.fold_in(
            _level_key(seed, lvl),
            _machine_flat_id(tree_axes, radices))
    s_new = greedy(objective, u_ids, u_pay, u_val, k,
                   ground=ground, ground_valid=ground_valid,
                   sample=sample_level, key=lvl_key,
                   engine=node_engine,
                   constraint=(constraint.bind(u_ids)
                               if constraint is not None else None))
    prev_score = replay_value(objective, s_prev.payloads,
                              s_prev.valid, ground, ground_valid)
    s_out = select_better(
        s_new, Solution(s_prev.ids, s_prev.payloads, s_prev.valid,
                        prev_score, s_prev.evals))
    return s_out, ground, ground_valid


def accumulate_levels(objective, s_prev: Solution, k: int,
                      tree_axes: Sequence[str], radices: Sequence[int],
                      aug_levels: Optional[jax.Array] = None,
                      sample_level: int = 0,
                      node_engine: str = "auto",
                      carry_prev: Optional[Solution] = None,
                      seed: Optional[int] = None,
                      constraint=None) -> Solution:
    """The accumulation rounds of Algorithm 3.1 as a standalone SPMD
    function: starting from ANY per-lane solution `s_prev` (a leaf Greedy
    for greedyml proper, a sieve summary for the streaming continuous
    mode — streaming/driver.py), run the level-ℓ gather + node-local
    Greedy + argmax{f(S), f(S_prev)} recurrence up the tree (a loop over
    `accumulate_one_level`). Must be called inside shard_map over
    `tree_axes`.

    ``aug_levels``: optional (L, A, …) per-level extra evaluation elements
    concatenated to each node's ground set (paper §6.4 augmentation; the
    streaming driver passes its fixed evaluation set here so merged
    summaries are scored against the query set, not only the union).
    ``carry_prev``: optional extra competitor (e.g. the last merged
    solution of a continuous stream) replayed on the ROOT node's ground
    and select_better'd against the result.
    ``seed``: static int reseeding every stochastic-greedy draw; None
    keeps the legacy fixed tape (PRNGKey(23 + lvl)), so unseeded runs
    stay bit-compatible while independent runs can finally diverge.
    """
    ground, ground_valid = s_prev.payloads, s_prev.valid
    for lvl in range(len(tree_axes)):
        s_prev, ground, ground_valid = accumulate_one_level(
            objective, s_prev, k, tree_axes, radices, lvl,
            aug=aug_levels[lvl] if aug_levels is not None else None,
            sample_level=sample_level, node_engine=node_engine, seed=seed,
            constraint=constraint)
    if carry_prev is not None:
        carry_score = replay_value(objective, carry_prev.payloads,
                                   carry_prev.valid, ground, ground_valid)
        s_prev = select_better(
            s_prev, Solution(carry_prev.ids, carry_prev.payloads,
                             carry_prev.valid, carry_score,
                             carry_prev.evals))
    return s_prev


# ---------------------------------------------------------------------------
# Level-by-level dispatch — the supervised runtime's unit of work
# ---------------------------------------------------------------------------
#
# Each stage is one dispatch over the per-lane Solution state, which
# round-trips through host memory between stages: the supervised runtime
# (runtime/supervisor.py) checkpoints it there, so a lost lane costs one
# level, not the whole tree. Identical lane-local bodies run either over a
# real mesh (shard_map, one device per lane) or on one device (nested vmap
# with the same named axes), so the recovery logic is testable on one CPU
# and deployable on a pod unchanged.


def shard_lanes(ids: jax.Array, payloads: jax.Array, valid: jax.Array,
                lanes: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split flat (n, …) candidate arrays into stacked (lanes, n/lanes, …)
    blocks — lane i gets contiguous block i."""
    n = ids.shape[0]
    if n % lanes:
        raise ValueError(f"n={n} must divide over {lanes} lanes")
    shp = (lanes, n // lanes)
    return (jnp.reshape(ids, shp),
            jnp.reshape(payloads, shp + payloads.shape[1:]),
            jnp.reshape(valid, shp))


def empty_lane_solutions(lanes: int, k: int,
                         payload_example: jax.Array) -> Solution:
    """Stacked all-invalid per-lane state — the checkpoint example tree
    (manager.restore needs the structure/dtypes without running a leaf
    dispatch)."""
    pay = jnp.zeros((lanes, k) + payload_example.shape[1:],
                    payload_example.dtype)
    return Solution(jnp.full((lanes, k), -1, jnp.int32), pay,
                    jnp.zeros((lanes, k), bool),
                    jnp.zeros((lanes,), F32),
                    jnp.zeros((lanes,), jnp.int32))


def root_solution(lane_sols: Solution) -> Solution:
    """Extract the final answer from the stacked state after the last
    level: the paper returns machine 0's solution, row 0 (another lane
    may hold a different one: each keeps its own S_prev when that scores
    higher)."""
    return jax.tree.map(lambda x: x[0], lane_sols)


@dataclasses.dataclass
class LevelDispatcher:
    """Dispatches one GreedyML stage at a time over stacked per-lane state.

    ``radices``: per-level branching (innermost level first); tree
    machines = prod(radices). ``shard`` > 1 splits EACH leaf machine's
    pool over that many additional cooperating lanes running the sharded
    cross-device engine (kernels/shard_gains.py) — the tree planner's
    knob for pools no single device can hold. Total lanes = machines ·
    shard, ordered machine-major with the shard digit LOWEST (lane =
    machine·shard + shard_digit), so `shard_lanes`' contiguous blocks
    hand each shard lane a contiguous slice of its machine's pool (the
    sharded engine's global pool order). ``mesh``: a real mesh with one
    device per lane runs every stage through shard_map; None simulates
    the lanes on the single local device with nested vmap over the same
    named axes (bit-identical lane-local math). All stages take/return
    STACKED arrays with a leading (lanes, …) dim living in
    host-reachable memory — that is the unit the supervisor checkpoints
    and reshards.
    """

    objective: Any
    k: int
    radices: Tuple[int, ...]
    mesh: Optional[Mesh] = None
    tree_axes: Optional[Tuple[str, ...]] = None
    engine: str = "auto"
    node_engine: Optional[str] = None
    sample_leaf: int = 0
    sample_level: int = 0
    seed: Optional[int] = None
    shard: int = 1
    shard_axis: str = "shard"
    tile_c: int = 0
    constraint: Any = None      # spec with bind(ids), e.g. KnapsackSpec

    def __post_init__(self):
        self.radices = tuple(self.radices)
        self.shard = max(1, int(self.shard))
        self.machines = int(math.prod(self.radices)) if self.radices else 1
        self.lanes = self.machines * self.shard
        if self.shard > 1 and self.sample_leaf:
            raise ValueError("sharded leaves do not support stochastic "
                             "leaf sampling (per-step host logic has no "
                             "cross-device protocol)")
        if self.tree_axes is None:
            if self.mesh is not None:
                # make_machine_mesh lists axes outermost-first; tree
                # levels are innermost-first (level 0 = low id digit);
                # the shard axis, when present, is the INNERMOST mesh
                # axis and is NOT a tree level
                axes = [a for a in self.mesh.axis_names
                        if a != self.shard_axis]
                self.tree_axes = tuple(reversed(axes))
            else:
                self.tree_axes = tuple(
                    f"flt{i}" for i in range(len(self.radices)))
        self.tree_axes = tuple(self.tree_axes)
        self.node_engine = self.node_engine or self.engine
        if self.mesh is not None:
            got = math.prod(self.mesh.shape[a] for a in self.tree_axes)
            if got != self.machines:
                raise ValueError(f"mesh axes {self.tree_axes} hold {got} "
                                 f"devices, need {self.machines}")
            if self.shard > 1 \
                    and self.mesh.shape.get(self.shard_axis) != self.shard:
                raise ValueError(
                    f"mesh axis {self.shard_axis!r} must hold "
                    f"{self.shard} devices, has "
                    f"{self.mesh.shape.get(self.shard_axis)}")
        self._fns: Dict[Any, Any] = {}

    @property
    def num_levels(self) -> int:
        return len(self.radices)

    # ---------------------------------------------------------------- stages
    def leaves(self, ids: jax.Array, payloads: jax.Array,
               valid: jax.Array) -> Solution:
        """Leaf Greedy per lane over stacked (lanes, n_l, …) pools —
        also the degraded tree's re-entry stage (the resharded survivor
        pools are just leaves of the new, smaller tree)."""
        return self._get("leaves", self._build_leaves)(ids, payloads, valid)

    def level(self, lane_sols: Solution, lvl: int,
              aug_row: Optional[jax.Array] = None) -> Solution:
        """One accumulation round: gather over tree_axes[lvl] + node
        Greedy + argmax{f(S), f(S_prev)}, over stacked per-lane state."""
        fn = self._get(("level", lvl, aug_row is not None),
                       lambda: self._build_level(lvl, aug_row is not None))
        return fn(lane_sols, aug_row) if aug_row is not None \
            else fn(lane_sols)

    def run(self, ids: jax.Array, payloads: jax.Array, valid: jax.Array,
            augment: Optional[jax.Array] = None) -> Solution:
        """Algorithm 3.1 in one call over flat (n, …) candidates: lane i
        takes contiguous block i, then the leaf Greedy and every level
        run in turn, and machine 0's solution is returned. ``augment``:
        optional (L, A, …) evaluation rows (paper §6.4); row ℓ joins the
        ground of every node at level ℓ. The supervisor runs the same
        stages with checkpoints between them."""
        state = self.leaves(*shard_lanes(ids, payloads, valid, self.lanes))
        for lvl in range(self.num_levels):
            state = self.level(state, lvl,
                               None if augment is None else augment[lvl])
        return root_solution(state)

    # ------------------------------------------------------------- builders
    def _get(self, key, build):
        if key not in self._fns:
            self._fns[key] = build()
        return self._fns[key]

    @jax.named_scope("greedyml.leaves")
    def _leaf_body(self, ids, pay, val, mid):
        key = None
        if self.sample_leaf:
            key = jax.random.fold_in(_leaf_key(self.seed), mid)
        return greedy(self.objective, ids, pay, val, self.k,
                      sample=self.sample_leaf, key=key, engine=self.engine,
                      constraint=(self.constraint.bind(ids)
                                  if self.constraint is not None else None))

    @jax.named_scope("greedyml.leaves")
    def _shard_leaf_body(self, ids, pay, val):
        return shard_greedy(self.objective, ids, pay, val, self.k,
                            axis=self.shard_axis, lanes=self.shard,
                            tile_c=self.tile_c)

    def _lane_spec(self) -> P:
        """PartitionSpec sharding the stacked lanes dim over every mesh
        axis, slowest lane digit first (tree root … level 0, then the
        shard digit)."""
        tail = (self.shard_axis,) if self.shard > 1 else ()
        return P(tuple(reversed(self.tree_axes)) + tail)

    def _build_leaves(self):
        if self.mesh is None:
            if self.shard > 1:
                # machines × shard grid: the shard dim is a NAMED vmap
                # axis so the sharded engine's collectives run over it
                inner = jax.vmap(self._shard_leaf_body,
                                 axis_name=self.shard_axis)
                f = jax.vmap(inner)          # over tree machines

                def run(ids, pay, val):
                    g = lambda x: x.reshape((self.machines, self.shard)
                                            + x.shape[1:])
                    out = jax.jit(f)(g(ids), g(pay), g(val))
                    return jax.tree.map(
                        lambda x: x.reshape((self.lanes,) + x.shape[2:]),
                        out)
                return run

            def run(ids, pay, val):
                mids = jnp.arange(self.lanes, dtype=jnp.int32)
                with kernel_ops.fused_replicas(self.lanes):
                    return jax.jit(jax.vmap(self._leaf_body))(
                        ids, pay, val, mids)
            return run
        spec = self._lane_spec()
        axes, radices = self.tree_axes, self.radices

        def body(ids, pay, val):
            if self.shard > 1:
                s = self._shard_leaf_body(ids[0], pay[0], val[0])
            else:
                mid = _machine_flat_id(axes, radices)
                s = self._leaf_body(ids[0], pay[0], val[0], mid)
            return jax.tree.map(lambda x: x[None], s)

        sol_spec = Solution(spec, spec, spec, spec, spec)
        return jax.jit(jax.shard_map(body, mesh=self.mesh,
                                     in_specs=(spec, spec, spec),
                                     out_specs=sol_spec, check_vma=False))

    def _build_level(self, lvl: int, has_aug: bool):
        axes, radices = self.tree_axes, self.radices

        @jax.named_scope(f"greedyml.level{lvl}")
        def body(sol, *aug):
            out, _, _ = accumulate_one_level(
                self.objective, sol, self.k, axes, radices, lvl,
                aug=aug[0] if aug else None,
                sample_level=self.sample_level,
                node_engine=self.node_engine, seed=self.seed,
                constraint=self.constraint)
            return out

        if self.mesh is None:
            f = body
            in_axes = (0, None) if has_aug else (0,)
            if self.shard > 1:
                # shard lanes carry replicated machine state; map them as
                # the FASTEST (last) grid dim so the lane order matches
                # the leaves (the level body never reduces over them)
                f = jax.vmap(f, in_axes=in_axes,
                             axis_name=self.shard_axis)
            for ax in axes:          # innermost level = innermost vmap
                f = jax.vmap(f, in_axes=in_axes, axis_name=ax)
            grouped_shape = tuple(reversed(radices)) \
                + ((self.shard,) if self.shard > 1 else ())
            ndims = len(grouped_shape)

            def run(lane_sols, *aug):
                # lane id's level-0 digit is LOW → row-major reshape with
                # the innermost radix last matches the tree arithmetic
                grouped = jax.tree.map(
                    lambda x: x.reshape(grouped_shape + x.shape[1:]),
                    lane_sols)
                with kernel_ops.fused_replicas(self.lanes):
                    out = jax.jit(f)(grouped, *aug)
                return jax.tree.map(
                    lambda x: x.reshape((self.lanes,)
                                        + x.shape[ndims:]), out)
            return run

        spec = self._lane_spec()
        sol_spec = Solution(spec, spec, spec, spec, spec)

        def shbody(sol_stacked, *aug):
            sol = jax.tree.map(lambda x: x[0], sol_stacked)
            out = body(sol, *aug)
            return jax.tree.map(lambda x: x[None], out)

        in_specs = (sol_spec, P()) if has_aug else (sol_spec,)
        return jax.jit(jax.shard_map(shbody, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=sol_spec, check_vma=False))
