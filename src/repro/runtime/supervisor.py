"""Supervised, round-resumable distributed GreedyML selection.

`core.greedyml.LevelDispatcher` runs Algorithm 3.1 one stage per
dispatch (each level = one gather + node-Greedy + argmax). This module
drives those stages from the host, as `LevelDispatcher.run` does, but
checkpoints the stacked per-lane Solution state through
checkpoint.manager after every merged level, so recovery is a three-tier
state machine (DESIGN §Fault tolerance):

  1. **Level replay** — a transient ``WorkerFailure`` (injected in tests,
     a real device error in deployment) restores the last merged level's
     checkpoint and re-dispatches just the failed level. Dispatches are
     deterministic pure functions of the checkpointed state, so the
     recovered run is BIT-IDENTICAL to a failure-free run.
  2. **Retry with backoff** — bounded by ``max_restarts`` per recovery
     episode (a successful checkpoint resets the budget), with
     exponential backoff between attempts.
  3. **Degraded-tree recovery** — when the same lane keeps failing it is
     declared lost: `runtime.elastic.plan_degraded_tree` picks the
     largest full b-ary tree over the survivors,
     `checkpoint.reshard.reshard_solutions` pools the surviving per-lane
     solutions onto the new leaves, and the recurrence re-enters from
     level 0 of the smaller tree. An m′-lane tree over the survivors'
     solutions is still a valid GreedyML tree; the dropped partition
     costs only the Barbosa et al. (1502.02606) / Lucic et al.
     (1605.09619) expected-quality term (tests assert a ≥0.95× band).

Every failure/restore/checkpoint/reshard/straggler event lands in a
structured recovery log (``events``: kind + level + lane + wall time;
a ``checkpoint`` also carries the save's ``dur_s``, a ``dispatch`` the
stage's ``lowerings``), and `StragglerMonitor` observations of
per-level wall times trigger pre-emptive checkpoints when the cadence
would otherwise skip one. The
same supervision wraps the continuous streaming driver's periodic tree
merges via `run_merge` (streaming/driver.stream_select_continuous): a
transient merge failure replays from the in-memory lane states, a lost
lane has its sieve state reset so a replacement worker joins cold.
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from types import SimpleNamespace
from typing import (Any, Callable, Dict, List, Mapping, Optional, Sequence,
                    Set, Tuple)

import jax
import jax.numpy as jnp

from repro.checkpoint import manager
from repro.checkpoint.reshard import reshard_solutions
from repro.core.greedy import Solution
from repro.core.greedyml import (LevelDispatcher, empty_lane_solutions,
                                 root_solution, shard_lanes)
from repro.runtime.elastic import plan_degraded_tree
from repro.runtime import telemetry
from repro.runtime.fault import WorkerFailure
from repro.runtime.straggler import StragglerMonitor


class LaneFailure(WorkerFailure):
    """A WorkerFailure attributed to a specific lane (mesh device/worker).

    ``lane`` is the worker id in the ORIGINAL lane numbering — it stays
    stable across degraded-tree re-plans so the supervisor can tell
    "the same lane again" from fresh failures elsewhere."""

    def __init__(self, msg: str, lane: Optional[int] = None,
                 level: Optional[int] = None):
        super().__init__(msg)
        self.lane = lane
        self.level = level


@dataclasses.dataclass
class LaneFailureInjector:
    """Deterministic failure injection for the supervised runtime.

    ``fail_at``: (level, lane) pairs that raise ONCE when the dispatch
    for that level runs — the transient-failure (level-replay) path.
    ``dead``: lane → level mapping; from that level on the lane fails
    EVERY attempt until the supervisor drops it — the lane-loss
    (degraded-tree) path. Lanes are original worker ids; a lane no
    longer in the caller's ``alive`` set never fires (it has already
    been dropped or reset)."""

    fail_at: Tuple[Tuple[int, int], ...] = ()
    dead: Mapping[int, int] = dataclasses.field(default_factory=dict)
    _fired: Set[Tuple[int, int]] = dataclasses.field(default_factory=set)

    def check(self, level: int, alive: Optional[Sequence[int]] = None
              ) -> None:
        live = None if alive is None else set(alive)
        for lane, frm in self.dead.items():
            if level >= frm and (live is None or lane in live):
                raise LaneFailure(f"lane {lane} is down (level {level})",
                                  lane=lane, level=level)
        for lv, lane in self.fail_at:
            key = (lv, lane)
            if (lv == level and key not in self._fired
                    and (live is None or lane in live)):
                self._fired.add(key)
                raise LaneFailure(
                    f"injected transient failure: lane {lane} at level "
                    f"{level}", lane=lane, level=level)


@dataclasses.dataclass
class SelectionSupervisor:
    """Host-side supervision of level-by-level distributed selection.

    ``ckpt_every_levels``: checkpoint cadence in merged levels (1 = after
    every level, the paper-scale default; the leaf stage and the root are
    always checkpointed, and a straggler action forces one regardless).
    ``max_restarts``: retry budget per recovery episode — reset by every
    successful checkpoint, so independent failures at different levels
    don't share one budget. ``sleep_fn``/``clock`` are injectable for
    deterministic tests."""

    ckpt_dir: str
    keep: int = 3
    max_restarts: int = 3
    backoff_s: float = 0.0
    backoff_cap_s: float = 2.0
    ckpt_every_levels: int = 1
    injector: Optional[LaneFailureInjector] = None
    monitor: Optional[StragglerMonitor] = None
    sleep_fn: Callable[[float], None] = time.sleep
    clock: Callable[[], float] = time.perf_counter
    events: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    _dispatches: int = 0
    _stream_dead: Set[int] = dataclasses.field(default_factory=set)

    # ------------------------------------------------------------------ log
    def _log(self, kind: str, **kw) -> Dict[str, Any]:
        ev = {"kind": kind, "time": time.time(), **kw}
        self.events.append(ev)
        return ev

    def _backoff(self, attempt: int) -> float:
        if self.backoff_s <= 0:
            return 0.0
        delay = min(self.backoff_s * (2 ** (attempt - 1)),
                    self.backoff_cap_s)
        self.sleep_fn(delay)
        return delay

    # ------------------------------------------------------- selection runs
    @telemetry.span("greedyml.select")
    def select(self, objective, ids: jax.Array, payloads: jax.Array,
               valid: jax.Array, k: int, *, lanes: int, branching: int = 0,
               mesh=None, tree_axes: Optional[Sequence[str]] = None,
               engine: str = "auto", node_engine: Optional[str] = None,
               sample_leaf: int = 0, sample_level: int = 0,
               seed: Optional[int] = None,
               augment: Optional[jax.Array] = None,
               resume: bool = False,
               shard: int = 0) -> Tuple[Solution, Dict[str, Any]]:
        """Run supervised distributed GreedyML over ``lanes`` machines.

        ``mesh``/``tree_axes``: a real mesh (one device per lane) runs
        every stage through shard_map; None simulates the lanes on the
        local device (nested vmap, identical math). ``branching=0``
        with no mesh hands the tree shape to the MEMORY-MODEL planner
        (`plans.plan_tree`): branching, levels, and per-leaf sharding
        come from the per-device budget instead of the caller —
        the paper's tree-selection step. ``shard`` > 1 forces that many
        lanes to cooperate per leaf through the sharded cross-device
        engine (0 = planner's choice / solo). A mesh may carry a
        ``'shard'`` axis holding the shard lanes; ``tree_axes`` then
        names only the tree levels. ``resume=True`` restores the latest
        checkpoint (any tree epoch) and continues from the next level.
        Returns ``(solution, info)`` where info carries the recovery
        log, the initial and final tree shapes, and the surviving
        worker set.

        Host spans (`runtime.telemetry`): this call is one
        ``greedyml.select``; each dispatched stage a ``greedyml.stage``
        (attrs level, epoch) whose ``lowerings`` count says which stage
        recompiled, with its ``greedyml.checkpoint`` inside; each
        level replay a ``greedyml.restart``."""
        tile_c = 0
        if mesh is not None:
            tree_axes = tuple(tree_axes)
            radices = tuple(mesh.shape[a] for a in tree_axes)
            shard = int(mesh.shape.get("shard", shard or 1)) or 1
            if math.prod(radices) * shard != lanes:
                raise ValueError(
                    f"mesh holds {math.prod(radices) * shard} lanes, "
                    f"asked for {lanes}")
            b = radices[0] if radices else 1
        elif branching or shard:
            shard = shard or 1
            if lanes % shard:
                raise ValueError(f"lanes ({lanes}) must divide by "
                                 f"shard ({shard})")
            m = lanes // shard
            b = branching or m
            levels = max(1, round(math.log(m, b))) if m > 1 else 0
            if b ** levels != m:
                raise ValueError(f"machines ({m}) must be "
                                 f"branching^levels (b={b})")
            radices = (b,) * levels
            tree_axes = None
        else:
            # no tree given: the memory model picks branching, levels,
            # and per-leaf sharding (the paper's tree-selection step)
            from repro.kernels.plans import plan_tree
            rule = objective.rule
            d = None if rule.is_bitmap else payloads.shape[1]
            w = payloads.shape[1] if rule.is_bitmap else None
            tp = plan_tree(rule, ids.shape[0], d, k, lanes,
                           backend=objective.backend, words=w)
            if tp is None:
                raise ValueError(
                    f"no accumulation tree over {lanes} lanes fits the "
                    "per-device budget for this instance "
                    "(plans.plan_tree found no feasible shape)")
            radices, shard, b = tp.radices, tp.shard, tp.branching
            tile_c = tp.leaf_plan.tile_c
            tree_axes = None
            self._log("plan", radices=list(radices), shard=shard,
                      peak_bytes=tp.peak_bytes,
                      leaf_engine=tp.leaf_plan.engine,
                      node_engine_plan=tp.node_plan.engine)

        disp = LevelDispatcher(objective, k, radices, mesh=mesh,
                               tree_axes=tree_axes, engine=engine,
                               node_engine=node_engine,
                               sample_leaf=sample_leaf,
                               sample_level=sample_level, seed=seed,
                               shard=shard, tile_c=tile_c)
        il, pl, vl = shard_lanes(jnp.asarray(ids), jnp.asarray(payloads),
                                 jnp.asarray(valid), lanes)
        workers = list(range(lanes))
        tree0 = (lanes, b, disp.num_levels)
        epoch = 0
        state: Optional[Solution] = None
        next_stage = 0           # 0 = leaves; s ≥ 1 = accumulation level s
        restarts = 0
        aug = augment

        if resume:
            resumed = self._try_resume(objective, k, payloads, engine,
                                       node_engine, sample_leaf,
                                       sample_level, seed, mesh is not None)
            if resumed is not None:
                disp, state, next_stage, workers, epoch, b = resumed

        while True:
            L = disp.num_levels
            example = empty_lane_solutions(
                disp.lanes, k,
                jnp.zeros((1,) + payloads.shape[1:], payloads.dtype))
            try:
                while next_stage <= L:
                    with telemetry.span("greedyml.stage",
                                        level=next_stage,
                                        epoch=epoch) as stage:
                        if self.injector is not None:
                            self.injector.check(next_stage, alive=workers)
                        t0 = self.clock()
                        if next_stage == 0:
                            new_state = disp.leaves(il, pl, vl)
                        else:
                            lvl = next_stage - 1
                            aug_row = aug[lvl] if aug is not None else None
                            new_state = disp.level(state, lvl, aug_row)
                        new_state = jax.block_until_ready(new_state)
                        wall = self.clock() - t0
                        self._dispatches += 1
                        self._log("dispatch", level=next_stage,
                                  epoch=epoch, wall_s=wall,
                                  lowerings=int(stage.counts.get(
                                      "lowerings", 0)))
                        preempt = False
                        if self.monitor is not None:
                            act = self.monitor.observe(self._dispatches,
                                                       wall)
                            if act:
                                self._log("straggler", level=next_stage,
                                          wall_s=wall, action=act)
                                preempt = True
                        state = new_state
                        if (next_stage == 0 or next_stage == L or preempt
                                or next_stage % self.ckpt_every_levels
                                == 0):
                            t_ck = self.clock()
                            with telemetry.span("greedyml.checkpoint",
                                                level=next_stage):
                                manager.save(
                                    self._epoch_dir(epoch), next_stage,
                                    state,
                                    extra={"stage": next_stage,
                                           "epoch": epoch,
                                           "workers": workers,
                                           "radices": list(disp.radices),
                                           "branching": b, "k": k,
                                           "shard": disp.shard,
                                           "tile_c": disp.tile_c,
                                           "preemptive": preempt},
                                    keep=self.keep)
                            self._log("checkpoint", level=next_stage,
                                      epoch=epoch, preemptive=preempt,
                                      dur_s=self.clock() - t_ck)
                            restarts = 0
                        next_stage += 1
                sol = root_solution(state)
                info = {"tree": tree0,
                        "final_tree": (disp.lanes, b, disp.num_levels),
                        "degraded": epoch > 0, "epochs": epoch + 1,
                        "shard": disp.shard,
                        "radices": tuple(disp.radices),
                        "workers": list(workers), "events": self.events}
                return sol, info
            except WorkerFailure as e:
                lane = getattr(e, "lane", None)
                restarts += 1
                self._log("failure", level=next_stage, epoch=epoch,
                          lane=lane, error=str(e), attempt=restarts)
                if restarts > self.max_restarts:
                    # sharded leaves have no degraded-tree story: the
                    # shard lanes of one machine hold SLICES of one
                    # pool, not poolable solutions — losing one loses
                    # the partition, so level replay is the only tier
                    if lane is None or len(workers) <= 1 or disp.shard > 1:
                        raise
                    # ---- repeated failure of one lane → degrade ---------
                    (disp, il, pl, vl, workers, epoch, state,
                     next_stage) = self._degrade(
                        objective, k, payloads, disp, state, il, pl, vl,
                        workers, lane, b, epoch, next_stage, engine,
                        node_engine, sample_leaf, sample_level, seed,
                        mesh is not None)
                    if aug is not None:
                        aug = aug[:disp.num_levels]
                    restarts = 0
                    continue
                with telemetry.span("greedyml.restart", level=next_stage,
                                    epoch=epoch):
                    delay = self._backoff(restarts)
                    state, next_stage = self._rewind(epoch, example)
                self._log("restart", level=next_stage, epoch=epoch,
                          lane=lane, backoff_s=delay)

    # -------------------------------------------------------------- helpers
    def _epoch_dir(self, epoch: int) -> str:
        return os.path.join(self.ckpt_dir, f"tree{epoch}")

    def _rewind(self, epoch: int,
                example: Solution) -> Tuple[Optional[Solution], int]:
        """Restore the last merged level's checkpoint (level replay); cold
        restart from the leaf stage when no checkpoint exists yet."""
        d = self._epoch_dir(epoch)
        last = manager.latest_step(d)
        if last is None:
            self._log("cold_restart", level=0, epoch=epoch)
            return None, 0
        state, manifest = manager.restore(d, example, step=last)
        stage = int(manifest["extra"]["stage"])
        self._log("restore", level=stage, epoch=epoch)
        return state, stage + 1

    def _degrade(self, objective, k, payloads, disp, state, il, pl, vl,
                 workers, dead_lane, b, epoch, failed_stage, engine,
                 node_engine, sample_leaf, sample_level, seed, use_mesh):
        """Drop the dead lane, re-plan the tree for the shrunken radix,
        and reshard the surviving per-lane state onto the new leaves."""
        rows = [i for i, w in enumerate(workers) if w != dead_lane]
        survivors = [w for w in workers if w != dead_lane]
        if not rows:
            raise WorkerFailure("all lanes lost")
        new_lanes, new_levels = plan_degraded_tree(len(survivors), b)
        if state is not None:
            # survivors' last merged solutions become the new tree's leaves
            pool = reshard_solutions(state, rows, new_lanes)
        else:
            # failure before any merged level: reshard the raw leaf pools
            raw = SimpleNamespace(ids=il, payloads=pl, valid=vl)
            pool = reshard_solutions(raw, rows, new_lanes)
        self._log("reshard", level=failed_stage, epoch=epoch,
                  lane=dead_lane, lanes_from=len(workers),
                  lanes_to=new_lanes, levels_to=new_levels,
                  survivors=survivors)
        new_mesh = None
        if use_mesh and new_levels >= 1:
            from repro.launch.mesh import make_machine_mesh
            new_mesh = make_machine_mesh(new_lanes, b, axis_prefix="deg")
        new_disp = LevelDispatcher(
            objective, k, (b,) * new_levels, mesh=new_mesh,
            engine=engine, node_engine=node_engine,
            sample_leaf=0,        # re-entry pools are tiny: exact greedy
            sample_level=sample_level, seed=seed)
        il2, pl2, vl2 = (jnp.asarray(pool[0]), jnp.asarray(pool[1]),
                         jnp.asarray(pool[2]))
        return (new_disp, il2, pl2, vl2, survivors[:new_lanes], epoch + 1,
                None, 0)

    def _try_resume(self, objective, k, payloads, engine, node_engine,
                    sample_leaf, sample_level, seed, use_mesh):
        """Find the newest tree epoch with a checkpoint and rebuild the
        dispatcher + state from its manifest. Returns None when there is
        nothing to resume."""
        if not os.path.isdir(self.ckpt_dir):
            return None
        epochs = sorted(int(n[4:]) for n in os.listdir(self.ckpt_dir)
                        if n.startswith("tree") and n[4:].isdigit()
                        and manager.latest_step(
                            os.path.join(self.ckpt_dir, n)) is not None)
        if not epochs:
            return None
        epoch = epochs[-1]
        d = self._epoch_dir(epoch)
        last = manager.latest_step(d)
        # manifest first: radices decide the example tree's lane count
        import json
        with open(os.path.join(d, f"step_{last:08d}",
                               "manifest.json")) as f:
            extra = json.load(f)["extra"]
        radices = tuple(extra["radices"])
        shard = int(extra.get("shard", 1))
        tile_c = int(extra.get("tile_c", 0))
        lanes = (int(math.prod(radices)) if radices else 1) * shard
        b = int(extra["branching"])
        mesh = None
        if use_mesh and (radices or shard > 1):
            from repro.launch.mesh import make_tree_mesh
            mesh = make_tree_mesh(radices, shard,
                                  axis_prefix="deg" if epoch else "lvl")
        example = empty_lane_solutions(
            lanes, k, jnp.zeros((1,) + payloads.shape[1:], payloads.dtype))
        state, manifest = manager.restore(d, example, step=last)
        stage = int(manifest["extra"]["stage"])
        disp = LevelDispatcher(objective, k, radices, mesh=mesh,
                               engine=engine, node_engine=node_engine,
                               sample_leaf=sample_leaf,
                               sample_level=sample_level, seed=seed,
                               shard=shard, tile_c=tile_c)
        self._log("resume", level=stage, epoch=epoch)
        return (disp, state, stage + 1, list(manifest["extra"]["workers"]),
                epoch, b)

    # ------------------------------------------------------ streaming merges
    def run_merge(self, merge_fn: Callable, states, merged, round_idx: int,
                  lane_init, lanes: int):
        """Supervise one periodic tree merge of the continuous streaming
        driver (streaming/driver.stream_select_continuous).

        A transient failure replays the merge from the in-memory per-lane
        sieve states (they ARE the last merged level's inputs); after
        ``max_restarts`` failures of one lane the lane is declared lost
        mid-merge — its sieve state is reset to ``lane_init`` (a
        replacement worker joining cold) and the merge proceeds without
        its summary. Lane states + the merged solution are checkpointed
        after every successful merge. Returns ``(merged, states)``."""
        workers = [l for l in range(lanes) if l not in self._stream_dead]
        attempts = 0
        while True:
            try:
                if self.injector is not None:
                    self.injector.check(round_idx, alive=workers)
                t0 = self.clock()
                out = jax.block_until_ready(merge_fn(states, merged))
                wall = self.clock() - t0
                self._dispatches += 1
                self._log("merge", level=round_idx, wall_s=wall)
                if self.monitor is not None:
                    act = self.monitor.observe(self._dispatches, wall)
                    if act:
                        self._log("straggler", level=round_idx,
                                  wall_s=wall, action=act)
                if self.ckpt_dir:
                    manager.save(os.path.join(self.ckpt_dir, "stream"),
                                 round_idx + 1,
                                 {"states": states, "merged": out},
                                 extra={"round": round_idx,
                                        "dead": sorted(self._stream_dead)},
                                 keep=self.keep)
                    self._log("checkpoint", level=round_idx, stream=True)
                return out, states
            except WorkerFailure as e:
                lane = getattr(e, "lane", None)
                attempts += 1
                self._log("failure", level=round_idx, lane=lane,
                          error=str(e), attempt=attempts, stream=True)
                if attempts > self.max_restarts:
                    if lane is None:
                        raise
                    # lane LOST mid-merge: replacement joins with a cold
                    # sieve; the merge proceeds without its summary
                    self._stream_dead.add(lane)
                    workers = [l for l in workers if l != lane]
                    if not workers:
                        raise
                    states = jax.tree.map(
                        lambda x, x0: x.at[lane].set(x0), states, lane_init)
                    self._log("lane_reset", level=round_idx, lane=lane)
                    attempts = 0
                    continue
                delay = self._backoff(attempts)
                self._log("restart", level=round_idx, lane=lane,
                          backoff_s=delay, stream=True)
