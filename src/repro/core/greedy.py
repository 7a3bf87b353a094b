"""TPU-native vectorized Greedy (Algorithm 2.1, hardware-adapted).

The paper's implementation uses Lazy Greedy (priority queue, data-dependent
evaluation counts) — a shape-dynamic structure with no vector analogue. On
TPU we instead evaluate ALL candidate marginal gains each step with one
kernel call (an MXU matmul / vector popcount pass) and take a masked argmax:
worst-case O(nk) evaluations, identical selections, fixed trip count. The
CPU simulator (core/simulate.py) retains true Lazy Greedy for the paper's
call-count accounting. See DESIGN §4.

Three inner-loop engines (DESIGN §Perf): the per-step path above; the
FUSED cached-matrix engine — `objective.prepare()` computes the N×C
interaction matrix once, then each scan step is a single fused kernel
(deferred winner-column fold + masked gains + on-chip argmax) over the
cache: O(N·C·D) + k·O(N·C) total instead of k·O(N·C·D), kernel calls per
greedy 3k → k+1; and the MEGAKERNEL engine — the ENTIRE k-step loop is
one Pallas dispatch (`objective.megakernel_loop` →
kernels/greedy_loop.py), 2 dispatches per greedy on the streaming tier
and 1 on the VMEM-resident tier (the accumulation-node fast path; also 1
for bitmap objectives, whose prepare is a transpose rather than a
kernel).

Engine selection is delegated ONCE per invocation to
`plans.select_engine` (DESIGN §Objective protocol): the objective's
KernelRule plus the (n, c, d) shapes and the sampling/constraint flags
resolve to an EnginePlan that the whole loop consumes — no
`hasattr` duck-typing, no per-objective special cases, and every
registered objective (coverage included) rides every tier its budget
admits. All engines make identical selections.

Solutions are fixed-shape: (k,) ids + (k, …) payloads + (k,) validity mask
(“maximum marginal gain is zero → break” becomes masking).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import plans
from repro.runtime import flags, telemetry

F32 = jnp.float32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Solution:
    ids: jax.Array              # (k,) int32 global element ids (-1 = empty)
    payloads: jax.Array         # (k, …) element payloads
    valid: jax.Array            # (k,) bool
    value: jax.Array            # () f32 objective value on the node's eval set
    evals: jax.Array            # () i32 marginal-gain evaluations performed

    def tree_flatten(self):
        return (self.ids, self.payloads, self.valid, self.value,
                self.evals), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def k(self) -> int:
        return self.ids.shape[0]


def greedy(objective, ids: jax.Array, payloads: jax.Array, valid: jax.Array,
           k: int, ground: Optional[jax.Array] = None,
           ground_valid: Optional[jax.Array] = None,
           sample: int = 0, key: Optional[jax.Array] = None,
           constraint=None, engine: str = "auto") -> Solution:
    """Select ≤ k elements maximizing the objective.

    ids/payloads/valid: (n, …) candidate pool. ground/ground_valid override
    the evaluation set (k-medoid/facility 'local objective' + augmentation);
    default: the candidate pool itself.

    ``sample > 0`` enables STOCHASTIC greedy (Mirzasoleiman et al. 2015,
    'Lazier Than Lazy Greedy'): each step evaluates gains on a random
    subset of `sample` DISTINCT candidates (drawn without replacement, as
    the paper's uniform s-subset requires) instead of all n — (1−1/e−ε)
    expected quality with sample ≈ (n/k)·ln(1/ε), cutting the dominant
    gains term by n/sample. Beyond-paper optimization, see EXPERIMENTS
    §Perf.

    ``constraint``: optional hereditary constraint (core.constraints) —
    e.g. PartitionMatroid; infeasible candidates are masked each step
    (paper §7 future work; Greedy is 1/2-approximate under matroids).

    ``engine`` selects the inner loop, resolved by `plans.select_engine`
    (DESIGN §Perf / §Objective protocol):
      * 'auto'  — megakernel when the tier gate admits it, sampling is
                  off, and no constraint is active; else the cached-matrix
                  fused engine when the cache fits the budget and sampling
                  is off; per-step otherwise.
      * 'mega'  — force the whole-greedy megakernel (one dispatch runs
                  all k steps; 2 dispatches/greedy streaming, 1 resident
                  or bitmap). Falls back to the fused engine under
                  constraints or sampling (the loop kernel evaluates
                  neither feasibility masks nor per-step subsets), and
                  further to per-step when the cache busts the budget.
      * 'fused' — force the cached per-step engine (even under sampling;
                  still silently falls back to per-step when the cache
                  exceeds the budget).
      * 'step'  — force the legacy recompute-per-step path.
    All engines make identical selections; the fused engine's total gains
    cost is O(N·C·D) + k·O(N·C) instead of k·O(N·C·D), and the megakernel
    additionally removes the per-step dispatch + state-row HBM round-trip.
    One caveat: on EXACT gain ties under ``sample > 0`` (e.g. duplicate
    payload rows drawn into one subset) the step path keeps the tied
    candidate that appears first in sample order while the fused path
    keeps the lowest candidate index — same payload, possibly different
    id.
    """
    if ground is None:
        ground, ground_valid = payloads, valid
    with jax.named_scope("greedy.prepare"):
        state = objective.init_state(ground, ground_valid)
    dims = objective.plan_dims(state, payloads)
    use_sampling = 0 < sample < ids.shape[0]

    # ONE planning decision for the whole invocation: rule + shapes +
    # budgets + the sampling/constraint flags (which demote the megakernel
    # to the fused scan — identical selections either way).
    plan = plans.select_engine(
        objective.rule, *dims, requested=engine, sampling=use_sampling,
        constrained=constraint is not None, backend=objective.backend)
    # one record per traced invocation: what the trace of this greedy
    # launches and pads, per execution (per lane under vmap/shard_map)
    rec = telemetry.record("greedy", rule=objective.rule.name,
                           logical=[int(dims[0]), int(dims[1])], k=int(k),
                           engine=plan.engine)
    with telemetry.span("greedy", engine=plan.engine) as sp:
        sol = _greedy(objective, state, ids, payloads, valid, k, plan,
                      sample, key, constraint, use_sampling)
    streams = [r for r in telemetry.records("stream") if r["span"] == sp.id]
    rec.update(launches=int(sp.counts.get("launches", 0)),
               relayout_bytes=int(sp.counts.get("relayout_bytes", 0)),
               build_bytes=int(sp.counts.get("build_bytes", 0)),
               mirrored_blocks=int(sp.counts.get("mirrored_blocks", 0)),
               streams=streams)
    return sol


def _greedy(objective, state, ids, payloads, valid, k, plan, sample, key,
            constraint, use_sampling) -> Solution:
    """`greedy`'s body under the resolved `plan`."""
    n = ids.shape[0]
    if use_sampling:
        key = key if key is not None else jax.random.PRNGKey(0)
        cand_idx = _sample_candidates(key, k, n, sample)

    if plan.engine in ("mega_stream", "mega_resident"):
        with jax.named_scope("greedy.loop"):
            mega = objective.megakernel_loop(state, payloads, valid, k,
                                             plan=plan)
        if mega is not None:
            return _finalize_mega(objective, mega, ids, payloads, valid, k)

    cache = None
    if plan.engine == "fused":
        with jax.named_scope("greedy.prepare"):
            cache = objective.prepare(state, payloads, valid, plan=plan)
    if cache is not None:
        return _greedy_fused(objective, state, cache, ids, payloads, valid,
                             k, constraint,
                             cand_idx if use_sampling else None)

    # a bitmap pool is held in the orientation its gains stream in
    # (plans.bitmap_orient): the (W, C) view is formed here, once, and the
    # scan reads the gains' operand and the winner's words from it alone,
    # so the (C, W) pool is never relaid out for the loop
    orient = (plans.bitmap_orient(*payloads.shape)
              if objective.rule.is_bitmap else "cw")
    pool, axis = (payloads.T, 1) if orient == "wc" else (payloads, 0)

    @jax.named_scope("greedy.step")
    def step(carry, xs):
        state, selected, evals, ccounts = carry
        feas = (constraint.feasible_mask(ccounts) if constraint is not None
                else jnp.ones((n,), bool))
        if use_sampling:
            idx = xs
            sub_pay = jnp.take(pool, idx, axis=axis)
            sub_valid = jnp.take(valid & feas & jnp.logical_not(selected),
                                 idx)
            gains = objective.gains(state, sub_pay, sub_valid, orient)
            best_local = jnp.argmax(gains)
            gain = gains[best_local]
            best = idx[best_local]
            n_evals = jnp.sum(sub_valid.astype(jnp.int32))
        else:
            cand_valid = valid & feas & jnp.logical_not(selected)
            gains = objective.gains(state, pool, cand_valid, orient)
            best = jnp.argmax(gains)
            gain = gains[best]
            n_evals = jnp.sum(cand_valid.astype(jnp.int32))
        accept = jnp.isfinite(gain) & (gain > 0)
        payload = lax.dynamic_index_in_dim(pool, best, axis, keepdims=False)
        new_state = objective.update(state, payload)
        state = jax.tree.map(
            lambda a, b: jnp.where(accept, a, b), new_state, state)
        selected = selected | (jax.nn.one_hot(best, n, dtype=jnp.bool_)
                               & accept)
        if constraint is not None:
            new_counts = constraint.update(ccounts, best)
            ccounts = jax.tree.map(
                lambda a, b: jnp.where(accept, a, b), new_counts, ccounts)
        evals = evals + n_evals
        out = (jnp.where(accept, ids[best], -1),
               jnp.where(accept, payload, jnp.zeros_like(payload)),
               accept)
        return (state, selected, evals, ccounts), out

    c0 = (constraint.init_state() if constraint is not None
          else jnp.zeros((), jnp.int32))
    carry0 = (state, jnp.zeros((n,), jnp.bool_), jnp.zeros((), jnp.int32),
              c0)
    with jax.named_scope("greedy.loop"), telemetry.repeat(k):
        (state, _, evals, _), (out_ids, out_pay, out_valid) = lax.scan(
            step, carry0, cand_idx if use_sampling else None, length=k,
            unroll=flags.scan_unroll())
    return Solution(out_ids, out_pay, out_valid, objective.value(state),
                    evals)


def _sample_candidates(key: jax.Array, k: int, n: int,
                       sample: int) -> jax.Array:
    """(k, sample) stochastic-greedy candidate draws, each step WITHOUT
    replacement. `jax.random.randint` sampled with replacement, which
    shrinks the effective per-step subset below `sample` (expected
    distinct count n·(1−(1−1/n)^s) < s) and with it the (1−1/e−ε)
    guarantee's ε; `choice(replace=False)` restores the paper's uniform
    s-subset."""
    draw = lambda kk: jax.random.choice(kk, n, (sample,), replace=False)
    return jax.vmap(draw)(jax.random.split(key, k))


def _finalize_mega(objective, mega, ids, payloads, valid, k) -> Solution:
    """Assemble a Solution from the megakernel's per-step outputs.

    mega: (final_state, bests (k,) i32 with −1 = rejected step, gains).
    The kernel applied the same accept rule (gain > 0) and mask updates
    as the scan engines, so ids/payloads/valid are pure gathers; evals
    reproduces the scan's count — every step evaluates all currently
    valid, unselected candidates."""
    state, bests, _gains = mega
    ok = bests >= 0
    safe = jnp.maximum(bests, 0)
    out_ids = jnp.where(ok, jnp.take(ids, safe), -1)
    out_pay = jax.tree.map(
        lambda p: jnp.where(ok.reshape((k,) + (1,) * (p.ndim - 1)),
                            jnp.take(p, safe, axis=0), 0), payloads)
    total = jnp.sum(valid.astype(jnp.int32))
    accepted_before = jnp.cumsum(ok.astype(jnp.int32)) - ok.astype(jnp.int32)
    evals = jnp.sum(total - accepted_before)
    return Solution(out_ids, out_pay, ok, objective.value(state), evals)


def _greedy_fused(objective, state, cache, ids, payloads, valid, k,
                  constraint, cand_idx) -> Solution:
    """Cached-matrix inner loop (DESIGN §Perf).

    Each scan step is ONE fused kernel call over the cached (N, C) matrix:
    it folds the previous step's winner column into the state row (the
    deferred update — no separate O(N·D) update matmul), accumulates the
    masked relu gains per row-block on-chip, and argmaxes them without the
    (1, C) gains row ever leaving VMEM. The final accepted winner's column
    is flushed after the scan so `value(state)` sees the full solution.
    """
    n = ids.shape[0]
    use_sampling = cand_idx is not None

    @jax.named_scope("greedy.step")
    def step(carry, xs):
        state, selected, evals, ccounts, prev = carry
        feas = (constraint.feasible_mask(ccounts) if constraint is not None
                else jnp.ones((n,), bool))
        cand_mask = valid & feas & jnp.logical_not(selected)
        if use_sampling:
            idx = xs
            in_sample = jnp.zeros((n,), jnp.bool_).at[idx].set(True)
            step_mask = cand_mask & in_sample
            n_evals = jnp.sum(jnp.take(cand_mask, idx).astype(jnp.int32))
        else:
            step_mask = cand_mask
            n_evals = jnp.sum(cand_mask.astype(jnp.int32))
        state, best, gain = objective.fused_step(state, cache, step_mask,
                                                 prev)
        accept = jnp.isfinite(gain) & (gain > 0)
        payload = jax.tree.map(lambda p: p[best], payloads)
        selected = selected | (jax.nn.one_hot(best, n, dtype=jnp.bool_)
                               & accept)
        if constraint is not None:
            new_counts = constraint.update(ccounts, best)
            ccounts = jax.tree.map(
                lambda a, b: jnp.where(accept, a, b), new_counts, ccounts)
        prev = jnp.where(accept, best.astype(jnp.int32), jnp.int32(-1))
        evals = evals + n_evals
        out = (jnp.where(accept, ids[best], -1),
               jnp.where(accept, payload, jnp.zeros_like(payload)),
               accept)
        return (state, selected, evals, ccounts, prev), out

    c0 = (constraint.init_state() if constraint is not None
          else jnp.zeros((), jnp.int32))
    carry0 = (state, jnp.zeros((n,), jnp.bool_), jnp.zeros((), jnp.int32),
              c0, jnp.int32(-1))
    with jax.named_scope("greedy.loop"), telemetry.repeat(k):
        (state, _, evals, _, prev), (out_ids, out_pay, out_valid) = \
            lax.scan(step, carry0, cand_idx, length=k,
                     unroll=flags.scan_unroll())
    state = objective.flush_pending(state, cache, prev)
    return Solution(out_ids, out_pay, out_valid, objective.value(state),
                    evals)


def replay_value(objective, payloads: jax.Array, valid: jax.Array,
                 ground: jax.Array, ground_valid: jax.Array) -> jax.Array:
    """f(S) of an existing solution evaluated on a (new) ground set —
    used at internal tree nodes to score S_prev under the node-local
    objective before the argmax{f(S), f(S_prev)} (Algorithm 3.1, line 15).

    When the objective provides `replay_batch`, all k elements are folded
    into the state in ONE pairwise-kernel call over the ground×solution
    matrix instead of a sequential k-step update scan (DESIGN §Perf)."""
    state = objective.init_state(ground, ground_valid)
    if hasattr(objective, "replay_batch"):
        return objective.value(objective.replay_batch(state, payloads,
                                                      valid))

    def step(state, xs):
        payload, ok = xs
        new_state = objective.update(state, payload)
        return jax.tree.map(lambda a, b: jnp.where(ok, a, b),
                            new_state, state), None

    state, _ = lax.scan(step, state, (payloads, valid),
                        unroll=flags.scan_unroll())
    return objective.value(state)


def select_better(a: Solution, b: Solution) -> Solution:
    """Elementwise argmax{f(a), f(b)} over fixed-shape solutions."""
    take_a = a.value >= b.value
    pick = lambda x, y: jnp.where(take_a, x, y)
    return Solution(pick(a.ids, b.ids),
                    jax.tree.map(pick, a.payloads, b.payloads),
                    pick(a.valid, b.valid), pick(a.value, b.value),
                    a.evals + b.evals)
