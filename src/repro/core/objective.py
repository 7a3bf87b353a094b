"""The objective protocol: declarative specs drive every engine tier.

One generic `RuleObjective` implements the WHOLE engine interface —
per-step gains/update/value, the fused cached-matrix engine, the
whole-greedy megakernel, batched replay, and (via streaming/sieve.py) the
sieve-streaming tier — from a single `KernelRule` (kernels/rules.py).
Objectives are therefore registry ENTRIES, not classes: adding one means
registering a rule plus a few lines of metadata, and every engine,
conformance test (tests/test_objective_protocol.py), CI sweep
(scripts/ci_smoke.sh), and benchmark column picks it up automatically.

Interface (all methods jit-safe, fixed shapes):
  init_state(ground, ground_valid) → RuleState    state of an EMPTY solution
  gains(state, cands, cand_valid[, orient])
                                   → (C,) marginal gains (−inf if invalid);
                                     bitmap cands may be the (W, C) view
                                     (orient 'wc')
  update(state, payload)           → state after adding one element
  value(state)                     → f(S) under this node's evaluation set
  plan_dims(state, cands)          → (n, c, d) for plans.select_engine
  prepare(state, cands, cand_valid[, plan]) → (matrix, EnginePlan) | None
  fused_step(state, cache, cand_mask, prev) → (state, best, gain)
  flush_pending(state, cache, prev) → state
  megakernel_loop(state, cands, cand_valid, k[, plan])
                                   → (state, bests, gains) | None
  replay_batch(state, payloads, valid) → state

State is one fixed-shape pytree for every objective: the per-ground-row
state vector `row` (mind / curmax / covered words / saturated sums) plus
the evaluation-set features and normalization scalars. Payloads are
feature vectors (C, D) for the vector rules and packed uint32 universe
bitmaps (C, W) for bitmap rules — the TPU-dense representation; the CPU
lazy simulator keeps the paper's sparse adjacency lists (DESIGN §4).

For the vector rules the evaluation ground set is the node's local data
(paper §6.4 'local objective'); internal tree nodes therefore rebuild
state over the union of child solutions (optionally + augment images).

Built-in registry:
  coverage  (kcover / kdom)   max-k-cover over packed bitmaps
  kmedoid                     exemplar clustering, L({e0}) − L(S ∪ {e0})
  facility  (facility_location)  mean max(0, ⟨u, v⟩) coresets
  satcover                    saturated coverage Σ_u min(cap, Σ relu⟨u,v⟩)/N
                              — the spec-only objective: registered as a
                              rule, zero objective- or kernel-specific code
  graphcut                    coverage − α/2·redundancy² per ground row
                              (quadratic graph-cut penalty, 'sum' fold)
  mmr                         λ·relevance + (1−λ)·saturated diversity —
                              the MMR tradeoff as one exact potential
                              (retrieval dedup in the serving engine)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops, plans
from repro.kernels import rules as R
from repro.kernels.plans import EnginePlan
from repro.kernels.rules import KernelRule

F32 = jnp.float32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RuleState:
    """Unified selection state for every registered objective.

    ground/gvalid are None for bitmap rules (their gains need no
    evaluation features); `base` is the value offset (k-medoid's L({e0})
    term, 0 elsewhere); `n_eff` the valid-ground normalizer (1 for
    bitmap rules, whose values are raw popcounts)."""
    ground: Any           # (N, D) evaluation features | None
    gvalid: Any           # (N,) bool | None
    row: jax.Array        # (N,) f32 state row | (W,) uint32 covered words
    base: jax.Array       # () f32
    n_eff: jax.Array      # () f32

    def tree_flatten(self):
        return (self.ground, self.gvalid, self.row, self.base,
                self.n_eff), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class RuleObjective:
    """A submodular objective defined entirely by its KernelRule."""

    def __init__(self, rule: KernelRule, *, name: Optional[str] = None,
                 words: int = 0, backend: Optional[str] = None):
        self.rule = rule
        self.name = name or rule.name
        self.words = words            # bitmap rules: packed universe words
        self.backend = backend
        assert not rule.is_bitmap or words > 0, \
            "bitmap rules need a universe size"

    # -- state ---------------------------------------------------------------

    def init_state(self, ground, ground_valid) -> RuleState:
        if self.rule.is_bitmap:
            row = R.empty_row(None, None, self.rule, words=self.words)
            return RuleState(None, None, row, jnp.zeros((), F32),
                             jnp.ones((), F32))
        row = R.empty_row(ground, ground_valid, self.rule)
        n_eff = jnp.maximum(jnp.sum(ground_valid.astype(F32)), 1.0)
        # 'min' measures improvement over the auxiliary element e0 = 0
        # (paper §6.4): the empty-solution row is d(·, e0), its mean the
        # value baseline. Other folds start from the zero baseline.
        base = (jnp.sum(row) / n_eff if self.rule.fold == "min"
                else jnp.zeros((), F32))
        return RuleState(ground, ground_valid, row, base, n_eff)

    def value(self, state: RuleState):
        if self.rule.is_bitmap:
            return jnp.sum(jax.lax.population_count(state.row)
                           .astype(jnp.int32)).astype(F32)
        if self.rule.fold == "sum":
            # W(r) = λ·(r ∧ BIG) + (1−λ)·h(r ∧ cap), h(t) = t − t²/(2·cap)
            # — the same potential whose increments gain_part emits, so
            # gain ≡ Δvalue holds bit-for-bit (conformance suite)
            t = jnp.minimum(state.row, self.rule.cap)
            w = (self.rule.lam * jnp.minimum(state.row, R.BIG)
                 + (1.0 - self.rule.lam)
                 * (t - t * t / (2.0 * self.rule.cap)))
            return jnp.sum(jnp.where(state.gvalid, w, 0.0)) / state.n_eff
        tot = jnp.sum(jnp.where(state.gvalid, state.row, 0.0))
        if self.rule.fold == "min":
            return state.base - tot / state.n_eff
        return tot / state.n_eff

    # -- per-step engine (the memory-capped path) ----------------------------

    def gains(self, state: RuleState, cands, cand_valid, orient="cw"):
        raw = ops.gains(state.ground, state.row, cands, cand_valid,
                        self.rule, backend=self.backend, orient=orient)
        return jnp.where(jnp.isfinite(raw), raw / state.n_eff, raw)

    def update(self, state: RuleState, payload) -> RuleState:
        row = R.update_row(state.ground, state.row, payload, self.rule)
        return dataclasses.replace(state, row=row)

    # -- planning ------------------------------------------------------------

    def plan_dims(self, state: RuleState, cands
                  ) -> Tuple[int, int, Optional[int]]:
        """(ground rows, candidates, feature dim) for plans.select_engine;
        bitmap rules plan over universe WORDS with no feature dim."""
        if self.rule.is_bitmap:
            return state.row.shape[0], cands.shape[0], None
        return (state.ground.shape[0], cands.shape[0],
                state.ground.shape[1])

    def _plan(self, state, cands, requested: str) -> EnginePlan:
        n, c, d = self.plan_dims(state, cands)
        return plans.select_engine(self.rule, n, c, d, requested=requested,
                                   backend=self.backend)

    # -- fused cached-matrix engine ------------------------------------------

    def prepare(self, state: RuleState, cands, cand_valid,
                plan: Optional[EnginePlan] = None):
        """One-time cached ground×candidate matrix + the EnginePlan that
        every step consumes (so block sizes are not re-derived k times);
        None in the memory-capped regime — callers then run the per-step
        path. For bitmap rules the matrix is a transpose of the candidate
        bitmaps: zero kernel dispatches."""
        del cand_valid
        if plan is None:
            plan = self._plan(state, cands, "fused")
        if not plan.cached:
            return None
        mat = ops.pairwise_matrix(state.ground, cands, self.rule,
                                  backend=self.backend, dtype=plan.dtype)
        return mat, plan

    def fused_step(self, state: RuleState, cache, cand_mask, prev):
        mat, plan = cache
        row, best, gain = ops.fused_step(mat, state.row, cand_mask, prev,
                                         self.rule, backend=self.backend,
                                         plan=plan)
        return (dataclasses.replace(state, row=row), best,
                gain / state.n_eff)

    def flush_pending(self, state: RuleState, cache, prev) -> RuleState:
        row = ops.apply_column(cache[0], state.row, prev, self.rule)
        return dataclasses.replace(state, row=row)

    # -- whole-greedy megakernel ---------------------------------------------

    def megakernel_loop(self, state: RuleState, cands, cand_valid, k: int,
                        plan: Optional[EnginePlan] = None):
        """All k selection steps in 1–2 dispatches (kernels/greedy_loop.py),
        or None when the planner refuses both megakernel tiers — callers
        drop to the fused/per-step engines (identical selections)."""
        if plan is None:
            plan = self._plan(state, cands, "mega")
        if plan.engine == "mega_resident":
            rows = ops.greedy_loop_resident(state.ground, cands, state.row,
                                            cand_valid, k, self.rule,
                                            backend=self.backend,
                                            cache_dtype=plan.dtype)
        elif plan.engine == "mega_stream":
            mat = ops.pairwise_matrix(state.ground, cands, self.rule,
                                      backend=self.backend,
                                      dtype=plan.dtype)
            rows = ops.greedy_loop(mat, state.row, cand_valid, k,
                                   self.rule, backend=self.backend,
                                   plan=plan)
        else:
            return None
        row, bests, gains = rows
        return (dataclasses.replace(state, row=row), bests,
                gains / state.n_eff)

    # -- batched serving (many queries, one dispatch) ------------------------

    def megakernel_loop_batched(self, payloads, valid, ks, k_max: int,
                                plan: Optional[EnginePlan] = None,
                                logical=None):
        """B rule-compatible queries as ONE vmapped resident dispatch
        (DESIGN §Serving): the query axis becomes a batch grid dim of the
        SAME pallas_call, so an admitted batch costs one kernel launch.

        payloads: (B, C, …) query pools pre-padded to a shared bucket
        shape (pad candidates carry zero payloads + valid=False); valid:
        (B, C); ks: (B,) per-query step budgets ≤ k_max (heterogeneous k
        — steps ≥ ks[i] are masked inside the kernel, so each query is
        bit-identical to its solo k=ks[i] run); logical: optional (B, 2)
        i32 per-query (ground-rows, candidates) logical extents bounding
        the sub-f32 rounding (defaults to the padded shape — correct
        when inputs are not pre-padded). Returns (stacked RuleStates,
        bests (B, k_max) i32 with −1 = rejected/masked, normalized gains
        (B, k_max)), or None when the planner refuses the resident tier
        — callers run each query solo (identical selections)."""
        bsz, c = valid.shape
        if self.rule.is_bitmap:
            n, d = self.words, None
        else:
            n, d = c, payloads.shape[-1]
        if plan is None:
            plan = plans.select_engine(self.rule, n, c, d,
                                       requested="mega",
                                       backend=self.backend)
        if plan.engine != "mega_resident":
            return None
        if logical is None:
            logical = jnp.broadcast_to(
                jnp.asarray([n, c], jnp.int32), (bsz, 2))

        def one(pay, val, kq, lim):
            state = self.init_state(pay, val)
            row, bests, gains = ops.greedy_loop_resident(
                state.ground, pay, state.row, val, k_max, self.rule,
                backend=self.backend, cache_dtype=plan.dtype,
                kq=kq, logical=(lim[0], lim[1]))
            return (dataclasses.replace(state, row=row), bests,
                    gains / state.n_eff)

        return jax.vmap(one)(payloads, valid,
                             jnp.asarray(ks, jnp.int32), logical)

    # -- batched replay ------------------------------------------------------

    def replay_batch(self, state: RuleState, payloads, valid) -> RuleState:
        """All k solution elements folded into a fresh state in ONE
        matrix pass (replaces the sequential k-step update scan)."""
        if self.rule.is_bitmap:
            mat = payloads.T                       # columns ARE the bitmaps
        else:
            mat = ops.pairwise_matrix(state.ground, payloads, self.rule,
                                      backend=self.backend)
        row = ops.masked_col_reduce(mat, valid, state.row, self.rule)
        return dataclasses.replace(state, row=row)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# name → factory(universe, backend, **params) → RuleObjective
_REGISTRY: Dict[str, Callable[..., RuleObjective]] = {}
_ALIASES = {"kcover": "coverage", "kdom": "coverage",
            "facility_location": "facility"}

DEFAULT_SAT_CAP = 2.0
DEFAULT_GC_ALPHA = 0.5     # graph-cut redundancy weight (cap = 1/α)
DEFAULT_MMR_LAM = 0.5      # MMR relevance weight λ
DEFAULT_MMR_THETA = 2.0    # MMR diversity saturation cap θ


def register(name: str, factory: Callable[..., RuleObjective]) -> None:
    """Register an objective factory. Registered names are automatically
    covered by the conformance suite (tests/test_objective_protocol.py)
    and the CI registry sweep (scripts/ci_smoke.sh)."""
    _REGISTRY[name] = factory


def registry() -> Tuple[str, ...]:
    """Canonical registered objective names, sorted."""
    return tuple(sorted(_REGISTRY))


def _coverage_factory(universe: int = 0, backend=None) -> RuleObjective:
    assert universe > 0, "coverage objectives need a universe size"
    return RuleObjective(R.BITS_OR, name="coverage",
                         words=(universe + 31) // 32, backend=backend)


def _kmedoid_factory(universe: int = 0, backend=None) -> RuleObjective:
    return RuleObjective(R.DIST_MIN, name="kmedoid", backend=backend)


def _facility_factory(universe: int = 0, backend=None) -> RuleObjective:
    return RuleObjective(R.DOT_MAX, name="facility", backend=backend)


def _satcover_factory(universe: int = 0, backend=None,
                      cap: float = DEFAULT_SAT_CAP) -> RuleObjective:
    # the spec-only objective: ONE rule line, no kernels, no class
    return RuleObjective(R.sat_sum(cap), name="satcover", backend=backend)


def _graphcut_factory(universe: int = 0, backend=None,
                      alpha: float = DEFAULT_GC_ALPHA) -> RuleObjective:
    # graph-cut-style coverage − α/2·redundancy² per ground row — a pure
    # spec on the 'sum' fold, zero objective- or kernel-specific code
    return RuleObjective(R.graph_cut(alpha), name="graphcut",
                         backend=backend)


def _mmr_factory(universe: int = 0, backend=None,
                 lam: float = DEFAULT_MMR_LAM,
                 theta: float = DEFAULT_MMR_THETA) -> RuleObjective:
    # MMR relevance–diversity tradeoff (λ modular relevance vs saturated
    # diversity-aware coverage) — the RAG retrieval-dedup serving spec
    return RuleObjective(R.mmr(lam, theta), name="mmr", backend=backend)


register("coverage", _coverage_factory)
register("kmedoid", _kmedoid_factory)
register("facility", _facility_factory)
register("satcover", _satcover_factory)
register("graphcut", _graphcut_factory)
register("mmr", _mmr_factory)


def make_objective(name: str, *, universe: int = 0, backend: str = None,
                   **params) -> RuleObjective:
    """Construct a registered objective ('kcover'/'kdom' alias coverage,
    'facility_location' aliases facility). Extra ``params`` go to the
    factory (e.g. satcover's ``cap``)."""
    key = _ALIASES.get(name, name)
    if key not in _REGISTRY:
        raise KeyError(name)
    return _REGISTRY[key](universe=universe, backend=backend, **params)
