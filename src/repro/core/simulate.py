"""Single-device simulation of GreedyML's accumulation tree T(m, L, b).

Two engines with identical tree semantics:

  * **dense** — the TPU algorithm (core.greedy vectorized gains) with leaves
    vmapped over machines and internal nodes vmapped per level; runs on one
    CPU device, supports ragged trees (≤1 node with arity < b per level,
    exactly as the paper). Used for quality experiments.

  * **lazy**  — the paper's actual implementation: Lazy Greedy (Minoux) with
    a priority queue over SPARSE adjacency data, counting true function
    evaluations per node. Used to reproduce the paper's call-count metrics
    (Fig. 4/5, Table 3): the critical path is the id-0 chain, 'the number of
    function calls made by nodes of the accumulation tree with id = 0'.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.functions import make_objective
from repro.core.greedy import greedy
from repro.core.greedyml import LevelDispatcher, root_solution
from repro.core.tree import AccumulationTree


@dataclasses.dataclass
class SimResult:
    value: float
    ids: np.ndarray                 # selected global element ids (≤ k)
    evals_total: int
    evals_critical: int             # id-0 chain (parallel-runtime proxy)
    per_node_evals: Dict[Tuple[int, int], int]
    comm_elements: int              # total solution elements communicated
    levels: int
    machines: int
    branching: int


def partition(n: int, m: int, seed: int) -> np.ndarray:
    """The paper's random tape: each element iid uniform over machines."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, m, size=n)


def global_value(objective_name: str, data: Any, ids: np.ndarray,
                 universe: int = 0) -> float:
    """f(S) evaluated on the FULL ground set — the reporting convention.

    During optimization k-medoid/facility nodes use the paper's local
    objective (§6.4); final qualities must be compared on one ground set.
    """
    ids = np.asarray(ids)
    ids = ids[ids >= 0]
    if objective_name in ("kcover", "kdom"):
        if isinstance(data, np.ndarray) and data.dtype == np.uint32:
            cov = np.zeros(data.shape[1], np.uint32)
            for e in ids:
                cov |= data[e]
            return float(np.unpackbits(cov.view(np.uint8)).sum())
        covered = np.zeros(universe, bool)
        for e in ids:
            covered[data[e]] = True
        return float(covered.sum())
    x = np.asarray(data, np.float32)
    if objective_name == "kmedoid":
        mind = np.linalg.norm(x, axis=1)          # d(·, e0)
        base = mind.mean()
        for e in ids:
            mind = np.minimum(mind,
                              np.linalg.norm(x - x[e][None, :], axis=1))
        return float(base - mind.mean())
    if objective_name == "facility":
        if len(ids) == 0:
            return 0.0
        sims = x @ x[ids].T
        return float(np.maximum(sims.max(axis=1), 0.0).mean())
    raise KeyError(objective_name)


# ---------------------------------------------------------------------------
# Dense engine (the TPU algorithm, vmapped)
# ---------------------------------------------------------------------------


def run_tree_dense(objective_name: str, payloads: np.ndarray, k: int,
                   tree: AccumulationTree, seed: int = 0, *,
                   universe: int = 0, augment: int = 0,
                   backend: Optional[str] = None,
                   engine: str = "auto",
                   node_engine: Optional[str] = None,
                   drop_leaves: Sequence[int] = ()) -> SimResult:
    """The paper's tree on one device: the random tape deals the elements
    to padded per-machine pools, and `LevelDispatcher` (no mesh) runs the
    leaf Greedy and every accumulation level. A ragged tree (m < b^L) runs
    as b^L lanes whose machines m … b^L−1 hold all-invalid pools; their
    slots sort after every real child's in each union, so each node's
    Greedy sees the candidates the paper's node sees, in the same order.

    ``engine`` drives the leaf Greedy calls; ``node_engine`` (default:
    inherit) the accumulation nodes. ``augment`` > 0 adds, for k-medoid
    and facility location, one sample of that many elements per level to
    every node's ground (paper §6.4), drawn from the ``seed + 1`` tape.

    ``drop_leaves``: machine ids whose partitions are LOST (their pools
    are invalidated, so they contribute empty leaf solutions) — the
    single-device reference for the degraded-tree fault-recovery path
    (runtime/supervisor.py): losing a constant fraction of partitions
    costs only the Barbosa et al. (1502.02606) / Lucic et al.
    (1605.09619) expected-quality term, which tests assert as a
    tolerance band against the failure-free run."""
    n = payloads.shape[0]
    m, b, L = tree.m, tree.b, tree.num_levels
    lanes = b ** L
    obj = make_objective(objective_name, universe=universe, backend=backend)
    assign = partition(n, m, seed)
    counts = np.bincount(assign, minlength=m)

    # padded per-machine pools, each in ascending element id
    order = np.argsort(assign, kind="stable")
    slot = np.arange(n) - np.repeat(np.cumsum(counts) - counts, counts)
    pool_ids = np.full((lanes, int(counts.max())), -1, np.int32)
    pool_ids[assign[order], slot] = order
    pool_valid = pool_ids >= 0
    pool_pay = np.zeros(pool_ids.shape + payloads.shape[1:], payloads.dtype)
    pool_pay[pool_valid] = payloads[pool_ids[pool_valid]]
    pool_valid[list(drop_leaves)] = False   # lost partition → empty leaf

    aug = None
    if augment > 0 and objective_name in ("kmedoid", "facility"):
        rng = np.random.default_rng(seed + 1)
        aug = jnp.asarray(payloads[rng.integers(0, n, size=(L, augment))])

    disp = LevelDispatcher(obj, k, (b,) * L, engine=engine,
                           node_engine=node_engine)
    sols = disp.leaves(jnp.asarray(pool_ids), jnp.asarray(pool_pay),
                       jnp.asarray(pool_valid))
    evals = np.asarray(sols.evals)
    per_node: Dict[Tuple[int, int], int] = {
        (0, i): int(evals[i]) for i in range(m)}
    comm = 0
    for lvl in range(1, L + 1):
        valid = np.asarray(sols.valid)
        sols = disp.level(sols, lvl - 1, None if aug is None else aug[lvl - 1])
        # select_better chains evals: a node's own are the increment
        new_evals = np.asarray(sols.evals)
        for nid in tree.nodes_at_level(lvl):
            per_node[(lvl, nid)] = int(new_evals[nid] - evals[nid])
            comm += int(valid[tree.children_of(lvl, nid)].sum())
        evals = new_evals

    final = root_solution(sols)
    evals_critical = sum(per_node[(lvl, 0)] for lvl in range(L + 1))
    ids_out = np.asarray(final.ids)[np.asarray(final.valid)]
    gval = global_value(objective_name, payloads, ids_out, universe)
    return SimResult(gval, ids_out,
                     int(sum(per_node.values())), int(evals_critical),
                     per_node, comm, L, m, b)


def run_greedy_dense(objective_name: str, payloads: np.ndarray, k: int, *,
                     universe: int = 0,
                     backend: Optional[str] = None,
                     engine: str = "auto") -> SimResult:
    """Sequential Greedy baseline (one node, whole data)."""
    obj = make_objective(objective_name, universe=universe, backend=backend)
    n = payloads.shape[0]
    sol = jax.jit(lambda i, p, v: greedy(obj, i, p, v, k, engine=engine))(
        jnp.arange(n, dtype=jnp.int32), jnp.asarray(payloads),
        jnp.ones(n, bool))
    ids_out = np.asarray(sol.ids)[np.asarray(sol.valid)]
    gval = global_value(objective_name, payloads, ids_out, universe)
    return SimResult(gval, ids_out, int(sol.evals),
                     int(sol.evals), {(0, 0): int(sol.evals)}, 0, 0, 1, 1)


# ---------------------------------------------------------------------------
# Lazy engine (the paper's implementation: Minoux lazy greedy, sparse data)
# ---------------------------------------------------------------------------


class SparseCoverage:
    """k-cover / k-dominating-set over adjacency lists (paper's repr)."""

    def __init__(self, sets: Sequence[np.ndarray], universe: int):
        self.sets = sets
        self.covered = np.zeros(universe, bool)
        self.total = 0

    def marginal(self, e: int) -> float:
        s = self.sets[e]
        return float(np.count_nonzero(~self.covered[s]))

    def add(self, e: int) -> None:
        s = self.sets[e]
        self.total += int(np.count_nonzero(~self.covered[s]))
        self.covered[s] = True

    def value(self) -> float:
        return float(self.total)


class DenseMedoid:
    """k-medoid over a LOCAL evaluation ground set (paper §6.4)."""

    def __init__(self, data: np.ndarray, ground_idx: np.ndarray):
        self.data = data
        self.ground = data[ground_idx].astype(np.float32)
        self.mind = np.linalg.norm(self.ground, axis=1)   # d(·, e0)
        self.base = float(self.mind.mean())

    def marginal(self, e: int) -> float:
        d = np.linalg.norm(self.ground - self.data[e][None, :], axis=1)
        return float(np.maximum(self.mind - d, 0.0).mean())

    def add(self, e: int) -> None:
        d = np.linalg.norm(self.ground - self.data[e][None, :], axis=1)
        self.mind = np.minimum(self.mind, d)

    def value(self) -> float:
        return self.base - float(self.mind.mean())


def lazy_greedy(state, candidates: Sequence[int], k: int
                ) -> Tuple[List[int], float, int]:
    """Minoux accelerated greedy. Returns (selected, value, n_evals)."""
    evals = 0
    heap = []
    for e in candidates:
        heap.append((-state.marginal(e), e, 0))
        evals += 1
    heapq.heapify(heap)
    selected: List[int] = []
    stamp = 0
    while heap and len(selected) < k:
        neg, e, st = heapq.heappop(heap)
        if st == stamp:
            if -neg <= 0:
                break
            state.add(e)
            selected.append(e)
            stamp += 1
        else:
            g = state.marginal(e)
            evals += 1
            heapq.heappush(heap, (-g, e, stamp))
    return selected, state.value(), evals


def run_tree_lazy(objective_name: str, data: Any, k: int,
                  tree: AccumulationTree, seed: int = 0, *,
                  universe: int = 0, augment: int = 0) -> SimResult:
    """data: list[np.ndarray] adjacency (coverage) or (n, d) array (medoid)."""
    n = len(data)
    m, b, L = tree.m, tree.b, tree.num_levels
    assign = partition(n, m, seed)
    rng = np.random.default_rng(seed + 1)

    def make_state(ground_idx: np.ndarray):
        if objective_name in ("kcover", "kdom"):
            return SparseCoverage(data, universe)
        return DenseMedoid(np.asarray(data), ground_idx)

    per_node: Dict[Tuple[int, int], int] = {}
    comm = 0
    sols: Dict[int, Tuple[List[int], float]] = {}
    for mi in range(m):
        cand = np.nonzero(assign == mi)[0]
        st = make_state(cand)
        sel, val, ev = lazy_greedy(st, cand.tolist(), k)
        sols[mi] = (sel, val)
        per_node[(0, mi)] = ev

    for lvl in range(1, L + 1):
        new_sols: Dict[int, Tuple[List[int], float]] = {}
        for nid in tree.nodes_at_level(lvl):
            ch = tree.children_of(lvl, nid)
            union: List[int] = []
            for cid in ch:
                union.extend(sols[cid][0])
                comm += len(sols[cid][0])
            ground = np.asarray(union, np.int64)
            if augment > 0 and objective_name == "kmedoid":
                ground = np.concatenate(
                    [ground, rng.integers(0, n, size=augment)])
            st = make_state(ground)
            sel, val, ev = lazy_greedy(st, union, k)
            per_node[(lvl, nid)] = ev
            # argmax{f(S), f(S_prev)} with S_prev = same-id child
            prev_sel, _ = sols[nid]
            st2 = make_state(ground)
            for e in prev_sel:
                st2.add(e)
            prev_val = st2.value()
            new_sols[nid] = (sel, val) if val >= prev_val else (prev_sel,
                                                                prev_val)
        sols = new_sols

    sel, val = sols[0]
    evals_critical = sum(per_node[(lvl, 0)] for lvl in range(L + 1))
    gval = global_value(objective_name, data, np.asarray(sel, np.int64),
                        universe)
    return SimResult(gval, np.asarray(sel), int(sum(per_node.values())),
                     int(evals_critical), per_node, comm, L, m, b)


def run_greedy_lazy(objective_name: str, data: Any, k: int, *,
                    universe: int = 0) -> SimResult:
    n = len(data)
    if objective_name in ("kcover", "kdom"):
        st = SparseCoverage(data, universe)
    else:
        st = DenseMedoid(np.asarray(data), np.arange(n))
    sel, val, ev = lazy_greedy(st, list(range(n)), k)
    gval = global_value(objective_name, data, np.asarray(sel, np.int64),
                        universe)
    return SimResult(gval, np.asarray(sel), ev, ev, {(0, 0): ev},
                     0, 0, 1, 1)
