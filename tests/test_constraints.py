"""Matroid + knapsack constraints (paper §7 future work): Greedy under
partition matroids — capacity respect, heredity, 1/2·OPT bound vs brute
force — plus the knapsack budget (per-element costs), its Composite
conjunction with matroids, the distributed KnapsackSpec threading, and
the streaming sieve's cost-ratio admission."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.constraints import Composite, Knapsack, KnapsackSpec, \
    PartitionMatroid, uniform_matroid
from repro.core.functions import make_objective
from repro.core.greedy import greedy
from repro.data.synthetic import gen_kcover, pack_bitmaps


def _cover(n, universe, seed):
    sets = gen_kcover(n, universe, seed=seed)
    return sets, jnp.asarray(pack_bitmaps(sets, universe))


@given(seed=st.integers(0, 5000))
@settings(max_examples=20, deadline=None)
def test_partition_matroid_capacities_respected(seed):
    n, u = 24, 64
    _, bm = _cover(n, u, seed)
    cats = jnp.asarray(np.arange(n) % 3, jnp.int32)
    caps = jnp.asarray([2, 1, 3], jnp.int32)
    obj = make_objective("kcover", universe=u)
    sol = greedy(obj, jnp.arange(n, dtype=jnp.int32), bm,
                 jnp.ones(n, bool), k=10,
                 constraint=PartitionMatroid(cats, caps))
    sel = np.asarray(sol.ids)[np.asarray(sol.valid)]
    counts = np.bincount(np.asarray(cats)[sel], minlength=3)
    assert np.all(counts <= np.asarray(caps)), (counts, sel)


def test_uniform_matroid_equals_cardinality():
    n, u, k = 32, 128, 6
    _, bm = _cover(n, u, 3)
    obj = make_objective("kcover", universe=u)
    plain = greedy(obj, jnp.arange(n, dtype=jnp.int32), bm,
                   jnp.ones(n, bool), k)
    mat = greedy(obj, jnp.arange(n, dtype=jnp.int32), bm,
                 jnp.ones(n, bool), k, constraint=uniform_matroid(n, k))
    assert float(plain.value) == float(mat.value)
    np.testing.assert_array_equal(np.asarray(plain.ids),
                                  np.asarray(mat.ids))


def _brute_force_matroid_opt(sets, universe, cats, caps, kmax):
    n = len(sets)
    best = 0
    for r in range(1, kmax + 1):
        for combo in itertools.combinations(range(n), r):
            counts = np.bincount(cats[list(combo)], minlength=len(caps))
            if np.any(counts > caps):
                continue
            cov = set()
            for e in combo:
                cov.update(sets[e].tolist())
            best = max(best, len(cov))
    return best


@given(seed=st.integers(0, 2000))
@settings(max_examples=10, deadline=None)
def test_greedy_matroid_half_opt_bound(seed):
    """Greedy is 1/2-approximate under matroid constraints (Fisher et al.)."""
    n, u = 9, 40
    sets, bm = _cover(n, u, seed)
    cats = np.arange(n) % 2
    caps = np.asarray([2, 1])
    opt = _brute_force_matroid_opt(sets, u, cats, caps, kmax=3)
    obj = make_objective("kcover", universe=u)
    sol = greedy(obj, jnp.arange(n, dtype=jnp.int32), bm,
                 jnp.ones(n, bool), k=3,
                 constraint=PartitionMatroid(
                     jnp.asarray(cats, jnp.int32),
                     jnp.asarray(caps, jnp.int32)))
    assert float(sol.value) >= 0.5 * opt - 1e-6


def test_matroid_composes_with_stochastic_sampling():
    n, u = 64, 256
    _, bm = _cover(n, u, 5)
    cats = jnp.asarray(np.arange(n) % 4, jnp.int32)
    caps = jnp.asarray([3, 3, 3, 3], jnp.int32)
    obj = make_objective("kcover", universe=u)
    sol = greedy(obj, jnp.arange(n, dtype=jnp.int32), bm,
                 jnp.ones(n, bool), k=12, sample=16,
                 key=jax.random.PRNGKey(2),
                 constraint=PartitionMatroid(cats, caps))
    sel = np.asarray(sol.ids)[np.asarray(sol.valid)]
    counts = np.bincount(np.asarray(cats)[sel], minlength=4)
    assert np.all(counts <= np.asarray(caps))
    assert float(sol.value) > 0


# ---------------------------------------------------------------------------
# knapsack (per-element costs, budget B)
# ---------------------------------------------------------------------------


def _costs(n, seed, lo=0.5, hi=2.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(lo, hi, size=(n,)), jnp.float32)


def _python_greedy_knapsack(sets, costs, budget, k):
    """Oracle transcription of the engines' knapsack greedy: each step
    masks infeasible candidates (spent + cost > B), takes the FIRST
    argmax marginal coverage gain, accepts iff gain > 0."""
    covered, picked, spent = set(), [], 0.0
    for _ in range(k):
        best, best_gain = -1, 0.0
        for e in range(len(sets)):
            if e in picked or spent + costs[e] > budget + 1e-6:
                continue
            gain = len(set(sets[e].tolist()) - covered)
            if gain > best_gain:
                best, best_gain = e, gain
        if best < 0:
            break
        picked.append(best)
        covered.update(sets[best].tolist())
        spent += costs[best]
    return picked, len(covered), spent


@given(seed=st.integers(0, 3000))
@settings(max_examples=15, deadline=None)
def test_knapsack_budget_and_heredity(seed):
    """Budget respected, and heredity: greedy accepts in PREFIX order,
    so every prefix of the selection must itself be feasible."""
    n, u, budget = 24, 64, 4.0
    _, bm = _cover(n, u, seed)
    costs = _costs(n, seed)
    obj = make_objective("kcover", universe=u)
    sol = greedy(obj, jnp.arange(n, dtype=jnp.int32), bm,
                 jnp.ones(n, bool), k=10,
                 constraint=Knapsack(costs, jnp.asarray(budget,
                                                        jnp.float32)))
    sel = np.asarray(sol.ids)[np.asarray(sol.valid)]
    c = np.asarray(costs)
    run = np.cumsum(c[sel]) if len(sel) else np.zeros((0,))
    assert np.all(run <= budget + 1e-5), (run, budget)


def test_knapsack_budget_exhaustion_freezes_selection():
    """Once nothing fits in the remaining budget, every later step must
    reject — no acceptance, no constraint-state drift."""
    n, u = 16, 96
    _, bm = _cover(n, u, 7)
    costs = jnp.full((n,), 2.0, jnp.float32)
    obj = make_objective("kcover", universe=u)
    sol = greedy(obj, jnp.arange(n, dtype=jnp.int32), bm,
                 jnp.ones(n, bool), k=8,
                 constraint=Knapsack(costs, jnp.asarray(3.0, jnp.float32)))
    # only ONE cost-2 element fits a budget of 3
    assert int(np.asarray(sol.valid).sum()) == 1
    ids = np.asarray(sol.ids)
    assert np.all(ids[1:] == -1), ids


@pytest.mark.parametrize("engine", ["step", "fused"])
@pytest.mark.parametrize("seed", [0, 11, 42])
def test_knapsack_greedy_matches_python_oracle(engine, seed):
    n, u, k, budget = 14, 48, 6, 5.0
    sets, bm = _cover(n, u, seed)
    costs = _costs(n, seed + 1)
    obj = make_objective("kcover", universe=u)
    sol = greedy(obj, jnp.arange(n, dtype=jnp.int32), bm,
                 jnp.ones(n, bool), k, engine=engine,
                 constraint=Knapsack(costs,
                                     jnp.asarray(budget, jnp.float32)))
    sel = np.asarray(sol.ids)[np.asarray(sol.valid)].tolist()
    picked, cov, _ = _python_greedy_knapsack(sets, np.asarray(costs),
                                             budget, k)
    assert sel == picked
    assert float(sol.value) == pytest.approx(cov)


def test_knapsack_composes_with_partition_matroid():
    """Composite = AND of constraints: a selection must satisfy BOTH the
    budget and the per-category capacities."""
    n, u, budget = 24, 96, 6.0
    _, bm = _cover(n, u, 9)
    costs = _costs(n, 3)
    cats = jnp.asarray(np.arange(n) % 3, jnp.int32)
    caps = jnp.asarray([2, 2, 1], jnp.int32)
    obj = make_objective("kcover", universe=u)
    con = Composite((Knapsack(costs, jnp.asarray(budget, jnp.float32)),
                     PartitionMatroid(cats, caps)))
    sol = greedy(obj, jnp.arange(n, dtype=jnp.int32), bm,
                 jnp.ones(n, bool), k=10, constraint=con)
    sel = np.asarray(sol.ids)[np.asarray(sol.valid)]
    assert np.asarray(costs)[sel].sum() <= budget + 1e-5
    counts = np.bincount(np.asarray(cats)[sel], minlength=3)
    assert np.all(counts <= np.asarray(caps)), counts
    assert float(sol.value) > 0


def test_knapsack_spec_threads_through_distributed_tree():
    """KnapsackSpec binds GLOBAL-id-indexed costs at every tree stage, so
    the distributed selection respects the budget even though gathered
    node pools reorder elements."""
    from repro.core.greedyml import LevelDispatcher, root_solution, \
        shard_lanes
    n, u, k, budget = 64, 192, 6, 5.0
    _, bm = _cover(n, u, 13)
    costs = _costs(n, 5)
    obj = make_objective("kcover", universe=u)
    spec = KnapsackSpec(costs, budget)
    disp = LevelDispatcher(obj, k, radices=(2, 2), constraint=spec)
    ids, pay, val = shard_lanes(jnp.arange(n, dtype=jnp.int32), bm,
                                jnp.ones(n, bool), disp.lanes)
    sols = disp.leaves(ids, pay, val)
    for lvl in range(disp.num_levels):
        sols = disp.level(sols, lvl)
    sol = root_solution(sols)
    sel = np.asarray(sol.ids)[np.asarray(sol.valid)]
    assert len(sel) > 0
    assert np.asarray(costs)[sel].sum() <= budget + 1e-5
    # every leaf lane's own selection respected the budget too (heredity
    # of the spec across stages, Theorem 4.4's feasibility argument)
    lids = np.asarray(sols.ids)
    lval = np.asarray(sols.valid)
    for lane in range(lids.shape[0]):
        lane_sel = lids[lane][lval[lane]]
        assert np.asarray(costs)[lane_sel].sum() <= budget + 1e-5


def _brute_force_knapsack_opt(sets, costs, budget, kmax):
    n = len(sets)
    best = 0
    for r in range(1, kmax + 1):
        for combo in itertools.combinations(range(n), r):
            if costs[list(combo)].sum() > budget + 1e-6:
                continue
            cov = set()
            for e in combo:
                cov.update(sets[e].tolist())
            best = max(best, len(cov))
    return best


@pytest.mark.parametrize("seed", [1, 8, 23])
def test_sieve_cost_ratio_quality_band(seed):
    """Streaming knapsack: the cost-ratio sieve's best level must land
    within a constant-factor band of the brute-force knapsack OPT on
    small instances, and never overspend."""
    from repro.core.objective import make_objective as make_obj
    from repro.streaming.sieve import SieveStreamer
    n, u, k, budget, nb = 12, 40, 6, 4.0, 4
    sets, bm = _cover(n, u, seed)
    costs = np.asarray(_costs(n, seed + 2))
    opt = _brute_force_knapsack_opt(sets, costs, budget, kmax=k)
    obj = make_obj("kcover", universe=u)
    st_ = SieveStreamer(obj, k, budget=budget)
    state = st_.init(payload_example=bm)
    for b0 in range(0, n, nb):
        sl = slice(b0, b0 + nb)
        state = st_.process_batch(
            state, jnp.arange(n, dtype=jnp.int32)[sl], bm[sl],
            jnp.ones((nb,), bool), costs=jnp.asarray(costs[sl]))
    assert np.all(np.asarray(state.spent) <= budget + 1e-5)
    sol = st_.solution(state)
    got = float(sol.value)
    assert got >= 0.25 * opt - 1e-6, (got, opt)


def test_graphcut_mmr_registered_and_swept():
    """The registry sweep (ci_smoke) iterates registry() — the new specs
    must be there, and the conformance suite must collect tests for
    them (the sweep fails CI otherwise; this is the in-suite mirror)."""
    from repro.core.objective import registry
    names = registry()
    assert "graphcut" in names and "mmr" in names
    import os
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "tests/test_objective_protocol.py", "-k", "graphcut or mmr"],
        capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "PYTHONPATH": "src"})
    n = out.stdout.count("::")
    assert n >= 2, out.stdout[-2000:]
