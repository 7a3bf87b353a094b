"""Property-based tests (hypothesis) on the system's submodular invariants:
diminishing returns, monotonicity, greedy's (1−1/e) bound vs brute-force
OPT, and GreedyML's α/(L+1) bound (Theorem 4.4) on exhaustive instances."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.functions import make_objective
from repro.core.greedy import greedy, replay_value, select_better
from repro.core.simulate import run_tree_dense, run_greedy_dense
from repro.core.tree import AccumulationTree
from repro.data.synthetic import gen_kcover, pack_bitmaps

SETTINGS = dict(max_examples=25, deadline=None)


def _instance(n, universe, seed):
    sets = gen_kcover(n, universe, seed=seed)
    return pack_bitmaps(sets, universe), sets


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_coverage_diminishing_returns(seed):
    """gains(state ∪ {e}) ≤ gains(state) elementwise — submodularity."""
    bm, _ = _instance(24, 64, seed)
    obj = make_objective("kcover", universe=64)
    pay = jnp.asarray(bm)
    valid = jnp.ones(24, bool)
    state = obj.init_state(pay, valid)
    g0 = obj.gains(state, pay, valid)
    state2 = obj.update(state, pay[int(np.argmax(g0))])
    g1 = obj.gains(state2, pay, valid)
    assert bool(jnp.all(g1 <= g0 + 1e-6))


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_coverage_monotone_value(seed):
    bm, _ = _instance(16, 64, seed)
    obj = make_objective("kcover", universe=64)
    pay = jnp.asarray(bm)
    state = obj.init_state(pay, jnp.ones(16, bool))
    prev = float(obj.value(state))
    for i in range(8):
        state = obj.update(state, pay[i])
        cur = float(obj.value(state))
        assert cur >= prev - 1e-6
        prev = cur


@given(seed=st.integers(0, 5_000), d=st.integers(4, 24))
@settings(**SETTINGS)
def test_facility_diminishing_returns(seed, d):
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(20, d)).astype(np.float32)
    obj = make_objective("facility")
    pay = jnp.asarray(pts)
    valid = jnp.ones(20, bool)
    state = obj.init_state(pay, valid)
    g0 = obj.gains(state, pay, valid)
    state = obj.update(state, pay[int(np.argmax(g0))])
    g1 = obj.gains(state, pay, valid)
    assert bool(jnp.all(g1 <= g0 + 1e-5))


def _brute_force_opt(sets, universe, k):
    best = 0
    for combo in itertools.combinations(range(len(sets)), k):
        cov = set()
        for e in combo:
            cov.update(sets[e].tolist())
        best = max(best, len(cov))
    return best


@given(seed=st.integers(0, 2_000))
@settings(max_examples=15, deadline=None)
def test_greedy_one_minus_inv_e_bound(seed):
    """Greedy ≥ (1−1/e)·OPT for cardinality-constrained coverage."""
    bm, sets = _instance(10, 48, seed)
    k = 3
    opt = _brute_force_opt(sets, 48, k)
    obj = make_objective("kcover", universe=48)
    sol = greedy(obj, jnp.arange(10, dtype=jnp.int32), jnp.asarray(bm),
                 jnp.ones(10, bool), k)
    assert float(sol.value) >= (1 - 1 / np.e) * opt - 1e-6


@given(seed=st.integers(0, 2_000), b=st.sampled_from([2, 3]))
@settings(max_examples=10, deadline=None)
def test_greedyml_alpha_over_Lplus1_bound(seed, b):
    """Theorem 4.4: E[f(GreedyML)] ≥ α/(L+1)·OPT; single draws satisfy the
    bound on these instances (empirically far above it, like the paper)."""
    bm, sets = _instance(12, 48, seed)
    k = 3
    opt = _brute_force_opt(sets, 48, k)
    tree = AccumulationTree(4, b)
    res = run_tree_dense("kcover", bm, k, tree, seed=seed, universe=48)
    alpha = 1 - 1 / np.e
    bound = alpha / (tree.num_levels + 1) * opt
    assert res.value >= bound - 1e-6


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_greedy_never_beats_bruteforce(seed):
    bm, sets = _instance(9, 40, seed)
    k = 3
    opt = _brute_force_opt(sets, 40, k)
    obj = make_objective("kcover", universe=40)
    sol = greedy(obj, jnp.arange(9, dtype=jnp.int32), jnp.asarray(bm),
                 jnp.ones(9, bool), k)
    assert float(sol.value) <= opt + 1e-6


@given(seed=st.integers(0, 10_000), k=st.integers(1, 6))
@settings(**SETTINGS)
def test_greedy_solution_valid(seed, k):
    """Selected ids unique, ≤ k, value == replay of its own payloads."""
    bm, _ = _instance(20, 64, seed)
    obj = make_objective("kcover", universe=64)
    pay = jnp.asarray(bm)
    valid = jnp.ones(20, bool)
    sol = greedy(obj, jnp.arange(20, dtype=jnp.int32), pay, valid, k)
    ids = np.asarray(sol.ids)[np.asarray(sol.valid)]
    assert len(set(ids.tolist())) == len(ids) <= k
    rv = replay_value(obj, sol.payloads, sol.valid, pay, valid)
    assert abs(float(rv) - float(sol.value)) < 1e-5


def test_select_better_picks_max():
    bm, _ = _instance(16, 64, 0)
    obj = make_objective("kcover", universe=64)
    pay = jnp.asarray(bm)
    a = greedy(obj, jnp.arange(16, dtype=jnp.int32), pay,
               jnp.ones(16, bool), 4)
    b = greedy(obj, jnp.arange(16, dtype=jnp.int32), pay,
               jnp.arange(16) < 4, 4)
    best = select_better(a, b)
    assert float(best.value) == max(float(a.value), float(b.value))


@given(seed=st.integers(0, 5_000))
@settings(max_examples=10, deadline=None)
def test_greedyml_le_greedy_value(seed):
    """Distribution can only lose vs sequential greedy on coverage (both
    bounded by OPT; greedy is the stronger heuristic on small instances)."""
    bm, _ = _instance(64, 256, seed)
    g = run_greedy_dense("kcover", bm, 8, universe=256)
    ml = run_tree_dense("kcover", bm, 8, AccumulationTree(4, 2), seed=seed,
                        universe=256)
    assert ml.value <= g.value * 1.25 + 1e-6  # sanity band
    assert ml.value >= 0.5 * g.value          # far above worst case, per paper
