"""Accumulation-tree structure invariants (hypothesis over (m, b))."""
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.tree import (AccumulationTree, MixedRadixTree, children,
                             level_of, parent, randgreedi_tree)


@given(m=st.integers(2, 64), b=st.integers(2, 8))
@settings(max_examples=60, deadline=None)
def test_levels_formula(m, b):
    t = AccumulationTree(m, b)
    assert t.num_levels == math.ceil(math.log(m, b)) or m == 1


@given(m=st.integers(2, 64), b=st.integers(2, 8))
@settings(max_examples=60, deadline=None)
def test_every_machine_has_root_path(m, b):
    """Following parent() from any leaf reaches node 0 at the top level."""
    t = AccumulationTree(m, b)
    for mid in range(m):
        assert parent(mid, t.num_levels, b) == 0


@given(m=st.integers(2, 64), b=st.integers(2, 8))
@settings(max_examples=60, deadline=None)
def test_children_partition_level(m, b):
    """At every level, children of the level's nodes exactly cover the
    previous level's nodes, disjointly (ragged-aware)."""
    t = AccumulationTree(m, b)
    for lvl in range(1, t.num_levels + 1):
        prev = set(t.nodes_at_level(lvl - 1))
        seen = []
        for nid in t.nodes_at_level(lvl):
            ch = t.children_of(lvl, nid)
            assert ch[0] == nid            # lowest child id = own id
            seen.extend(ch)
        assert sorted(seen) == sorted(prev)
        assert len(seen) == len(set(seen))


@given(m=st.integers(2, 64), b=st.integers(2, 8))
@settings(max_examples=60, deadline=None)
def test_at_most_one_ragged_node_per_level(m, b):
    t = AccumulationTree(m, b)
    for lvl in range(1, t.num_levels + 1):
        arities = [len(t.children_of(lvl, nid))
                   for nid in t.nodes_at_level(lvl)]
        assert sum(1 for a in arities if a < b) <= 1
        assert all(a >= 1 for a in arities)


@given(mid=st.integers(0, 63), b=st.integers(2, 8))
@settings(max_examples=60, deadline=None)
def test_level_of_matches_divisibility(mid, b):
    lvl = level_of(mid, b, num_levels=10)
    if mid == 0:
        assert lvl == 10
    else:
        assert mid % (b ** lvl) == 0
        assert mid % (b ** (lvl + 1)) != 0


def test_randgreedi_is_single_level():
    t = randgreedi_tree(17)
    assert t.num_levels == 1
    assert t.children_of(1, 0) == list(range(17))


def test_mixed_radix_coords():
    t = MixedRadixTree((16, 16, 2))
    assert t.m == 512
    assert t.machine_coords(0) == (0, 0, 0)
    assert t.machine_coords(511) == (15, 15, 1)
    assert t.machine_coords(17) == (1, 1, 0)


@pytest.mark.parametrize("obj", ["coverage", "kmedoid"])
def test_cost_model_tradeoffs(obj):
    """Table 1 structure: deeper trees shrink interior cost & comm per node,
    RandGreedi (L=1) maximizes both."""
    n, k, delta = 1_000_000, 1000, 8.0
    rg = randgreedi_tree(64).cost_model(n, k, delta, obj)
    ml = AccumulationTree(64, 2).cost_model(n, k, delta, obj)
    assert ml["elements_per_interior"] < rg["elements_per_interior"]
    assert ml["comm_cost"] < rg["comm_cost"]
    assert ml["levels"] == 6 and rg["levels"] == 1


def test_cost_model_bsp_terms_exact():
    """Table 1, term by term: per-machine element/call counts, the BSP
    compute/comm split, and linear delta scaling — the exact quantities
    plans.plan_tree validates feasible tree shapes against."""
    n, k, delta = 4096, 32, 1.0
    t = AccumulationTree(16, 4)                 # m = b^L: 16 = 4^2
    mdl = t.cost_model(n, k, delta)
    assert (mdl["machines"], mdl["branching"], mdl["levels"]) == (16, 4, 2)
    assert mdl["elements_per_leaf"] == n / 16
    assert mdl["calls_per_leaf"] == n * k / 16
    assert mdl["elements_per_interior"] == k * 4          # the b*k pool
    assert mdl["calls_per_interior"] == (k * 4) * k
    assert mdl["calls_critical_path"] == n * k / 16 + 2 * (k * 4) * k
    assert mdl["compute_cost"] == k * (n / 16 + 2 * 4 * k)
    assert mdl["comm_cost"] == k * 2 * 4
    km = t.cost_model(n, k, delta, objective="kmedoid")
    assert km["compute_cost"] == (n / 16) ** 2 * k + 2 * (k * 4) ** 2 * k
    half = t.cost_model(n, k, 0.5)
    assert half["compute_cost"] == 0.5 * mdl["compute_cost"]
    assert half["comm_cost"] == 0.5 * mdl["comm_cost"]


@given(m=st.integers(2, 64), b=st.integers(2, 8))
@settings(max_examples=40, deadline=None)
def test_cost_model_structure_matches_tree(m, b):
    """The structural terms plan_tree asserts on hold for every (m, b):
    levels match num_levels and the interior pool is always b*k."""
    t = AccumulationTree(m, b)
    mdl = t.cost_model(10_000, 64, 2.0)
    assert mdl["levels"] == t.num_levels
    assert mdl["elements_per_interior"] == 64 * b
    assert mdl["calls_per_interior"] == 64 * mdl["elements_per_interior"]
    assert mdl["calls_critical_path"] == (mdl["calls_per_leaf"]
                                          + t.num_levels
                                          * mdl["calls_per_interior"])
