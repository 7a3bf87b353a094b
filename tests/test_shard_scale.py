"""Paper-scale sharded selection: tier gates, memory-model tree planner,
bit-identity of the cross-device sharded engine, per-lane dispatch
accounting, and the supervised planner default.

The 8-device mesh checks run in a subprocess (forced host devices) so
the in-process test session keeps the single real CPU device."""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.greedy import Solution, greedy
from repro.core.objective import make_objective
from repro.kernels import ops, plans
from repro.kernels.shard_gains import (shard_greedy_distributed,
                                       shard_greedy_sim)
from repro.runtime.supervisor import (LaneFailureInjector,
                                      SelectionSupervisor, WorkerFailure)

BUDGET = "REPRO_FUSED_CACHE_MB"


def _pool(n, d, seed=0):
    pay = jax.random.normal(jax.random.PRNGKey(seed), (n, d), jnp.float32)
    return (jnp.arange(n, dtype=jnp.int32), pay, jnp.ones((n,), bool))


# ---------------------------------------------------------------------------
# tier gate + escalation
# ---------------------------------------------------------------------------

def test_shard_plan_gates(monkeypatch):
    monkeypatch.setenv(BUDGET, "0.02")
    feat = make_objective("facility").rule
    bit = make_objective("coverage", universe=512).rule
    assert plans.shard_plan(bit, 512, None, 8) is None      # bitmap ground
    assert plans.shard_plan(feat, 512, 16, 1) is None       # nothing to shard
    sp = plans.shard_plan(feat, 512, 16, 8)
    assert sp is not None and sp["dtype"] == "float32"
    # the ladder picks the WIDEST tile whose working set fits
    assert sp["tile_c"] == 16
    assert sp["bytes"] == plans.shard_bytes(512, 16, 8, 16) <= 0.02 * 2 ** 20
    monkeypatch.setenv(BUDGET, "0.001")                     # min tile busts
    assert plans.shard_plan(feat, 512, 16, 8) is None


def test_select_engine_escalates_to_sharded(monkeypatch):
    monkeypatch.setenv(BUDGET, "0.02")
    rule = make_objective("facility").rule
    p = plans.select_engine(rule, 512, 512, 16, lanes=8)
    assert p.engine == "sharded" and p.lanes == 8 and p.tile_c == 16
    assert not p.cached
    # per-step host logic (sampling / constraints) demotes to step
    assert plans.select_engine(rule, 512, 512, 16, lanes=8,
                               sampling=True).engine == "step"
    assert plans.select_engine(rule, 512, 512, 16, lanes=8,
                               constrained=True).engine == "step"
    # a single lane can never escalate
    assert plans.select_engine(rule, 512, 512, 16).engine == "step"
    monkeypatch.delenv(BUDGET)
    # roomy budget: a cached solo tier wins before escalation fires
    assert plans.select_engine(rule, 512, 512, 16, lanes=8).cached


# ---------------------------------------------------------------------------
# bit-identity: the sharded engine IS solo greedy over the same pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["facility", "kmedoid", "satcover"])
@pytest.mark.parametrize("lanes", [2, 4])
def test_sim_bit_identical_to_solo(name, lanes):
    obj = make_objective(name)
    ids, pay, val = _pool(96, 8, seed=3)
    solo = greedy(obj, ids, pay, val, 6, engine="step")
    sim = shard_greedy_sim(obj, ids, pay, val, 6, lanes=lanes, tile_c=8)
    assert np.array_equal(np.asarray(sim.ids), np.asarray(solo.ids))
    assert np.array_equal(np.asarray(sim.valid), np.asarray(solo.valid))
    np.testing.assert_allclose(np.asarray(sim.value),
                               np.asarray(solo.value), rtol=1e-5, atol=1e-5)


def test_sim_handles_invalid_and_ragged_pools():
    """Padding rows (-1 ids, invalid) never win; a pool that does not
    split evenly across lanes still matches solo exactly."""
    obj = make_objective("facility")
    ids, pay, val = _pool(90, 8, seed=7)            # 90 !| 4 lanes
    val = val.at[::7].set(False)
    solo = greedy(obj, ids, pay, val, 5, engine="step")
    sim = shard_greedy_sim(obj, ids, pay, val, 5, lanes=4, tile_c=8)
    assert np.array_equal(np.asarray(sim.ids), np.asarray(solo.ids))
    assert np.array_equal(np.asarray(sim.valid), np.asarray(solo.valid))


# ---------------------------------------------------------------------------
# dispatch accounting: k gains dispatches per tile, PER LANE
# ---------------------------------------------------------------------------

def _abstract_shard_mesh(lanes):
    from jax.sharding import AbstractMesh
    return AbstractMesh((lanes,), ("shard",))


def test_dispatch_count_per_lane_contract():
    """ops.count_pallas_dispatches under shard_map counts ONE lane's SPMD
    program (the documented contract): the sharded leaf is exactly
    k * ntiles gains dispatches, identical between the vmap simulation
    and the real shard_map jaxpr — NOT multiplied by the lane count."""
    obj = make_objective("facility", backend="interpret")
    k, lanes, n, d, tile = 5, 4, 64, 8, 8
    ids, pay, val = _pool(n, d)
    sim_jaxpr = jax.make_jaxpr(
        lambda i, p, v: shard_greedy_sim(obj, i, p, v, k, lanes=lanes,
                                         tile_c=tile))(ids, pay, val)
    mesh = _abstract_shard_mesh(lanes)
    map_jaxpr = jax.make_jaxpr(
        lambda i, p, v: shard_greedy_distributed(obj, i, p, v, k, mesh,
                                                 tile_c=tile))(ids, pay, val)
    ntiles = (n // lanes) // tile
    assert ops.count_pallas_dispatches(sim_jaxpr) == k * ntiles
    assert ops.count_pallas_dispatches(map_jaxpr) == k * ntiles


# ---------------------------------------------------------------------------
# memory-model tree planner
# ---------------------------------------------------------------------------

def test_plan_tree_beats_flat_and_solo(monkeypatch):
    monkeypatch.setenv(BUDGET, "0.25")
    rule = make_objective("facility").rule
    d, k, lanes, n = 64, 32, 8, 4096
    budget = 0.25 * 2 ** 20
    tp = plans.plan_tree(rule, n, d, k, lanes)
    assert tp is not None and tp.peak_bytes <= budget
    assert tp.machines * tp.shard == lanes == tp.lanes
    # the same instance busts a single device ...
    sp = plans.select_engine(rule, n, n, d)
    assert plans.engine_hbm_bytes(sp, n, n, d) > budget
    # ... and flat RandGreedi busts on its m*k node pool, at ANY n
    nc = lanes * k
    fp = plans.select_engine(rule, nc, nc, d)
    assert plans.engine_hbm_bytes(fp, nc, nc, d) > budget


def test_plan_tree_shard_vs_machines_by_objective(monkeypatch):
    """Same pool, same budget: the linear-leaf objective takes the
    sharded single leaf (cost n*k/lanes), the quadratic k-medoid leaf
    moves devices from sharding toward tree machines (smaller pools
    beat split gains calls) — the planner's verdict comes from
    AccumulationTree.cost_model, not a fixed preference."""
    monkeypatch.setenv(BUDGET, "0.02")
    fac = plans.plan_tree(make_objective("facility").rule, 512, 16, 8, 4)
    assert fac is not None and fac.shard == 4 and fac.radices == ()
    assert fac.leaf_plan.engine == "sharded" and fac.model == {}
    km = plans.plan_tree(make_objective("kmedoid").rule, 512, 16, 8, 4)
    assert km is not None and km.shard == 2 and km.machines == 2
    assert km.radices == (2,)
    # structural wiring: the BSP model agrees with the enumerated tree
    assert km.model["levels"] == len(km.radices)
    assert km.model["elements_per_interior"] == km.branching * 8
    assert km.model["machines"] == km.machines


def test_plan_tree_infeasible_and_bitmap_guard(monkeypatch):
    monkeypatch.setenv(BUDGET, "0.001")
    rule = make_objective("facility").rule
    assert plans.plan_tree(rule, 1 << 20, 64, 32, 8) is None
    bit = make_objective("coverage", universe=512).rule
    with pytest.raises(ValueError):
        plans.plan_tree(bit, 256, None, 8, 4)       # bitmap needs words=
    monkeypatch.setenv(BUDGET, "64")
    tp = plans.plan_tree(bit, 256, None, 8, 4, words=16)
    assert tp is not None and tp.shard == 1         # bitmap never shards


# ---------------------------------------------------------------------------
# supervised planner default + recovery
# ---------------------------------------------------------------------------

def test_supervisor_planned_default_sharded(monkeypatch, tmp_path):
    monkeypatch.setenv(BUDGET, "0.02")
    obj = make_objective("facility")
    ids, pay, val = _pool(512, 16, seed=1)
    sup = SelectionSupervisor(ckpt_dir=str(tmp_path))
    sol, info = sup.select(obj, ids, pay, val, 8, lanes=4)
    assert info["shard"] == 4 and info["radices"] == ()
    plan_ev = [e for e in sup.events if e["kind"] == "plan"]
    assert plan_ev and plan_ev[0]["leaf_engine"] == "sharded"
    solo = greedy(obj, ids, pay, val, 8, engine="step")
    assert np.array_equal(np.asarray(sol.ids), np.asarray(solo.ids))


def test_supervisor_planned_tree_replays_bit_identically(monkeypatch,
                                                         tmp_path):
    monkeypatch.setenv(BUDGET, "0.0095")    # gather slab busts: solo tree
    obj = make_objective("facility")
    ids, pay, val = _pool(512, 16, seed=2)

    def run(sub, injector=None):
        sup = SelectionSupervisor(ckpt_dir=str(tmp_path / sub),
                                  injector=injector)
        sol, info = sup.select(obj, ids, pay, val, 8, lanes=4)
        return sol, info, sup

    clean, cinfo, _ = run("a")
    assert cinfo["shard"] == 1 and cinfo["radices"]     # multi-machine tree
    rep, _, rsup = run("b", LaneFailureInjector(fail_at=((1, 2),)))
    assert any(e["kind"] == "failure" for e in rsup.events)
    assert np.array_equal(np.asarray(rep.ids), np.asarray(clean.ids))
    assert np.array_equal(np.asarray(rep.valid), np.asarray(clean.valid))


def test_supervisor_resume_restores_planned_dispatcher(monkeypatch,
                                                       tmp_path):
    """Checkpoints carry shard/tile_c: a fresh supervisor resuming the
    run rebuilds the planned dispatcher and returns the same answer."""
    monkeypatch.setenv(BUDGET, "0.02")
    obj = make_objective("facility")
    ids, pay, val = _pool(512, 16, seed=5)
    clean, _ = SelectionSupervisor(ckpt_dir=str(tmp_path)).select(
        obj, ids, pay, val, 8, lanes=4)
    sup2 = SelectionSupervisor(ckpt_dir=str(tmp_path))
    res, info = sup2.select(obj, ids, pay, val, 8, lanes=4, resume=True)
    assert any(e["kind"] == "resume" for e in sup2.events)
    assert info["shard"] == 4
    assert np.array_equal(np.asarray(res.ids), np.asarray(clean.ids))


def test_sharded_leaves_refuse_degraded_tree(monkeypatch, tmp_path):
    """Shard lanes hold SLICES of one pool, not poolable solutions —
    lane loss cannot degrade the tree, it must surface as a failure."""
    monkeypatch.setenv(BUDGET, "0.02")
    obj = make_objective("facility")
    ids, pay, val = _pool(512, 16, seed=4)
    sup = SelectionSupervisor(ckpt_dir=str(tmp_path), max_restarts=1,
                              injector=LaneFailureInjector(dead={1: 0}))
    with pytest.raises(WorkerFailure):
        sup.select(obj, ids, pay, val, 8, lanes=4)


# ---------------------------------------------------------------------------
# XLA_FLAGS helper
# ---------------------------------------------------------------------------

def test_force_host_devices(monkeypatch):
    from repro.launch.mesh import force_host_devices
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    # applied whatever JAX_PLATFORMS says (the flag only touches the CPU
    # backend), so every simulator gets its host devices
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    # trigger absent: untouched
    assert not force_host_devices(8, trigger="--mesh", argv=["prog"])
    assert "XLA_FLAGS" not in os.environ
    # trigger present: appended
    assert force_host_devices(8, trigger="--mesh", argv=["prog", "--mesh"])
    assert os.environ["XLA_FLAGS"].endswith(
        "--xla_force_host_platform_device_count=8")
    # count_flag value wins over the default count, existing flags kept
    monkeypatch.setenv("XLA_FLAGS", "--foo")
    assert force_host_devices(4, argv=["prog", "--lanes", "6"])
    assert os.environ["XLA_FLAGS"] == \
        "--foo --xla_force_host_platform_device_count=6"


def test_device_lanes():
    """One lane per device JAX sees; a --lanes naming another count is
    refused, never quietly resized."""
    from repro.launch.mesh import device_lanes
    n = len(jax.devices())
    assert device_lanes(None, "--mesh") == n
    assert device_lanes(n, "--mesh") == n
    with pytest.raises(SystemExit, match="one lane per device"):
        device_lanes(n + 1, "--mesh")


# ---------------------------------------------------------------------------
# real 8-device mesh (subprocess: forced host devices)
# ---------------------------------------------------------------------------

MESH_SNIPPET = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
os.environ['REPRO_FUSED_CACHE_MB'] = '0.02'
import tempfile
import numpy as np
import jax
import jax.numpy as jnp
from repro.core.greedy import greedy
from repro.core.objective import make_objective
from repro.kernels import plans
from repro.launch.mesh import make_tree_mesh
from repro.runtime.supervisor import (LaneFailureInjector,
                                      SelectionSupervisor)

budget = 0.02 * 2 ** 20
obj = make_objective('facility')
n, d, k = 512, 16, 8
pay = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
ids, val = jnp.arange(n, dtype=jnp.int32), jnp.ones(n, bool)

tp = plans.plan_tree(obj.rule, n, d, k, 8)
assert tp.shard == 8 and tp.radices == ()
assert tp.leaf_plan.engine == 'sharded'
# the budget rejects every single-device tier for the full pool ...
solo_plan = plans.select_engine(obj.rule, n, n, d)
assert not solo_plan.cached            # no resident/streaming cache fits
assert plans.engine_hbm_bytes(solo_plan, n, n, d) > budget
# ... while each mesh device holds only its modeled slice
assert plans.shard_bytes(n, d, 8, tp.leaf_plan.tile_c) \
    == tp.peak_bytes <= budget

mesh = make_tree_mesh((), 8)

def run(injector=None):
    with tempfile.TemporaryDirectory() as td:
        sup = SelectionSupervisor(ckpt_dir=td, injector=injector)
        sol, info = sup.select(obj, ids, pay, val, k, lanes=8,
                               mesh=mesh, tree_axes=())
    return sol, info, sup

solo = greedy(obj, ids, pay, val, k, engine='step')
sol, info, _ = run()
assert info['shard'] == 8
assert np.array_equal(np.asarray(sol.ids), np.asarray(solo.ids))
assert np.array_equal(np.asarray(sol.valid), np.asarray(solo.valid))
# transient lane failure at the leaf stage: replay is bit-identical
rep, _, rsup = run(LaneFailureInjector(fail_at=((0, 3),)))
assert any(e['kind'] == 'failure' for e in rsup.events)
assert np.array_equal(np.asarray(rep.ids), np.asarray(solo.ids))
print('SHARD-MESH-OK', float(sol.value))
"""


@pytest.mark.slow
def test_sharded_mesh_bit_identical_under_budget():
    """The sharded tier on a REAL 8-device mesh (subprocess so this
    session keeps its single device): selections bit-identical to solo
    greedy(), modeled per-device bytes under a budget that rejects every
    single-device tier, and leaf-stage replay after a lane failure."""
    proc = subprocess.run(
        [sys.executable, "-c", MESH_SNIPPET],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root"}, cwd="/root/repo")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARD-MESH-OK" in proc.stdout
