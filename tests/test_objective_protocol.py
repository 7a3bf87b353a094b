"""Generic objective-conformance suite (DESIGN §Objective protocol).

EVERY objective in the core registry is parity-tested from this ONE
parameterized file: ref↔interpret kernel parity, selection parity across
all engine tiers (step / fused / megakernel / auto), the constraint and
stochastic-sampling branches, batched replay, sieve-streaming parity and
quality, submodularity sanity, and the megakernel dispatch count. A new
objective registered via core.objective.register is covered automatically
— scripts/ci_smoke.sh sweeps the registry through this file per
objective, so registering a spec that fails conformance fails CI.

Includes the coverage-on-megakernel / coverage-on-stream-filter parity
cases that predated the protocol refactor without any test coverage.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.constraints import PartitionMatroid
from repro.core.greedy import greedy, replay_value
from repro.core.objective import make_objective, registry
from repro.data.synthetic import gen_images, gen_kcover, gen_stream, \
    pack_bitmaps
from repro.kernels import ops, plans, rules
from repro.streaming import SieveStreamer, stream_select

UNIVERSE = 384
OBJECTIVES = registry()          # every registered name, automatically
BACKENDS = ("ref", "interpret")


def _make(name, backend=None):
    return make_objective(name, universe=UNIVERSE, backend=backend)


def _is_bitmap(name):
    return _make(name).rule.is_bitmap


def _pool(name, n=120, seed=2, d=32):
    """Candidate pool in the objective's payload representation."""
    if _is_bitmap(name):
        pay = jnp.asarray(pack_bitmaps(gen_kcover(n, UNIVERSE, seed=seed),
                                       UNIVERSE))
    else:
        pay = jnp.asarray(gen_images(n, d, classes=8, seed=seed))
    ids = jnp.arange(n, dtype=jnp.int32)
    valid = (jnp.arange(n) % 11) != 0
    return ids, pay, valid


def _assert_same_selection(a, b, value_tol=1e-5):
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.valid), np.asarray(b.valid))
    assert int(a.evals) == int(b.evals)
    np.testing.assert_allclose(float(a.value), float(b.value),
                               rtol=value_tol, atol=value_tol)


# ---------------------------------------------------------------------------
# engine-tier selection parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", OBJECTIVES)
def test_engine_parity_all_tiers(name, backend):
    """step / fused / mega / auto must select identical elements."""
    ids, pay, valid = _pool(name)
    obj = _make(name, backend)
    tol = 0 if obj.rule.is_bitmap else 1e-4
    sols = {e: greedy(obj, ids, pay, valid, 12, engine=e)
            for e in ("step", "fused", "mega", "auto")}
    assert int(sols["step"].valid.sum()) > 0
    for e in ("fused", "mega", "auto"):
        _assert_same_selection(sols["step"], sols[e], value_tol=tol)


@pytest.mark.parametrize("name", OBJECTIVES)
def test_interpret_matches_ref_selection(name):
    """Same ids regardless of backend — the compiled-path ground truth."""
    ids, pay, valid = _pool(name, n=160)
    sols = {b: greedy(_make(name, b), ids, pay, valid, 10, engine="auto")
            for b in BACKENDS}
    np.testing.assert_array_equal(np.asarray(sols["ref"].ids),
                                  np.asarray(sols["interpret"].ids))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", OBJECTIVES)
def test_int8_cache_selection_identity(name, backend, monkeypatch):
    """Forced int8 cache storage (ISSUE 7) must pick the SAME element
    ids as the f32 run on every engine tier — the quantization parity
    gate is selection identity, not bitwise gains. The pool (seed=7) is
    margin-robust: every greedy pick's gain margin exceeds the ≤1/254
    per-row rounding, verified across all engines/backends. Near-tie
    pools may legitimately flip a pick under quantization — those are
    gated by the autotuner's measurement-time identity check, which
    REJECTS any candidate whose selection drifts (launch/autotune.py)."""
    if _is_bitmap(name):
        pytest.skip("bitmap rules always store uint32 — nothing to "
                    "quantize")
    ids, pay, valid = _pool(name, seed=7)
    f32 = {e: greedy(_make(name, backend), ids, pay, valid, 10, engine=e)
           for e in ("step", "fused", "mega")}
    monkeypatch.setenv("REPRO_FUSED_CACHE_DTYPE", "int8")
    for e in ("step", "fused", "mega"):
        q = greedy(_make(name, backend), ids, pay, valid, 10, engine=e)
        np.testing.assert_array_equal(np.asarray(q.ids),
                                      np.asarray(f32[e].ids))
        np.testing.assert_array_equal(np.asarray(q.valid),
                                      np.asarray(f32[e].valid))


@pytest.mark.parametrize("name", OBJECTIVES)
def test_constraint_branch_parity(name):
    """PartitionMatroid demotes mega → fused scan; selections must match
    the step engine and respect the caps."""
    ids, pay, valid = _pool(name)
    n = ids.shape[0]
    cats = jnp.asarray(np.arange(n) % 3, jnp.int32)
    caps = jnp.asarray([3, 2, 4], jnp.int32)
    obj = _make(name, "ref")
    a = greedy(obj, ids, pay, valid, 9, engine="step",
               constraint=PartitionMatroid(cats, caps))
    b = greedy(obj, ids, pay, valid, 9, engine="auto",
               constraint=PartitionMatroid(cats, caps))
    _assert_same_selection(a, b)
    sel = np.asarray(b.ids)[np.asarray(b.valid)]
    counts = np.bincount(np.asarray(cats)[sel], minlength=3)
    assert np.all(counts <= np.asarray(caps))


@pytest.mark.parametrize("name", OBJECTIVES)
def test_sampling_branch_parity(name):
    """Stochastic greedy: the forced-fused path must match the step path
    under the same key."""
    ids, pay, valid = _pool(name)
    obj = _make(name, "ref")
    kw = dict(sample=48, key=jax.random.PRNGKey(7))
    a = greedy(obj, ids, pay, valid, 8, engine="step", **kw)
    b = greedy(obj, ids, pay, valid, 8, engine="fused", **kw)
    _assert_same_selection(a, b)


@pytest.mark.parametrize("name", OBJECTIVES)
def test_memory_cap_falls_back_to_step(name, monkeypatch):
    """Under a shrunken HBM budget the planner must refuse every cached
    tier (prepare/megakernel_loop → None) and 'auto' must silently equal
    the per-step result — the paper's memory-capped regime."""
    monkeypatch.setenv("REPRO_FUSED_CACHE_MB", "0.001")
    ids, pay, valid = _pool(name)
    obj = _make(name, "ref")
    state = obj.init_state(pay, valid)
    assert obj.prepare(state, pay, valid) is None
    assert obj.megakernel_loop(state, pay, valid, 8) is None
    a = greedy(obj, ids, pay, valid, 8, engine="step")
    b = greedy(obj, ids, pay, valid, 8, engine="auto")
    _assert_same_selection(a, b, value_tol=0)


@pytest.mark.parametrize("name", OBJECTIVES)
def test_megakernel_reachable_and_dispatch_count(name):
    """greedy(engine='mega') must lower to ≤ 2 Pallas dispatches for every
    registered objective — exactly 1 where prepare is free (bitmap rules)
    or the resident tier fits."""
    ids, pay, valid = _pool(name)
    obj = _make(name, "interpret")
    jaxpr = jax.make_jaxpr(
        lambda i, p, v: greedy(obj, i, p, v, 10, engine="mega"))(
            jax.ShapeDtypeStruct(ids.shape, ids.dtype),
            jax.ShapeDtypeStruct(pay.shape, pay.dtype),
            jax.ShapeDtypeStruct(valid.shape, valid.dtype))
    n_disp = ops.count_pallas_dispatches(jaxpr.jaxpr)
    assert 1 <= n_disp <= 2, (name, n_disp)
    if obj.rule.is_bitmap:
        assert n_disp == 1      # transpose-prepare: the loop is the greedy


# ---------------------------------------------------------------------------
# kernel ↔ oracle parity on objective states
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", OBJECTIVES)
def test_gains_kernel_parity_on_live_state(name):
    """ops.gains (interpret) vs ref oracle on a mid-run state — after two
    real updates, not just the empty solution."""
    ids, pay, valid = _pool(name, n=96)
    obj = _make(name, "ref")
    state = obj.init_state(pay, valid)
    state = obj.update(state, pay[3])
    state = obj.update(state, pay[17])
    r = ops.gains(state.ground, state.row, pay, valid, obj.rule,
                  backend="ref")
    p = ops.gains(state.ground, state.row, pay, valid, obj.rule,
                  backend="interpret")
    tol = 0 if obj.rule.is_bitmap else 1e-4
    np.testing.assert_allclose(np.where(np.isfinite(np.asarray(r)),
                                        np.asarray(r), 0),
                               np.where(np.isfinite(np.asarray(p)),
                                        np.asarray(p), 0),
                               atol=tol, rtol=tol)


class _NoBatchShim:
    """Delegates to an objective but hides replay_batch → forces the
    sequential scan replay, to check the batched replay against it."""

    def __init__(self, obj):
        self._obj = obj

    def __getattr__(self, item):
        if item == "replay_batch":
            raise AttributeError(item)
        return getattr(self._obj, item)


@pytest.mark.parametrize("name", OBJECTIVES)
def test_replay_batch_matches_scan(name):
    ids, pay, valid = _pool(name, n=96)
    obj = _make(name, "ref")
    sol = greedy(obj, ids, pay, valid, 10, engine="step")
    batched = replay_value(obj, sol.payloads, sol.valid, pay, valid)
    scanned = replay_value(_NoBatchShim(obj), sol.payloads, sol.valid,
                           pay, valid)
    np.testing.assert_allclose(float(batched), float(scanned),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# submodularity sanity — any registered spec must be a valid objective
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", OBJECTIVES)
def test_diminishing_returns_and_monotone(name):
    ids, pay, valid = _pool(name, n=48)
    obj = _make(name, "ref")
    state = obj.init_state(pay, valid)
    v0 = float(obj.value(state))
    g0 = obj.gains(state, pay, valid)
    state2 = obj.update(state, pay[int(jnp.argmax(g0))])
    v1 = float(obj.value(state2))
    g1 = obj.gains(state2, pay, valid)
    assert v1 >= v0 - 1e-6                      # monotone
    assert bool(jnp.all(g1 <= g0 + 1e-5))       # diminishing returns
    assert abs(v1 - v0 - float(jnp.max(g0))) < 1e-4   # gain = Δvalue


# ---------------------------------------------------------------------------
# sieve-streaming tier
# ---------------------------------------------------------------------------


def _stream_setup(name, n=256, batch=64, order="shuffled", seed=0):
    st = gen_stream(name if not _is_bitmap(name) else "kcover", n, d=24,
                    universe=UNIVERSE, batch=batch, order=order, seed=seed)
    obj = _make(name, "ref")
    ground = None if obj.rule.is_bitmap else jnp.asarray(st.payloads)
    return st, obj, ground


def _ids(sol):
    return np.asarray(sol.ids)[np.asarray(sol.valid)]


@pytest.mark.parametrize("name", OBJECTIVES)
def test_sieve_selections_identical_across_backends(name):
    """Full sieve runs must pick the same elements on ref and interpret —
    including coverage, which rides the Pallas stream-filter kernel since
    the protocol refactor (previously untested on any fast tier)."""
    st, obj, ground = _stream_setup(name, n=192, batch=64)
    sols = {}
    for backend in BACKENDS:
        sols[backend] = stream_select(obj, st, 8, ground=ground,
                                      backend=backend)
    np.testing.assert_array_equal(np.asarray(sols["ref"].ids),
                                  np.asarray(sols["interpret"].ids))
    np.testing.assert_array_equal(np.asarray(sols["ref"].valid),
                                  np.asarray(sols["interpret"].valid))


@pytest.mark.parametrize("name", OBJECTIVES)
def test_sieve_is_one_dispatch_per_batch(name):
    """One arrival batch × ALL sieve levels = ONE pallas_call, for every
    registered objective."""
    st, _, _ = _stream_setup(name, n=64, batch=32)
    obj = _make(name, "interpret")
    ground = (None if obj.rule.is_bitmap
              else jnp.asarray(st.payloads[:64]))
    streamer = SieveStreamer(obj, 8, ground=ground, backend="interpret")
    pay_sds = jax.ShapeDtypeStruct(st.payloads[:32].shape,
                                   st.payloads.dtype)
    state = jax.eval_shape(lambda p: streamer.init(p), pay_sds)
    jaxpr = jax.make_jaxpr(streamer.process_batch)(
        state, jax.ShapeDtypeStruct((32,), jnp.int32), pay_sds,
        jax.ShapeDtypeStruct((32,), jnp.bool_))
    assert ops.count_pallas_dispatches(jaxpr.jaxpr) == 1


@pytest.mark.parametrize("name", OBJECTIVES)
def test_sieve_quality_bound(name):
    """Sieve value ≥ (1/2 − ε)·offline greedy, scored uniformly via
    replay_value on the full ground set (works for every registered
    objective, unlike the name-switched global_value helper)."""
    eps = 0.1
    st, obj, ground = _stream_setup(name, n=256, batch=64, order="drift",
                                    seed=3)
    pay = jnp.asarray(st.payloads)
    allv = jnp.ones(st.n, bool)
    sol = stream_select(obj, st, 8, eps=eps, ground=ground, backend="ref")
    g = greedy(obj, jnp.arange(st.n, dtype=jnp.int32), pay, allv, 8)
    sv = float(replay_value(obj, sol.payloads, sol.valid, pay, allv))
    gv = float(replay_value(obj, g.payloads, g.valid, pay, allv))
    assert sv >= (0.5 - eps) * gv, (name, sv, gv)


# ---------------------------------------------------------------------------
# serving tier — admitted-batch parity (DESIGN §Serving)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", OBJECTIVES)
def test_serving_batched_parity(name, backend):
    """A mixed admitted batch — the named objective at ≥3 heterogeneous k
    (forcing co-batching with masked steps), every OTHER registered
    objective riding along in its own sub-batch, plus one constrained
    query on the solo-fallback path — must return selections BIT-
    IDENTICAL (ids, valid, evals) to solo greedy() runs on the same
    pools. Registry-parameterized: a newly registered spec gets batched
    serving coverage automatically (ci_smoke.sh sweeps this file per
    objective)."""
    from repro.serving import Query, QueryEngine
    eng = QueryEngine(backend=backend)

    def _q(nm, k, n, seed):
        ids, pay, valid = _pool(nm, n=n, seed=seed)
        uni = UNIVERSE if _is_bitmap(nm) else 0
        return (eng.submit(Query(nm, k, ids, pay, valid, tenant=nm,
                                 universe=uni)),
                nm, k, (ids, pay, valid))
    subs = [_q(name, 5, 96, 2), _q(name, 9, 120, 3), _q(name, 12, 96, 4)]
    for other in OBJECTIVES:
        if other != name:
            subs.append(_q(other, 7, 96, 5))
    ids, pay, valid = _pool(name, n=96, seed=6)
    con = PartitionMatroid(jnp.asarray(np.arange(96) % 3, jnp.int32),
                           jnp.asarray([3, 2, 4], jnp.int32))
    qc = eng.submit(Query(name, 6, ids, pay, valid, constraint=con,
                          universe=UNIVERSE if _is_bitmap(name) else 0))
    results = eng.drain()
    assert len(results) == len(subs) + 1
    for qid, nm, k, (qi, qp, qv) in subs:
        solo = greedy(_make(nm, backend), qi, qp, qv, k)
        r = results[qid]
        assert r.batched, (nm, k)
        np.testing.assert_array_equal(np.asarray(r.solution.ids),
                                      np.asarray(solo.ids))
        np.testing.assert_array_equal(np.asarray(r.solution.valid),
                                      np.asarray(solo.valid))
        assert int(r.solution.evals) == int(solo.evals)
        np.testing.assert_allclose(float(r.solution.value),
                                   float(solo.value), rtol=1e-5,
                                   atol=1e-5)
    solo_c = greedy(_make(name, backend), ids, pay, valid, 6,
                    constraint=con)
    rc = results[qc]
    assert not rc.batched
    np.testing.assert_array_equal(np.asarray(rc.solution.ids),
                                  np.asarray(solo_c.ids))
    # the named objective's 3 queries co-batched: same serve key
    keys = {results[qid].key for qid, nm, _, _ in subs if nm == name}
    assert len(keys) == 1 and None not in keys
    assert {results[qid].batch_size for qid, nm, _, _ in subs
            if nm == name} == {3}


# ---------------------------------------------------------------------------
# registry & planning surface
# ---------------------------------------------------------------------------


def test_registry_complete_and_aliases():
    names = registry()
    assert {"coverage", "kmedoid", "facility", "satcover"} <= set(names)
    for name in names:
        obj = _make(name)
        assert obj.rule.fold in ("min", "max", "or", "satsum", "sum")
        hash(obj.rule)                      # rules must be jit-static
    assert make_objective("kcover", universe=64).name == "coverage"
    assert make_objective("kdom", universe=64).name == "coverage"
    assert make_objective("facility_location").name == "facility"
    with pytest.raises(KeyError):
        make_objective("nope")


def test_satcover_is_spec_only():
    """The extensibility proof: satcover exists purely as a rule — no
    objective class, no kernel file — yet rides every tier (the
    parameterized tests above). Its cap parameter round-trips and equal
    caps share one rule identity (jit cache key)."""
    a = make_objective("satcover", cap=1.5)
    b = make_objective("satcover", cap=1.5)
    assert a.rule is b.rule and a.rule.cap == 1.5
    assert rules.sat_sum(1.5) is a.rule
    import repro.kernels as K
    import os
    kdir = os.path.dirname(K.__file__)
    assert not any("satcover" in f for f in os.listdir(kdir))


def test_planner_is_the_single_gate(monkeypatch):
    """core/objective.py must not reach into private backend state: the
    planner resolves backends and budgets."""
    import inspect
    import repro.core.objective as O
    import repro.core.functions as F
    src = inspect.getsource(O) + inspect.getsource(F)
    assert "_backend" not in src
    assert "hasattr(objective" not in inspect.getsource(
        __import__("repro.core.greedy", fromlist=["greedy"]).greedy)


# ---------------------------------------------------------------------------
# seed threading (greedyml / randgreedi / streaming drivers)
# ---------------------------------------------------------------------------


def test_distributed_seed_threading():
    """Explicit seeds reproduce and reseed the stochastic draws; None
    keeps the legacy fixed tape."""
    from repro.core.greedyml import LevelDispatcher
    from repro.launch.mesh import make_tree_mesh
    mesh = make_tree_mesh((1,))
    ids, pay, valid = _pool("facility", n=96)
    obj = _make("facility", "ref")
    kw = dict(sample_leaf=24, sample_level=24)

    def tree(**kw):
        return LevelDispatcher(obj, 6, (1,), mesh=mesh, **kw).run(
            ids, pay, valid)

    legacy = tree(**kw)
    legacy2 = tree(**kw)
    s5a = tree(seed=5, **kw)
    s5b = tree(seed=5, **kw)
    np.testing.assert_array_equal(np.asarray(legacy.ids),
                                  np.asarray(legacy2.ids))
    np.testing.assert_array_equal(np.asarray(s5a.ids), np.asarray(s5b.ids))
    seeds = {tuple(np.asarray(tree(seed=s, **kw).ids).tolist())
             for s in range(4)}
    assert len(seeds) > 1, "reseeding never changes the draws"
    # RandGreedi: the one-level tree, sampling at the leaves only
    rg = tree(sample_leaf=24, seed=3)
    rg2 = tree(sample_leaf=24, seed=3)
    np.testing.assert_array_equal(np.asarray(rg.ids), np.asarray(rg2.ids))


def test_streaming_driver_seed_threading():
    from repro.streaming import stream_select_continuous
    st, obj, ground = _stream_setup("facility", n=128, batch=32)
    a, _ = stream_select_continuous(obj, st, 6, lanes=2, merge_every=2,
                                    ground=ground, backend="ref",
                                    sample_level=8, seed=11)
    b, _ = stream_select_continuous(obj, st, 6, lanes=2, merge_every=2,
                                    ground=ground, backend="ref",
                                    sample_level=8, seed=11)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
