"""In-program telemetry (`runtime.telemetry`): spans and their buffer, the
trace-time counters of the greedy driver and the kernel wrappers, the
planner's record of refused tiers, and the supervisor's spans, with the
lowerings each stage span carries."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.greedy import greedy
from repro.core.objective import make_objective
from repro.kernels import ops, plans
from repro.runtime import telemetry
from repro.runtime.supervisor import (LaneFailureInjector,
                                      SelectionSupervisor)
from repro.serving import Query, QueryEngine

N, K = 300, 5


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()


def _spans(name=None):
    return [s for s in telemetry.snapshot()["spans"]
            if name is None or s["name"] == name]


def _pool(name, n=N, seed=0):
    rng = np.random.default_rng(seed)
    if name == "coverage":
        bits = (rng.integers(0, 2 ** 32, (n, 4), dtype=np.uint32)
                & rng.integers(0, 2 ** 32, (n, 4), dtype=np.uint32))
        return jnp.asarray(bits)
    return jnp.asarray(rng.normal(size=(n, 16)).astype(np.float32))


# ---------------------------------------------------------------------------
# the module
# ---------------------------------------------------------------------------


def test_spans_nest_with_parents_ids_and_counts():
    with telemetry.span("outer", level=1) as a:
        telemetry.count("x", 2)
        with telemetry.span("inner") as b:
            telemetry.count("x")
            with telemetry.repeat(3), telemetry.repeat(2):
                telemetry.count("y")
        rec = telemetry.record("note", v=1)
    inner, outer = _spans()
    assert (inner["name"], outer["name"]) == ("inner", "outer")
    assert inner["id"] == b.id and outer["id"] == a.id and a.id < b.id
    assert inner["parent"] == a.id and outer["parent"] is None
    assert outer["attrs"] == {"level": 1}
    assert outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]
    assert outer["counts"] == {"x": 2} and inner["counts"] == {"x": 1, "y": 6}
    assert rec["span"] == a.id and telemetry.records("note") == [rec]
    assert telemetry.snapshot()["totals"] == {"x": 3, "y": 6}


@pytest.mark.parametrize("kind", ["spans", "records"])
def test_buffers_stay_bounded(kind, tmp_path):
    bound = telemetry.MAX_SPANS if kind == "spans" else telemetry.MAX_RECORDS
    for i in range(bound + 7):
        if kind == "spans":
            with telemetry.span("s", i=i):
                pass
        else:
            telemetry.record("r", i=i)
    snap = telemetry.snapshot()
    kept = snap["spans"] if kind == "spans" else snap["records"]["r"]
    key = (lambda s: s["attrs"]["i"]) if kind == "spans" else \
        (lambda r: r["i"])
    assert len(kept) == bound and key(kept[0]) == 7
    assert key(kept[-1]) == bound + 6
    path = telemetry.dump(str(tmp_path / "t.json"))
    assert open(path).read().startswith("{")


# ---------------------------------------------------------------------------
# the greedy driver and the wrappers
# ---------------------------------------------------------------------------


ENGINES = [("step", "step", None), ("fused", "fused", None),
           ("mega_stream", "mega", "0.5"), ("mega_resident", "mega", None)]


@pytest.mark.parametrize("name", ["kmedoid", "coverage"])
@pytest.mark.parametrize("want,engine,vmem", ENGINES,
                         ids=[e[0] for e in ENGINES])
def test_driver_launches_match_the_jaxpr(monkeypatch, name, want, engine,
                                         vmem):
    """Per engine, the greedy record's `launches` equals the Pallas
    dispatches counted in the jaxpr; its lowered module holds no host
    callback."""
    if vmem is not None:
        monkeypatch.setenv("REPRO_FUSED_VMEM_MB", vmem)
    obj = make_objective(name, universe=128, backend="interpret")
    ids, pay = jnp.arange(N, dtype=jnp.int32), _pool(name)
    valid = jnp.ones((N,), bool)
    f = lambda i, p, v: greedy(obj, i, p, v, K, engine=engine)
    jx = jax.make_jaxpr(f)(ids, pay, valid)
    rec = telemetry.records("greedy")[-1]
    assert rec["engine"] == want and rec["k"] == K
    assert rec["logical"] == ([4, N] if name == "coverage" else [N, N])
    assert rec["launches"] == ops.count_pallas_dispatches(jx.jaxpr) > 0
    # every engine pads what it streams but the per-step bitmap gains,
    # which read the candidate bitmaps in place
    in_place = want == "step" and name == "coverage"
    assert rec["streams"] and (rec["relayout_bytes"] == 0) == in_place
    text = jax.jit(f).lower(ids, pay, valid).as_text()
    assert "callback" not in text.lower()


@pytest.mark.parametrize("lanes", ["vmap", "shard_map"])
def test_driver_launches_per_lane(lanes):
    """One lane's launches, as `count_pallas_dispatches` counts them."""
    obj = make_objective("kmedoid", backend="interpret")
    one = lambda i, p, v: greedy(obj, i, p, v, K, engine="step")
    if lanes == "vmap":
        f, m = jax.vmap(one), 3
    else:
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:1]), ("lane",))
        f, m = jax.shard_map(
            lambda i, p, v: jax.tree.map(lambda x: x[None],
                                         one(i[0], p[0], v[0])),
            mesh=mesh, in_specs=P("lane"), out_specs=P("lane"),
            check_vma=False), 1
    pay = jnp.stack([_pool("kmedoid", 64, s) for s in range(m)])
    ids = jnp.tile(jnp.arange(64, dtype=jnp.int32), (m, 1))
    jx = jax.make_jaxpr(f)(ids, pay, jnp.ones((m, 64), bool))
    assert telemetry.records("greedy")[-1]["launches"] == K == \
        ops.count_pallas_dispatches(jx.jaxpr)


def test_step_engine_streams_the_bitmaps_in_place():
    """Bitmap per-step gains: the kernel streams the candidate bitmaps as
    they are, here the (4, N) word-major view of the (N, 4) pool, so the
    streamed operand's padded shape is its logical shape and no step
    writes a copy."""
    obj = make_objective("coverage", universe=128, backend="interpret")
    jax.make_jaxpr(lambda i, p, v: greedy(obj, i, p, v, K, engine="step"))(
        jnp.arange(N, dtype=jnp.int32), _pool("coverage"),
        jnp.ones((N,), bool))
    rec = telemetry.records("greedy")[-1]
    (s,) = rec["streams"]
    assert s == {"span": s["span"], "kernel": "gains_pallas",
                 "logical": [4, N], "padded": [4, N],
                 "bytes": N * 4 * 4, "repeat": K, "orient": "wc"}
    assert rec["relayout_bytes"] == 0


def test_ref_backend_launches_nothing():
    obj = make_objective("kmedoid", backend="ref")
    jax.make_jaxpr(lambda i, p, v: greedy(obj, i, p, v, K))(
        jnp.arange(N, dtype=jnp.int32), _pool("kmedoid"),
        jnp.ones((N,), bool))
    rec = telemetry.records("greedy")[-1]
    assert rec["launches"] == rec["relayout_bytes"] == 0
    assert rec["streams"] == []


# the k-medoid cell: one chip's pool at Tiny ImageNet's pixel width, and
# the k-cover cell: FIMI retail's sets over its item words
LEAF, WIDE = 16_384, 12_288
RETAIL, WORDS = 88_162, 515


@pytest.mark.parametrize("case", ["own_pool", "distinct_ground",
                                  "kcover"])
def test_greedy_record_counts_the_mirrored_blocks(case):
    """Traced for the Pallas backend at the cells' shapes: a k-medoid
    greedy over its own pool builds the blocks on and above the diagonal
    of the 32 × 32 grid of (512, 512) tiles and mirrors the other 496,
    while its counted build bytes stay the full walk's; given a distinct
    ground it mirrors nothing, nor does k-cover, which builds no
    matrix."""
    S = jax.ShapeDtypeStruct
    if case == "kcover":
        obj = make_objective("coverage", universe=32 * WORDS,
                             backend="pallas")
        n, pool, k = RETAIL, S((RETAIL, WORDS), jnp.uint32), 64
    else:
        obj = make_objective("kmedoid", backend="pallas")
        n, pool, k = LEAF, S((LEAF, WIDE), jnp.float32), 200
    args = [S((n,), jnp.int32), pool, S((n,), jnp.bool_)]
    if case == "distinct_ground":
        args += [pool, S((n,), jnp.bool_)]
    jx = jax.make_jaxpr(lambda i, p, v, *g: greedy(obj, i, p, v, k, *g))(
        *args)
    rec = telemetry.records("greedy")[-1]
    assert rec["launches"] == ops.count_pallas_dispatches(jx.jaxpr) > 0
    if case == "kcover":
        assert rec["mirrored_blocks"] == rec["build_bytes"] == 0
        return
    tiles = plans.feature_tiles("pairwise", LEAF, LEAF, WIDE)
    assert (tiles.tn, tiles.tc) == (512, 512)
    assert rec["build_bytes"] == tiles.hbm_bytes
    assert rec["mirrored_blocks"] == (496 if case == "own_pool" else 0)
    kernels = [s["kernel"] for s in rec["streams"]]
    assert ("pairwise_mirror" in kernels) == (case == "own_pool")


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("knob,mb,gates", [
    ("REPRO_FUSED_VMEM_MB", "0.25",
     {"resident_vmem", "loop_block_vmem", "fused_block_vmem"}),
    ("REPRO_FUSED_CACHE_MB", "0.5", {"hbm_cache"}),
])
def test_planner_records_refused_tiers(monkeypatch, knob, mb, gates):
    monkeypatch.setenv(knob, mb)
    rule = make_objective("coverage", universe=2048).rule
    plan = plans.select_engine(rule, 64, 4096, None, backend="pallas")
    rec = telemetry.records("plan")[-1]
    assert plan.engine == rec["engine"] == "step"
    assert rec["source"] == "static" and rec["plan_s"] >= 0
    assert (rec["n"], rec["c"], rec["d"]) == (64, 4096, None)
    assert {r["gate"] for r in rec["refused"]} == gates
    assert all(r["need"] > r["budget"] for r in rec["refused"])
    refused = []
    assert plans.fused_plan(64, 4096, backend="pallas", rule=rule,
                            refused=refused) is None
    assert refused == rec["refused"]


def test_planner_record_of_an_admitted_plan():
    rule = make_objective("kmedoid").rule
    plans.select_engine(rule, 300, 300, 16, backend="pallas")
    rec = telemetry.records("plan")[-1]
    assert rec["engine"] == "mega_resident" and rec["refused"] == []
    plans.select_engine(rule, 300, 300, 16, backend="pallas",
                        requested="step")
    assert telemetry.records("plan")[-1]["source"] == "requested"


# ---------------------------------------------------------------------------
# the supervisor and the serving engine
# ---------------------------------------------------------------------------


def _tree_data(n=256):
    return jax.block_until_ready(
        (jnp.arange(n, dtype=jnp.int32), _pool("kmedoid", n, seed=3),
         jnp.ones((n,), bool)))


def _tree(tmp_path, sub, data, injector=None):
    obj = make_objective("kmedoid", backend="ref")
    sup = SelectionSupervisor(ckpt_dir=str(tmp_path / sub),
                              injector=injector)
    sup.select(obj, *data, K, lanes=4, branching=2)
    return sup


def _descendants(spans, root):
    ids, out = {root}, []
    for s in sorted(spans, key=lambda s: s["id"]):
        if s["parent"] in ids:
            ids.add(s["id"])
            out.append(s)
    return out


def test_stage_spans_carry_every_lowering(tmp_path):
    """A simulated tree run twice: every lowering of a run is attributed
    to its `greedyml.select` span tree, and on the second run, when only
    the stages re-lower, to the stage spans alone."""
    seen = []

    def listen(event, duration, **_):
        if event == telemetry.LOWERING_EVENT:
            seen.append(event)
    data = _tree_data()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for run in range(2):
            telemetry.reset()
            seen.clear()
            _tree(tmp_path, f"run{run}", data)
            spans = _spans()
            (sel,) = [s for s in spans if s["name"] == "greedyml.select"]
            tree = [sel] + _descendants(spans, sel["id"])
            got = sum(s["counts"].get("lowerings", 0) for s in tree)
            assert got == len(seen) > 0
            stages = [s for s in tree if s["name"] == "greedyml.stage"]
            assert [s["attrs"] for s in stages] == \
                [{"level": lv, "epoch": 0} for lv in range(3)]
        assert sum(s["counts"].get("lowerings", 0)
                   for s in stages) == len(seen)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def test_checkpoint_events_carry_their_duration(tmp_path):
    sup = _tree(tmp_path, "ck", _tree_data(),
                injector=LaneFailureInjector(fail_at=((1, 2),)))
    ck = [e for e in sup.events if e["kind"] == "checkpoint"]
    assert ck and all(e["dur_s"] >= 0 and "time" in e for e in ck)
    disp = [e for e in sup.events if e["kind"] == "dispatch"]
    assert all(e["lowerings"] >= 0 for e in disp)
    spans = _spans()
    by_id = {s["id"]: s for s in spans}
    saves = [s for s in spans if s["name"] == "greedyml.checkpoint"]
    assert len(saves) == len(ck)
    assert all(by_id[s["parent"]]["name"] == "greedyml.stage"
               for s in saves)
    (restart,) = [s for s in spans if s["name"] == "greedyml.restart"]
    assert by_id[restart["parent"]]["name"] == "greedyml.select"


def test_serving_drain_spans_each_batch():
    eng = QueryEngine(backend="ref", max_batch=2)
    for s in range(3):
        eng.submit(Query("facility", 4, jnp.arange(64, dtype=jnp.int32),
                         _pool("facility", 64, s), jnp.ones((64,), bool)))
    assert len(eng.drain()) == 3
    names = [s["name"] for s in _spans() if s["name"].startswith("serve.")]
    assert names == ["serve.admit", "serve.dispatch", "serve.wait",
                     "serve.unpack"] * 2
