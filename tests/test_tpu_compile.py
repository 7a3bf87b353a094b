"""Compile every main-path Pallas kernel for a TPU v5e, with no chip attached.

Interpret mode runs the kernel bodies as plain jnp on the CPU, so it cannot
see what Mosaic refuses: lane slices at traced offsets, blocks that break
the (8, 128) tiling rule, casts the TPU has no instruction for, or a working
set over the scoped-VMEM limit. Here each kernel is lowered and compiled
through the `kernels/ops.py` wrappers (``backend="pallas"``) at real widths
against a described ``v5e:2x2`` topology, and its compiled text must hold
the Mosaic custom call.

Each compiled kernel must also keep the instruction name the benchmark's
trace reducer (`bench/lib/trace.py`) finds it by: `gains_pallas`,
`pairwise_pallas`, `pairwise_mirror`, `fused_step_pallas`,
`greedy_loop_pallas`, `greedy_loop_resident_pallas`,
`stream_filter_pallas`.

The topology is described inside a module fixture only: one process at a
time may load the TPU compiler library, so describing it while modules are
imported would make parallel test workers collect different tests.
"""
import functools
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bench.lib import trace as bench_trace
from repro.core.greedy import greedy
from repro.core.objective import make_objective
from repro.kernels import ops, plans
from repro.kernels import rules as R
from repro.kernels.shard_gains import shard_greedy_distributed

N = C = 4096        # cached-matrix tiers: the (N, C) matrix is 64 MiB in f32
D = 768
K = 32
NODE = 512          # an accumulation-node pool: resident-tier shapes
WORDS = 512         # coverage universe of 16,384 elements, in uint32 words
F32, I32, U32 = jnp.float32, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=F32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compiles(fn, *args, kernels):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert all(bench_trace.opcode(c) == "custom-call" for c in calls)
    assert {bench_trace.base_name(c) for c in calls} == set(kernels)


def _plan(rule, n, c, d, requested="mega"):
    return plans.select_engine(rule, n, c, d, requested=requested,
                               backend="pallas")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("name", ["kmedoid", "facility"])
def test_pairwise(spec, name, dtype):
    rule = R.get(name)
    _compiles(lambda g, c: ops.pairwise_matrix(g, c, rule, backend="pallas",
                                               dtype=dtype),
              spec((N, D)), spec((C, D)), kernels=["pairwise_pallas"])


# per-step bitmap gains read the bitmaps in place, (C, W) or their (W, C)
# view: FIMI retail's and kosarak's transaction counts over their item
# words, off every alignment
BITMAPS = {"coverage": (C, WORDS), "retail": (88_162, 515),
           "kosarak": (990_002, 1_290)}


@pytest.mark.parametrize("case", ["f32", "int8", *BITMAPS])
def test_gains(spec, case, monkeypatch):
    if case in BITMAPS:
        c, words = BITMAPS[case]
        _compiles(lambda r, b, v: ops.gains(None, r, b, v, R.BITS_OR,
                                            backend="pallas"),
                  spec((words,), U32), spec((c, words), U32),
                  spec((c,), jnp.bool_), kernels=["gains_pallas"])
        return
    if case == "int8":          # per-row-quantized ground, `gscale` operand
        monkeypatch.setenv("REPRO_FUSED_CACHE_DTYPE", "int8")
    _compiles(lambda g, r, c, v: ops.gains(g, r, c, v, R.DOT_MAX,
                                           backend="pallas"),
              spec((N, D)), spec((N,)), spec((C, D)), spec((C,), jnp.bool_),
              kernels=["gains_pallas"])


def _loop_bodies(text):
    """The compiled text of every computation a while loop's body reaches
    (fusions, reductions and nested loops included)."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    seen, todo = set(), re.findall(r"body=%([\w.\-]+)", text)
    while todo:
        n = todo.pop()
        if n in comps and n not in seen:
            seen.add(n)
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                "\n".join(comps[n]))
    return "\n".join(line for n in seen for line in comps[n])


@pytest.mark.parametrize("case", ["retail", "kosarak"])
def test_step_engine_greedy_streams_retail_in_place(spec, case):
    """The whole k-cover greedy at FIMI retail's (and kosarak's) shape
    takes the per-step engine and streams the pool in the TPU's own
    layout of it: the loop body reads the (W, C) view, which the entry
    computation makes by a bitcast of the pool, never a copy (which would
    hold a (C, W) array the size of the pool as a temporary); the loop
    body copies nothing: no pad, and no bitmap array of power-of-two rows
    or whole 512-word tiles."""
    (n, words), k = BITMAPS[case], 64
    obj = make_objective("coverage", universe=32 * words - 10,
                         backend="pallas")
    fn = lambda i, p, v: greedy(obj, i, p, v, k, engine="auto")
    compiled = jax.jit(fn).lower(spec((n,), I32), spec((n, words), U32),
                                 spec((n,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert plans.select_engine(obj.rule, words, n, None,
                               backend="pallas").engine == "step"
    assert plans.bitmap_orient(n, words) == "wc"
    body = _loop_bodies(text)
    assert "gains_pallas" in body and f"u32[{words},{n}]" in body
    assert f"u32[{n},{words}]" not in body
    assert not re.search(r"\bpad\(", body)
    shapes = re.findall(r"u32\[(\d+),(\d+)\]", body)
    padded = {str(1 << (n - 1).bit_length()), str(-(-words // 512) * 512)}
    assert shapes and not [s for s in shapes if padded & set(s)]
    assert re.search(rf"= u32\[{words},{n}\]\S* bitcast\(", text)
    assert not re.search(rf"= u32\[{n},{words}\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 20


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_fused_step(spec, dtype):
    rule = R.DIST_MIN
    plan = _plan(rule, N, C, D, requested="fused")
    assert plan.engine == "fused" and plan.block_n

    def step(g, c, row, mask, prev):
        mat = ops.pairwise_matrix(g, c, rule, backend="pallas", dtype=dtype)
        return ops.fused_step(mat, row, mask, prev, rule, backend="pallas",
                              plan=plan)

    _compiles(step, spec((N, D)), spec((C, D)), spec((N,)),
              spec((C,), jnp.bool_), spec((), I32),
              kernels=["pairwise_pallas", "fused_step_pallas"])


@pytest.mark.parametrize("name", ["kmedoid", "coverage"])
def test_greedy_loop_streaming(spec, name):
    rule = R.get(name)
    if rule.is_bitmap:
        plan = _plan(rule, WORDS, 8 * C, None)
        assert plan.engine == "mega_stream"
        _compiles(lambda c, row, mask: ops.greedy_loop(
            ops.pairwise_matrix(None, c, rule, backend="pallas"), row, mask,
            K, rule, backend="pallas", plan=plan),
            spec((8 * C, WORDS), U32), spec((WORDS,), U32),
            spec((8 * C,), jnp.bool_), kernels=["greedy_loop_pallas"])
        return
    plan = _plan(rule, N, C, D)
    assert plan.engine == "mega_stream"
    _compiles(lambda g, c, row, mask: ops.greedy_loop(
        ops.pairwise_matrix(g, c, rule, backend="pallas"), row, mask, K,
        rule, backend="pallas", plan=plan),
        spec((N, D)), spec((C, D)), spec((N,)), spec((C,), jnp.bool_),
        kernels=["pairwise_pallas", "greedy_loop_pallas"])


@pytest.mark.parametrize("case", ["float32", "int8", "coverage"])
def test_greedy_loop_resident(spec, case):
    """One accumulation-node greedy; the `(1, 3)` i32 ctl operand carries
    a traced step budget and logical extents."""
    if case == "coverage":
        _compiles(lambda c, row, mask, kq: ops.greedy_loop_resident(
            None, c, row, mask, K, R.BITS_OR, backend="pallas", kq=kq),
            spec((NODE, WORDS), U32), spec((WORDS,), U32),
            spec((NODE,), jnp.bool_), spec((), I32),
            kernels=["greedy_loop_resident_pallas"])
        return
    rule = R.DOT_MAX
    assert plans.resident_fits(NODE, NODE, D, rule=rule)
    _compiles(lambda g, row, mask, kq, ln, lc: ops.greedy_loop_resident(
        g, g, row, mask, K, rule, backend="pallas", cache_dtype=case,
        kq=kq, logical=(ln, lc)),
        spec((NODE, D)), spec((NODE,)), spec((NODE,), jnp.bool_),
        spec((), I32), spec((), I32), spec((), I32),
        kernels=["greedy_loop_resident_pallas"])


def test_greedy_loop_resident_serving_batch(spec):
    """The query service's admitted batch: B=4 MMR queries stacked on a
    vmap axis over the resident megakernel, one dispatch."""
    obj = make_objective("mmr", backend="pallas")
    b, c = 4, NODE
    assert plans.serve_plan(obj.rule, c, c, D, backend="pallas") is not None
    _compiles(lambda p, v, ks: obj.megakernel_loop_batched(p, v, ks, 16),
              spec((b, c, D)), spec((b, c), jnp.bool_), spec((b,), I32),
              kernels=["greedy_loop_resident_pallas"])


@pytest.mark.parametrize("case", ["facility", "knapsack", "coverage"])
def test_stream_filter(spec, case):
    """One arrival batch of B=128 against every sieve level; `knapsack`
    adds the costs / spent / budget operands."""
    rule = R.BITS_OR if case == "coverage" else R.DOT_MAX
    n, lv, b = 1024, 32, 128
    d = None if rule.is_bitmap else D
    plan = plans.stream_plan(n if d else WORDS, lv, b, d, backend="pallas",
                             rule=rule)
    assert plan is not None and plan["tier"] == "kernel"
    has_cost = case == "knapsack"

    def filt(ground, batch, rows, row0, bvalid, costs, spent):
        kw = dict(costs=costs, spent=spent,
                  budget=jnp.float32(10.0)) if has_cost else {}
        return ops.stream_filter(
            ground, batch, rows, row0, jnp.zeros((lv,), F32),
            jnp.zeros((lv,), I32), jnp.arange(lv, dtype=I32),
            jnp.float32(0.0), bvalid, K, 0.1, rule, backend="pallas",
            plan=plan, **kw)

    if rule.is_bitmap:
        ground, batch = None, spec((b, WORDS), U32)
        rows, row0 = spec((lv, WORDS), U32), spec((WORDS,), U32)
    else:
        ground, batch = spec((n, D)), spec((b, D))
        rows, row0 = spec((lv, n)), spec((n,))
    _compiles(functools.partial(filt, ground) if ground is None else filt,
              *([] if ground is None else [ground]), batch, rows, row0,
              spec((b,), jnp.bool_), spec((b,)), spec((lv,)),
              kernels=["stream_filter_pallas"])


# Tiny ImageNet's pixel width (64 × 64 × 3) and the pool one chip holds
# at it (bench/configs/kmedoid_tinyimg.json)
WIDE = 12_288
LEAF = 16_384


def test_pairwise_at_pixel_width(spec):
    """The build tiles the features: full-width blocks of 256 rows would
    need about 38 MB of VMEM."""
    tiles = plans.feature_tiles("pairwise", LEAF, LEAF, WIDE)
    assert tiles.d_pad // tiles.td > 1 and tiles.limit <= 32 * 2 ** 20
    _compiles(lambda g, c: ops.pairwise_matrix(g, c, R.DIST_MIN,
                                               backend="pallas"),
              spec((LEAF, WIDE)), spec((LEAF, WIDE)),
              kernels=["pairwise_pallas"])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_symmetric_pairwise_at_pixel_width(spec, dtype):
    """Ground and candidates one array, on the cell's square tiles: the
    build computes the blocks on and above the diagonal, and the mirror
    fills the rest in place, with no second (n, n) buffer."""
    tiles = plans.feature_tiles("pairwise", LEAF, LEAF, WIDE,
                                out_itemsize=jnp.dtype(dtype).itemsize)
    assert tiles.tn == tiles.tc and LEAF // tiles.tn >= 2
    fn = lambda x: ops.pairwise_matrix(x, x, R.DIST_MIN, backend="pallas",
                                       dtype=dtype)
    _compiles(fn, spec((LEAF, WIDE)),
              kernels=["pairwise_pallas", "pairwise_mirror"])
    mem = jax.jit(fn).lower(spec((LEAF, WIDE))).compile().memory_analysis()
    assert mem.temp_size_in_bytes == 0


@pytest.mark.parametrize("case", ["f32", "int8"])
def test_gains_at_pixel_width(spec, case, monkeypatch):
    if case == "int8":
        monkeypatch.setenv("REPRO_FUSED_CACHE_DTYPE", "int8")
    _compiles(lambda g, r, c, v: ops.gains(g, r, c, v, R.DIST_MIN,
                                           backend="pallas"),
              spec((LEAF, WIDE)), spec((LEAF,)), spec((LEAF, WIDE)),
              spec((LEAF,), jnp.bool_), kernels=["gains_pallas"])


@pytest.mark.parametrize("engine,want,kernels", [
    ("auto", "mega_stream", ["pairwise_pallas", "pairwise_mirror",
                             "greedy_loop_pallas"]),
    ("mega", "mega_stream", ["pairwise_pallas", "pairwise_mirror",
                             "greedy_loop_pallas"]),
    ("fused", "fused", ["pairwise_pallas", "pairwise_mirror",
                        "fused_step_pallas"]),
    ("step", "step", ["gains_pallas"]),
])
def test_kmedoid_tiers_at_pixel_width(spec, engine, want, kernels):
    """Every tier the planner admits for k-medoid on one chip's pool at
    Tiny ImageNet's width compiles as a whole greedy (k = 200); the
    resident tier, which would build the matrix on chip over every
    feature, is refused by its gate."""
    from repro.runtime import telemetry
    plan = plans.select_engine(R.DIST_MIN, LEAF, LEAF, WIDE,
                               requested=engine, backend="pallas")
    assert plan.engine == want
    gates = {r["gate"] for r in telemetry.records("plan")[-1]["refused"]}
    assert engine == "step" or "resident_vmem" in gates
    obj = make_objective("kmedoid", backend="pallas")
    _compiles(lambda i, p, v: greedy(obj, i, p, v, 200, engine=engine),
              spec((LEAF,), I32), spec((LEAF, WIDE)),
              spec((LEAF,), jnp.bool_), kernels=kernels)


def test_full_width_on_chip_builds_compile_where_gated_in(spec):
    """The resident megakernel and the stream filter build their matrix
    on chip over every feature. At Tiny ImageNet's width the largest pool
    the resident gate admits compiles, and the stream gate admits no
    batch at all."""
    n = 32                          # 64 rows need 9.5 MB: refused
    assert plans.resident_fits(n, 128, WIDE, rule=R.DIST_MIN)
    assert not plans.resident_fits(2 * n, 128, WIDE, rule=R.DIST_MIN)
    _compiles(lambda g, row, mask: ops.greedy_loop_resident(
        g, g, row, mask, K, R.DIST_MIN, backend="pallas"),
        spec((n, WIDE)), spec((n,)), spec((n,), jnp.bool_),
        kernels=["greedy_loop_resident_pallas"])
    assert plans.stream_plan(1, 8, 1, WIDE, backend="pallas",
                             rule=R.DOT_MAX) is None


def test_sharded_leaf_shard_map(topo):
    """The sharded leaf tier over a real 4-device mesh: the pool's ground
    axis is split over the `shard` axis, candidate tiles are all-gathered
    and each lane dispatches the gains kernel on its shard."""
    mesh = jax.sharding.Mesh(topo.devices, ("shard",))
    obj = make_objective("facility", backend="pallas")
    n = 8192
    rows = NamedSharding(mesh, P("shard"))
    _compiles(lambda ids, pay, val: shard_greedy_distributed(
        obj, ids, pay, val, 8, mesh, tile_c=256),
        jax.ShapeDtypeStruct((n,), I32, sharding=rows),
        jax.ShapeDtypeStruct((n, D), F32, sharding=rows),
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=rows),
        kernels=["gains_pallas"])
