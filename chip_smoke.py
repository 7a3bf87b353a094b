#!/usr/bin/env python3
"""Bring-up check: run the selection system's main paths on one TPU chip.

    python3 chip_smoke.py              # four phases on one chip
    python3 chip_smoke.py --chips 4    # the four-chip tree phase only

Every phase goes through the entry points a user calls, at the shapes of
the repository's paper configurations, on compiled Pallas kernels:

  greedy   `core.greedy.greedy` on `configs/paper_kmedoid` (k-medoid and
           facility location, n=8192, d=768, k=200) and
           `configs/paper_kcover` (n=65,536 sets over 16,384 items, k=64),
           with the engine `plans.select_engine` picks and with the fused,
           megakernel and per-step engines forced, so that every kernel runs
  tree     `SelectionSupervisor.select` over paper_kmedoid on 32 simulated
           machines with branching 2 (`LevelDispatcher`, one device)
  serve    `serving.QueryEngine` answering 16 mixed queries (facility,
           k-medoid, coverage, MMR; pools of 1-2k; d=768; k from 5 to 20)
  sieve    `streaming.SieveStreamer` (facility) over 64 batches of 128
           arrivals through the stream-filter kernel

Each phase is checked against the same call on the jnp reference backend,
on the same chip: the selected ids must match, or the value of the
selection, scored by the reference objective, must be at least 0.999 of
the reference's. ``--chips 4`` runs the planned tree over a real 2x2
device mesh and a leaf sharded over 4 devices, and requires both to
select exactly what the single-device runs select.

Timings printed here are bring-up diagnostics labelled with the device
they ran on, not benchmark results. Without a TPU the script exits with
code 2 and runs nothing. The last line of a passing run is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
VALUE_RATIO_MIN = 0.999


class SmokeFailure(AssertionError):
    """A phase produced a wrong or unchecked result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _np(x):
    import numpy as np
    return np.asarray(x)


def _timed_jit(fn, *args):
    """(output, compile seconds, warm seconds): compile ahead of time, run
    once, then time a second run to `block_until_ready`."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t_compile = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t_compile, time.perf_counter() - t0


def _timed_call(fn):
    """(output, cold seconds, warm seconds) of an entry point that compiles
    inside and waits for its own results: the first call (compile and
    run) and a second one."""
    t0 = time.perf_counter()
    fn()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return out, cold, time.perf_counter() - t0


def _score(ref_obj, sol, ground, ground_valid):
    """f(S) of a Solution under the reference objective on `ground`."""
    from repro.core.greedy import replay_value
    return float(replay_value(ref_obj, sol.payloads, sol.valid, ground,
                              ground_valid))


def _parity(label, sol, ref_sol, ref_obj, ground, ground_valid) -> str:
    """'ids identical', or the value ratio when ids differ; raises below
    VALUE_RATIO_MIN."""
    import numpy as np
    ids = np.where(_np(sol.valid), _np(sol.ids), -1)
    ref_ids = np.where(_np(ref_sol.valid), _np(ref_sol.ids), -1)
    if np.array_equal(ids, ref_ids):
        return "ids identical"
    v = _score(ref_obj, sol, ground, ground_valid)
    v_ref = _score(ref_obj, ref_sol, ground, ground_valid)
    ratio = v / v_ref if v_ref else float("inf") if v else 1.0
    differ = int(np.sum(ids != ref_ids))
    check(ratio >= VALUE_RATIO_MIN,
          f"{label}: {differ} of {ids.size} ids differ from the reference "
          f"and the value ratio {ratio!r} is below {VALUE_RATIO_MIN}")
    return (f"ids differ at {differ} of {ids.size} slots, value ratio "
            f"{ratio!r} (>= {VALUE_RATIO_MIN})")


def _plan_str(plan) -> str:
    s = f"{plan.engine} (tier {plan.tier}, {plan.dtype}"
    if plan.block_n or plan.loop_block_n:
        s += f", block_n {plan.block_n}, loop_block_n {plan.loop_block_n}"
    return s + ")"


def _kernel_backend(plan_backend: str, want: str) -> None:
    check(plan_backend == want,
          f"kernels resolved to backend {plan_backend!r}, expected {want!r}")


# ---------------------------------------------------------------------------
# data at the paper configurations
# ---------------------------------------------------------------------------


def kmedoid_instance(n=None, d=None):
    import jax.numpy as jnp
    from repro.configs import paper_kmedoid
    from repro.data.synthetic import gen_images
    cfg = paper_kmedoid.CONFIG
    n, d = n or cfg.n, d or cfg.feature_dim
    x = jnp.asarray(gen_images(n, d, seed=cfg.seed))
    return cfg, jnp.arange(n, dtype=jnp.int32), x, jnp.ones((n,), bool)


def kcover_instance(n=None, universe=None):
    import jax.numpy as jnp
    from repro.configs import paper_kcover
    from repro.data.synthetic import gen_kcover, pack_bitmaps
    cfg = paper_kcover.CONFIG
    n, universe = n or cfg.n, universe or cfg.universe
    bits = pack_bitmaps(gen_kcover(n, universe, seed=cfg.seed), universe)
    return (cfg, universe, jnp.arange(n, dtype=jnp.int32),
            jnp.asarray(bits), jnp.ones((n,), bool))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_greedy(device: str, backend=None, want="pallas", small=False):
    import jax
    from repro.core.greedy import greedy
    from repro.core.objective import make_objective
    from repro.kernels import plans

    cfg, ids, x, valid = kmedoid_instance(*((512, 64) if small else ()))
    kcfg, universe, cids, bits, cvalid = kcover_instance(
        *((2048, 1024) if small else ()))
    k_med, k_cov = (8, 8) if small else (cfg.k, kcfg.k)
    cases = [("kmedoid", "auto"), ("facility", "auto"),
             ("coverage", "auto"), ("kmedoid", "mega"),
             ("kmedoid", "fused"), ("kmedoid", "step"),
             ("coverage", "step")]
    seen = set()
    for name, engine in cases:
        cover = name == "coverage"
        obj = make_objective(name, universe=universe, backend=backend)
        ref = make_objective(name, universe=universe, backend="ref")
        pool = (cids, bits, cvalid) if cover else (ids, x, valid)
        k = k_cov if cover else k_med
        n = pool[0].shape[0]
        dims = ((obj.words, n, None) if cover
                else (n, n, x.shape[1]))
        plan = plans.select_engine(obj.rule, *dims, requested=engine,
                                   backend=backend)
        _kernel_backend(plan.backend, want)
        if (name, plan.engine) in seen:
            continue            # a forced engine the planner already ran
        seen.add((name, plan.engine))
        sol, t_c, t_w = _timed_jit(
            lambda i, p, v: greedy(obj, i, p, v, k, engine=engine), *pool)
        ref_sol = jax.jit(lambda i, p, v: greedy(ref, i, p, v, k,
                                                 engine=engine))(*pool)
        par = _parity(f"greedy {name} engine={engine}", sol, ref_sol, ref,
                      pool[1], pool[2])
        shape = (f"n={n} universe={universe}" if cover
                 else f"n={n} d={x.shape[1]}")
        print(f"[greedy] {name} {shape} k={k} engine={engine} -> "
              f"{_plan_str(plan)} | compile {t_c!r} s | warm {t_w!r} s "
              f"on {device} | parity: {par}", flush=True)


def phase_tree(device: str, backend=None, want="pallas", small=False):
    import jax
    from repro.core.objective import make_objective
    from repro.kernels import plans
    from repro.runtime.supervisor import SelectionSupervisor

    cfg, ids, x, valid = kmedoid_instance(*((2048, 64) if small else ()))
    k = 50 if small else cfg.k
    lanes, b = cfg.num_machines, cfg.branching
    obj = make_objective(cfg.objective, backend=backend)
    ref = make_objective(cfg.objective, backend="ref")
    n_leaf, d = ids.shape[0] // lanes, x.shape[1]
    leaf = plans.select_engine(obj.rule, n_leaf, n_leaf, d, backend=backend)
    node = plans.select_engine(obj.rule, b * k, b * k, d, backend=backend)
    _kernel_backend(leaf.backend, want)

    def run(objective):
        with tempfile.TemporaryDirectory() as td:
            sup = SelectionSupervisor(ckpt_dir=td)
            sol, info = sup.select(objective, ids, x, valid, k, lanes=lanes,
                                   branching=b)
            return jax.block_until_ready(sol), info

    (sol, info), t_cold, t_warm = _timed_call(lambda: run(obj))
    ref_sol, _ = run(ref)
    par = _parity("tree", sol, ref_sol, ref, x, valid)
    print(f"[tree] {cfg.objective} n={ids.shape[0]} d={d} k={k} "
          f"machines={lanes} branching={b} levels={len(info['radices'])} "
          f"-> leaves {_plan_str(leaf)}, nodes {_plan_str(node)} | "
          f"cold {t_cold!r} s | warm {t_warm!r} s on {device} | "
          f"parity: {par}", flush=True)


def _serve_queries(small=False):
    import jax.numpy as jnp
    from repro.data.synthetic import gen_images, gen_kcover, pack_bitmaps
    from repro.serving import Query

    d, universe = (64, 1024) if small else (768, 16384)
    sizes = (96, 128, 160) if small else (1024, 1536, 2048)
    queries = []
    for i in range(16):
        name = ("facility", "kmedoid", "coverage", "mmr")[i % 4]
        k = 5 + (i * 5) % 16
        if name == "coverage":
            # equal pools so the coverage queries can share one batch
            c = sizes[0]
            pay = pack_bitmaps(gen_kcover(c, universe, seed=100 + i),
                               universe)
        else:
            c = sizes[i % len(sizes)]
            pay = gen_images(c, d, seed=100 + i)
        queries.append(Query(name, k, jnp.arange(c, dtype=jnp.int32),
                             jnp.asarray(pay), jnp.ones((c,), bool),
                             tenant=f"tenant{i % 4}",
                             universe=universe if name == "coverage" else 0))
    return queries


def phase_serve(device: str, backend=None, want="pallas", small=False):
    import jax
    from repro.core.objective import make_objective
    from repro.kernels import plans
    from repro.serving import QueryEngine

    _kernel_backend(plans.resolve_backend(backend), want)
    queries = _serve_queries(small)

    def serve(be):
        eng = QueryEngine(backend=be)
        for q in queries:
            eng.submit(q)
        res = eng.drain()
        jax.block_until_ready([r.solution.ids for r in res.values()])
        return eng, res

    (eng, res), t_cold, t_warm = _timed_call(lambda: serve(backend))
    _, ref_res = serve("ref")
    check(len(res) == len(queries), f"{len(res)} of {len(queries)} served")
    multi = [bt for bt in eng.metrics.batches if bt["size"] > 1]
    check(any(bt["dispatches"] == 1 for bt in multi),
          "no admitted batch of more than one query ran as one dispatch")
    worst, differ = None, 0
    for qid, r in sorted(res.items()):
        q = queries[qid]
        ref_obj = make_objective(q.objective, universe=q.universe,
                                 backend="ref")
        par = _parity(f"serve query {qid} ({q.objective})", r.solution,
                      ref_res[qid].solution, ref_obj, q.payloads, q.valid)
        if par != "ids identical":
            differ += 1
            worst = par
    solo = sum(1 for r in res.values() if not r.batched)
    shapes = ", ".join(f"B={bt['size']} in {bt['dispatches']} dispatch"
                       for bt in multi)
    print(f"[serve] {len(queries)} queries -> batched: {shapes}; solo "
          f"greedy: {solo} | cold {t_cold!r} s | warm {t_warm!r} s on "
          f"{device} | parity: {len(queries) - differ} ids identical"
          + (f", {differ} within value ratio (last: {worst})"
             if differ else ""), flush=True)


def phase_sieve(device: str, backend=None, want="pallas", small=False):
    import jax
    import jax.numpy as jnp
    from repro.core.objective import make_objective
    from repro.data.synthetic import gen_images
    from repro.kernels import plans
    from repro.streaming import SieveStreamer

    n_ground, d, k, batch, nb = ((256, 64, 8, 32, 4) if small
                                 else (1024, 768, 32, 128, 64))
    ground = jnp.asarray(gen_images(n_ground, d, seed=21))
    arrivals = jnp.asarray(gen_images(batch * nb, d, seed=22))
    gvalid = jnp.ones((n_ground,), bool)

    # every batch's inputs are on the device before the clock starts, so
    # the loop times the step and nothing else
    batches = jax.block_until_ready([
        (jnp.arange(i * batch, (i + 1) * batch, dtype=jnp.int32),
         arrivals[i * batch:(i + 1) * batch], jnp.ones((batch,), bool))
        for i in range(nb)])

    def stream(be):
        obj = make_objective("facility", backend=be)
        st = SieveStreamer(obj, k, 0.1, ground=ground, backend=be)
        state = jax.block_until_ready(st.init(arrivals[:1]))
        # compiled once, ahead of time: a state whose types drift between
        # batches is refused instead of quietly compiled again
        t0 = time.perf_counter()
        step = jax.jit(st.process_batch).lower(state, *batches[0]).compile()
        t_compile = time.perf_counter() - t0
        times = []
        for args in batches:
            t0 = time.perf_counter()
            state = jax.block_until_ready(step(state, *args))
            times.append(time.perf_counter() - t0)
        return st, st.solution(state), t_compile, times

    st, sol, t_compile, times = stream(backend)
    plan = plans.stream_plan(n_ground, st.levels, batch, d, backend=backend,
                             rule=st.rule)
    check(plan is not None and plan["tier"] == ("kernel" if want != "ref"
                                                else "ref"),
          f"stream_plan gave {plan}: the stream-filter kernel did not run")
    _kernel_backend(plans.resolve_backend(backend), want)
    _, ref_sol, _, _ = stream("ref")
    ref = make_objective("facility", backend="ref")
    par = _parity("sieve", sol, ref_sol, ref, ground, gvalid)
    warm = sum(times[1:]) / max(len(times) - 1, 1)
    print(f"[sieve] facility ground={n_ground} d={d} k={k} levels="
          f"{st.levels} {nb} batches of {batch} -> stream_filter tier "
          f"{plan['tier']} ({plan['dtype']}) | compile {t_compile!r} s | "
          f"first batch {times[0]!r} s | warm {warm!r} s per batch on "
          f"{device} | parity: {par}", flush=True)


def phase_four_chips(device: str, backend=None, want="pallas",
                     small=False):
    """The planned tree over a real 2x2 mesh and one leaf sharded over 4
    devices, each against its single-device twin: bit-identical ids."""
    import jax
    import numpy as np
    from repro.core.greedy import greedy
    from repro.core.objective import make_objective
    from repro.kernels import plans
    from repro.launch.mesh import make_tree_mesh
    from repro.runtime.supervisor import SelectionSupervisor

    cfg, ids, x, valid = kmedoid_instance(*((512, 64) if small else ()))
    k = 8 if small else cfg.k
    _kernel_backend(plans.resolve_backend(backend), want)

    def select(objective, **kw):
        with tempfile.TemporaryDirectory() as td:
            sup = SelectionSupervisor(ckpt_dir=td)
            sol, info = sup.select(objective, ids, x, valid, k, lanes=4,
                                   **kw)
            return jax.block_until_ready(sol), info

    def same(a, b, label):
        check(np.array_equal(_np(a.ids), _np(b.ids))
              and np.array_equal(_np(a.valid), _np(b.valid)),
              f"{label}: ids differ")
        return "ids identical"

    kmed = make_objective("kmedoid", backend=backend)
    mesh = make_tree_mesh((2, 2))
    devs = sorted({int(d.id) for d in mesh.devices.flat})
    check(len(devs) == 4, f"tree mesh spans devices {devs}")
    (tree, info), t_cold, t_warm = _timed_call(lambda: select(
        kmed, mesh=mesh, tree_axes=("lvl0", "lvl1")))
    sim, _ = select(kmed, branching=2)
    par = same(tree, sim, "tree on the 2x2 mesh vs LevelDispatcher")
    print(f"[tree-4] kmedoid n={ids.shape[0]} d={x.shape[1]} k={k} mesh "
          f"{dict(mesh.shape)} on devices {devs} radices "
          f"{info['radices']} | cold {t_cold!r} s | warm {t_warm!r} s on "
          f"4 x {device} | parity vs single-device LevelDispatcher: {par}",
          flush=True)

    fac = make_objective("facility", backend=backend)
    smesh = make_tree_mesh((), 4)
    (shard, info), t_cold, t_warm = _timed_call(lambda: select(
        fac, mesh=smesh, tree_axes=()))
    check(info["shard"] == 4, f"leaf ran with shard={info['shard']}")
    solo = greedy(fac, ids, x, valid, k, engine="step")
    par = same(shard, solo, "leaf sharded over 4 devices vs solo greedy")
    print(f"[shard-4] facility n={ids.shape[0]} d={x.shape[1]} k={k} "
          f"shard=4 | cold {t_cold!r} s | warm {t_warm!r} s on 4 x "
          f"{device} | parity vs solo greedy(engine='step'): {par}",
          flush=True)


PHASES_ONE_CHIP = (("greedy", phase_greedy), ("tree", phase_tree),
                   ("serve", phase_serve), ("sieve", phase_sieve))
PHASES_FOUR_CHIPS = (("four-chip tree", phase_four_chips),)


def run_phases(phases, device: str, **kw) -> list:
    """Run every phase; a phase that raises is reported with its traceback
    and the run fails. Returns the names of the failed phases."""
    failed = []
    for name, fn in phases:
        print(f"== phase {name}", flush=True)
        try:
            fn(device, **kw)
        except Exception:       # report, then fail the run at the end
            traceback.print_exc()
            print(f"FAILED phase {name}", flush=True)
            failed.append(name)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees "
              f"{devices[0].platform!r} devices); nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.runtime import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e}); nothing was run", file=sys.stderr)
        return 2
    from repro.kernels import plans

    cache = compile_cache.enable()
    backend = plans.resolve_backend(None)
    check(backend == "pallas",
          f"the default kernel backend resolved to {backend!r}")
    kind = devices[0].device_kind
    print(f"chip_smoke: {len(devices)} x {kind}, kernel backend {backend}, "
          f"compile cache {cache}", flush=True)
    phases = PHASES_FOUR_CHIPS if args.chips == 4 else PHASES_ONE_CHIP
    t0 = time.perf_counter()
    failed = run_phases(phases, kind)
    print(f"chip_smoke: the phases took {time.perf_counter() - t0!r} s on "
          f"{kind}, compiles included", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
