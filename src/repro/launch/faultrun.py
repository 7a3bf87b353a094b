from repro.launch.mesh import device_lanes, force_host_devices

force_host_devices(8, trigger="--mesh")     # pragma: no cover - env setup
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Fault-tolerant distributed selection driver (DESIGN §Fault tolerance).

    PYTHONPATH=src python -m repro.launch.faultrun --objective kcover \
        --n 512 --k 8 --lanes 8 --branching 2 --mesh \
        --fail-level 1 --fail-lane 3

Runs the supervised level-by-level GreedyML runtime
(runtime.supervisor.SelectionSupervisor over core.greedyml.LevelDispatcher)
with deterministic failure injection and prints the structured recovery
log. Modes:

  * default          — clean supervised run (still checkpoints per level)
  * --fail-level L --fail-lane W
                     — inject ONE transient failure at level L on lane W:
                       the level-replay path (bit-identical recovery)
  * --permanent      — the same lane instead fails EVERY attempt from
                       level L on: the degraded-tree path (lane dropped,
                       tree re-planned over the survivors)
  * --stream         — supervise the continuous streaming driver's merges
                       instead (transient replay + lane_reset)
  * --mesh           — run every stage over a real mesh, one lane per
                       device: the chips, or --lanes (default 8) host
                       devices on the CPU; default is the single-device
                       vmap simulation

``--smoke`` runs the CI acceptance suite: replay bit-identity against the
failure-free run, the degraded tree's ≥0.95× quality band, and a
supervised streaming pass — exit nonzero on any violation
(scripts/ci_smoke.sh fault stage).
"""

import argparse
import json
import tempfile
import time

import numpy as np

from repro.runtime import compile_cache


def _build(args):
    import jax.numpy as jnp
    from repro.core.functions import make_objective
    from repro.data import synthetic

    if args.objective == "kcover":
        sets = synthetic.gen_kcover(args.n, args.universe, seed=args.seed)
        pay = synthetic.pack_bitmaps(sets, args.universe)
        obj = make_objective("kcover", universe=args.universe,
                             backend=args.backend)
    else:
        pay = synthetic.gen_images(args.n, args.d, seed=args.seed)
        obj = make_objective(args.objective, backend=args.backend)
    ids = jnp.arange(args.n, dtype=jnp.int32)
    valid = jnp.ones(args.n, bool)
    return obj, ids, jnp.asarray(pay), valid


def _mesh_or_none(args):
    if not args.mesh:
        return None, None
    from repro.launch.mesh import make_machine_mesh
    mesh = make_machine_mesh(args.lanes, args.branching or args.lanes)
    return mesh, tuple(reversed(mesh.axis_names))


def _supervised(args, ckpt_dir, injector=None, max_restarts=None):
    from repro.runtime.supervisor import SelectionSupervisor

    mesh, tree_axes = _mesh_or_none(args)
    sup = SelectionSupervisor(
        ckpt_dir=ckpt_dir, injector=injector,
        max_restarts=args.max_restarts if max_restarts is None
        else max_restarts)
    obj, ids, pay, valid = _build(args)
    t0 = time.time()
    sol, info = sup.select(obj, ids, pay, valid, args.k, lanes=args.lanes,
                           branching=args.branching, mesh=mesh,
                           tree_axes=tree_axes)
    info["wall_s"] = time.time() - t0
    return sol, info


def _print_events(events):
    for ev in events:
        kw = {k: v for k, v in ev.items() if k not in ("kind", "time")}
        print(f"  [{ev['kind']:>12s}] " + " ".join(
            f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in kw.items()))


def run(args) -> int:
    from repro.runtime.supervisor import LaneFailureInjector

    injector = None
    if args.fail_level >= 0:
        if args.permanent:
            injector = LaneFailureInjector(
                dead={args.fail_lane: args.fail_level})
        else:
            injector = LaneFailureInjector(
                fail_at=((args.fail_level, args.fail_lane),))

    if args.stream:
        return _run_stream(args, injector)

    with tempfile.TemporaryDirectory() as d:
        ckpt = args.ckpt_dir or d
        sol, info = _supervised(args, ckpt, injector=injector)
    mode = "mesh" if args.mesh else "sim"
    print(f"faultrun[{mode}] {args.objective} n={args.n} k={args.k} "
          f"tree={info['tree']} final={info['final_tree']} "
          f"degraded={info['degraded']} f={float(sol.value):.3f} "
          f"[{info['wall_s']:.1f}s]")
    _print_events(info["events"])
    return 0


def _run_stream(args, injector) -> int:
    import jax.numpy as jnp
    from repro.core.functions import make_objective
    from repro.data.synthetic import gen_stream
    from repro.runtime.supervisor import SelectionSupervisor
    from repro.streaming.driver import stream_select_continuous

    st = gen_stream(args.objective, args.n, d=args.d,
                    universe=args.universe, batch=args.batch, seed=args.seed)
    if args.objective == "kcover":
        obj = make_objective("kcover", universe=args.universe,
                             backend=args.backend)
        ground = None
    else:
        obj = make_objective(args.objective, backend=args.backend)
        ground = jnp.asarray(st.payloads)
    with tempfile.TemporaryDirectory() as d:
        sup = SelectionSupervisor(ckpt_dir=args.ckpt_dir or d,
                                  injector=injector,
                                  max_restarts=args.max_restarts)
        t0 = time.time()
        sol, info = stream_select_continuous(
            obj, st, args.k, lanes=args.lanes,
            branching=args.branching or args.lanes,
            merge_every=args.merge_every, ground=ground,
            backend=args.backend, supervisor=sup)
        dt = time.time() - t0
    print(f"faultrun[stream] {args.objective} n={args.n} k={args.k} "
          f"lanes={args.lanes} f={float(sol.value):.3f} "
          f"merges={info['merges']} [{dt:.1f}s]")
    _print_events(info["events"])
    return 0


def smoke(args) -> int:
    """CI acceptance: replay bit-identity, degraded quality band,
    supervised streaming. Exit nonzero on any violation."""
    from repro.runtime.supervisor import (LaneFailureInjector,
                                          SelectionSupervisor)

    args.objective, args.n, args.universe = "kcover", 512, 512
    args.k, args.seed = 8, 2
    rc = 0
    fail_lane = args.lanes - 1

    with tempfile.TemporaryDirectory() as d0:
        clean, cinfo = _supervised(args, d0)
    print(f"clean     f={float(clean.value):.3f} tree={cinfo['tree']}")

    # --- transient failure at level 1 → level replay, bit-identical ------
    inj = LaneFailureInjector(fail_at=((1, fail_lane),))
    with tempfile.TemporaryDirectory() as d1:
        sol, info = _supervised(args, d1, injector=inj)
    kinds = [e["kind"] for e in info["events"]]
    ok = (bool(np.array_equal(np.asarray(sol.ids), np.asarray(clean.ids)))
          and float(sol.value) == float(clean.value)
          and "failure" in kinds and "restore" in kinds)
    print(f"replay    f={float(sol.value):.3f} bit-identical="
          f"{bool(np.array_equal(np.asarray(sol.ids), np.asarray(clean.ids)))}")
    if not ok:
        print("FAIL: replay path not bit-identical to failure-free run")
        _print_events(info["events"])
        rc |= 1

    # --- permanent lane loss → degraded tree, ≥0.95× quality band -------
    inj = LaneFailureInjector(dead={fail_lane: 1})
    with tempfile.TemporaryDirectory() as d2:
        sol, info = _supervised(args, d2, injector=inj, max_restarts=1)
    kinds = [e["kind"] for e in info["events"]]
    ratio = float(sol.value) / float(clean.value)
    print(f"degraded  f={float(sol.value):.3f} ratio={ratio:.4f} "
          f"final_tree={info['final_tree']}")
    if not (info["degraded"] and "reshard" in kinds and ratio >= 0.95):
        print("FAIL: degraded-tree run outside the 0.95 quality band "
              "or no reshard event")
        _print_events(info["events"])
        rc |= 1

    # --- supervised streaming: transient merge failure replays ----------
    from repro.core.functions import make_objective
    from repro.data.synthetic import gen_stream
    from repro.streaming.driver import stream_select_continuous

    st = gen_stream("kcover", 256, universe=384, batch=64, seed=args.seed)
    obj = make_objective("kcover", universe=384, backend=args.backend)
    sref, _ = stream_select_continuous(obj, st, args.k, lanes=4,
                                       merge_every=2, backend=args.backend)
    with tempfile.TemporaryDirectory() as d3:
        sup = SelectionSupervisor(ckpt_dir=d3,
                                  injector=LaneFailureInjector(
                                      fail_at=((1, 1),)))
        ssol, sinfo = stream_select_continuous(
            obj, st, args.k, lanes=4, merge_every=2, backend=args.backend,
            supervisor=sup)
    skinds = [e["kind"] for e in sinfo["events"]]
    sok = (bool(np.array_equal(np.asarray(ssol.ids), np.asarray(sref.ids)))
           and "failure" in skinds and "restart" in skinds)
    print(f"stream    f={float(ssol.value):.3f} replay-identical={sok}")
    if not sok:
        print("FAIL: supervised streaming replay diverged")
        _print_events(sinfo["events"])
        rc |= 1
    print("fault smoke", "FAILED" if rc else "OK")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--objective", default="kcover",
                    choices=["facility", "kmedoid", "kcover"])
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--d", type=int, default=24)
    ap.add_argument("--universe", type=int, default=512)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=None)
    ap.add_argument("--branching", type=int, default=2)
    ap.add_argument("--seed", type=int, default=2)
    ap.add_argument("--backend", default=None)
    ap.add_argument("--mesh", action="store_true")
    ap.add_argument("--fail-level", type=int, default=-1)
    ap.add_argument("--fail-lane", type=int, default=0)
    ap.add_argument("--permanent", action="store_true")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--stream", action="store_true")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--merge-every", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.mesh:
        args.lanes = device_lanes(args.lanes, "--mesh")
    elif args.lanes is None:
        args.lanes = 8
    if args.smoke:
        return smoke(args)
    return run(args)


if __name__ == "__main__":
    raise SystemExit(main())
