from repro.launch.mesh import auto_mesh, device_lanes, force_host_devices

force_host_devices(4, trigger="--distributed")  # pragma: no cover - env
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Streaming selection driver — the online counterpart of summarize.py.

    PYTHONPATH=src python -m repro.launch.stream --objective facility \
        --n 2048 --batch 128 --k 32 --order drift --compare

Runs the sieve-streaming engine (repro.streaming, DESIGN §Streaming) over
a deterministic synthetic arrival stream. Modes:

  * default        — single-device sieve over the whole stream
  * --continuous   — vmapped-lane continuous mode with periodic GreedyML
                     tree merges (single device)
  * --distributed  — the same continuous mode via shard_map over a real
                     mesh, one lane per device: the chips, or --lanes
                     (default 4) host devices on the CPU
  * --window W     — sliding-window summary of the last W arrivals

``--smoke`` runs a tiny instance through single + window + continuous
(including a checkpoint/resume round-trip) and exits nonzero on any
quality or resume mismatch — the CI entry point (scripts/ci_smoke.sh).
"""

import argparse
import tempfile
import time

import numpy as np

from repro.core.functions import make_objective
from repro.core.greedy import greedy
from repro.core.simulate import global_value
from repro.data.synthetic import gen_stream
from repro.streaming import (SieveStreamer, SlidingSieve, stream_select,
                             stream_select_continuous,
                             stream_select_distributed)

import jax
import jax.numpy as jnp

from repro.runtime import compile_cache


def _make(args):
    st = gen_stream(args.objective, args.n, d=args.d,
                    universe=args.universe, batch=args.batch,
                    order=args.order, seed=args.seed)
    if args.objective in ("kcover", "kdom"):
        obj = make_objective("kcover", universe=args.universe,
                             backend=args.backend)
        ground = None
    else:
        obj = make_objective(args.objective, backend=args.backend)
        ground = jnp.asarray(st.payloads)
    return st, obj, ground


def _ids(sol):
    return np.asarray(sol.ids)[np.asarray(sol.valid)]


def run(args) -> int:
    st, obj, ground = _make(args)
    t0 = time.time()
    info = {}
    if args.window:
        streamer = SieveStreamer(obj, args.k, args.eps, ground=ground,
                                 backend=args.backend)
        win = SlidingSieve(streamer, args.window,
                           args.stride or args.window // 2)
        wstate = None
        for ids, pay, valid in st:
            ids, pay, valid = (jnp.asarray(ids), jnp.asarray(pay),
                               jnp.asarray(valid))
            if wstate is None:
                wstate = win.init(pay)
            wstate = win.process_batch(wstate, ids, pay, valid)
        sol = win.query(wstate)
        mode = f"window[{args.window}/{win.stride}]"
    elif args.distributed:
        mesh = auto_mesh((args.lanes,), ("stream",))
        sol, info = stream_select_distributed(
            obj, st, args.k, mesh, ("stream",), ground=ground,
            merge_every=args.merge_every, eps=args.eps,
            backend=args.backend)
        mode = f"distributed[{args.lanes} lanes]"
    elif args.continuous:
        sol, info = stream_select_continuous(
            obj, st, args.k, lanes=args.lanes, merge_every=args.merge_every,
            eps=args.eps, ground=ground, backend=args.backend)
        mode = f"continuous[{args.lanes} lanes]"
    else:
        sol = stream_select(obj, st, args.k, eps=args.eps, ground=ground,
                            backend=args.backend, ckpt_dir=args.ckpt_dir,
                            ckpt_every=args.ckpt_every, resume=args.resume)
        mode = "single"
    dt = time.time() - t0
    ids = _ids(sol)
    gv = global_value(args.objective if args.objective != "kdom"
                      else "kcover", st.payloads, ids, args.universe)
    rate = st.n / max(dt, 1e-9)
    print(f"stream[{mode}] {args.objective} n={st.n} k={args.k} "
          f"f={gv:.3f} |S|={len(ids)} arrivals/s={rate:.0f} "
          f"[{dt:.1f}s] {info.get('merges', '')}")
    if args.compare:
        g = greedy(obj, jnp.arange(st.n, dtype=jnp.int32),
                   jnp.asarray(st.payloads), jnp.ones(st.n, bool), args.k)
        ggv = global_value(args.objective if args.objective != "kdom"
                           else "kcover", st.payloads, _ids(g),
                           args.universe)
        print(f"offline greedy f={ggv:.3f}  sieve/greedy = {gv / ggv:.4f}")
        if gv < (0.5 - args.eps) * ggv:
            print("FAIL: below the (1/2 - eps) sieve bound")
            return 1
    return 0


def smoke(args) -> int:
    """Tiny end-to-end pass across the subsystem (CI)."""
    args.n, args.batch, args.k = 256, 64, 8
    args.d, args.universe = 24, 384
    rc = 0
    for objective in ("facility", "kcover"):
        args.objective = objective
        args.compare = True
        for setup in ("single", "window", "continuous"):
            a = argparse.Namespace(**vars(args))
            a.window = 128 if setup == "window" else 0
            a.stride = 64
            a.continuous = setup == "continuous"
            a.distributed = False
            a.lanes, a.merge_every = 4, 2
            rc |= run(a)
    # checkpoint/resume round-trip: half the stream, checkpoint, resume
    st, obj, ground = _make(args)
    with tempfile.TemporaryDirectory() as d:
        full = stream_select(obj, st, args.k, ground=ground,
                             backend=args.backend)
        half = list(st.batches())[: st.n // args.batch // 2]
        stream_select(obj, half, args.k, ground=ground,
                      backend=args.backend, ckpt_dir=d, ckpt_every=1)
        resumed = stream_select(obj, st, args.k, ground=ground,
                                backend=args.backend, ckpt_dir=d,
                                resume=True)
        if not np.array_equal(_ids(full), _ids(resumed)):
            print("FAIL: checkpoint resume diverged")
            rc |= 1
        else:
            print("checkpoint resume OK")
    print("stream smoke", "FAILED" if rc else "OK")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--objective", default="facility",
                    choices=["facility", "kmedoid", "kcover"])
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--universe", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--eps", type=float, default=0.1)
    ap.add_argument("--order", default="shuffled",
                    choices=["shuffled", "adversarial", "drift"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default=None)
    ap.add_argument("--continuous", action="store_true")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--lanes", type=int, default=None)
    ap.add_argument("--merge-every", type=int, default=4)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--stride", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compare", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.distributed:
        args.lanes = device_lanes(args.lanes, "--distributed")
    elif args.lanes is None:
        args.lanes = 4
    if args.smoke:
        return smoke(args)
    return run(args)


if __name__ == "__main__":
    raise SystemExit(main())
