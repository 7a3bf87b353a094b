"""Measured engine autotuning (DESIGN §Autotune).

The static planner (kernels/plans.py) picks a selection-engine tier from
closed-form VMEM/HBM budget math. That math is deliberately conservative
and dtype-laddered (f32 → bf16 → int8 only as each busts the HBM cache),
so it never *chooses* to quantize for speed: e.g. at N = C = 1024,
D = 64 the f32 resident working set busts the 8 MB VMEM budget and the
heuristic settles for the 2-dispatch streaming megakernel, even though
the int8-resident working set (~2.2 MB) fits and runs the whole greedy
in ONE dispatch.

This tuner closes that gap by MEASURING: for each (objective, shape) it
enumerates every candidate plan the budget gates admit — tier ×
power-of-two row blocks × cache storage dtype, including combinations
the static ladder never reaches — times each through the REAL greedy
driver (`plans.plan_override` forces the plan at trace time; warmup +
best-of-reps wall clock, the launch/hillclimb.py measurement idiom), and
persists the winner to the JSON cache that `plans.select_engine`
consults (REPRO_AUTOTUNE_CACHE). Every entry records the live budget
snapshot, so tuning under one REPRO_FUSED_{CACHE,VMEM}_MB configuration
can never leak into another.

Sub-f32 candidates are parity-gated on SELECTION IDENTITY, not bitwise
gains: a candidate whose greedy picks different element ids than the
static plan is rejected no matter how fast it is.

    REPRO_AUTOTUNE_CACHE=.autotune/plans.json \
        PYTHONPATH=src python -m repro.launch.autotune --smoke
    PYTHONPATH=src python -m repro.launch.autotune \
        --objective facility --objective kmedoid --n 1024 --d 64 --k 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core.greedy import greedy
from repro.core.objective import make_objective, registry
from repro.data.synthetic import gen_images, gen_kcover, pack_bitmaps
from repro.kernels import ops, plans
from repro.kernels.rules import cache_itemsize
from repro.runtime import compile_cache, flags

FEATURE_DTYPES = ("float32", "bfloat16", "int8")
STEP_PLAN = {"tier": "step", "block_n": 0, "loop_block_n": 0,
             "dtype": "float32"}


def _pool(name, n, d, universe=0, seed=0):
    """Candidate pool in the objective's payload representation (the
    bench_selection.py idiom: the pool is its own evaluation ground)."""
    obj = make_objective(name, universe=universe or n, backend="ref")
    if obj.rule.is_bitmap:
        u = universe or n
        pay = jnp.asarray(pack_bitmaps(gen_kcover(n, u, seed=seed), u))
    else:
        pay = jnp.asarray(gen_images(n, d, classes=8, seed=seed))
    return jnp.arange(n, dtype=jnp.int32), pay, jnp.ones(n, bool)


def _pow2_down(bn: int, itemsize: int, limit: int):
    """The top `limit` feasible power-of-two row blocks ≤ bn (the budget
    inequalities are monotone in bn, so every smaller power of two down
    to the dtype's min tile is also feasible)."""
    out = []
    while bn >= plans._block_min(itemsize) and len(out) < limit:
        out.append(bn)
        bn //= 2
    return out


def candidate_plans(rule, n, c, d, *, dtypes=None, blocks_per_tier=2):
    """Every plan candidate the budget gates admit for this shape: the
    per-step engine, then tier × row-block × storage-dtype combinations
    — crucially including rungs the static `fused_plan` ladder never
    reaches (it stops at the first dtype whose HBM cache fits, so it
    never tries int8-resident while f32-streaming is available)."""
    bitmap = rule.is_bitmap
    n_pad, c_pad = plans.bucket_len(n, 256), plans.bucket_len(c, 128)
    n_res = plans.bucket_len(n, 128 if bitmap else plans.RES_TILE_N)
    d_pad = -(-d // 128) * 128 if d else None
    cache = flags.fused_cache_mb() * 2 ** 20
    forced = {"f32": "float32", "bf16": "bfloat16",
              "int8": "int8"}.get(flags.fused_cache_dtype())
    cands = [dict(STEP_PLAN)]
    for dtype in (("uint32",) if bitmap else (dtypes or FEATURE_DTYPES)):
        if forced is not None and not bitmap and dtype != forced:
            continue                # select_engine would reject the entry
        size = cache_itemsize(dtype)
        if ((bitmap or d_pad is not None)
                and plans.resident_fits(n_res, c_pad, d_pad, rule=rule,
                                        itemsize=size)):
            cands.append({"tier": "resident", "block_n": 0,
                          "loop_block_n": 0, "dtype": dtype})
        if n_pad * c_pad * size > cache:
            continue                # HBM cache busted: no cached tiers
        bl_max = plans.loop_block_n(n_pad, c_pad, size)
        bn_max = plans.fused_block_n(n_pad, c_pad, size)
        for bl in _pow2_down(bl_max, size, blocks_per_tier):
            cands.append({"tier": "streaming", "block_n": bn_max,
                          "loop_block_n": bl, "dtype": dtype})
        for bn in _pow2_down(bn_max, size, blocks_per_tier):
            cands.append({"tier": "fused", "block_n": bn,
                          "loop_block_n": 0, "dtype": dtype})
    return cands


def _measure(obj, ids, pay, valid, k, fp, reps):
    """Wall time (warmup + best-of-reps) and solution for one forced
    plan. A fresh lambda per call keeps jit cache entries distinct."""
    with plans.plan_override(fp):
        fn = jax.jit(lambda i, p, v: greedy(obj, i, p, v, k,
                                            engine="auto"))
        sol = fn(ids, pay, valid)
        jax.block_until_ready(sol.ids)        # compile + warmup
        best = float("inf")
        for _ in range(max(1, reps)):
            t0 = time.time()
            sol = fn(ids, pay, valid)
            jax.block_until_ready(sol.ids)
            best = min(best, time.time() - t0)
    return best, sol


def _dispatches(obj, ids, pay, valid, k, fp):
    """Jaxpr-counted Pallas dispatches per greedy under this plan."""
    with plans.plan_override(fp):
        fn = lambda i, p, v: greedy(obj, i, p, v, k, engine="auto")
        jaxpr = jax.make_jaxpr(fn)(
            jax.ShapeDtypeStruct(ids.shape, ids.dtype),
            jax.ShapeDtypeStruct(pay.shape, pay.dtype),
            jax.ShapeDtypeStruct(valid.shape, valid.dtype)).jaxpr
    return ops.count_pallas_dispatches(jaxpr)


def _fmt(fp):
    return (f"{fp['tier']:9s} dtype={fp['dtype']:8s} "
            f"bn={fp['block_n']:3d} bl={fp['loop_block_n']:3d}")


def tune_one(name, n, d, k, *, universe=0, backend=None, reps=2,
             dtypes=None, blocks_per_tier=2, seed=0, verbose=True):
    """Tune one (objective, shape): measure the static plan and every
    admitted candidate, reject candidates that change the selected ids,
    and return (key, winner entry). The pool is its own candidate set,
    so c = n (the greedy driver's shape). `backend` defaults to the
    resolved one (plans.resolve_backend): Pallas on a TPU."""
    backend = plans.resolve_backend(backend)
    obj = make_objective(name, universe=universe or n, backend=backend)
    rule = obj.rule
    ids, pay, valid = _pool(name, n, d, universe, seed=seed)
    # planner dims exactly as objective.plan_dims derives them: bitmap
    # rules plan over universe WORDS (pay is (C, W)) with no feature dim
    nn, c, dd = ((pay.shape[1], n, None) if rule.is_bitmap
                 else (n, n, d))
    fp_static = plans.fused_plan(nn, c, d=dd, backend=backend,
                                 rule=rule) or dict(STEP_PLAN)
    t_static, sol_static = _measure(obj, ids, pay, valid, k, fp_static,
                                    reps)
    base_ids = jnp.asarray(sol_static.ids)
    if verbose:
        print(f"{name} n={nn} c={c} d={dd} k={k} [{backend}]",
              flush=True)
        print(f"  static  {_fmt(fp_static)} {t_static*1e3:9.2f} ms",
              flush=True)
    best_fp, best_t = fp_static, t_static
    for fp in candidate_plans(rule, nn, c, dd, dtypes=dtypes,
                              blocks_per_tier=blocks_per_tier):
        if fp == fp_static:
            continue
        t, sol = _measure(obj, ids, pay, valid, k, fp, reps)
        same = bool((jnp.asarray(sol.ids) == base_ids).all())
        mark = "" if same else "  REJECTED: selection differs"
        if verbose:
            print(f"  cand    {_fmt(fp)} {t*1e3:9.2f} ms{mark}",
                  flush=True)
        if same and t < best_t:
            best_fp, best_t = fp, t
    entry = dict(best_fp,
                 budgets=plans.budget_snapshot(),
                 wall_s=round(best_t, 6),
                 static_tier=fp_static["tier"],
                 static_dtype=fp_static["dtype"],
                 static_wall_s=round(t_static, 6),
                 speedup=round(t_static / max(best_t, 1e-9), 3),
                 shape={"n": nn, "c": c, "d": dd or 0, "k": k},
                 dispatches=_dispatches(obj, ids, pay, valid, k,
                                        best_fp),
                 static_dispatches=_dispatches(obj, ids, pay, valid, k,
                                               fp_static))
    key = plans.autotune_key(rule, nn, c, dd, backend)
    if verbose:
        print(f"  winner  {_fmt(best_fp)} {best_t*1e3:9.2f} ms "
              f"({entry['speedup']}x vs static)", flush=True)
    return key, entry


def tune(objectives, shapes, *, backend=None, reps=2,
         dtypes=None, blocks_per_tier=2, universe=0, out=None,
         verbose=True):
    """Tune the (objective × shape) grid and persist the winners to the
    measured-plan cache (REPRO_AUTOTUNE_CACHE, or `out`). Returns the
    entries written."""
    entries = {}
    for name in objectives:
        for (n, d, k) in shapes:
            key, entry = tune_one(name, n, d, k, universe=universe,
                                  backend=backend, reps=reps,
                                  dtypes=dtypes,
                                  blocks_per_tier=blocks_per_tier,
                                  verbose=verbose)
            entries[key] = entry
    path = plans.save_autotune_cache(entries, path=out)
    if verbose:
        print(f"wrote {len(entries)} tuned plan(s) -> {path}",
              flush=True)
    return entries


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--objective", action="append", default=[],
                    choices=sorted(registry()),
                    help="objective(s) to tune (repeatable)")
    ap.add_argument("--n", type=int, default=1024,
                    help="pool size (ground = candidates)")
    ap.add_argument("--d", type=int, default=64, help="feature dim")
    ap.add_argument("--k", type=int, default=16, help="solution size")
    ap.add_argument("--universe", type=int, default=0,
                    help="bitmap universe (coverage; default n)")
    ap.add_argument("--backend", default=None,
                    help="kernel backend to measure under (default: the "
                         "resolved one, Pallas on a TPU)")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--blocks-per-tier", type=int, default=2,
                    help="power-of-two row blocks tried per tier/dtype")
    ap.add_argument("--dtypes", default="",
                    help="comma list limiting cache dtypes tried")
    ap.add_argument("--out", default=None,
                    help="cache path (default: REPRO_AUTOTUNE_CACHE)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI grid: facility @ n=192 d=32 k=6, "
                         "f32+int8 only, 1 rep")
    args = ap.parse_args(argv)
    compile_cache.enable()
    dtypes = tuple(s for s in args.dtypes.split(",") if s) or None
    if args.smoke:
        objectives = args.objective or ["facility"]
        shapes = [(192, 32, 6)]
        entries = tune(objectives, shapes, backend=args.backend,
                       reps=1, dtypes=dtypes or ("float32", "int8"),
                       blocks_per_tier=1, out=args.out)
    else:
        objectives = args.objective or ["facility", "kmedoid"]
        shapes = [(args.n, args.d, args.k)]
        entries = tune(objectives, shapes, backend=args.backend,
                       reps=args.reps, dtypes=dtypes,
                       blocks_per_tier=args.blocks_per_tier,
                       universe=args.universe, out=args.out)
    return entries


if __name__ == "__main__":
    main()
