"""Selection-query serving driver — the multi-tenant front door of the
serving subsystem (DESIGN §Serving; distinct from launch/serve.py, which
serves model DECODE batches — this serves SELECTION queries).

    PYTHONPATH=src python -m repro.launch.qserve --tenants 8 --qps 200 \
        --duration 5

Spins up a synthetic multi-tenant workload: each tenant owns a candidate
pool and a registered objective (tenants cycle facility / kmedoid /
coverage / satcover), and submits one-shot selection queries with
heterogeneous k at --qps into one shared `serving.QueryEngine`. The
engine admission-batches rule-compatible queries into single vmapped
megakernel dispatches and the driver reports per-tenant p50/p99 latency,
served queries/s, mean admitted-batch size, and the measured dispatch
count per batch.

``--smoke`` is the CI gate (scripts/ci_smoke.sh): N mixed queries in
(≥3 objectives × heterogeneous k × one constrained) → N results out,
every selection bit-identical to its solo greedy() run, every batched
group exactly ONE pallas dispatch (jaxpr-measured), QueueFull raised at
the queue bound, and a TenantSession stream bit-identical to
stream_select_continuous. Exits nonzero on any mismatch.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax.numpy as jnp

from repro.core.constraints import PartitionMatroid
from repro.core.greedy import greedy
from repro.core.objective import make_objective
from repro.data.synthetic import gen_images, gen_kcover, gen_stream, \
    pack_bitmaps
from repro.kernels import plans
from repro.runtime import compile_cache
from repro.serving import Query, QueryEngine, QueueFull, ServeMetrics, \
    TenantSession
from repro.streaming import stream_select_continuous

OBJ_CYCLE = ("facility", "kmedoid", "coverage", "satcover", "mmr")


def _fmt_ms(v) -> str:
    """Latency percentile for printing — None (no completed queries yet)
    renders as n/a instead of crashing the format spec."""
    return "n/a" if v is None else f"{v:.1f}ms"


def _pool(name, n, d, universe, seed):
    """Candidate pool in the objective's payload representation."""
    if name == "coverage":
        pay = jnp.asarray(pack_bitmaps(gen_kcover(n, universe, seed=seed),
                                       universe))
    else:
        pay = jnp.asarray(gen_images(n, d, classes=8, seed=seed))
    ids = jnp.arange(n, dtype=jnp.int32)
    valid = (jnp.arange(n) % 11) != 0
    return ids, pay, valid


def _query(name, k, n, d, universe, seed, tenant, **kw):
    ids, pay, valid = _pool(name, n, d, universe, seed)
    return Query(name, k, ids, pay, valid, tenant=tenant,
                 universe=universe if name == "coverage" else 0, **kw)


def run(args) -> int:
    rng = np.random.default_rng(args.seed)
    eng = QueryEngine(backend=args.backend, max_batch=args.batch or None)
    # one pool spec per tenant; query k varies per submission
    tenant_objs = [OBJ_CYCLE[t % len(OBJ_CYCLE)]
                   for t in range(args.tenants)]
    period = 1.0 / args.qps if args.qps > 0 else 0.0
    t_end = time.time() + args.duration
    next_t = time.time()
    n_sub = 0
    results = {}
    while time.time() < t_end:
        t = n_sub % args.tenants
        q = _query(tenant_objs[t], int(rng.integers(4, args.k + 1)),
                   args.n, args.d, args.universe, args.seed + t,
                   f"tenant{t}")
        try:
            eng.submit(q)
        except QueueFull:
            results.update(eng.drain())
            eng.submit(q)
        n_sub += 1
        if eng.pending >= (args.batch or 16):
            results.update(eng.drain())
        next_t += period
        lag = next_t - time.time()
        if lag > 0:
            time.sleep(lag)
    results.update(eng.drain())
    snap = eng.metrics.snapshot()
    sizes = [b["size"] for b in eng.metrics.batches]
    qps = snap["queries_per_s"]
    qps_s = f"{qps:.0f}" if qps else "n/a"
    print(f"qserve tenants={args.tenants} submitted={n_sub} "
          f"served={snap['total_queries']} batches={snap['total_batches']} "
          f"mean_B={np.mean(sizes):.1f} "
          f"p50={_fmt_ms(snap['p50_ms'])} p99={_fmt_ms(snap['p99_ms'])} "
          f"served_qps={qps_s}")
    for t in sorted(snap["tenants"]):
        s = snap["tenants"][t]
        obj_name = (tenant_objs[int(t[6:])] if t.startswith("tenant")
                    else "?")
        print(f"  {t:>10s} [{obj_name}] served={s['completed']} "
              f"p50={_fmt_ms(s['p50_ms'])} p99={_fmt_ms(s['p99_ms'])}")
    return 0 if len(results) == n_sub else 1


def smoke(args) -> int:
    """CI gate: correctness of the whole serving surface on a tiny mixed
    workload (see module docstring)."""
    rc = 0
    backend = plans.resolve_backend(args.backend)
    eng = QueryEngine(backend=backend, queue_cap=64)
    universe = 384
    specs = [("facility", 5, 96, 1), ("facility", 9, 120, 2),
             ("kmedoid", 12, 96, 3), ("coverage", 7, 96, 4),
             ("satcover", 6, 120, 5)]
    qids = []
    for name, k, n, seed in specs:
        qids.append(eng.submit(_query(name, k, n, 32, universe, seed,
                                      name)))
    # a constrained query must fall back solo and still be served
    ids, pay, valid = _pool("facility", 96, 32, universe, 9)
    con = PartitionMatroid(jnp.asarray(np.arange(96) % 3, jnp.int32),
                           jnp.asarray([2, 2, 2], jnp.int32))
    qc = eng.submit(Query("facility", 6, ids, pay, valid,
                          tenant="constrained", constraint=con))
    results = eng.drain()
    if len(results) != len(specs) + 1:
        print(f"FAIL: {len(specs) + 1} queries in, {len(results)} out")
        return 1
    for qid, (name, k, n, seed) in zip(qids, specs):
        ids, pay, valid = _pool(name, n, 32, universe, seed)
        obj = make_objective(name,
                             universe=universe if name == "coverage" else 0,
                             backend=backend)
        solo = greedy(obj, ids, pay, valid, k)
        r = results[qid]
        same = (np.array_equal(np.asarray(r.solution.ids),
                               np.asarray(solo.ids))
                and np.array_equal(np.asarray(r.solution.valid),
                                   np.asarray(solo.valid))
                and int(r.solution.evals) == int(solo.evals))
        if not (same and r.batched):
            print(f"FAIL: {name} k={k} batched={r.batched} "
                  f"parity={same}")
            rc |= 1
    if results[qc].batched or not bool(results[qc].solution.valid.any()):
        print("FAIL: constrained query should run solo and select")
        rc |= 1
    exp = 0 if plans.resolve_backend(backend) == "ref" else 1
    disp = [b["dispatches"] for b in eng.metrics.batches]
    if not (disp and all(d == exp for d in disp)):
        print(f"FAIL: batched dispatch counts {disp}, expected all {exp}")
        rc |= 1
    # bounded queue backpressure
    tiny = QueryEngine(backend=backend, queue_cap=2)
    for seed in (0, 1):
        tiny.submit(_query("facility", 4, 96, 32, universe, seed, "t"))
    try:
        tiny.submit(_query("facility", 4, 96, 32, universe, 2, "t"))
        print("FAIL: queue bound not enforced")
        rc |= 1
    except QueueFull:
        pass
    # per-tenant continuous session == one-shot continuous driver
    st = gen_stream("facility", 128, d=24, universe=universe, batch=32,
                    seed=args.seed)
    obj = make_objective("facility", backend="ref")
    ground = jnp.asarray(st.payloads)
    sess = TenantSession("streamer", obj, 6, metrics=eng.metrics,
                         lanes=2, merge_every=2, ground=ground,
                         backend="ref")
    for bids, bpay, bval in st:
        sess.push(bids, bpay, bval)
    ref_sol, _ = stream_select_continuous(obj, st, 6, lanes=2,
                                          merge_every=2, ground=ground,
                                          backend="ref")
    if not np.array_equal(np.asarray(sess.query().ids),
                          np.asarray(ref_sol.ids)):
        print("FAIL: session stream diverged from continuous driver")
        rc |= 1
    snap = eng.metrics.snapshot()
    print(f"qserve smoke: {snap['total_queries']} queries, "
          f"{snap['total_batches']} batches, dispatches/batch={disp}, "
          f"stream_pushes={snap['tenants']['streamer']['stream_pushes']}")
    print("qserve smoke", "FAILED" if rc else "OK")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--qps", type=float, default=200.0)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--universe", type=int, default=384)
    ap.add_argument("--batch", type=int, default=0,
                    help="admission cap override (0 → REPRO_SERVE_BATCH)")
    ap.add_argument("--backend", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.smoke:
        return smoke(args)
    return run(args)


if __name__ == "__main__":
    raise SystemExit(main())
