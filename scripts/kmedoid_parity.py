#!/usr/bin/env python3
"""Where k-medoid selections of the kernels and the reference part ways.

    PYTHONPATH=src python scripts/kmedoid_parity.py            # on a TPU
    PYTHONPATH=src python scripts/kmedoid_parity.py --part noise --dim 12288
    JAX_PLATFORMS=cpu REPRO_KERNEL_BACKEND=interpret \
        PYTHONPATH=src python scripts/kmedoid_parity.py --small

noise    the rounding noise of the squared-distance expansion
         ‖g‖²+‖c‖²−2⟨g,c⟩, before `rules.DIST_REL_TOL` cuts it: the worst
         |d² − exact| / (‖g‖²+‖c‖²) on the diagonal and off it, for the
         kernel backend and the jnp reference, on the paper's unit-norm
         images and on an offset, non-unit copy of them; and how many
         distinct pairs the cut would zero (exact = float64)
witness  the accumulation tree of `configs/paper_kmedoid` (32 machines,
         branching 2) run stage by stage on both backends: the first stage
         and lane whose selection differs, the first differing slot, and
         the float64 marginal gains of the two picks there against the
         float64 best — a gap at rounding level means a near-tie that
         either side may take

Diagnostics only: the numbers printed are not benchmark results.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import paper_kmedoid
from repro.core.greedyml import LevelDispatcher, shard_lanes
from repro.core.objective import make_objective
from repro.data.synthetic import gen_images
from repro.kernels import ops
from repro.kernels import rules as R
from repro.kernels.plans import resolve_backend


def raw_sq_dist(x, backend: str) -> np.ndarray:
    """(n, n) squared distances by the matrix path of `backend`, without
    the noise cut (and without the square root)."""
    cut = R._dist_from_sq
    R._dist_from_sq = lambda d2, scale: d2       # traced into the kernel
    try:
        m = jax.jit(lambda a: ops.pairwise_matrix(a, a, R.DIST_MIN,
                                                  backend=backend))(x)
        m = np.asarray(m)
    finally:
        R._dist_from_sq = cut
    n = x.shape[0]
    return m[:n, :n].astype(np.float64)


def noise(n: int, d: int, backend: str) -> None:
    unit = gen_images(n, d, seed=13)
    sets = (("unit-norm", unit), ("offset 3 + 4x", 3.0 + 4.0 * unit))
    for name, x in sets:
        x = x.astype(np.float32)
        x64 = x.astype(np.float64)
        sq = np.sum(x64 * x64, axis=1)
        scale = sq[:, None] + sq[None, :]
        exact = np.maximum(scale - 2.0 * x64 @ x64.T, 0.0)
        np.fill_diagonal(exact, 0.0)
        off = ~np.eye(n, dtype=bool)
        got = {b: raw_sq_dist(jnp.asarray(x), b) for b in (backend, "ref")}
        for b, d2 in got.items():
            rel = np.abs(d2 - exact) / scale
            diag = np.diag(d2) / np.diag(scale)
            print(f"[noise] {name} n={n} d={d} backend={b}: worst "
                  f"|d2-exact|/scale diagonal {float(rel[~off].max())!r}, "
                  f"off-diagonal {float(rel[off].max())!r}; diagonal "
                  f"d2/scale from {float(diag.min())!r} to "
                  f"{float(diag.max())!r}", flush=True)
        between = np.abs(got[backend] - got["ref"]) / scale
        closest = float((exact / scale)[off].min())
        cut = int(np.sum((exact / scale)[off] <= R.DIST_REL_TOL))
        print(f"[noise] {name}: worst |{backend} - ref|/scale "
              f"{float(between.max())!r}; closest distinct pair exact "
              f"d2/scale {closest!r}; distinct pairs under DIST_REL_TOL="
              f"{R.DIST_REL_TOL!r}: {cut}", flush=True)


def tree_stages(obj, ids, x, valid, k, lanes, b):
    """Per-stage stacked lane solutions of the supervised tree's
    dispatcher (leaves, then each accumulation level), on the host."""
    levels = round(np.log(lanes) / np.log(b))
    disp = LevelDispatcher(obj, k, (b,) * levels)
    pools = shard_lanes(ids, x, valid, lanes)
    states = [disp.leaves(*pools)]
    for lvl in range(levels):
        states.append(disp.level(states[-1], lvl))
    return [jax.device_get(s) for s in states], jax.device_get(pools)


def gains64(pool_pay, pool_val, prefix_pay):
    """float64 k-medoid marginal gains Σ_x relu(mind(x) − ‖x − c‖) of
    every pool element c, given the selected payloads (e0 = origin)."""
    g = pool_pay[pool_val].astype(np.float64)
    mind = np.linalg.norm(g, axis=1)
    for p in prefix_pay:
        mind = np.minimum(mind, np.linalg.norm(g - p, axis=1))
    c = pool_pay.astype(np.float64)
    dist = np.sqrt(np.maximum(
        np.sum(g * g, 1)[:, None] + np.sum(c * c, 1)[None, :]
        - 2.0 * g @ c.T, 0.0))
    return np.sum(np.maximum(mind[:, None] - dist, 0.0), axis=0)


def witness(n: int, d: int, k: int, backend: str) -> None:
    cfg = paper_kmedoid.CONFIG
    lanes, b = cfg.num_machines, cfg.branching
    x = jnp.asarray(gen_images(n, d, seed=cfg.seed))
    ids = jnp.arange(n, dtype=jnp.int32)
    valid = jnp.ones((n,), bool)
    kern, pools = tree_stages(make_objective("kmedoid", backend=backend),
                              ids, x, valid, k, lanes, b)
    ref, _ = tree_stages(make_objective("kmedoid", backend="ref"),
                         ids, x, valid, k, lanes, b)
    root = [np.where(s.valid[0], s.ids[0], -1) for s in (kern[-1], ref[-1])]
    print(f"[witness] tree n={n} d={d} k={k} machines={lanes} b={b}: root "
          f"slots differing {int(np.sum(root[0] != root[1]))} of {k}",
          flush=True)
    for s, (sk, sr) in enumerate(zip(kern, ref)):
        ik = np.where(sk.valid, sk.ids, -1)
        ir = np.where(sr.valid, sr.ids, -1)
        lanes_diff = np.nonzero(np.any(ik != ir, axis=1))[0]
        if lanes_diff.size:
            break
    else:
        print("[witness] every stage selects the same ids", flush=True)
        return
    j = int(lanes_diff[0])
    if s == 0:
        pid, ppay, pval = (p[j] for p in pools)
        where = "leaf"
    else:
        # the node's pool: its group's solutions from the stage before
        # (the same on both backends), in gather order
        step = b ** (s - 1)
        first = j - (j // step % b) * step
        group = [first + t * step for t in range(b)]
        prev = ref[s - 1]
        pid = np.concatenate([prev.ids[g] for g in group])
        ppay = np.concatenate([prev.payloads[g] for g in group])
        pval = np.concatenate([prev.valid[g] for g in group])
        where = f"level {s - 1} node, children lanes {group}"
        for name, st in (("kernel", sk), ("ref", sr)):
            if np.array_equal(st.ids[j], prev.ids[j]):
                print(f"[witness] {name} kept lane {j}'s previous "
                      f"solution at stage {s}", flush=True)
    t = int(np.nonzero(ik[j] != ir[j])[0][0])
    a_id, r_id = int(ik[j][t]), int(ir[j][t])
    pos = {int(i): q for q, i in enumerate(pid) if pval[q]}
    chosen = [pos[int(i)] for i in ir[j][:t] if int(i) in pos]
    g = gains64(ppay, pval, [ppay[q] for q in chosen])
    taken = np.zeros(len(pid), bool)
    taken[chosen] = True
    g = np.where(pval & ~taken, g, -np.inf)
    order = np.argsort(-g)
    best = int(order[0])
    rank = {int(pid[q]): r for r, q in enumerate(order)}
    ga, gr = (float(g[pos[i]]) if i in pos else np.nan
              for i in (a_id, r_id))
    gap = abs(ga - gr) / max(abs(ga), abs(gr))
    print(f"[witness] {lanes_diff.size} lanes differ first at stage "
          f"{s} ({where}); lane {j}, slot {t}: kernel picks {a_id} (f64 "
          f"rank {rank.get(a_id)}, gain {ga!r}), ref picks {r_id} (f64 "
          f"rank {rank.get(r_id)}, gain {gr!r}); relative gap {gap!r}; "
          f"f64 best {int(pid[best])} gain {float(g[best])!r}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--part", choices=("noise", "witness", "all"),
                    default="all")
    ap.add_argument("--small", action="store_true",
                    help="CPU-sized shapes (n=2048, d=64, k=50)")
    ap.add_argument("--backend", default=None)
    ap.add_argument("--dim", type=int, default=0,
                    help="feature width (default: the config's, or 64 "
                         "with --small); Tiny ImageNet's pixels are 12288")
    args = ap.parse_args(argv)
    backend = resolve_backend(args.backend)
    cfg = paper_kmedoid.CONFIG
    n, d, k = (2048, 64, 50) if args.small else (cfg.n, cfg.feature_dim,
                                                  cfg.k)
    d = args.dim or d
    print(f"kmedoid_parity: {jax.devices()[0].device_kind}, kernel "
          f"backend {backend}", flush=True)
    if args.part in ("noise", "all"):
        noise(min(n, 2048), d, backend)
    if args.part in ("witness", "all"):
        witness(n, d, k, backend)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
