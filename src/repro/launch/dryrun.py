from repro.launch.mesh import force_host_devices

force_host_devices(512, count_flag=None)
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile EVERY (arch × shape × mesh) cell and
record memory / FLOPs / collective-bytes for the roofline analysis.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun \
        --mesh both [--only qwen2-7b:train_4k] [--out results/dryrun] \
        [--no-probe]

For each cell:  with mesh: jax.jit(step, in_shardings=…).lower(**specs)
                .compile() → memory_analysis() (fits?), cost_analysis()
                (FLOPs/bytes), HLO collective scan (bytes by op type).

FLOP/collective accounting: XLA's HloCostAnalysis counts while-loop bodies
ONCE, so rolled layer/microbatch scans under-count by the trip count. The
dry-run therefore compiles two small UNROLLED probe variants (1× and 2× the
layer period, one microbatch) per cell and fits cost = intercept + slope·R,
extrapolating to the full depth and microbatch count. The full-size
compile still provides memory_analysis (fits-check) and the real
collective schedule.
"""
import argparse
import os
import json
import re
import time
import traceback
from typing import Any, Callable, Dict, List, Tuple

from repro.configs import registry
from repro.configs.base import OptimConfig, ShapeConfig, TrainConfig
from repro.launch import steps
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.runtime import flags

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "c64": 8, "c128": 16,
               "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

# Per-device bytes moved ≈ factor × result bytes (ring algorithms).
_COLL_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo: str) -> Dict[str, Any]:
    """Per-device collective bytes from the post-SPMD HLO text."""
    out = {"ops": {}, "moved_bytes": 0.0, "result_bytes": 0.0}
    for line in hlo.splitlines():
        m = re.search(r"= ([^=]*?) (all-reduce|all-gather|reduce-scatter|"
                      r"all-to-all|collective-permute)(?:-start)?\(", line)
        if not m or "-done(" in line:
            continue
        kind = m.group(2)
        shapes = _SHAPE_RE.findall(m.group(1))
        rb = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        g = _GROUP_RE.search(line)
        gsize = int(g.group(2)) if g else 0
        eff = 1.0 if gsize <= 1 else (gsize - 1) / gsize
        moved = _COLL_FACTOR[kind] * rb * eff
        rec = out["ops"].setdefault(kind, {"count": 0, "result_bytes": 0.0,
                                           "moved_bytes": 0.0})
        rec["count"] += 1
        rec["result_bytes"] += rb
        rec["moved_bytes"] += moved
        out["moved_bytes"] += moved
        out["result_bytes"] += rb
    return out


def analyze(compiled, devices: int) -> Dict[str, Any]:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    mem = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
        "output_bytes": getattr(ma, "output_size_in_bytes", 0),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
    }
    mem["total_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                          + mem["temp_bytes"] - mem["alias_bytes"])
    return {
        "devices": devices,
        "per_device": {
            "flops_hlo_static": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "memory": mem,
            "collectives_static": colls,
        },
        "hlo_bytes": len(hlo),
    }


# ---------------------------------------------------------------------------
# Cost probes (unrolled small-depth compiles → linear/quadratic fit)
# ---------------------------------------------------------------------------


def _probe(build: Callable[[int], Any], rs) -> List[Tuple[int, float, float, float]]:
    out = []
    flags.UNROLL_SCANS = True
    try:
        for r in rs:
            compiled = build(r).compile()
            ca = compiled.cost_analysis() or {}
            colls = parse_collectives(compiled.as_text())
            out.append((r, float(ca.get("flops", 0.0)),
                        float(colls["moved_bytes"]),
                        float(ca.get("bytes accessed", 0.0))))
    finally:
        flags.UNROLL_SCANS = False
    return out


def _linfit(pts, r_full: int):
    p1, p2 = pts[0], pts[-1]
    r1, r2 = p1[0], p2[0]
    return tuple(v1 + (v2 - v1) / (r2 - r1) * (r_full - r1)
                 for v1, v2 in zip(p1[1:], p2[1:]))


def _opt_flops_per_device(cfg, devices: int) -> float:
    # AdamW (~10 flops/param) + global-norm clip (~2) on sharded params
    return 12.0 * cfg.param_count() / devices


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def _cell_cfgs(arch: str):
    cfg = registry.get_arch(arch).replace(param_dtype="bfloat16")
    big = cfg.param_count() > 1e11      # 400B-class: Adafactor (factored v)
    ocfg = OptimConfig(                 # + bf16 grad accumulation/reduction
        name=("adafactor" if big else "adamw"),
        compress_grads=("bf16" if big else "none"))
    return cfg, ocfg


def _shrink(cfg, r: int):
    period = T.period_of(cfg)
    kw = {"num_layers": r * period}
    if cfg.encoder_layers:
        kw["encoder_layers"] = max(1, round(
            cfg.encoder_layers * r * period / cfg.num_layers))
    return cfg.replace(**kw)


def lower_cell(cfg, ocfg, shape, mesh, remat=None):
    # >20B params: save-nothing remat (carry-only residuals) — the layer
    # scan otherwise stores per-iteration matmul outputs for the backward
    if remat is None:
        remat = "full" if cfg.param_count() > 2e10 else "block"
    tcfg = TrainConfig(remat=remat)
    if shape.kind == "train":
        jitted, state_sds, batch_sds, *_ = steps.jit_train_step(
            cfg, ocfg, tcfg, shape, mesh)
        return jitted.lower(state_sds, batch_sds)
    if shape.kind == "prefill":
        jitted, params_sds, in_specs, *_ = steps.jit_prefill_step(
            cfg, ocfg, shape, mesh)
        return jitted.lower(params_sds, in_specs["batch"])
    jitted, params_sds, in_specs, *_ = steps.jit_decode_step(
        cfg, ocfg, shape, mesh)
    return jitted.lower(params_sds, in_specs["cache"], in_specs["batch"])


def probe_lm_cell(arch: str, shape_name: str, mesh, devices: int
                  ) -> Dict[str, Any]:
    """Unrolled 1×/2×-period probes → per-device flops & collective bytes."""
    cfg, ocfg = _cell_cfgs(arch)
    shape = registry.get_shape(shape_name)
    tcfg = TrainConfig()
    period = T.period_of(cfg)
    r_full = cfg.num_layers // period
    n_micro = (steps.num_microbatches(shape, mesh, tcfg)
               if shape.kind == "train" else 1)
    probe_shape = shape
    if shape.kind == "train":
        probe_shape = ShapeConfig(shape.name, shape.kind, shape.seq_len,
                                  max(shape.global_batch // n_micro, 1))

    # remat policy must match the FULL-depth compile, not the shrunk one
    remat = "full" if cfg.param_count() > 2e10 else "block"

    def build(r):
        return lower_cell(_shrink(cfg, r), ocfg, probe_shape, mesh,
                          remat=remat)

    pts = _probe(build, (1, 2))
    flops_fb = []
    for r, f, c, by in pts:
        opt = (_opt_flops_per_device(_shrink(cfg, r), devices)
               if shape.kind == "train" else 0.0)
        # optimizer runs once per step, not per microbatch: subtract its
        # flops AND its state traffic (~14 bytes/param) before scaling
        opt_by = (14.0 * _shrink(cfg, r).param_count() / devices
                  if shape.kind == "train" else 0.0)
        flops_fb.append((r, f - opt, c, by - opt_by))
    f_full, c_full, b_full = _linfit(flops_fb, r_full)
    opt_full = (_opt_flops_per_device(cfg, devices)
                if shape.kind == "train" else 0.0)
    opt_by_full = (14.0 * cfg.param_count() / devices
                   if shape.kind == "train" else 0.0)
    return {
        "method": "unrolled 2-point linear fit in layer repeats",
        "points": pts,
        "n_micro": n_micro,
        "flops": f_full * n_micro + opt_full,
        "collective_moved_bytes": c_full * n_micro,
        "bytes_accessed": b_full * n_micro + opt_by_full,
    }


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             probe: bool = True) -> Dict[str, Any]:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    devices = 512 if multi else 256
    t0 = time.time()
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "devices": devices}
    try:
        with mesh:
            cfg, ocfg = _cell_cfgs(arch)
            shape = registry.get_shape(shape_name)
            lowered = lower_cell(cfg, ocfg, shape, mesh)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            rec.update(analyze(compiled, devices))
            del lowered, compiled
            rec["lower_s"] = round(t_lower, 1)
            rec["compile_s"] = round(t_compile, 1)
            if probe and mesh_kind == "single":
                t1 = time.time()
                rec["estimated"] = probe_lm_cell(arch, shape_name, mesh,
                                                 devices)
                rec["probe_s"] = round(time.time() - t1, 1)
            rec["ok"] = True
            ma = rec["per_device"]["memory"]
            est = rec.get("estimated", {})
            print(f"[OK] {arch:28s} {shape_name:12s} {mesh_kind:6s} "
                  f"mem/dev={ma['total_bytes']/2**30:6.2f} GiB "
                  f"flops/dev={est.get('flops', 0):.3e} "
                  f"coll/dev={est.get('collective_moved_bytes', 0)/2**20:9.1f} MiB "
                  f"({time.time()-t0:.0f}s)", flush=True)
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        print(f"[FAIL] {arch} {shape_name} {mesh_kind}: {rec['error'][:200]}",
              flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_kind}.json".replace("/", "_")
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--only", default="",
                    help="comma list of arch or arch:shape filters")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    args = ap.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = [(a, s) for a, s, skip in registry.cells() if skip is None]
    if args.only:
        keep = set(args.only.split(","))
        cells = [(a, s) for a, s in cells
                 if a in keep or f"{a}:{s}" in keep]

    results = []
    for mesh_kind in meshes:
        for arch, shape_name in cells:
            fname = os.path.join(
                args.out, f"{arch}__{shape_name}__{mesh_kind}.json")
            if args.skip_existing and os.path.exists(fname):
                with open(fname) as f:
                    prev = json.load(f)
                if prev.get("ok"):
                    print(f"[skip] {arch} {shape_name} {mesh_kind} (cached)")
                    results.append(prev)
                    continue
            results.append(run_cell(arch, shape_name, mesh_kind, args.out,
                                    probe=not args.no_probe))

    ok = sum(1 for r in results if r.get("ok"))
    print(f"\n{ok}/{len(results)} cells compiled successfully")
    if ok < len(results):
        for r in results:
            if not r.get("ok"):
                print("  FAILED:", r["arch"], r["shape"], r["mesh"])


if __name__ == "__main__":
    main()
