from repro.launch.mesh import force_host_devices

force_host_devices(512, count_flag=None)
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Perf hillclimbing harness (EXPERIMENTS §Perf).

Each experiment = (cell, variant-transform). For every variant we re-lower
the full cell (memory_analysis) and re-run the unrolled cost probes
(flops / collective-bytes / bytes-accessed fits), then report all three
roofline terms next to the baseline. Variants are opt-in config/profile
flags so baselines stay paper-faithful.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.hillclimb \
        --exp llama4_token_exchange
"""
import argparse
import os
import dataclasses
import json
import time

import jax

from repro.configs import registry
from repro.configs.base import OptimConfig, ShapeConfig, TrainConfig
from repro.launch import steps
from repro.launch.dryrun import (_cell_cfgs, _linfit,
                                 _opt_flops_per_device, _probe, _shrink,
                                 analyze, lower_cell)
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.sharding import axes as AX

HW = {"flops": 197e12, "hbm": 819e9, "link": 50e9}


def run_lm_variant(arch, shape_name, mesh, devices, cfg_fn=None,
                   profile="default", remat=None, micro_per_dev=1):
    """Full compile (memory) + probe fits for a (possibly transformed) cfg."""
    AX.use_profile(profile)
    try:
        cfg, ocfg = _cell_cfgs(arch)
        if cfg_fn is not None:
            cfg = cfg_fn(cfg)
        shape = registry.get_shape(shape_name)
        rm = remat or ("full" if cfg.param_count() > 2e10 else "block")
        with mesh:
            compiled = lower_cell(cfg, ocfg, shape, mesh, remat=rm).compile()
            rec = analyze(compiled, devices)
            del compiled
            # probes (train: single-microbatch shape)
            tcfg = TrainConfig(microbatch_per_device=micro_per_dev)
            n_micro = (steps.num_microbatches(shape, mesh, tcfg)
                       if shape.kind == "train" else 1)
            pshape = shape
            if shape.kind == "train":
                pshape = ShapeConfig(shape.name, shape.kind, shape.seq_len,
                                     max(shape.global_batch // n_micro, 1))
            period = T.period_of(cfg)
            r_full = cfg.num_layers // period

            def build(r):
                return lower_cell(_shrink(cfg, r), ocfg, pshape, mesh,
                                  remat=rm)

            pts = _probe(build, (1, 2))
            fb = []
            for r, f, c, by in pts:
                opt = (_opt_flops_per_device(_shrink(cfg, r), devices)
                       if shape.kind == "train" else 0.0)
                opt_by = (14.0 * _shrink(cfg, r).param_count() / devices
                          if shape.kind == "train" else 0.0)
                fb.append((r, f - opt, c, by - opt_by))
            f_full, c_full, b_full = _linfit(fb, r_full)
            opt_f = (_opt_flops_per_device(cfg, devices)
                     if shape.kind == "train" else 0.0)
            opt_b = (14.0 * cfg.param_count() / devices
                     if shape.kind == "train" else 0.0)
            rec["estimated"] = {
                "flops": f_full * n_micro + opt_f,
                "collective_moved_bytes": c_full * n_micro,
                "bytes_accessed": b_full * n_micro + opt_b,
                "n_micro": n_micro,
            }
        return rec
    finally:
        AX.use_profile("default")


def terms(rec):
    est = rec["estimated"]
    return {
        "mem_gib": rec["per_device"]["memory"]["total_bytes"] / 2 ** 30,
        "t_compute": max(est["flops"], 0.0) / HW["flops"],
        "t_memory": max(est["bytes_accessed"], 0.0) / HW["hbm"],
        "t_collective": max(est["collective_moved_bytes"], 0.0) / HW["link"],
    }


def report(name, rec):
    t = terms(rec)
    dom = max(("t_compute", "t_memory", "t_collective"), key=t.get)
    print(f"{name:42s} mem={t['mem_gib']:7.2f}GiB "
          f"compute={t['t_compute']:8.3f}s memory={t['t_memory']:8.3f}s "
          f"collective={t['t_collective']:8.3f}s  dominant={dom}",
          flush=True)
    return t


EXPERIMENTS = {}


def exp(name):
    def deco(fn):
        EXPERIMENTS[name] = fn
        return fn
    return deco


@exp("llama4_token_exchange")
def llama4_token_exchange():
    """Hypothesis: the baseline's collective term is dominated by per-layer
    FSDP all-gathers of expert weights (2 GB/layer/microbatch per device);
    constraining the dispatched tokens' embed dim onto the weights' 'data'
    shards turns weight movement into token movement (~3 MB/layer) + an
    f-dim partial-sum all-reduce. Predicted: collective term ↓ ≥ 10×."""
    mesh = make_production_mesh(multi_pod=False)
    base = run_lm_variant("llama4-maverick-400b-a17b", "train_4k", mesh, 256)
    report("llama4 train_4k BASELINE", base)

    def flip(cfg):
        return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                   token_exchange=True))
    var = run_lm_variant("llama4-maverick-400b-a17b", "train_4k", mesh, 256,
                         cfg_fn=flip)
    report("llama4 train_4k +token_exchange", var)
    return {"baseline": base, "token_exchange": var}


@exp("llama4_iter2_bf16ar")
def llama4_iter2_bf16ar():
    """Iteration 2. Hypothesis: after token-exchange the residual collective
    is the f32 partial-sum all-reduce of the two expert activations
    (2 × 1.7 GB/layer). bf16 accumulation for those einsums halves both the
    AR bytes and the h-tensor HBM traffic. Predicted: collective ↓ ~2×,
    memory ↓ ~1.3×."""
    mesh = make_production_mesh(multi_pod=False)

    def flip(cfg):
        return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                   token_exchange=True))
    var = run_lm_variant("llama4-maverick-400b-a17b", "train_4k", mesh, 256,
                         cfg_fn=flip)
    report("llama4 train_4k token_exchange+bf16AR", var)
    return {"token_exchange_bf16ar": var}


@exp("smollm_dp_only")
def smollm_dp_only():
    """Hypothesis: smollm-135m wastes the model axis (9 heads & tiny dims
    don't shard 16-way → replicated attention = 16× redundant compute).
    Folding the model axis into the batch (dp_only profile: 256-way DP,
    1 seq/device) removes all TP replication. Predicted: compute term
    ↓ ~5–10×, collective term changes shape (no TP all-reduces; FSDP
    gathers over a 256-way axis)."""
    mesh = make_production_mesh(multi_pod=False)
    base = run_lm_variant("smollm-135m", "train_4k", mesh, 256)
    report("smollm train_4k BASELINE", base)
    var = run_lm_variant("smollm-135m", "train_4k", mesh, 256,
                         profile="dp_only")
    report("smollm train_4k +dp_only", var)
    return {"baseline": base, "dp_only": var}


@exp("smollm_dp_only_micro4")
def smollm_dp_only_micro4():
    """Follow-up: with 256-way DP each device has exactly 1 sequence, so
    there is no microbatch loop left (n_micro=1) — FSDP weights are
    gathered once per step instead of 16×. Predicted: collective ↓ ~16×
    vs dp_only-with-16-micro."""
    mesh = make_production_mesh(multi_pod=False)
    var = run_lm_variant("smollm-135m", "train_4k", mesh, 256,
                         profile="dp_only", micro_per_dev=1)
    report("smollm train_4k dp_only micro=1", var)
    return {"dp_only_micro1": var}


@exp("smollm_iter2_no_remat")
def smollm_iter2_no_remat():
    """Iteration 2 (after dp_only). Hypothesis: a 135M model at 1 seq/device
    needs no activation checkpointing — remat='none' removes the recompute
    pass (compute −25%) and its re-read traffic (memory ↓). Predicted:
    compute ↓ ~1.3×, memory ↓ ~1.2×, small activation-memory increase."""
    mesh = make_production_mesh(multi_pod=False)
    var = run_lm_variant("smollm-135m", "train_4k", mesh, 256,
                         profile="dp_only", remat="none")
    report("smollm train_4k dp_only+no_remat", var)
    return {"dp_only_no_remat": var}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", default="all",
                    choices=["all"] + sorted(EXPERIMENTS))
    ap.add_argument("--out", default="results/hillclimb")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    names = sorted(EXPERIMENTS) if args.exp == "all" else [args.exp]
    for name in names:
        print(f"\n### {name}: {EXPERIMENTS[name].__doc__.splitlines()[0]}",
              flush=True)
        t0 = time.time()
        out = EXPERIMENTS[name]()
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump(out, f, indent=1, default=str)
        print(f"### {name} done in {time.time()-t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
