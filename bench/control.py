#!/usr/bin/env python3
"""Readings that set the limits of `correct` (not run by the benchmark).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        [--program] [--control high,bf16] \
        [--faults stale,half,altered,no_exchange]

For each seed it builds the cell's pools as a run does, runs one selection
on each of the first `check` pools with each path asked for, and applies
the run's comparison, printing one JSON line per (seed, path):

  --program  the system under test (the lower readings)
  --control  the reference in the program's place at each listed
             precision below the program's (the upper readings)
  --faults   the reference at full precision with one fault planted each

`--program` on the tree cell needs the cell's chips; the reference paths
run on one device.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, paths, out=None):
    import jax
    from bench.lib import spec
    ref = spec.reference(cell.root, cell.objective)
    traffic = cell.traffic
    pools = jax.block_until_ready(cell.generator.pools(
        cell.config, cell.path.Program.pool_n(cell), int(traffic["pools"]),
        seed))
    used = list(range(min(int(traffic["check"]), len(pools))))
    rows = []
    for label, make in paths:
        tmp = tempfile.mkdtemp(prefix="control-")
        try:
            t = time.perf_counter()
            path = make(cell, pools, tmp, ref)
            outs = [path.run(p) for p in used]
            nums = path.check(ref, outs, used)
            row = {"workload": cell.name, "seed": seed, "path": label,
                   "numbers": nums,
                   "seconds": time.perf_counter() - t}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        print(json.dumps(row), flush=True)
        if out is not None:
            out.write(json.dumps(row) + "\n")
            out.flush()
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib import spec, systems
    cell = spec.load_cell(ROOT, args.workload)
    import jax
    if jax.devices()[0].platform == "tpu":
        from repro.runtime import compile_cache
        compile_cache.enable()
    paths = []
    if args.program:
        paths.append(("program",
                      lambda c, p, t, r: systems.program(c, p, t)))
    for prec in filter(None, args.control.split(",")):
        paths.append((f"control:{prec}", lambda c, p, t, r, prec=prec:
                      systems.reference(c, p, t, r, precision=prec)))
    for f in filter(None, args.faults.split(",")):
        paths.append((f"fault:{f}", lambda c, p, t, r, f=f:
                      systems.reference(c, p, t, r, fault=f)))
    out = open(args.out, "a", encoding="utf-8") if args.out else None
    try:
        for s in args.seeds.split(","):
            readings(cell, int(s), paths, out)
    finally:
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
