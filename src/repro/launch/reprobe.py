from repro.launch.mesh import force_host_devices

force_host_devices(512, count_flag=None)
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Surgical probe refresh: re-run the cost probes (flops/collective/bytes
fits) for already-compiled dry-run cells and merge into their JSONs —
avoids re-compiling the full-size cell when only the probe schema changed.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.reprobe \
        [--only arch:shape]
"""
import argparse
import os
import glob
import json
import time

from repro.launch.dryrun import probe_lm_cell
from repro.launch.mesh import make_production_mesh


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--only", default="")
    ap.add_argument("--force", action="store_true",
                    help="re-probe even if bytes_accessed already present")
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=False)
    for path in sorted(glob.glob(os.path.join(args.out, "*__single.json"))):
        with open(path) as f:
            rec = json.load(f)
        if not rec.get("ok"):
            continue
        key = f"{rec['arch']}:{rec['shape']}"
        if args.only and args.only not in (rec["arch"], key):
            continue
        if (not args.force and
                rec.get("estimated", {}).get("bytes_accessed")):
            continue
        t0 = time.time()
        try:
            with mesh:
                est = probe_lm_cell(rec["arch"], rec["shape"], mesh,
                                    rec["devices"])
            rec["estimated"] = est
            rec["probe_s"] = round(time.time() - t0, 1)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"[re-probed] {key:45s} flops={est['flops']:.3e} "
                  f"bytes={est['bytes_accessed']:.3e} "
                  f"({rec['probe_s']:.0f}s)", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"[probe-fail] {key}: {e}", flush=True)


if __name__ == "__main__":
    main()
