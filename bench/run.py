#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
through `BENCHMARK.json` (see `bench/lib/spec.py`). A run:

1. builds the cell's pools on the device from the seed, builds the timed
   path and runs it once on every pool (set-up, timed as `setup_s`, with
   its parts printed on standard error);
2. drives the path's window for `--seconds` (`bench/lib/systems.py`: by
   default a closed loop, one selection in flight, each starting when
   the last one's result is ready);
3. reads the peak device memory, then checks a sample of the window's
   selections against the configuration's plain reference
   (`bench/lib/check.py`);
4. prints one JSON line: the end-to-end metrics with `--trace 0`, the
   per-layer metrics from a profiler trace of the window with `--trace 1`,
   each read by its reader `bench/metrics/<name>.py`.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 3.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse                                          # noqa: E402
import contextlib                                        # noqa: E402
import dataclasses                                       # noqa: E402
import json                                              # noqa: E402
import math                                              # noqa: E402
import os                                                # noqa: E402
import shutil                                            # noqa: E402
import sys                                               # noqa: E402
import tempfile                                          # noqa: E402
import traceback                                         # noqa: E402
from typing import Callable, Dict, List, Optional        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2 ** 30
NO_DEVICE = 3


class LoweringCounter:
    """Counts jaxpr-to-MLIR lowerings while inside its `with` block."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.count, self._on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self._on and event == self.EVENT:
            self.count += 1

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False


def peak_bytes(stats: dict) -> int:
    """Peak device memory: the peak of buffers in use plus the peak of the
    region the runtime reserves for executables' temporaries (the cached
    matrix lives there, not among the buffers in use)."""
    return int(stats["peak_bytes_in_use"]) + int(
        stats.get("peak_bytes_reserved", 0))


@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: dict
    checks: Dict[str, dict]
    setup: Dict[str, float]     # seconds of each part of the set-up
    breakdown: Optional[dict] = None
    errors: List[str] = dataclasses.field(default_factory=list)

    def line(self) -> str:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = self.checks
        return json.dumps(out)


def run_cell(cell, seed: int, seconds: float, trace: bool, *, devices,
             t0: float, make_path: Optional[Callable] = None,
             on_chip: bool = True) -> Result:
    """Set up, run the window, check, reduce. `make_path(cell, pools, tmp)`
    replaces the system under test (the tests plant faults through it);
    `on_chip=False` skips what only a chip has: the memory reading, the
    device trace and the chip's peaks."""
    import jax
    from bench.lib import check, peaks, readings, spec, systems
    from bench.lib import trace as tr

    marks = [("imports_s", time.perf_counter())]
    n_pools = int(cell.traffic["pools"])
    ref = spec.reference(cell.root, cell.objective)
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        pools = jax.block_until_ready(cell.generator.pools(
            cell.config, cell.path.Program.pool_n(cell), n_pools, seed))
        marks.append(("data_s", time.perf_counter()))
        path = (make_path or systems.program)(cell, pools, tmp)
        marks.append(("build_s", time.perf_counter()))
        path.warm()
        marks.append(("warm_s", time.perf_counter()))
        counter = LoweringCounter()
        trace_dir = os.path.join(tmp, "trace")
        if trace:
            jax.profiler.start_trace(trace_dir)
        span = (jax.profiler.TraceAnnotation(tr.WINDOW_SPAN) if trace
                else contextlib.nullcontext())
        with counter, span:
            win = path.window(seconds)
        if trace:
            jax.profiler.stop_trace()
        setup = {name: t - prev for (name, t), (_, prev)
                 in zip(marks, [("", t0)] + marks[:-1])}
        setup["setup_s"] = win.start - t0
        peak = 0
        if on_chip:
            peak = max(peak_bytes(d.memory_stats()) for d in devices)
        outs = win.outs
        done = [i for i, o in enumerate(outs) if o is not None]
        picked = check.sample(seed, len(done), n_pools,
                              int(cell.traffic["check"]))
        numbers = path.check(ref, [outs[done[j]] for j in picked],
                             [done[j] % n_pools for j in picked])
        limits = cell.config["checks"]
        checks = {name: {"value": float(v), "limit": float(limits[name])}
                  for name, v in numbers.items()}
        correct = (not win.errors and bool(done) and all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in checks.values()))
        failed = len(win.errors) + (0 if correct else len(picked))
        kind = devices[0].device_kind
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
        r = readings.Readings(win, len(done), setup["setup_s"], peak,
                              counter.count)
        breakdown = None
        if trace:
            r.summary = (tr.summarize(tr.find_xplane(trace_dir),
                                      len(devices)) if on_chip else None)
            r.inventory = path.inventory()
            r.peaks = peaks.load(kind) if on_chip else None
            r.events = path.events([outs[i] for i in done])
            r.logical = path.logical()
            if r.summary is not None:
                device["busy_s"] = r.summary.busy_s
                device["window_s"] = r.summary.window_s
                breakdown = {"device_ops": r.summary.top_ops(10),
                             "idle_gaps": r.summary.idle_gaps(10)}
        metrics, errors_read = {}, []
        wanted = cell.per_layer if trace else cell.end_to_end
        readers = spec.readers(cell, wanted)
        for m in wanted:
            try:
                v = readers[m["name"]].read(r)
            except Exception:              # a reader's fault drops its metric
                errors_read.append(traceback.format_exc())
                continue
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return Result(correct, len(outs), failed, metrics, device, checks,
                      setup, breakdown, win.errors + errors_read)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib import spec
    cell = spec.load_cell(ROOT, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform!r} device(s). "
              "Nothing was run.", file=sys.stderr)
        return NO_DEVICE
    try:
        from repro.runtime import compile_cache
    except ImportError as e:
        print(f"bench: the system under test is not in {ROOT}/src ({e})",
              file=sys.stderr)
        return NO_DEVICE
    compile_cache.enable()
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices=devices[:cell.chips], t0=_T0)
    for err in res.errors[:3]:
        print(err, file=sys.stderr)
    print("setup " + " ".join(f"{k} {v!r}" for k, v in res.setup.items()),
          file=sys.stderr)
    for name, c in res.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(res.line(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
