"""Reduce a profiler trace to device busy time, kernel time, collective time
and idle gaps.

`jax.profiler` writes an `.xplane.pb`. On a TPU each chip is a plane named
`/device:TPU:<i>`; its `XLA Ops` line holds one event per executed HLO
instruction, named by the instruction's text (`%pairwise_pallas.1 = f32[...]
custom-call(...)`). A Pallas kernel's instruction takes the name of the
jitted wrapper that holds its `pallas_call`, so a kernel is found by that
name with the numeric suffix removed. Host threads are lines of the
`/host:CPU` plane; `jax.profiler.TraceAnnotation` spans land on the Python
thread's line, on the same clock as the device events (to about a
millisecond).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
OPS_LINE = "XLA Ops"
# ops whose event spans the ops of their body on the same line
CONTAINER_OPCODES = ("while", "conditional", "call")
COLLECTIVE_OPCODES = ("all-gather", "all-reduce", "all-to-all",
                      "collective-permute", "reduce-scatter",
                      "collective-broadcast")
_SUFFIX = re.compile(r"\.\d+$")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")

Interval = Tuple[float, float]


def instruction(event_name: str) -> str:
    """`%fusion.3 = ...` -> `fusion.3`; names without HLO text pass."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def base_name(event_name: str) -> str:
    """Instruction name without its numeric suffix: `pairwise_pallas`."""
    return _SUFFIX.sub("", instruction(event_name))


def _skip_type(text: str) -> str:
    """Drop the leading result type (a tuple type is parenthesised and
    layouts hold parentheses of their own)."""
    if text.startswith("("):
        depth = 0
        for i, ch in enumerate(text):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return text[i + 1:].lstrip()
        return ""
    return text.split(" ", 1)[1] if " " in text else ""


def opcode(event_name: str) -> str:
    """HLO opcode of an op event: `custom-call`, `all-gather-start`, ..."""
    if " = " not in event_name:
        return ""
    rest = _skip_type(event_name.split(" = ", 1)[1])
    return rest.split("(", 1)[0].strip()


def result_shapes(event_name: str) -> Tuple[Tuple[str, Tuple[int, ...]],
                                            ...]:
    """(dtype, dims) of each result of an op event, in order."""
    if " = " not in event_name:
        return ()
    text = event_name.split(" = ", 1)[1]
    rest = _skip_type(text)
    typ = text[:len(text) - len(rest)]
    return tuple((dt, tuple(int(x) for x in dims.split(",") if x))
                 for dt, dims in _SHAPE.findall(typ))


def is_collective(event_name: str) -> bool:
    op = opcode(event_name)
    return any(op.startswith(c) for c in COLLECTIVE_OPCODES)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class Op:
    start: float                # ns
    end: float
    name: str                   # full event name (HLO text)


@dataclasses.dataclass
class Summary:
    """What one traced window holds, reduced. Times are ns unless the
    name says seconds."""
    window: Interval
    devices: List[str]
    ops: Dict[str, List[Op]]            # device plane -> ops in window
    host: List[Op]                      # spans of the Python thread
    span: str = WINDOW_SPAN             # the host span that is the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, device: str) -> List[Interval]:
        return union([(o.start, o.end) for o in self.ops[device]])

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(total(self.busy(d)) for d in self.devices) \
            / len(self.devices) * 1e-9

    def kernel(self, name: str) -> List[Op]:
        """Every op of the kernel (by instruction base name), all devices."""
        return [o for d in self.devices for o in self.ops[d]
                if base_name(o.name) == name]

    def collective_s(self) -> float:
        """Device seconds of collective ops, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(total(union([(o.start, o.end) for o in self.ops[d]
                               if is_collective(o.name)]))
                  for d in self.devices)
        return tot / len(self.devices) * 1e-9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device ops that took most time (by base name), seconds per
        device; loops and calls are left out, their bodies' ops count."""
        acc: Dict[str, float] = collections.Counter()
        for d in self.devices:
            for o in self.ops[d]:
                if opcode(o.name) not in CONTAINER_OPCODES:
                    acc[base_name(o.name)] += (o.end - o.start) * 1e-9
        k = max(len(self.devices), 1)
        return [(name, s / k) for name, s in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def gaps(self, device: str) -> List[Interval]:
        """Idle intervals of one device inside the window."""
        out, cur = [], self.window[0]
        for s, e in self.busy(device):
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < self.window[1]:
            out.append((cur, self.window[1]))
        return out

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """Idle time of the first device, summed by what the host was doing
        in each gap: the shortest host span covering at least half of the
        gap, else the span overlapping it most."""
        if not self.devices:
            return []
        acc: Dict[str, float] = collections.Counter()
        for s, e in self.gaps(self.devices[0]):
            best, best_key = "(no host span)", None
            for h in self.host:
                ov = min(e, h.end) - max(s, h.start)
                if ov <= 0 or h.name == self.span:
                    continue
                covers = ov >= 0.5 * (e - s)
                key = (not covers, (h.end - h.start) if covers else -ov)
                if best_key is None or key < best_key:
                    best, best_key = h.name, key
            acc[best] += (e - s) * 1e-9
        return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _device_order(name: str) -> int:
    tail = name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else 1 << 30


def summarize(path: str, chips: int, window: Optional[Interval] = None,
              span: str = WINDOW_SPAN) -> Summary:
    """Read one `.xplane.pb`. The window is the host span named `span`,
    else `window`, else the extent of the device ops; only the first
    `chips` TPU planes count."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host: List[Op] = []
    for pl in planes:
        if pl.name != "/host:CPU":
            continue
        for line in pl.lines:
            evs = [Op(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            if any(o.name == span for o in evs):
                host = evs
                break
    if window is None:
        win = [o for o in host if o.name == span]
        if win:
            window = (win[0].start, win[0].end)
    devs = sorted((pl for pl in planes if pl.name.startswith("/device:TPU:")),
                  key=lambda pl: _device_order(pl.name))[:chips]
    raw: Dict[str, List[Op]] = {}
    for pl in devs:
        ops = []
        for line in pl.lines:
            if line.name == OPS_LINE:
                ops = [Op(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
        raw[pl.name] = ops
    if window is None:
        every = [o for ops in raw.values() for o in ops]
        window = ((min(o.start for o in every), max(o.end for o in every))
                  if every else (0.0, 0.0))
    lo, hi = window
    ops = {d: [Op(max(o.start, lo), min(o.end, hi), o.name) for o in v
               if min(o.end, hi) > max(o.start, lo)]
           for d, v in raw.items()}
    return Summary(window, list(raw), ops, host, span)
