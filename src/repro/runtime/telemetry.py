"""In-program telemetry: host spans on the profiler's clock, trace-time
counters, and bounded records of what the program planned and padded.

- `span(name, **attrs)` opens a host span. It enters
  `jax.profiler.TraceAnnotation`, so under a profiler session it lands on
  the Python thread's line of the device trace, and it appends
  ``{id, parent, name, start, end, attrs, counts}`` to a bounded buffer.
  ``start``/``end`` are `time.time_ns()`, the clock the profiler stamps
  host events with.
- `count(name, n)` adds to the innermost open span (and to the process
  totals). The kernel wrappers count at TRACE time: a jitted selection
  is traced once, so its counts describe one execution, not a number of
  executions.
- `repeat(n)` multiplies what is counted while tracing inside it by
  ``n``, the way a scan body of length ``n`` runs ``n`` times.
- `record(kind, **fields)` keeps a trace-time record (a planner verdict,
  one greedy invocation, one kernel's streamed operand), newest last,
  at most `MAX_RECORDS` per kind. The record names the innermost open
  span in ``span``.
- A `jax.monitoring` listener counts each jaxpr-to-MLIR lowering as
  ``lowerings`` (and its seconds as ``lowering_s``) against the innermost
  open span: which stage recompiled. XLA compiles that the persistent
  cache did not answer add their seconds as ``compile_s``.

`snapshot()` returns all of it as plain JSON-able data, `dump(path)`
writes it, `reset()` clears it. There is no switch: with no profiler
running a `TraceAnnotation` costs a check, and the counters are Python
that runs while tracing, so no compiled program changes.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import json
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import jax

MAX_SPANS = 4096
MAX_RECORDS = 256
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Span:
    id: int
    parent: Optional[int]
    name: str
    start: int                      # time.time_ns()
    end: Optional[int]              # None while open
    attrs: Dict[str, Any]
    counts: Dict[str, float] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


_LOCK = threading.Lock()
_IDS = itertools.count(1)
_SPANS: collections.deque = collections.deque(maxlen=MAX_SPANS)
_RECORDS: Dict[str, collections.deque] = {}
_TOTALS: Dict[str, float] = collections.defaultdict(float)
_LOCAL = threading.local()          # .stack: open spans; .mult: repeat


def _stack() -> List[Span]:
    if not hasattr(_LOCAL, "stack"):
        _LOCAL.stack = []
    return _LOCAL.stack


def _add(name: str, n: float) -> None:
    stack = _stack()
    with _LOCK:
        _TOTALS[name] += n
        if stack:
            c = stack[-1].counts
            c[name] = c.get(name, 0) + n


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[Span]:
    """A host span named `name`, nested under the innermost open one."""
    stack = _stack()
    sp = Span(next(_IDS), stack[-1].id if stack else None, name,
              time.time_ns(), None, dict(attrs))
    stack.append(sp)
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield sp
    finally:
        sp.end = time.time_ns()
        stack.remove(sp)
        _SPANS.append(sp)


def count(name: str, n: float = 1) -> None:
    """Add `n` (times the enclosing `repeat` factors) to counter `name`."""
    _add(name, n * getattr(_LOCAL, "mult", 1))


@contextlib.contextmanager
def repeat(n: int) -> Iterator[None]:
    """Counts made while tracing inside run `n` times per execution."""
    old = getattr(_LOCAL, "mult", 1)
    _LOCAL.mult = old * int(n)
    try:
        yield
    finally:
        _LOCAL.mult = old


def multiplier() -> int:
    """The product of the enclosing `repeat` factors."""
    return getattr(_LOCAL, "mult", 1)


def record(kind: str, **fields) -> Dict[str, Any]:
    """Keep a record of `kind`; returns it, so the caller may fill it in."""
    stack = _stack()
    rec = {"span": stack[-1].id if stack else None, **fields}
    with _LOCK:
        if kind not in _RECORDS:
            _RECORDS[kind] = collections.deque(maxlen=MAX_RECORDS)
        _RECORDS[kind].append(rec)
    return rec


def records(kind: str) -> List[Dict[str, Any]]:
    """The records of `kind`, oldest first."""
    with _LOCK:
        return list(_RECORDS.get(kind, ()))


def snapshot() -> Dict[str, Any]:
    """Finished spans, records by kind and counter totals, as plain data."""
    with _LOCK:
        return {"spans": [s.as_dict() for s in _SPANS],
                "records": {k: [dict(r) for r in v]
                            for k, v in _RECORDS.items()},
                "totals": dict(_TOTALS)}


def reset() -> None:
    with _LOCK:
        _SPANS.clear()
        _RECORDS.clear()
        _TOTALS.clear()


def dump(path: str) -> str:
    """Write `snapshot()` to `path` as JSON; returns the path."""
    with open(path, "w", encoding="utf-8") as f:
        json.dump(snapshot(), f, indent=1, default=str)
    return path


def _on_duration(event: str, duration: float, **_) -> None:
    if event == LOWERING_EVENT:
        _add("lowerings", 1)
        _add("lowering_s", duration)
    elif event == COMPILE_EVENT:
        _add("compile_s", duration)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
