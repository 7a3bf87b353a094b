"""Readings the program keeps itself (`repro.runtime.telemetry`): the
trace-time record of the greedy invocation whose logical shape is the
cell's. A program without that module keeps none; the readers then return
None and the harness leaves their metrics out."""
from __future__ import annotations

import math
from typing import Optional


def greedy_record(r) -> Optional[dict]:
    """The newest `greedy` record whose logical (rows, candidates) is
    `r.logical`, or None."""
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    want = [int(x) for x in r.logical]
    for rec in reversed(telemetry.records("greedy")):
        if rec.get("logical") == want:
            return rec
    return None


def streamed(r) -> Optional[dict]:
    """The largest operand a kernel of that invocation streams: its
    kernel, logical and padded shapes and bytes."""
    rec = greedy_record(r)
    if rec is None or not rec.get("streams"):
        return None
    return max(rec["streams"], key=lambda s: s["bytes"])


def pad_share(s: dict) -> float:
    return 100.0 * (1.0 - math.prod(s["logical"]) / math.prod(s["padded"]))
