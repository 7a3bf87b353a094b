"""Published peaks of each chip the benchmark may run on, keyed by the
`device_kind` JAX reports. A chip that is not in `peaks.json` is an error:
a roofline share against a guessed peak is not a measurement.

The v5e publishes no float32 peak. Every counted operation is held against
the bf16 peak, so a float32 kernel's compute share reads low, never high.
"""
from __future__ import annotations

import dataclasses
import json
import os

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


class UnknownDevice(KeyError):
    """The device kind has no entry in the peaks table."""


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    source: str
    flops: float            # bf16 operations per second
    int8_ops: float
    hbm_bytes_per_s: float
    hbm_bytes: float

    def least_seconds(self, ops: float, nbytes: float) -> float:
        """The roofline bound: the larger of compute time and HBM time."""
        return max(ops / self.flops, nbytes / self.hbm_bytes_per_s)


def load(kind: str, table: str = TABLE) -> Peaks:
    with open(table, encoding="utf-8") as f:
        rows = json.load(f)
    if kind not in rows:
        raise UnknownDevice(f"no published peaks for device kind {kind!r} "
                            f"in {table}")
    r = rows[kind]
    return Peaks(kind, r["source"], float(r["bf16_flops"]),
                 float(r["int8_ops"]), float(r["hbm_bytes_per_s"]),
                 float(r["hbm_bytes"]))
