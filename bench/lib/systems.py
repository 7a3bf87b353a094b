"""What every timed path shares, and how a cell finds its own.

A path kind is `bench/paths/<name>.py`, named by the traffic file's `path`
key. It gives `Program`, the system under test driven as its users call
it, and `Reference`, the configuration's plain reference put in the
program's place (for the control and for planted faults). Both are `Path`s:
they hold the pools on the device, run one selection per `run` call
(returning once the result is ready), drive the measured `window`, and
compare a sample of the window's answers against the reference (`check`).
"""
from __future__ import annotations

import dataclasses
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Window:
    outs: List[Any]             # one per selection started; None if it failed
    errors: List[str]
    start: float                # perf_counter at the window's start
    end: float                  # ... when its last selection was ready


class Path:
    """Pools, their host copies, a closed-loop window. Subclasses give
    `run`, `check` and `inventory`."""

    def __init__(self, cell, pools: Sequence[jax.Array], tmp: str):
        self.cell = cell
        self.cfg = cell.config
        self.k = int(self.cfg["k"])
        self.pools = list(pools)
        self.tmp = tmp
        self._host: Dict[int, np.ndarray] = {}
        n = self.pools[0].shape[0]
        self.ids = jnp.arange(n, dtype=jnp.int32)
        self.valid = jnp.ones((n,), bool)

    @classmethod
    def pool_n(cls, cell) -> int:
        """Elements per pool."""
        return int(cell.config["n"])

    def host(self, p: int) -> np.ndarray:
        if p not in self._host:
            self._host[p] = np.asarray(jax.device_get(self.pools[p]))
        return self._host[p]

    def warm(self) -> None:
        for p in range(len(self.pools)):
            self.run(p)

    def window(self, seconds: float) -> Window:
        """Closed loop: one selection in flight, each started when the last
        one's result is ready, over the pools in turn, until `seconds`
        have passed. A selection that raises counts as failed."""
        outs, errors = [], []
        start = time.perf_counter()
        while True:
            try:
                outs.append(self.run(len(outs) % len(self.pools)))
            except Exception:
                errors.append(traceback.format_exc())
                outs.append(None)
            end = time.perf_counter()
            if end - start >= seconds:
                return Window(outs, errors, start, end)

    def leaf_n(self) -> int:
        return self.pools[0].shape[0]

    def logical(self) -> Tuple[int, int]:
        """(ground rows, candidates) of the leaf greedy, unpadded."""
        n = self.leaf_n()
        return self.cell.generator.rows(self.cfg, n), n

    def events(self, outs) -> List[List[dict]]:
        return []


def alter(ids, n: int):
    """The answer at the middle slot, replaced by the next element."""
    ids = np.array(ids)
    mid = len(ids) // 2
    ids[mid] = (ids[mid] + 1) % n
    return ids


def program(cell, pools, tmp) -> Path:
    return cell.path.Program(cell, pools, tmp)


def reference(cell, pools, tmp, ref, precision: str = "exact",
              fault: Optional[str] = None) -> Path:
    return cell.path.Reference(cell, pools, tmp, ref, precision=precision,
                               fault=fault)
