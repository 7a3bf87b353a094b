"""One greedy per selection on one chip:
`jax.jit(greedy(objective, ids, pool, valid, k, engine="auto"))`, compiled
once, over the pools in turn."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import numpy as np

from bench.lib import check, counts
from bench.lib.systems import Path, alter


@dataclasses.dataclass
class Out:
    ids: Any
    valid: Any


class _Checks:
    def check(self, ref, outs, pools) -> Dict[str, float]:
        gap = 0.0
        for out, p in zip(outs, pools):
            r = check.greedy_gap(ref, self.host(p), np.asarray(out.ids),
                                 np.asarray(out.valid))
            gap = max(gap, r["gap"])
        return {"pick_gap": gap}


class Program(_Checks, Path):
    def __init__(self, cell, pools, tmp):
        super().__init__(cell, pools, tmp)
        from repro.core.greedy import greedy
        from repro.core.objective import make_objective
        obj = make_objective(self.cfg["objective"],
                             universe=self.cfg.get("universe", 0))
        k = self.k
        self.fn = jax.jit(lambda i, p, v: greedy(obj, i, p, v, k,
                                                 engine="auto"))

    def run(self, p: int) -> Out:
        sol = self.fn(self.ids, self.pools[p], self.valid)
        return Out(*jax.block_until_ready((sol.ids, sol.valid)))

    def inventory(self) -> List[counts.Kernel]:
        return counts.kernels(jax.make_jaxpr(self.fn)(
            self.ids, self.pools[0], self.valid))


class Reference(_Checks, Path):
    """The reference's own greedy, run where the program runs, at
    `precision` and with `fault` planted ('stale', 'half', 'altered')."""

    def __init__(self, cell, pools, tmp, ref, precision="exact",
                 fault=None):
        super().__init__(cell, pools, tmp)
        self.ref, self.precision, self.fault = ref, precision, fault

    def run(self, p: int) -> Out:
        fault = None if self.fault == "altered" else self.fault
        picks, ok, _ = self.ref.device_greedy(
            self.pools[p], self.valid, self.k, precision=self.precision,
            fault=fault)
        picks, ok = jax.block_until_ready((picks, ok))
        if self.fault == "altered":
            picks = alter(picks, self.pools[p].shape[0])
        return Out(picks, ok)

    def inventory(self) -> List[counts.Kernel]:
        return []
