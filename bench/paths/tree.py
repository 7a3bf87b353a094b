"""The accumulation tree on a mesh with one tree level per axis:
`SelectionSupervisor(ckpt_dir=<fresh>).select(...)`, as a user of the tree
calls it. Traffic keys: `mesh`, the radices; each leaf takes the
configuration's n, so a pool holds n times the leaves."""
from __future__ import annotations

import dataclasses
import math
import tempfile
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from bench.lib import check, counts
from bench.lib.systems import Path, alter


@dataclasses.dataclass
class Out:
    ids: Any                    # the root the call returned
    valid: Any
    events: List[dict]          # the supervisor's log of this selection
    ckpt_dir: Optional[str] = None
    held: Optional[list] = None  # stages, when no checkpoints were written

    def stages(self, lanes: int, k: int):
        if self.held is not None:
            return self.held
        return check.read_stages(self.ckpt_dir, lanes, k)


class _Checks:
    @classmethod
    def pool_n(cls, cell) -> int:
        return int(cell.config["n"]) * math.prod(cell.traffic["mesh"])

    def radices(self) -> Tuple[int, ...]:
        return tuple(int(r) for r in self.cell.traffic["mesh"])

    def leaf_n(self) -> int:
        return self.pools[0].shape[0] // math.prod(self.radices())

    def check(self, ref, outs, pools) -> Dict[str, float]:
        radices = self.radices()
        gap = 0.0
        for out, p in zip(outs, pools):
            stages = out.stages(math.prod(radices), self.k)
            r = check.tree_gaps(ref, self.host(p), stages,
                                np.asarray(out.ids), np.asarray(out.valid),
                                radices, self.k)
            gap = max(gap, r["pick_gap"])
        return {"pick_gap": gap}

    def events(self, outs) -> List[List[dict]]:
        return [o.events for o in outs if o is not None]


class Program(_Checks, Path):
    def __init__(self, cell, pools, tmp):
        super().__init__(cell, pools, tmp)
        from repro.core.objective import make_objective
        from repro.launch.mesh import make_tree_mesh
        self.obj = make_objective(self.cfg["objective"],
                                  universe=self.cfg.get("universe", 0))
        radices = self.radices()
        self.mesh = make_tree_mesh(radices)
        self.axes = tuple(f"lvl{i}" for i in range(len(radices)))

    def warm(self) -> None:
        self.run(0)

    def run(self, p: int) -> Out:
        from repro.runtime.supervisor import SelectionSupervisor
        d = tempfile.mkdtemp(prefix="select-", dir=self.tmp)
        sup = SelectionSupervisor(ckpt_dir=d)
        sol, _ = sup.select(self.obj, self.ids, self.pools[p], self.valid,
                            self.k, lanes=math.prod(self.radices()),
                            mesh=self.mesh, tree_axes=self.axes)
        ids, valid = jax.block_until_ready((sol.ids, sol.valid))
        return Out(ids, valid, list(sup.events), d)

    def inventory(self) -> List[counts.Kernel]:
        """Kernels of the leaf stage and of every level, traced through
        the dispatcher the supervisor builds for this mesh."""
        from repro.core.greedyml import LevelDispatcher, shard_lanes
        lanes = math.prod(self.radices())
        disp = LevelDispatcher(self.obj, self.k, self.radices(),
                               mesh=self.mesh, tree_axes=self.axes)
        leaf_in = jax.eval_shape(lambda i, p, v: shard_lanes(i, p, v, lanes),
                                 self.ids, self.pools[0], self.valid)
        out = counts.kernels(jax.make_jaxpr(disp.leaves)(*leaf_in))
        state = jax.eval_shape(disp.leaves, *leaf_in)
        for lvl in range(disp.num_levels):
            out += counts.kernels(jax.make_jaxpr(
                lambda s, lvl=lvl: disp.level(s, lvl))(state))
        return out


class Reference(_Checks, Path):
    """The reference's tree on one device, with `fault` planted: one of
    the reference's own, 'no_exchange', or 'altered' (the root's answer)."""

    def __init__(self, cell, pools, tmp, ref, precision="exact",
                 fault=None):
        super().__init__(cell, pools, tmp)
        self.ref, self.precision, self.fault = ref, precision, fault

    def run(self, p: int) -> Out:
        fault = None if self.fault == "altered" else self.fault
        stages, ids, valid = check.reference_tree(
            self.ref, self.host(p), self.k, self.radices(),
            precision=self.precision, fault=fault)
        if self.fault == "altered":
            ids = alter(ids, self.pools[p].shape[0])
        return Out(ids, valid, [], held=stages)

    def inventory(self) -> List[counts.Kernel]:
        return []
