"""Operations and HBM bytes of each Pallas kernel, counted from the kernel
as traced: its grid, its block specs and the shapes and dtypes of its
operands.

Bytes follow the TPU pipeline: an input block is fetched when its block
index differs from the previous grid step's, an output block is written
back when its index is about to change and at the end. Which grid axes an
index map depends on is found by evaluating it; the grid is walked in
row-major order (last axis fastest), so a block whose index depends on
axes up to `j` moves prod(grid[:j+1]) times.

Operations are counted per kernel by the arithmetic its body does, in
`OPS` below, keyed by the name of the jitted wrapper that holds the
`pallas_call` (the name the kernel's op carries in a device trace). A
kernel with no entry there, or whose operands no longer have the layout
its entry reads, is not counted, and gets no roofline share.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Block:
    """One operand of a pallas_call as the pipeline moves it."""
    shape: Tuple[int, ...]          # the whole array
    dtype: str
    itemsize: int
    block: Tuple[int, ...]          # one block
    moves: int                      # fetches (inputs) or write-backs

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * self.itemsize

    @property
    def traffic(self) -> int:
        return self.moves * math.prod(self.block) * self.itemsize


@dataclasses.dataclass(frozen=True)
class Kernel:
    name: str                       # jitted wrapper holding the call
    grid: Tuple[int, ...]
    inputs: Tuple[Block, ...]
    outputs: Tuple[Block, ...]
    ops: Optional[float]            # None: not counted

    @property
    def nbytes(self) -> int:
        """HBM bytes moved by one call."""
        return sum(b.traffic for b in self.inputs + self.outputs)

    @property
    def out_shapes(self) -> Tuple[Tuple[int, ...], ...]:
        return tuple(b.shape for b in self.outputs)

    def least_seconds(self, peaks) -> Optional[float]:
        if self.ops is None:
            return None
        return peaks.least_seconds(self.ops, self.nbytes)


def _bitmap(k: Kernel, i: int) -> bool:
    return k.inputs[i].dtype == "uint32"


def _ops_pairwise(k: Kernel) -> float:
    # one (N, D) x (D, C) matmul, then the distance expansion per entry
    n, c = k.outputs[0].shape
    d = k.inputs[0].shape[1]
    return 2.0 * n * c * d + 3.0 * n * c


def _ops_loop(k: Kernel) -> float:
    # every step but the last folds the winner and sums relu(row - m)
    # (or popcount(m & ~row)) over the whole (N, C) matrix
    steps = k.grid[0] - 1
    n, c = k.inputs[0].shape
    return 3.0 * steps * n * c


def _ops_resident(k: Kernel) -> float:
    # matrix built on chip (a matmul for feature rules), then k steps
    n = k.inputs[2].shape[1]
    c = k.inputs[3].shape[1]
    steps = k.outputs[1].shape[1]
    build = 0.0 if _bitmap(k, 1) else 2.0 * n * c * k.inputs[0].shape[1]
    return build + 3.0 * steps * n * c


def _ops_gains(k: Kernel) -> float:
    # per-step gains: bitmaps AND-NOT + popcount + sum per word, features a
    # matmul against the ground rows plus the gain fold
    c, w = k.inputs[2].shape
    if _bitmap(k, 2):
        return 4.0 * c * w
    n, d = k.inputs[0].shape
    return 2.0 * n * c * d + 3.0 * n * c


OPS: Dict[str, Callable[[Kernel], float]] = {
    "pairwise_pallas": _ops_pairwise,
    "greedy_loop_pallas": _ops_loop,
    "greedy_loop_resident_pallas": _ops_resident,
    "gains_pallas": _ops_gains,
}


def _depends(index_map, grid: Sequence[int]) -> List[bool]:
    """Which grid axes the block index depends on, by evaluation."""
    import jax.numpy as jnp
    from jax import core as jcore
    nargs = len(index_map.jaxpr.invars)

    def at(point):
        args = [jnp.int32(p) for p in point] + [jnp.int32(0)] * (
            nargs - len(point))
        return tuple(int(x) for x in jcore.eval_jaxpr(
            index_map.jaxpr, index_map.consts, *args))

    base = at([0] * len(grid))
    dep = []
    for ax, size in enumerate(grid):
        moved = False
        for v in {1, size - 1} - {0}:
            if v < size:
                p = [0] * len(grid)
                p[ax] = v
                moved |= at(p) != base
        dep.append(moved)
    return dep


def _moves(dep: Sequence[bool], grid: Sequence[int]) -> int:
    axes = [i for i, d in enumerate(dep) if d]
    if not axes:
        return 1
    return math.prod(grid[:axes[-1] + 1])


def _block_dims(bm) -> Tuple[int, ...]:
    out = []
    for b in bm.block_shape:
        size = getattr(b, "block_size", b)
        out.append(1 if size is None else int(size))
    return tuple(out)


def kernel_from_eqn(eqn, name: str) -> Kernel:
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    blocks = []
    for bm in gm.block_mappings:
        aval = bm.array_aval
        dep = _depends(bm.index_map_jaxpr, grid) if grid else []
        blocks.append(Block(tuple(int(s) for s in aval.shape),
                            str(aval.dtype), aval.dtype.itemsize,
                            _block_dims(bm), _moves(dep, grid)))
    nin = gm.num_inputs
    k = Kernel(name, grid, tuple(blocks[:nin]), tuple(blocks[nin:]), None)
    fn = OPS.get(name)
    try:
        return dataclasses.replace(k, ops=fn(k)) if fn else k
    except (IndexError, ValueError):
        return k                    # operands no longer as the formula reads


def _subjaxprs(eqn):
    for p in eqn.params.values():
        for q in (p if isinstance(p, (list, tuple)) else (p,)):
            j = getattr(q, "jaxpr", None)
            if j is not None and not hasattr(j, "eqns"):
                j = getattr(j, "jaxpr", None)
            if j is not None and hasattr(j, "eqns"):
                yield j
            elif hasattr(q, "eqns"):
                yield q


def kernels(jaxpr, outer: str = "") -> List[Kernel]:
    """Every pallas_call reachable from a (closed) jaxpr, each named after
    the innermost jitted function that holds it."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: List[Kernel] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(kernel_from_eqn(eqn, outer))
            continue
        name = eqn.params.get("name", outer) \
            if eqn.primitive.name in ("pjit", "jit") else outer
        for sub in _subjaxprs(eqn):
            out.extend(kernels(sub, name))
    return out


def match(inventory: Sequence[Kernel], name: str,
          out_shapes: Tuple[Tuple[int, ...], ...]) -> Optional[Kernel]:
    """The counted kernel a trace event stands for: same wrapper name and
    the same result shapes."""
    for k in inventory:
        if k.name == name and k.out_shapes == out_shapes:
            return k
    return None
