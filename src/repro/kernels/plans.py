"""Engine planning: one place that turns (rule, shapes, budgets) into the
selection engine every caller runs (DESIGN §Objective protocol).

`select_engine` is the single decision point that used to be scattered
across `hasattr(objective, ...)` duck-typing in core/greedy.py, per-class
`prepare` gates in core/functions.py, and the ops.fused_plan dict: it
resolves the backend, applies the HBM/VMEM budget math below, honors the
caller's requested engine, and returns an `EnginePlan` that the kernels
consume verbatim (block sizes, cache dtype) — so no layer re-derives
memory decisions per step.

The low-level budget gates (`fused_plan`, `stream_plan`) remain available
for tests and benchmarks; they are rule-aware: bitmap rules store uint32
matrices (no bf16/int8 option) and need no feature dim for residency.
All gates are dtype-aware: the cache storage dtype's ACTUAL itemsize
(4/2/1 for f32/bf16/int8) threads through the VMEM/HBM math, so cheaper
storage genuinely widens the block and residency ceilings.

Measured plans (DESIGN §Autotune): when REPRO_AUTOTUNE_CACHE points at a
JSON cache written by launch/autotune.py, `select_engine` consults it —
keyed by (rule, bucketed shape, backend) — BEFORE the static heuristics,
so steady-state callers get measured winners with zero tuning overhead.
Entries whose recorded budget snapshot no longer matches the live
REPRO_FUSED_{CACHE,VMEM}_MB knobs (or whose file is corrupt) are ignored
and the heuristics take over; a stale cache can never crash a run.

Backends resolve through `resolve_backend` (the public face of
runtime.flags.kernel_backend): 'auto' → compiled Pallas on TPU, jnp
reference elsewhere; 'interpret' runs the kernel bodies on CPU.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import time
from typing import List, Optional, Tuple

from repro.kernels.rules import KernelRule, cache_itemsize
from repro.runtime import flags, telemetry

# resident-tier padding base: accumulation-node shapes drift level by
# level, so the ground-row axis buckets from a small base to keep the
# on-chip matrix (and the compile cache) tight
RES_TILE_N = 8

ENGINES = ("step", "fused", "mega_stream", "mega_resident", "sharded")


def resolve_backend(override: Optional[str] = None) -> str:
    """Public backend resolution — explicit override, then
    REPRO_KERNEL_BACKEND, then 'auto' (Pallas on TPU, jnp elsewhere)."""
    return flags.kernel_backend(override)


@dataclasses.dataclass(frozen=True)
class EnginePlan:
    """The planner's verdict for one greedy invocation.

    engine        'step' | 'fused' | 'mega_stream' | 'mega_resident'
                  | 'sharded' (cross-device tiled leaf,
                  kernels/shard_gains.py)
    rule          the objective's KernelRule
    backend       resolved backend ('pallas' | 'interpret' | 'ref')
    tier          raw fused_plan tier ('resident'|'streaming'|'fused'|
                  'sharded'), None when the budget gate refused every
                  cached engine
    block_n       row block for the per-step fused kernel (0 on ref)
    loop_block_n  row block for the streaming loop kernel
    dtype         cache storage dtype
                  ('float32'|'bfloat16'|'int8'|'uint32')
    tile_c        sharded tier only: candidate tile each lane contributes
                  per exchange round
    lanes         sharded tier only: devices the ground set is split over
    """
    engine: str
    rule: KernelRule
    backend: str
    tier: Optional[str] = None
    block_n: int = 0
    loop_block_n: int = 0
    dtype: str = "float32"
    tile_c: int = 0
    lanes: int = 1

    @property
    def cached(self) -> bool:
        # a cached (n, c) matrix exists; the sharded tier recomputes
        # tiles per step like 'step', so it is NOT cached
        return self.engine not in ("step", "sharded")


def bucket_len(size: int, tile: int) -> int:
    """Next power-of-two multiple of `tile` ≥ size (jit-cache bucketing)."""
    target = tile
    while target < size:
        target *= 2
    return target


# ---------------------------------------------------------------------------
# VMEM / HBM budget math
# ---------------------------------------------------------------------------

_VMAP_REPLICAS = 1          # caches live concurrently under vmap (trace-time)


@contextlib.contextmanager
def fused_replicas(n: int):
    """Declare that the code traced inside holds `n` cached matrices alive
    at once (e.g. vmapped leaf greedys in core/simulate.py) so fused_plan
    divides the HBM budget accordingly. Trace-time only, like the plan:
    a jit function compiled OUTSIDE the context replays its baked-in
    replicas=1 decision on cache hits — trace (or build the jit wrapper)
    inside the context, as simulate.py does. Not thread-safe."""
    global _VMAP_REPLICAS
    old = _VMAP_REPLICAS
    _VMAP_REPLICAS = max(1, int(n))
    try:
        yield
    finally:
        _VMAP_REPLICAS = old


def _block_min(itemsize: int) -> int:
    """Min row-block by storage dtype's TPU min tile: (8|16|32, 128) for
    f32|bf16|int8."""
    return {1: 32, 2: 16}.get(itemsize, 8)


def fused_need(bn: int, n_pad: int, c_pad: int, itemsize: int = 4) -> int:
    """VMEM bytes of one fused-step grid cell at row block `bn`: the
    (BN, C) matrix slab (cache storage dtype), the (BN, C) f32
    gain-partials temporary the kernel materializes (int8 storage pays a
    SECOND f32 slab for the in-kernel dequant before the partials), the
    (1, C) gains accumulator and mask blocks, and the whole (N,) state row
    in and out (plus the int8 scale row)."""
    f32_slabs = 2 if itemsize == 1 else 1
    rows = 3 if itemsize == 1 else 2
    return (bn * c_pad * itemsize
            + (bn * c_pad * f32_slabs + 3 * c_pad + rows * n_pad) * 4)


def loop_need(bn: int, n_pad: int, c_pad: int, itemsize: int = 4) -> int:
    """VMEM bytes of one streaming-megakernel grid cell: the fused-step
    cell plus the loop's (1, C) evolving candidate mask."""
    return fused_need(bn, n_pad, c_pad, itemsize) + c_pad * 4


def _widest_block(n_pad: int, itemsize: int, need) -> int:
    """Largest power-of-two row block (≤256, ≥ the dtype's min tile) whose
    working set `need(bn)` fits the fused VMEM budget; 0 if none fits."""
    vmem = flags.fused_vmem_mb() * 2 ** 20
    bn = 256
    while bn >= _block_min(itemsize):
        if bn <= n_pad and need(bn) <= vmem:
            return bn
        bn //= 2
    return 0


def fused_block_n(n_pad: int, c_pad: int, itemsize: int = 4) -> int:
    """Row block for the per-step fused kernel (`fused_need`); 0 if none
    fits. bf16/int8 storage floors BN at their (16, 128)/(32, 128) min
    tiles."""
    return _widest_block(n_pad, itemsize,
                         lambda bn: fused_need(bn, n_pad, c_pad, itemsize))


def loop_block_n(n_pad: int, c_pad: int, itemsize: int = 4) -> int:
    """Row block for the STREAMING megakernel tier (`loop_need`); 0 if
    none fits."""
    return _widest_block(n_pad, itemsize,
                         lambda bn: loop_need(bn, n_pad, c_pad, itemsize))


# candidate rows per block of the per-step bitmap gains kernel on a
# row-major ('cw') operand, at most (a v5e reading, PERF §6)
BITMAP_BLOCK_C_MAX = 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def bitmap_gains_need(tc: int, w: int, orient: str) -> int:
    """VMEM bytes of one per-step bitmap-gains grid cell: the uint32
    candidate block, double-buffered, and the kernel's f32 popcount
    temporary of the same shape. A 'cw' block is (TC, W), each row padded
    to whole 128-lane vregs; a 'wc' block is (W, TC), the words padded to
    whole 8-row sublane tiles."""
    if orient == "wc":
        return 3 * _round_up(w, 8) * tc * 4
    return 3 * tc * _round_up(w, 128) * 4


def bitmap_block_c(w: int, orient: str) -> int:
    """Candidates per block of the per-step bitmap gains over all `w`
    words of each, 0 if no block fits the fused VMEM budget. 'wc': the
    widest multiple of 128 lanes whose `bitmap_gains_need` fits. 'cw':
    the largest multiple of 128 rows up to `BITMAP_BLOCK_C_MAX`, else 64,
    32, 16 or 8 rows, that fits."""
    vmem = flags.fused_vmem_mb() * 2 ** 20
    if orient == "wc":
        return int(vmem // bitmap_gains_need(128, w, "wc")) * 128
    for tc in [*range(BITMAP_BLOCK_C_MAX, 0, -128), 64, 32, 16, 8]:
        if bitmap_gains_need(tc, w, "cw") <= vmem:
            return tc
    return 0


def bitmap_orient(c: int, w: int) -> str:
    """How the per-step bitmap gains stream a pool of `c` candidates of
    `w` uint32 words. 'wc': the (W, C) view, words on sublanes and
    candidates on lanes, where its (8, 128)-tiled footprint
    ⌈W/8⌉·8 × ⌈C/128⌉·128 is the smaller of the two and a block of 128
    candidates fits the VMEM budget. Else 'cw': the (C, W) array, words
    on lanes. The TPU compiler lays a 2-D array out the way that pads it
    less, so the 'wc' view of such a pool is a bitcast of the array as it
    lies, and neither orientation streams the other's padding."""
    wc = _round_up(w, 8) * _round_up(c, 128)
    cw = _round_up(c, 8) * _round_up(w, 128)
    return "wc" if wc < cw and bitmap_block_c(w, "wc") else "cw"


# Mosaic's scoped-VMEM limit for a kernel that names none (v5e: 16 MiB)
_SCOPED_VMEM_DEFAULT = 16 * 2 ** 20


def vmem_limit(need: int) -> int:
    """Scoped-VMEM limit handed to Mosaic for a kernel whose modeled
    working set is `need` bytes: twice the model, because Pallas
    double-buffers every pipelined block, and never below Mosaic's own
    default."""
    return max(_SCOPED_VMEM_DEFAULT, 2 * int(need))


@dataclasses.dataclass(frozen=True)
class FeatureTiles:
    """Blocks of one contraction-tiled feature kernel (kernels/pairwise.py):
    `tn` ground rows × `tc` candidates, summed over the features `td` at a
    time (the innermost grid axis), the features zero-padded to `d_pad`,
    a whole number of tiles."""
    kernel: str         # 'pairwise' | 'gains'
    tn: int
    tc: int
    td: int
    d_pad: int
    need: int           # VMEM bytes of one grid cell (`feature_need`)
    hbm_bytes: int      # HBM bytes one call moves (`feature_bytes`)

    @property
    def limit(self) -> int:
        return vmem_limit(self.need)

    def as_record(self) -> dict:
        return {**dataclasses.asdict(self), "limit": self.limit}


# the smallest blocks of the feature kernels, and the pad bases of their
# ground and candidate axes (ops.py buckets N and C on them)
FEATURE_TILE_N = 256
FEATURE_TILE_C = 128


def feature_need(kernel: str, tn: int, tc: int, td: int,
                 itemsize: int = 4) -> int:
    """VMEM bytes of one grid cell of a contraction-tiled feature kernel:
    the (TN, TD) ground block at `itemsize` and the (TC, TD) f32
    candidate block; their f32 squares (or int8 rescale); the (TN, TC)
    f32 cross-term accumulator with the tile's product and the finished
    block beside it; the (TN, 1) and (1, TC) squared-norm accumulators,
    padded to whole vregs; and the output block — (TN, TC) for the
    pairwise build, the (1, TC) gains row and (1, TN) state row (and
    int8 scale row) for the gains kernel."""
    feat = tn * td * itemsize + tc * td * 4 + (tn + tc) * td * 4
    acc = 3 * tn * tc * 4 + 4 * (128 * tn + 8 * tc)
    if kernel == "pairwise":
        return feat + acc + tn * tc * 4
    rows = 2 if itemsize == 1 else 1
    return feat + acc + 4 * 8 * (tc + rows * tn)


def _moves(grid: Tuple[int, ...], axes: Tuple[int, ...]) -> int:
    """Times a block whose index follows grid `axes` is moved over a
    row-major walk of `grid`: the pipeline fetches (or writes back) it
    whenever its index changes, so once per step of the innermost such
    axis that has more than one step."""
    live = [a for a in axes if grid[a] > 1]
    return math.prod(grid[:max(live) + 1]) if live else 1


def feature_bytes(kernel: str, n_pad: int, c_pad: int, tn: int, tc: int,
                  td: int, d_pad: int, itemsize: int = 4,
                  out_itemsize: int = 4) -> int:
    """HBM bytes one call of a contraction-tiled feature kernel moves.

    'pairwise': grid (N/TN, C/TC, D/TD), ground (N, D) and candidates
    (C, D) in, the (N, C) matrix out at `out_itemsize`: ground · ⌈C/TC⌉
    + candidates · ⌈N/TN⌉ + output once, where one feature tile lets a
    block that stays put be read once. 'gains': grid (C/TC, N/TN, D/TD),
    the ground and its (1, N) state row re-read per candidate block, the
    candidates per ground block when the features are tiled, the (1, C)
    gains written once."""
    if kernel == "pairwise":
        grid = (n_pad // tn, c_pad // tc, d_pad // td)
        return (_moves(grid, (0, 2)) * tn * td * itemsize
                + _moves(grid, (1, 2)) * tc * td * itemsize
                + _moves(grid, (0, 1)) * tn * tc * out_itemsize)
    grid = (c_pad // tc, n_pad // tn, d_pad // td)
    rows = 2 if itemsize == 1 else 1
    return (_moves(grid, (1, 2)) * tn * td * itemsize
            + rows * _moves(grid, (1,)) * tn * 4
            + _moves(grid, (0, 2)) * tc * td * 4
            + _moves(grid, (0,)) * tc * 4)


def feature_tiles(kernel: str, n_pad: int, c_pad: int, d: int,
                  itemsize: int = 4, out_itemsize: int = 4,
                  budget: Optional[int] = None) -> FeatureTiles:
    """Blocks for a contraction-tiled feature kernel ('pairwise' or
    'gains') over ground rows `n_pad` and candidates `c_pad` (each a
    power-of-two multiple of its `FEATURE_TILE_*`) and `d` features, the
    ground stored at `itemsize` bytes (the pairwise build's matrix written
    at `out_itemsize`).

    Where the smallest blocks hold every feature within `budget` bytes
    of VMEM (default: the fused VMEM budget), they are taken: one tile,
    the blocks the kernels had before the features were tiled, so their
    output is what it was, bit for bit (d ≤ 2,432 at the default 8 MiB).
    Wider features are tiled: among the tilings whose `feature_need`
    fits, the one that moves the fewest HBM bytes (`feature_bytes`),
    then the one with the fewest grid steps. TN and TC run over powers
    of two from the smallest tile up to the padded axis; TD splits the
    128-lane feature axis into 1, 2, 3, … tiles as evenly as whole vregs
    allow. When none fits, the smallest blocks, whose `limit` still
    covers them."""
    if budget is None:
        budget = int(flags.fused_vmem_mb() * 2 ** 20)
    lanes = -(-d // 128)
    tds = sorted({-(-lanes // t) * 128 for t in range(1, lanes + 1)})

    def tiling(tn, tc, td):
        d_pad = -(-d // td) * td
        return FeatureTiles(
            kernel, tn, tc, td, d_pad,
            feature_need(kernel, tn, tc, td, itemsize),
            feature_bytes(kernel, n_pad, c_pad, tn, tc, td, d_pad,
                          itemsize, out_itemsize))

    whole = tiling(FEATURE_TILE_N, FEATURE_TILE_C, lanes * 128)
    if whole.need <= budget:
        return whole
    best, best_key = tiling(FEATURE_TILE_N, FEATURE_TILE_C, 128), None
    tn = FEATURE_TILE_N
    while tn <= n_pad:
        tc = FEATURE_TILE_C
        while tc <= c_pad:
            for td in tds:
                if feature_need(kernel, tn, tc, td, itemsize) > budget:
                    continue
                t = tiling(tn, tc, td)
                key = (t.hbm_bytes,
                       (n_pad // tn) * (c_pad // tc) * (t.d_pad // td))
                if best_key is None or key < best_key:
                    best, best_key = t, key
            tc *= 2
        tn *= 2
    return best


def resident_need(n_pad: int, c_pad: int, d_pad: Optional[int],
                   rule: Optional[KernelRule] = None,
                   itemsize: int = 4) -> Optional[int]:
    """Bytes of VMEM one resident-tier invocation holds (the working-set
    model `resident_fits` gates on, and the per-query term `serve_plan`
    multiplies by B for an admitted serving batch); None when the shape
    cannot be resident at all (feature rules without a feature dim)."""
    if rule is not None and rule.is_bitmap:
        return 4 * (3 * n_pad * c_pad + 4 * c_pad + 4 * n_pad)
    if d_pad is None:
        return None
    if itemsize >= 4:
        return 4 * (n_pad * d_pad + c_pad * d_pad
                    + 2 * n_pad * c_pad
                    + 4 * c_pad + 4 * n_pad)
    return (4 * (n_pad * d_pad + c_pad * d_pad)
            + n_pad * c_pad * itemsize
            + 4 * RES_TILE_N * c_pad
            + 4 * (4 * c_pad + 5 * n_pad))


def resident_fits(n_pad: int, c_pad: int, d_pad: Optional[int],
                  rule: Optional[KernelRule] = None,
                  itemsize: int = 4) -> bool:
    """Whole-working-set VMEM residency check for the megakernel's
    resident tier, dtype-aware via ``itemsize`` (the cache storage
    dtype's bytes/entry).

    f32 storage (the legacy model): feature rules hold the (N, D)/(C, D)
    blocks, the on-chip (N, C) matrix, its gain-partials temporary, and
    the state/mask/gains rows — all f32. Bitmap rules hold the (C, W)
    bits input, the transposed (W, C) matrix, and the f32 partials
    instead — no feature blocks at all (always uint32: itemsize ignored).

    Sub-f32 storage (bf16/int8): the dominant N·C matrix term shrinks to
    ``n·c·itemsize`` because the kernel stores the ROUNDED matrix and
    rebuilds/accumulates through an (RES_TILE_N, C) f32 strip instead of
    a second full-size f32 temporary (plus the (1, N) per-row scale
    column for int8). That is what raises the memory-bounded N ceiling
    ~2× per halving of the storage width — the paper's larger-instance
    regime (§6.4) at fixed per-node memory."""
    need = resident_need(n_pad, c_pad, d_pad, rule=rule,
                          itemsize=itemsize)
    return need is not None and need <= flags.fused_vmem_mb() * 2 ** 20


def _refuse(refused: Optional[list], tier: str, gate: str,
            need: Optional[int], budget: float, **extra) -> None:
    if refused is not None:
        refused.append({"tier": tier, "gate": gate,
                        "need": None if need is None else int(need),
                        "budget": int(budget), **extra})


def fused_plan(n: int, c: int, d: Optional[int] = None,
               backend=None, rule: Optional[KernelRule] = None,
               refused: Optional[list] = None) -> Optional[dict]:
    """Static (trace-time) three-way memory gate for the cached-matrix
    engines (DESIGN §Perf).

    Returns None when no (n, c) matrix fits the cache budget in any
    permitted storage dtype — the paper's memory-capped regime (§6.4)
    where callers must use the per-step engine. Otherwise a dict:

      tier         'resident'  — the whole working set fits VMEM; the
                                 megakernel builds the matrix on-chip
                                 (feature rules need d) and the greedy is
                                 ONE dispatch
                   'streaming' — cache in HBM, loop kernel re-reads it per
                                 step; greedy is TWO dispatches (ONE for
                                 bitmap rules: their prepare is a
                                 transpose, not a kernel)
                   'fused'     — cache fits HBM but the loop scratch does
                                 not: per-step fused kernels only (k+1)
      block_n      row block for the per-step fused kernel (0 on ref)
      loop_block_n row block for the streaming loop kernel (0 unless
                   tier == 'streaming' on a Pallas backend)
      dtype        cache storage dtype: 'float32' | 'bfloat16' | 'int8'
                   for feature rules (the ladder descends f32 → bf16 →
                   int8 as each busts the HBM budget — or one dtype is
                   forced via REPRO_FUSED_CACHE_DTYPE; int8 stores
                   per-row-scaled quantized entries, kernels rescale and
                   accumulate in f32 either way); bitmap rules always
                   store 'uint32'

    ``refused``: when a list is given, every tier or storage dtype the
    gates turn down is appended to it as ``{'tier', 'gate', 'need',
    'budget'}`` in bytes. Gates: 'hbm_cache' (the (n, c) matrix, with
    its 'dtype'), 'resident_vmem', 'fused_block_vmem' and
    'loop_block_vmem' (the working set at the smallest row block).
    """
    b = resolve_backend(backend)
    bitmap = rule is not None and rule.is_bitmap
    if b == "ref":
        n_pad, c_pad = n, c
        n_res, d_pad = n, d
    else:
        n_pad, c_pad = bucket_len(n, 256), bucket_len(c, 128)
        # gate the resident tier on what the kernel will actually
        # allocate: feature rules pad the ground axis from the small
        # RES_TILE_N base, but bitmap rules pad their word axis to a
        # 128-lane multiple (it is the last axis of the bits input)
        n_res = bucket_len(n, 128 if bitmap else RES_TILE_N)
        d_pad = -(-d // 128) * 128 if d else None
    cache = flags.fused_cache_mb() * 2 ** 20
    vmem = flags.fused_vmem_mb() * 2 ** 20
    pref = flags.fused_cache_dtype()
    forced = {"f32": "float32", "bf16": "bfloat16",
              "int8": "int8"}.get(pref)
    dtype, itemsize = None, 4
    for cand in (("uint32",) if bitmap
                 else ("float32", "bfloat16", "int8")):
        if forced is not None and not bitmap and cand != forced:
            continue
        size = cache_itemsize(cand)
        need = n_pad * c_pad * size * _VMAP_REPLICAS
        if need <= cache:
            dtype, itemsize = cand, size
            break
        _refuse(refused, "cached", "hbm_cache", need, cache, dtype=cand)
    if dtype is None:
        return None
    res_need = ((bitmap or d_pad is not None)
                and resident_need(n_res, c_pad, d_pad, rule=rule,
                                  itemsize=itemsize))
    resident = bool(res_need) and res_need <= vmem
    if res_need and not resident:
        _refuse(refused, "resident", "resident_vmem", res_need, vmem)
    if b == "ref":
        return {"tier": "resident" if resident else "streaming",
                "block_n": 0, "loop_block_n": 0, "dtype": dtype}
    bn = fused_block_n(n_pad, c_pad, itemsize)
    if resident:
        return {"tier": "resident", "block_n": bn, "loop_block_n": 0,
                "dtype": dtype}
    bn_loop = loop_block_n(n_pad, c_pad, itemsize)
    least = _block_min(itemsize)
    if bn_loop == 0:
        _refuse(refused, "streaming", "loop_block_vmem",
                loop_need(least, n_pad, c_pad, itemsize), vmem)
    if bn == 0:
        _refuse(refused, "fused", "fused_block_vmem",
                fused_need(least, n_pad, c_pad, itemsize), vmem)
        return None
    return {"tier": "streaming" if bn_loop else "fused",
            "block_n": bn, "loop_block_n": bn_loop, "dtype": dtype}


def stream_need(n: int, l: int, b: int, d: Optional[int],
                dtype: str) -> int:
    """VMEM bytes of one stream-filter dispatch: the (N, D)/(B, D) feature
    blocks — or the (B, W) bits input for bitmap rules (N = W) — the
    on-chip (N, B) matrix and its (B, N) transpose scratch, the (L, N)
    level rows (in, out, and the gain-partials temporary), and the
    (L, B) admit matrix plus per-level columns. N (rows or words) and B
    are padded to 128 lanes and L to a sublane multiple, as the ops.py
    wrapper pads them."""
    n_pad, b_pad = -(-n // 128) * 128, -(-b // 128) * 128
    l_pad = -(-l // RES_TILE_N) * RES_TILE_N
    if dtype == "uint32":
        feat = 4 * b_pad * n_pad
    else:
        d_pad = -(-(d or 0) // 128) * 128
        feat = (n_pad * d_pad * cache_itemsize(dtype)
                + 4 * b_pad * d_pad
                + (4 * n_pad if dtype == "int8" else 0))   # scale row
    return feat + 4 * (2 * n_pad * b_pad
                       + 3 * l_pad * n_pad + 2 * l_pad * b_pad
                       + 8 * l_pad)


def stream_plan(n: int, l: int, b: int, d: Optional[int],
                backend=None, rule: Optional[KernelRule] = None
                ) -> Optional[dict]:
    """Static VMEM gate for the batched stream-filter kernel, in the style
    of `fused_plan`. Feature rules hold the (N, D)/(B, D) feature blocks,
    the on-chip (N, B) matrix, the (L, N) level rows (in, out, and the
    gain-partials temporary), and the (L, B) admit matrix resident for
    the whole dispatch; bitmap rules swap the feature blocks for the
    (B, W) bits input (N = W). Returns {'tier': 'kernel', 'dtype': …}
    when that fits the stream VMEM budget, {'tier': 'ref', 'dtype': …}
    on the jnp backend, and None when the Pallas working set busts the
    budget — callers then use the ref.stream_sieve oracle path (one
    fused jnp computation, still one jit call per batch).

    dtype is the GROUND-FEATURE storage dtype: 'int8' only when
    REPRO_FUSED_CACHE_DTYPE forces it for a feature rule (the fixed
    evaluation set is stored per-row-quantized, arrivals stay f32, and
    the gate budgets the (N, D) block at 1 byte/entry + the (1, N) f32
    scale row); 'auto' never silently quantizes a stream.
    """
    bk = resolve_backend(backend)
    bitmap = rule is not None and rule.is_bitmap
    dtype = ("uint32" if bitmap
             else ("int8" if flags.fused_cache_dtype() == "int8"
                   else "float32"))
    if bk == "ref":
        return {"tier": "ref", "dtype": dtype}
    need = stream_need(n, l, b, d, dtype)
    if need <= flags.stream_vmem_mb() * 2 ** 20:
        return {"tier": "kernel", "dtype": dtype}
    return None


# ---------------------------------------------------------------------------
# sharded cross-device leaf plans (kernels/shard_gains.py, DESIGN
# §Distributed scale)
# ---------------------------------------------------------------------------

# candidate-tile ladder for the sharded tier: wide tiles amortize the
# per-tile all_gather/psum, narrow ones shrink the gathered working set
SHARD_TILE_MIN = 8
_SHARD_TILES = (512, 256, 128, 64, 32, 16, 8)


def shard_bytes(n: int, d: int, lanes: int, tile_c: int) -> int:
    """Modeled PER-DEVICE HBM bytes of one sharded greedy over an
    n-element pool split across `lanes` devices: the lane's (n_s, d)
    feature shard plus its ids/valid/state-row columns, and the gathered
    (lanes·tile_c, d) candidate tile with its mask and global gains row.
    No N×C term at all — that is the point of the tier."""
    n_s = -(-(-(-n // lanes)) // tile_c) * tile_c    # padded lane shard
    return 4 * n_s * (d + 3) + 4 * lanes * tile_c * (d + 2)


def shard_plan(rule: KernelRule, n: int, d: Optional[int], lanes: int,
               backend=None, refused: Optional[list] = None
               ) -> Optional[dict]:
    """Budget gate for the `sharded` engine tier, in the style of
    `fused_plan`: the widest candidate tile whose per-device working set
    (`shard_bytes`) fits the REPRO_FUSED_CACHE_MB per-device budget, or
    None when the tier does not apply — bitmap rules (sharding the
    ground axis would shard the universe words, i.e. the payload columns
    themselves), a single lane (nothing to shard over), no feature dim,
    or a pool so large even the minimal tile busts the budget.

    Returns {'tile_c', 'bytes', 'dtype'} — the tier streams f32 features
    through the same rule-parameterized gains kernels as the solo tiers
    (the int8 ladder is a CACHE storage option; there is no cache here).
    ``refused``: as in `fused_plan`, gate 'shard' (need None when the
    tier does not apply at all).
    """
    budget = flags.fused_cache_mb() * 2 ** 20
    if rule.is_bitmap or lanes < 2 or not d:
        _refuse(refused, "sharded", "shard", None, budget)
        return None
    for tile in _SHARD_TILES:
        need = shard_bytes(n, d, lanes, tile)
        if need <= budget:
            return {"tile_c": tile, "bytes": need, "dtype": "float32"}
    _refuse(refused, "sharded", "shard", need, budget, tile_c=tile)
    return None


def engine_hbm_bytes(plan: EnginePlan, n: int, c: int,
                     d: Optional[int] = None) -> int:
    """Modeled per-device HBM bytes one greedy invocation holds under
    `plan` — the common currency `plan_tree` compares leaf and node
    engines in. Solo tiers hold the whole pool (features or bitmap
    words + ids/valid/state row) plus, for cached tiers, the padded
    (n, c) matrix at the plan's storage width; the sharded tier holds
    only its `shard_bytes` slice (its `n` is the GLOBAL pool)."""
    if plan.engine == "sharded":
        return shard_bytes(n, d or 0, plan.lanes, plan.tile_c)
    if plan.rule.is_bitmap:
        feat = 4 * (c * n + 2 * c + n)      # (C, W) bits + ids/valid + row
    else:
        feat = 4 * (n * (d or 0) + 3 * n)
    if not plan.cached:
        return feat
    if plan.backend == "ref":
        n_pad, c_pad = n, c
    else:
        n_pad, c_pad = bucket_len(n, 256), bucket_len(c, 128)
    return feat + n_pad * c_pad * cache_itemsize(plan.dtype)


# ---------------------------------------------------------------------------
# serving admission plans (serving/engine.py, DESIGN §Serving)
# ---------------------------------------------------------------------------


def serve_key(rule: KernelRule, n: int, c: int, d: Optional[int],
              backend: str) -> str:
    """Admission-compatibility key for the serving engine, in the style
    of `autotune_key`: queries sharing a key can stack into ONE vmapped
    resident dispatch. Rule identity includes the name, cap AND λ
    (satcover queries with different caps — or mmr queries with different
    relevance weights — bake different kernel constants and must not
    co-batch). The candidate axis buckets exactly like the resident
    kernel pads (queries in one bucket stack losslessly after
    zero-padding), while the trailing payload axis — features D for
    vector rules, universe WORDS for bitmap rules — must match EXACTLY:
    it is a stacking dim of the batched operand, not a padded one."""
    tail = f"w{n}" if rule.is_bitmap else f"d{d}"
    return (f"{rule.name}|cap{rule.cap}|lam{rule.lam}"
            f"|c{bucket_len(c, 128)}|{tail}|{backend}")


def serve_plan(rule: KernelRule, n: int, c: int, d: Optional[int],
               backend: Optional[str] = None) -> Optional[dict]:
    """Admission plan for ONE batched serving group, or None when the
    query cannot ride the batched path (its solo plan is not
    mega_resident — e.g. the working set overflows the resident tier) —
    the engine then runs it solo through greedy() (DESIGN §Serving).

    Otherwise ``{'plan': EnginePlan, 'b_max': int, 'bytes_per_query':
    int}``: b_max caps the admitted batch so B stacked per-query
    resident working sets fit the REPRO_SERVE_VMEM_MB budget (under
    vmap the query axis becomes a grid dimension — programs share VMEM
    sequentially on hardware, but B operand sets are alive in HBM and
    pipelined prefetch overlaps them, so budgeting B× keeps the stacked
    footprint honest) and the REPRO_SERVE_BATCH admission cap."""
    b = resolve_backend(backend)
    plan = select_engine(rule, n, c, d, requested="mega", backend=b)
    if plan.engine != "mega_resident":
        return None
    itemsize = cache_itemsize(plan.dtype)
    if b == "ref":
        n_res, c_pad, d_pad = n, c, d
    else:
        c_pad = bucket_len(c, 128)
        n_res = bucket_len(n, 128 if rule.is_bitmap else RES_TILE_N)
        d_pad = -(-d // 128) * 128 if d else None
    need = resident_need(n_res, c_pad, d_pad, rule=rule,
                          itemsize=itemsize)
    if need is None:
        return None
    b_vmem = int(flags.serve_vmem_mb() * 2 ** 20 // max(need, 1))
    b_max = max(1, min(flags.serve_batch(), b_vmem))
    return {"plan": plan, "b_max": b_max, "bytes_per_query": need}


# ---------------------------------------------------------------------------
# measured plans: the on-disk autotune cache (launch/autotune.py)
# ---------------------------------------------------------------------------

AUTOTUNE_VERSION = 1

# mtime-memoized parse of the JSON cache: steady-state select_engine calls
# cost one os.stat, not a reparse — and a rewritten file (new mtime) is
# picked up without restarting the process
_AUTOTUNE_MEMO: dict = {}


def autotune_key(rule: KernelRule, n: int, c: int, d: Optional[int],
                 backend: str) -> str:
    """Cache key per (rule, BUCKETED shape, backend): shapes bucket
    exactly like the kernels' pad targets, so every shape that shares a
    compile-cache entry shares a tuned plan."""
    bitmap = rule.is_bitmap
    n_pad, c_pad = bucket_len(n, 256), bucket_len(c, 128)
    d_pad = 0 if (bitmap or not d) else -(-d // 128) * 128
    return f"{rule.name}|n{n_pad}|c{c_pad}|d{d_pad}|{backend}"


def budget_snapshot() -> dict:
    """The live budget knobs a tuned entry was measured under — recorded
    at save time, compared at lookup time (stale budgets ⇒ entry ignored,
    heuristics take over)."""
    return {"cache_mb": flags.fused_cache_mb(),
            "vmem_mb": flags.fused_vmem_mb()}


def load_autotune_cache(path: Optional[str] = None) -> dict:
    """Entries of the measured-plan cache, or {} when the knob is off,
    the file is missing, or it fails to parse / carries a different
    schema version — a corrupt or stale cache NEVER crashes a run."""
    path = path if path is not None else flags.autotune_cache_path()
    if not path:
        return {}
    ap = os.path.abspath(path)
    try:
        st = os.stat(ap)
    except OSError:
        return {}
    memo = _AUTOTUNE_MEMO.get(ap)
    if memo is not None and memo[0] == st.st_mtime_ns:
        return memo[1]
    try:
        with open(ap, "r", encoding="utf-8") as f:
            blob = json.load(f)
        entries = blob["entries"]
        if blob.get("version") != AUTOTUNE_VERSION \
                or not isinstance(entries, dict):
            entries = {}
    except (OSError, ValueError, KeyError, TypeError):
        entries = {}
    _AUTOTUNE_MEMO[ap] = (st.st_mtime_ns, entries)
    return entries


def save_autotune_cache(entries: dict, path: Optional[str] = None) -> str:
    """Atomically persist tuned entries (merged over any existing valid
    file): write to a sibling tmp file, fsync, rename — a crashed tuner
    leaves the previous cache intact."""
    path = path if path is not None else flags.autotune_cache_path()
    assert path, "save_autotune_cache needs REPRO_AUTOTUNE_CACHE (or path=)"
    ap = os.path.abspath(path)
    merged = dict(load_autotune_cache(ap))
    merged.update(entries)
    os.makedirs(os.path.dirname(ap) or ".", exist_ok=True)
    tmp = ap + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump({"version": AUTOTUNE_VERSION, "entries": merged}, f,
                  indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, ap)
    return ap


def _tuned_plan(rule: KernelRule, n: int, c: int, d: Optional[int],
                backend: str) -> Optional[dict]:
    """The validated fused_plan-shaped dict for a tuned entry, or None
    (no cache / no entry / stale budgets / malformed fields / dtype
    conflicts with a forced REPRO_FUSED_CACHE_DTYPE)."""
    entries = load_autotune_cache()
    if not entries:
        return None
    e = entries.get(autotune_key(rule, n, c, d, backend))
    if not isinstance(e, dict):
        return None
    if e.get("budgets") != budget_snapshot():
        return None
    tier = e.get("tier")
    if tier == "step":
        return {"tier": "step", "block_n": 0, "loop_block_n": 0,
                "dtype": "float32"}
    dtype = e.get("dtype")
    allowed = (("uint32",) if rule.is_bitmap
               else ("float32", "bfloat16", "int8"))
    forced = {"f32": "float32", "bf16": "bfloat16",
              "int8": "int8"}.get(flags.fused_cache_dtype())
    if tier not in ("resident", "streaming", "fused") \
            or dtype not in allowed \
            or (forced is not None and not rule.is_bitmap
                and dtype != forced):
        return None
    try:
        bn, bl = int(e.get("block_n", 0)), int(e.get("loop_block_n", 0))
    except (TypeError, ValueError):
        return None
    if backend != "ref":
        if tier in ("streaming", "fused") and bn <= 0:
            return None
        if tier == "streaming" and bl <= 0:
            return None
    return {"tier": tier, "block_n": bn, "loop_block_n": bl,
            "dtype": dtype}


_PLAN_OVERRIDE: Optional[dict] = None


@contextlib.contextmanager
def plan_override(fp: Optional[dict]):
    """Force select_engine to use this fused_plan-shaped dict verbatim
    (bypassing both the autotune cache and the static heuristics) for
    code traced inside — how launch/autotune.py times each candidate
    plan through the REAL greedy drivers. Trace-time only, like
    fused_replicas; not thread-safe."""
    global _PLAN_OVERRIDE
    old = _PLAN_OVERRIDE
    _PLAN_OVERRIDE = fp
    try:
        yield
    finally:
        _PLAN_OVERRIDE = old


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------


def select_engine(rule: KernelRule, n: int, c: int,
                  d: Optional[int] = None, *, requested: str = "auto",
                  sampling: bool = False, constrained: bool = False,
                  backend: Optional[str] = None,
                  lanes: int = 1) -> EnginePlan:
    """Resolve the selection engine for one greedy invocation.

    n: ground rows (universe WORDS for bitmap rules), c: candidates,
    d: feature dim (None for bitmap rules). `requested` is the caller's
    greedy(engine=...) argument; `sampling`/`constrained` mark the
    branches that need per-step host logic and therefore demote the
    megakernel to the fused scan (identical selections either way):

      auto   megakernel when the tier gate admits it and neither branch
             is active; fused when the cache fits and sampling is off
             (under sampling the step path evaluates only `sample`
             candidates — cheaper than k whole-(N, C) reductions);
             per-step otherwise
      mega   megakernel, falling back to fused (constraints/sampling or
             no loop tier), then step (budget-refused cache)
      fused  the cached per-step engine even under sampling; step when
             the cache busts the budget
      step   always the legacy recompute-per-step path

    `lanes` > 1 declares that the caller CAN split this greedy's ground
    set over that many mesh devices (kernels/shard_gains.py). It extends
    the escalation ladder past the cache budget: resident → streaming →
    fused → SHARDED — when every cached tier is refused and the shard
    gate admits the pool, the plan comes back as engine='sharded' with
    the gate's tile_c instead of falling all the way to 'step'. Sampling
    and constrained selection stay on the solo paths (their per-step
    host logic has no cross-device protocol).

    Every call leaves a ``plan`` record (`runtime.telemetry`): the
    shape, the request, the resulting plan, where it came from
    ('requested' | 'override' | 'tuned' | 'static'), the host seconds
    the decision took, each tier the budget gates refused, with the
    bytes it needed and the budget (`fused_plan`'s ``refused``), and the
    ``tiles`` of the feature kernel the plan runs (`plan_tiles`).
    """
    if requested not in ("auto", "mega", "fused", "step"):
        raise ValueError(f"unknown engine {requested!r}; "
                         "expected 'auto', 'mega', 'fused', or 'step'")
    t0 = time.perf_counter()
    refused: List[dict] = []
    plan, source = _select_engine(rule, n, c, d, requested, sampling,
                                  constrained, resolve_backend(backend),
                                  lanes, refused)
    telemetry.record(
        "plan", rule=rule.name, n=int(n), c=int(c),
        d=None if d is None else int(d), requested=requested,
        sampling=bool(sampling), constrained=bool(constrained),
        lanes=int(lanes), source=source, engine=plan.engine,
        tier=plan.tier, dtype=plan.dtype, block_n=plan.block_n,
        loop_block_n=plan.loop_block_n, tile_c=plan.tile_c,
        refused=refused, tiles=plan_tiles(plan, n, c, d),
        plan_s=time.perf_counter() - t0)
    return plan


def plan_tiles(plan: EnginePlan, n: int, c: int,
               d: Optional[int]) -> Optional[dict]:
    """`FeatureTiles.as_record()` of the contraction-tiled kernel `plan`
    runs on f32 features, as the ops.py wrappers plan it: the pairwise
    build of the HBM-cached tiers ('fused', 'mega_stream'), the per-step
    gains of 'step'. None for bitmap rules, the ref backend, and the
    resident and sharded tiers."""
    if plan.rule.is_bitmap or plan.backend == "ref" or not d:
        return None
    n_pad = bucket_len(n, FEATURE_TILE_N)
    c_pad = bucket_len(c, FEATURE_TILE_C)
    if plan.engine in ("fused", "mega_stream"):
        # an int8 cache is quantized from the kernel's f32 output
        out = 4 if plan.dtype == "int8" else cache_itemsize(plan.dtype)
        tiles = feature_tiles("pairwise", n_pad, c_pad, d,
                              out_itemsize=out)
    elif plan.engine == "step":
        quant = flags.fused_cache_dtype() == "int8"
        tiles = feature_tiles("gains", n_pad, c_pad, d,
                              itemsize=1 if quant else 4)
    else:
        return None
    return tiles.as_record()


def _select_engine(rule: KernelRule, n: int, c: int, d: Optional[int],
                   requested: str, sampling: bool, constrained: bool,
                   b: str, lanes: int, refused: list
                   ) -> Tuple[EnginePlan, str]:
    """`select_engine`'s decision, with where the plan came from."""
    step = EnginePlan("step", rule, b)
    if requested == "step":
        return step, "requested"
    # measured plans outrank the heuristics: an explicit override (the
    # autotuner timing one candidate), then a validated cache entry
    source, fp = "override", _PLAN_OVERRIDE
    if fp is None:
        source, fp = "tuned", _tuned_plan(rule, n, c, d, b)
    if fp is None:
        source = "static"
        fp = fused_plan(n, c, d=d, backend=b, rule=rule, refused=refused)
    elif fp.get("tier") == "step":
        return step, source
    if fp is None:
        # paper's memory-capped regime: no cached tier fits one device —
        # escalate to the cross-device sharded tier when the caller
        # offered lanes and the shard gate admits the pool
        if (lanes > 1 and requested in ("auto", "mega")
                and not sampling and not constrained):
            sp = shard_plan(rule, n, d, lanes, backend=b, refused=refused)
            if sp is not None:
                return EnginePlan("sharded", rule, b, tier="sharded",
                                  dtype=sp["dtype"], tile_c=sp["tile_c"],
                                  lanes=lanes), source
        return step, source
    mega_ok = (requested in ("auto", "mega") and not sampling
               and not constrained and fp["tier"] in ("resident",
                                                      "streaming"))
    if mega_ok:
        engine = ("mega_resident" if fp["tier"] == "resident"
                  else "mega_stream")
    elif requested in ("fused", "mega") or not sampling:
        engine = "fused"
    else:
        return step, source                 # auto + sampling: step wins
    return EnginePlan(engine, rule, b, tier=fp["tier"],
                      block_n=fp["block_n"],
                      loop_block_n=fp["loop_block_n"],
                      dtype=fp["dtype"]), source


# ---------------------------------------------------------------------------
# the tree planner: memory model → accumulation-tree shape
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TreePlan:
    """The planner's verdict for one distributed selection: how `lanes`
    devices are split between tree machines and per-leaf shards, and the
    engines each stage runs.

    radices     per-level branching, innermost (leaf-adjacent) first —
                the LevelDispatcher radices; () means ONE machine (all
                devices shard a single leaf)
    shard       devices cooperating on EACH leaf greedy (the sharded
                tier's mesh axis size; 1 = solo leaves)
    leaf_plan   EnginePlan for the leaf greedys
    node_plan   EnginePlan for the accumulation-node greedys ((b·k)-pool)
    leaf_n      elements each leaf machine owns (pre-shard split)
    peak_bytes  max modeled per-device HBM over leaf and node stages
    cost        planner objective (BSP call counts from
                AccumulationTree.cost_model; lower is better)
    model       the cost_model dict the plan was validated against
                ({} for the single-machine shape it cannot express)
    """
    radices: Tuple[int, ...]
    shard: int
    leaf_plan: EnginePlan
    node_plan: EnginePlan
    leaf_n: int
    peak_bytes: int
    cost: float
    model: dict

    @property
    def machines(self) -> int:
        return math.prod(self.radices)

    @property
    def branching(self) -> int:
        return max(self.radices) if self.radices else 1

    @property
    def lanes(self) -> int:
        return self.machines * self.shard


def _radix_options(m: int):
    """Uniform-branching level stacks multiplying to m, innermost first:
    every (b,)·L with b^L == m — includes the flat RandGreedi shape
    (m,) and the deepest binary stack when m is a power of two."""
    if m == 1:
        return [()]
    opts = []
    for b in range(2, m + 1):
        level, total = 0, 1
        while total < m:
            total *= b
            level += 1
        if total == m:
            opts.append((b,) * level)
    return opts


def plan_tree(rule: KernelRule, n: int, d: Optional[int], k: int,
              lanes: int, budget_mb: Optional[int] = None,
              backend: Optional[str] = None,
              words: Optional[int] = None) -> Optional[TreePlan]:
    """Pick the accumulation-tree shape for `lanes` devices from the
    same dtype-aware memory model the engine tiers gate on — the paper's
    core move (§4/§6.4): choose branching and levels so every tree node
    fits per-device memory, instead of taking the tree as user input.

    Enumerates shard ∈ divisors(lanes) (devices cooperating per leaf)
    and every uniform radix stack over the remaining m = lanes/shard
    machines — from the flat RandGreedi (m,) through the deepest stack —
    and keeps the shapes whose leaf AND node stages fit `budget_mb`
    (default REPRO_FUSED_CACHE_MB) per device:

      leaf stage   shard == 1: `select_engine` on the ceil(n/m)-pool
                   (folding in autotune-cache winners, like any solo
                   call), costed by `engine_hbm_bytes`;
                   shard > 1: the sharded tier via `select_engine(...,
                   lanes=shard)` — the shape is only feasible if the
                   escalation actually fires
      node stage   `select_engine` on the (b·k)-candidate accumulation
                   pool — the paper's b·k per-node memory term

    Feasible shapes are ranked by BSP cost from
    `AccumulationTree.cost_model` (leaf compute ÷ shard, since shard
    devices split each gains call, plus interior compute and comm),
    with fewer levels then more sharding as tie-breaks. The model's
    structural terms are asserted against the enumerated shape —
    the satellite wiring that keeps cost_model honest. Returns None
    only when NO shape fits the budget (the instance is unsolvable at
    this lane count under this model).

    ``words``: bitmap rules plan their ground axis over universe WORDS
    (d is None); the shard shapes are then naturally infeasible and the
    planner only sizes the solo tree."""
    from repro.core.tree import AccumulationTree    # lazy: core→kernels

    if rule.is_bitmap and not words:
        raise ValueError("bitmap rules need words= for tree planning")
    b = resolve_backend(backend)
    budget = (budget_mb if budget_mb is not None
              else flags.fused_cache_mb()) * 2 ** 20
    obj = "kmedoid" if rule.fold == "min" else "coverage"
    rows = (lambda c: words) if rule.is_bitmap else (lambda c: c)
    best = None
    for shard in (s for s in range(1, lanes + 1) if lanes % s == 0):
        m = lanes // shard
        leaf_n = -(-n // m)
        # leaf stage: solo plan, or the sharded tier over `shard` devices
        if shard == 1:
            lp = select_engine(rule, rows(leaf_n), leaf_n, d, backend=b)
        else:
            lp = select_engine(rule, rows(leaf_n), leaf_n, d, backend=b,
                               lanes=shard)
            if lp.engine != "sharded":
                continue    # escalation didn't fire: solo shapes cover it
        leaf_bytes = engine_hbm_bytes(lp, rows(leaf_n), leaf_n, d)
        if leaf_bytes > budget:
            continue
        for radices in _radix_options(m):
            if radices:
                br = radices[0]
                nc = br * k
                np_ = select_engine(rule, rows(nc), nc, d, backend=b)
                node_bytes = engine_hbm_bytes(np_, rows(nc), nc, d)
                if node_bytes > budget:
                    continue
                model = AccumulationTree(m, br).cost_model(
                    n, k, 1.0, objective=obj)
                # satellite wiring: the BSP model must agree with the
                # enumerated structure, or the planner (and the model)
                # is lying about the tree it costs
                assert model["levels"] == len(radices), (model, radices)
                assert model["elements_per_interior"] == br * k
                cost = (model["compute_cost"] / shard
                        + model["comm_cost"])
            else:
                np_, node_bytes = lp, 0
                model = {}
                cost = ((n ** 2) * k if obj == "kmedoid"
                        else n * k) / shard
            cand = TreePlan(radices, shard, lp, np_, leaf_n,
                            max(leaf_bytes, node_bytes), cost, model)
            key = (cand.cost, len(cand.radices), -cand.shard)
            if best is None or key < (best.cost, len(best.radices),
                                      -best.shard):
                best = cand
    return best
