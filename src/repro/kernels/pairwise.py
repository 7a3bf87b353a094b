"""Pallas TPU kernels: pairwise matrix materialization + the ONE
rule-parameterized per-step gains kernel.

Two entry points, both driven by a `KernelRule` (kernels/rules.py):

  * ``pairwise_pallas`` — the fused engine's `prepare()` stage (DESIGN
    §Perf): compute the (N, C) ground×candidate matrix ONCE per greedy
    invocation for the feature rules ('dist' k-medoid, 'dot'
    facility/satcover). Bitmap rules never reach it — their matrix is a
    transpose of the candidate payloads, built by ops.py without a
    dispatch. Grid: (N/TN, C/TC); each block is one MXU matmul over the
    full feature dim.

  * ``gains_pallas`` — the per-step (uncached) marginal-gains pass, the
    paper's memory-capped regime. This single kernel replaces the three
    per-objective kernels (kmedoid_gains / facility_gains /
    coverage_gains) that predated the objective protocol: the rule picks
    the matrix op and the gain part, so feature rules tile
    (TC candidates × TN ground rows) with an MXU matmul per block,
    partial sums accumulating over the inner grid dimension in f32, and
    bitmap rules read the (C, W) candidate bitmaps in place, in blocks of
    whole rows (TC × W words, TC from plans.bitmap_block_c) with AND-NOT
    + popcount.

VMEM per block: TN·D·4 + TC·D·4 + TN·TC·4 ≈ 1.9 MB at D=768 (feature
rules) / plans.bitmap_gains_need (bitmap rules: ≈ 7.9 MB at W=515).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rules as R
from repro.kernels.rules import KernelRule, pairwise_block  # noqa: F401

F32 = jnp.float32

TILE_N = 256        # ground rows per block (feature rules)
TILE_C = 128        # candidates per block (feature rules)


def _kernel(ground_ref, cands_ref, out_ref, *, mode: str):
    g = ground_ref[...].astype(F32)                    # (TN, D)
    c = cands_ref[...].astype(F32)                     # (TC, D)
    out_ref[...] = pairwise_block(g, c, mode).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("mode", "out_dtype", "interpret"))
def pairwise_pallas(ground: jax.Array, cands: jax.Array, mode: str = "dist",
                    out_dtype: str = "float32",
                    interpret: bool = False) -> jax.Array:
    """ground: (N, D), cands: (C, D) → (N, C) matrix in ``out_dtype``
    (compute always f32; 'bfloat16' halves the cache's HBM footprint).

    N, C, D must be padded to tile multiples by the ops.py wrapper (zero
    padding: pad rows/cols produce ‖·‖ / 0 entries that callers mask).
    """
    n, d = ground.shape
    c = cands.shape[0]
    assert n % TILE_N == 0 and c % TILE_C == 0 and d % 128 == 0, (n, c, d)
    grid = (n // TILE_N, c // TILE_C)
    return pl.pallas_call(
        functools.partial(_kernel, mode=mode),
        name="pairwise_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_N, d), lambda ni, ci: (ni, 0)),
            pl.BlockSpec((TILE_C, d), lambda ni, ci: (ci, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_N, TILE_C), lambda ni, ci: (ni, ci)),
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.dtype(out_dtype)),
        # every block is independent — Mosaic may pipeline/reorder both dims
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(ground, cands)


def _gains_kernel(ground_ref, row_ref, cands_ref, out_ref, *,
                  rule: KernelRule):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += R.block_gains(ground_ref[...], cands_ref[...],
                                  row_ref[...], rule)


def _gains_kernel_quant(ground_ref, gscale_ref, row_ref, cands_ref,
                        out_ref, *, rule: KernelRule):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # int8 rescale-accumulate: the (TN, D) ground block is 1-byte
    # storage; rescale it against the (1, TN) per-row scales on-chip,
    # then the identical f32 gain algebra
    g = R.dequant(ground_ref[...], gscale_ref[...])
    out_ref[...] += R.block_gains(g, cands_ref[...], row_ref[...], rule)


def _bitmap_gains_kernel(ground_ref, row_ref, cands_ref, out_ref, *,
                         rule: KernelRule, tc: int):
    # one (TC, W) block covers every word of its candidates: no
    # accumulation; a block under 128 rows fills the front of its
    # lane-dense output block
    del ground_ref
    out_ref[:, :tc] = R.block_gains(None, cands_ref[...], row_ref[...],
                                    rule)


@functools.partial(jax.jit, static_argnames=("rule", "interpret", "block_c",
                                             "vmem_limit_bytes"))
def gains_pallas(ground: jax.Array, row: jax.Array, cands: jax.Array,
                 rule: KernelRule, interpret: bool = False,
                 gscale=None, block_c: int = 0,
                 vmem_limit_bytes: int = 0) -> jax.Array:
    """RAW marginal-gain sums (C,) f32 for ANY registered rule (callers
    normalize outside the kernel so the logical N never becomes a static
    compile key).

    Feature rules: ground (N, D), row (1, N) state (mind/curmax/cursum),
    cands (C, D); grid (C/TC, N/TN), N innermost (output-block revisiting
    accumulation). Padded ground rows must carry row = rule.row_pad (⇒
    zero contribution); the ops.py wrapper guarantees this. When
    `gscale` (1, N) f32 is given, `ground` is int8 per-row-quantized
    storage (rules.quantize_rows) and the kernel rescales each block to
    f32 on-chip — quartering the dominant per-step HBM read.

    Bitmap rules: ground is an ignored (8, 128) placeholder, row (1, W)
    covered words, cands (C, W) candidate bitmaps of any shape, read in
    place: each block is `block_c` candidate rows (all of them when C is
    smaller) over all W words, grid (⌈C/TC⌉,). The last block may run
    past C; its rows only reach entries past C, which the caller cuts.
    `vmem_limit_bytes`: Mosaic's scoped-VMEM limit (plans.vmem_limit).
    """
    c = cands.shape[0]
    if rule.is_bitmap:
        w = cands.shape[1]
        assert row.shape == (1, w) and block_c > 0, (row.shape, block_c)
        tc = min(block_c, c)
        lanes = -(-tc // 128) * 128
        blocks = pl.cdiv(c, tc)
        out = pl.pallas_call(
            functools.partial(_bitmap_gains_kernel, rule=rule, tc=tc),
            name="gains_pallas",
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec(ground.shape, lambda ci: (0, 0)),
                pl.BlockSpec((1, w), lambda ci: (0, 0)),
                pl.BlockSpec((tc, w), lambda ci: (ci, 0)),
            ],
            out_specs=pl.BlockSpec((1, lanes), lambda ci: (0, ci)),
            out_shape=jax.ShapeDtypeStruct((1, blocks * lanes), F32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=vmem_limit_bytes or None),
            interpret=interpret,
        )(ground, row, cands)
        return out.reshape(blocks, lanes)[:, :tc].reshape(-1)
    kernel = _gains_kernel
    n, d = ground.shape
    assert n % TILE_N == 0 and c % TILE_C == 0 and d % 128 == 0
    assert row.shape == (1, n) and cands.shape[1] == d
    grid = (c // TILE_C, n // TILE_N)
    in_specs = [
        pl.BlockSpec((TILE_N, d), lambda ci, ni: (ni, 0)),
        pl.BlockSpec((1, TILE_N), lambda ci, ni: (0, ni)),
        pl.BlockSpec((TILE_C, d), lambda ci, ni: (ci, 0)),
    ]
    operands = [ground, row, cands]
    if gscale is not None:
        assert gscale.shape == (1, n), (gscale.shape, n)
        in_specs.insert(1, pl.BlockSpec((1, TILE_N),
                                        lambda ci, ni: (0, ni)))
        operands.insert(1, gscale)
        kernel = _gains_kernel_quant
    out = pl.pallas_call(
        functools.partial(kernel, rule=rule),
        name="gains_pallas",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, TILE_C), lambda ci, ni: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, c), F32),
        # candidate blocks are independent (parallel); the inner
        # ground dim accumulates into the revisited output block
        # (arbitrary), which Mosaic can still software-pipeline
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[0]
