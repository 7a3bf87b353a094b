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
    (TC candidates × TN ground rows) with an MXU matmul per block, and
    bitmap rules tile (TC × TW words) with AND-NOT + popcount — partial
    sums accumulate over the inner grid dimension in f32 either way.

VMEM per block: TN·D·4 + TC·D·4 + TN·TC·4 ≈ 1.9 MB at D=768 (feature
rules) / TC·TW·4 ≈ 0.25 MB (bitmap rules).
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
TILE_C = 128        # candidates per block
TILE_W = 512        # universe words per block (bitmap rules)


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


@functools.partial(jax.jit, static_argnames=("rule", "interpret"))
def gains_pallas(ground: jax.Array, row: jax.Array, cands: jax.Array,
                 rule: KernelRule, interpret: bool = False,
                 gscale=None) -> jax.Array:
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
    covered words, cands (C, W) candidate bitmaps; grid (C/TC, W/TW).
    Zero-padded bits/words contribute zero gain.
    """
    c = cands.shape[0]
    kernel = _gains_kernel
    if rule.is_bitmap:
        w = cands.shape[1]
        assert c % TILE_C == 0 and w % TILE_W == 0, (c, w)
        assert row.shape == (1, w)
        grid = (c // TILE_C, w // TILE_W)
        in_specs = [
            pl.BlockSpec(ground.shape, lambda ci, ni: (0, 0)),
            pl.BlockSpec((1, TILE_W), lambda ci, ni: (0, ni)),
            pl.BlockSpec((TILE_C, TILE_W), lambda ci, ni: (ci, ni)),
        ]
        operands = [ground, row, cands]
    else:
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
        # ground/word dim accumulates into the revisited output block
        # (arbitrary), which Mosaic can still software-pipeline
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[0]
