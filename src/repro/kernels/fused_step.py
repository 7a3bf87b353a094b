"""Pallas TPU kernel: one fused greedy selection step over a cached matrix.

Second half of the fused selection engine (DESIGN §Perf). Given the cached
(N, C) matrix from `pairwise.py` (or the transposed bitmap stack for
coverage — see kernels/rules.py), a greedy step is

    1. apply the PREVIOUS winner's column to the per-ground-row state via
       the rule's fold (min for k-medoid, max for facility, OR for
       coverage, saturated-add for satcover) — the deferred update, fused
       here so no separate O(N·D) update pass exists;
    2. per-tile partial gains  Σ_rows part(state, M)  accumulated in a
       VMEM scratch row — the (1, C) gains never round-trip through HBM;
    3. masked argmax over the accumulated gains ON-CHIP at the last grid
       step, emitting only (best_idx, best_gain) scalars.

Grid: (N/BN,) — each program holds a (BN, C) row-block of the cached matrix
in VMEM; the (N,) state row stays resident whole. BN comes from the EnginePlan (kernels/plans.py); when even BN=8
does not fit, the planner routes the caller to the per-step engine (the
paper's memory-capped regime).

All objective math — fold, gain part, argmax tie-break — comes from the
shared rule primitives, so this kernel serves every registered objective
with zero per-objective code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rules as R
from repro.kernels.rules import (KernelRule, fold_winner,  # noqa: F401
                                 masked_argmax, partial_gains)

F32 = jnp.float32
LANES = R.LANES


def _kernel(prev_ref, mat_ref, *refs, rule: KernelRule, quant: bool):
    """The fused step over one (BN, C) slab of the cached matrix. The
    state row (and, for int8 storage, the per-row scales) arrive whole as
    (N/BN, BN) blocks, so a row block narrower than 128 lanes still tiles;
    grid step `ni` reads and writes row `ni` of them. int8 storage is
    rescaled on-chip against its scales before the identical f32 algebra."""
    scale_ref, refs = (refs[0], refs[1:]) if quant else (None, refs)
    row_ref, mask_ref, newrow_ref, best_ref, gain_ref, acc_ref = refs
    ni = pl.program_id(0)
    prev = prev_ref[0]
    scale = scale_ref[pl.ds(ni, 1), :] if quant else None
    m = R.dequant(mat_ref[...], scale) if quant else mat_ref[...]

    # 1. deferred update: fold the previous winner's column into the state
    new_r = R.fold_winner(row_ref[pl.ds(ni, 1), :],
                          R.read_col(mat_ref, prev, scale), prev, rule)
    newrow_ref[pl.ds(ni, 1), :] = new_r

    # 2. partial gains for this row block, accumulated on-chip
    @pl.when(ni == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += R.partial_gains(new_r, m, rule)

    # 3. masked argmax at the final grid step — scalars out (broadcast
    # over one lane-dense (1, 128) block), no (1, C) row
    @pl.when(ni == pl.num_programs(0) - 1)
    def _argmax():
        first, mx = R.masked_argmax(acc_ref[...], mask_ref[...])
        best_ref[...] = jnp.broadcast_to(first, best_ref.shape)
        gain_ref[...] = jnp.broadcast_to(mx, gain_ref.shape)


@functools.partial(jax.jit, static_argnames=("rule", "block_n", "interpret",
                                             "vmem_limit_bytes"))
def fused_step_pallas(mat: jax.Array, row: jax.Array, mask: jax.Array,
                      prev: jax.Array, rule: KernelRule,
                      block_n: int = 256, interpret: bool = False,
                      scale=None, vmem_limit_bytes: int = 0):
    """mat: (N, C) cached matrix, row: (N,) state in the rule's row dtype,
    mask: (C,) 0/1 f32, prev: () int32 previous winner (-1 = none).
    scale: (1, N) f32 per-row scales when `mat` is int8-quantized storage
    (rules.quantize_rows) — the kernel rescales each slab to f32 on-chip
    before the shared algebra; None for f32/bf16/uint32 storage.
    vmem_limit_bytes: Mosaic's scoped-VMEM limit (plans.vmem_limit).

    Returns (new_row (N,), best () int32, best_gain () f32). best_gain is
    the raw masked part-sum — callers normalize by the valid ground count.
    N, C padded to (block_n, 128) multiples by the ops.py wrapper.
    """
    n, c = mat.shape
    assert n % block_n == 0 and c % 128 == 0, (n, c, block_n)
    nb = n // block_n
    whole_row = pl.BlockSpec((nb, block_n), lambda ni: (0, 0))
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((block_n, c), lambda ni: (ni, 0)),
        whole_row,
        pl.BlockSpec((1, c), lambda ni: (0, 0)),
    ]
    operands = [prev.reshape(1).astype(jnp.int32), mat,
                row.reshape(nb, block_n), mask.reshape(1, c)]
    if scale is not None:
        assert scale.shape == (1, n), (scale.shape, n)
        in_specs.insert(2, whole_row)
        operands.insert(2, scale.reshape(nb, block_n))
    new_row, best, gain = pl.pallas_call(
        functools.partial(_kernel, rule=rule, quant=scale is not None),
        name="fused_step_pallas",
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[
            whole_row,
            pl.BlockSpec((1, LANES), lambda ni: (0, 0)),
            pl.BlockSpec((1, LANES), lambda ni: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block_n), rule.dtype),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), F32),
        ],
        scratch_shapes=[pltpu.VMEM((1, c), F32)],
        # the row-block dim carries the gains accumulator + end-of-grid
        # argmax, so it is order-dependent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes or None),
        interpret=interpret,
    )(*operands)
    return new_row.reshape(n), best[0, 0], gain[0, 0]
