"""Pallas TPU megakernel: the ENTIRE k-step greedy selection in one dispatch.

The fused engine (kernels/fused_step.py) cut a greedy invocation from 3k to
k+1 kernel calls, but still pays one dispatch per selection step and a full
HBM round-trip of the (N,) state row between steps. This kernel fuses the
loop itself: the step dimension becomes the OUTER, order-dependent grid
dimension, and the selection state — state row, candidate mask, gains
accumulator, previous winner — lives in VMEM/SMEM scratch ACROSS grid
iterations, so the whole selection is one `pallas_call`. Two tiers:

  * **streaming** — grid `(k + 1, N/BN)`: each step re-reads the cached
    (N, C) matrix from HBM block by block (the only HBM traffic), while the
    state row persists in its (N/BN, BN) output block, resident in VMEM
    across the grid (in the rule's row dtype), the evolving candidate mask and gains accumulator in (1, C)
    VMEM scratch, and the previous winner in SMEM. Step s folds the winner
    of step s−1 into the row (deferred update), accumulates masked gains
    per block, argmaxes on-chip at the last block, and records
    `(best, gain)`; grid step k only flushes the final winner fold and
    writes the row out. 2 dispatches per greedy: pairwise prepare + this
    loop — and ONE for bitmap rules, whose prepare is a transpose rather
    than a kernel.

  * **resident** — a single program (no grid) for matrices that fit VMEM
    whole: the kernel takes the (N, D)/(C, D) FEATURE blocks (or the
    (C, W) candidate bitmaps), builds the matrix on-chip via the rule's
    pairwise op, and runs the k-step loop as a `fori_loop` over the
    VMEM-resident matrix. This is exactly the accumulation-node shape of
    the GreedyML tree — (b·k + A)×(b·k) — making every internal node a
    SINGLE dispatch, where launch overhead is the runtime.

Selection semantics are bit-identical to the fused/step engines (same
fold → part-sum → first-argmax primitives from kernels/rules.py, same
`gain > 0` accept rule): a rejected step leaves the state and mask
untouched and emits best = −1, exactly like the host-side scan. Gains
emitted are RAW masked part sums — callers normalize by the valid ground
count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rules as R
from repro.kernels.rules import KernelRule

F32 = jnp.float32


def _stream_body(mat_ref, scale_ref, row_ref, mask_ref,
                 rowout_ref, best_ref, gain_ref,
                 msk_ref, acc_ref, prev_ref, rule: KernelRule):
    """One (step, row-block) grid cell over the (BN, C) slab in `mat_ref`.
    The state row lives in the (N/BN, BN) output block, resident across
    the whole grid (row `ni` per row block), seeded from `row_ref` at step
    0. `scale_ref` holds the (N/BN, BN) per-row scales of int8 storage
    (None otherwise): each step then re-reads the 1-byte slab from HBM (a
    quarter of the f32 traffic) and rescales it on-chip before the
    identical f32 algebra."""
    s = pl.program_id(0)                    # selection step (sequential)
    ni = pl.program_id(1)                   # row block within a step
    k = pl.num_programs(0) - 1              # last grid step only flushes
    nb = pl.num_programs(1)

    @pl.when((s == 0) & (ni == 0))
    def _init_selection():
        msk_ref[...] = mask_ref[...]
        prev_ref[0] = -1
        best_ref[...] = jnp.full(best_ref.shape, -1, jnp.int32)
        gain_ref[...] = jnp.zeros_like(gain_ref)

    @pl.when(s == 0)
    def _init_row_block():
        rowout_ref[pl.ds(ni, 1), :] = row_ref[pl.ds(ni, 1), :]

    prev = prev_ref[0]
    scale = scale_ref[pl.ds(ni, 1), :] if scale_ref is not None else None
    m = mat_ref[...] if scale is None else R.dequant(mat_ref[...], scale)

    # deferred update: fold the previous step's winner into this row block
    # (at step k this is the final flush)
    r = R.fold_winner(rowout_ref[pl.ds(ni, 1), :],
                      R.read_col(mat_ref, prev, scale), prev, rule)
    rowout_ref[pl.ds(ni, 1), :] = r

    @pl.when(s < k)
    def _select():
        @pl.when(ni == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += R.partial_gains(r, m, rule)

        @pl.when(ni == nb - 1)
        def _argmax():
            best, mx = R.masked_argmax(acc_ref[...], msk_ref[...])
            accept = mx > 0.0
            best_i = jnp.where(accept, best, jnp.int32(-1))
            # lane s of the (1, K) outputs, which stay resident across
            # the grid and reach HBM once, after the last step
            at = jax.lax.broadcasted_iota(jnp.int32, best_ref.shape, 1) == s
            best_ref[...] = jnp.where(at, best_i, best_ref[...])
            gain_ref[...] = jnp.where(at, mx, gain_ref[...])
            cols = jax.lax.broadcasted_iota(jnp.int32, msk_ref.shape, 1)
            msk_ref[...] = jnp.where(accept & (cols == best), 0.0,
                                     msk_ref[...])
            prev_ref[0] = best_i


def _stream_kernel(mat_ref, *refs, rule: KernelRule, quant: bool):
    scale_ref, refs = (refs[0], refs[1:]) if quant else (None, refs)
    _stream_body(mat_ref, scale_ref, *refs, rule)


@functools.partial(jax.jit,
                   static_argnames=("k", "rule", "block_n", "interpret",
                                    "vmem_limit_bytes"))
def greedy_loop_pallas(mat: jax.Array, row: jax.Array, mask: jax.Array,
                       k: int, rule: KernelRule, block_n: int = 256,
                       interpret: bool = False, scale=None,
                       vmem_limit_bytes: int = 0):
    """Streaming tier. mat: (N, C) cached matrix (f32/bf16/int8 storage
    for feature rules — f32 accumulate — or uint32 word-major bitmaps);
    row: (1, N) state in the rule's row dtype; mask: (1, C) 0/1 f32;
    scale: (1, N) f32 per-row scales when `mat` is int8-quantized storage
    (None otherwise). vmem_limit_bytes: Mosaic's scoped-VMEM limit
    (plans.vmem_limit).

    Returns (final_row (N,), bests (k,) i32 with −1 = rejected step,
    gains (k,) f32 raw part sums). N, C padded by the ops.py wrapper.
    """
    n, c = mat.shape
    assert n % block_n == 0 and c % 128 == 0, (n, c, block_n)
    nb = n // block_n
    kp = -(-k // 128) * 128           # lane-dense per-step outputs
    # the state row (and int8 scales) stay whole as (N/BN, BN) blocks, so
    # a row block narrower than 128 lanes still tiles
    whole_row = pl.BlockSpec((nb, block_n), lambda s, ni: (0, 0))
    in_specs = [
        pl.BlockSpec((block_n, c), lambda s, ni: (ni, 0)),
        whole_row,
        pl.BlockSpec((1, c), lambda s, ni: (0, 0)),
    ]
    operands = [mat, row.reshape(nb, block_n), mask]
    if scale is not None:
        assert scale.shape == (1, n), (scale.shape, n)
        in_specs.insert(1, whole_row)
        operands.insert(1, scale.reshape(nb, block_n))
    row_out, best, gain = pl.pallas_call(
        functools.partial(_stream_kernel, rule=rule,
                          quant=scale is not None),
        name="greedy_loop_pallas",
        grid=(k + 1, nb),
        in_specs=in_specs,
        out_specs=[
            whole_row,
            pl.BlockSpec((1, kp), lambda s, ni: (0, 0)),
            pl.BlockSpec((1, kp), lambda s, ni: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block_n), rule.dtype),
            jax.ShapeDtypeStruct((1, kp), jnp.int32),
            jax.ShapeDtypeStruct((1, kp), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, c), F32),                # evolving cand mask
            pltpu.VMEM((1, c), F32),                # gains accumulator
            pltpu.SMEM((1,), jnp.int32),            # previous winner
        ],
        # both dims are order-dependent: steps are sequential by definition,
        # and the row-block dim carries the accumulator + mask/prev updates
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes or None),
        interpret=interpret,
    )(*operands)
    return row_out.reshape(n), best[0, :k], gain[0, :k]


def _resident_kernel(ground_ref, cands_ref, row_ref, mask_ref, ctl_ref,
                     rowout_ref, best_ref, gain_ref, mat_ref, *,
                     k: int, rule: KernelRule, cache_dtype: str):
    # ctl: (1, 3) i32 [kq, logical_n, logical_c] — TRACED, not static, so
    # the serving engine can vmap this kernel over a query axis with
    # per-query step budgets and logical extents (DESIGN §Serving) while
    # solo calls share one compile-cache entry across logical shapes
    kq = ctl_ref[0, 0]
    m = R.matrix_block(ground_ref[...], cands_ref[...], rule)  # (N, C)
    if not rule.is_bitmap and cache_dtype == "int8":
        # quantized residency: the matrix the loop sees is the int8
        # per-row-scaled storage rounded back to f32 — identical rounding
        # to the HBM-cached int8 tiers, so selections agree across tiers.
        # Pad rows/cols are zeroed first so the per-row scales see only
        # logical columns (bit-parity with the ref oracle's logical build)
        rows = jax.lax.broadcasted_iota(jnp.int32, m.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
        m = jnp.where((rows < ctl_ref[0, 1]) & (cols < ctl_ref[0, 2]),
                      m, 0.0)
        m = R.dequant(*R.quantize_rows(m))
    elif not rule.is_bitmap and cache_dtype == "bfloat16":
        m = m.astype(jnp.bfloat16).astype(F32)
    # the loop reads the matrix back from scratch: winner columns come
    # from aligned lane windows of a ref (R.read_col)
    mat_ref[...] = m

    cols = jax.lax.broadcasted_iota(jnp.int32, (1, m.shape[1]), 1)
    steps = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(s, carry):
        row, mask, prev, bests, gains = carry
        row = R.fold_winner(row, R.read_col(mat_ref, prev), prev, rule)
        best, mx = R.masked_argmax(
            R.partial_gains(row, mat_ref[...], rule), mask)
        # masked steps (s ≥ kq): the deferred fold above still flushed the
        # winner of step kq−1 (matching a solo run's final flush), but no
        # further element is taken — bests/gains beyond kq stay −1/0 and
        # the state freezes, so a k_max-padded query is bit-identical to
        # its solo k=kq run
        accept = (mx > 0.0) & (s < kq)
        best_i = jnp.where(accept, best, jnp.int32(-1))
        mask = jnp.where(accept & (cols == best), 0.0, mask)
        sel = (steps == s) & (s < kq)
        return (row, mask, best_i,
                jnp.where(sel, best_i, bests), jnp.where(sel, mx, gains))

    carry = (row_ref[...], mask_ref[...].astype(F32),
             jnp.int32(-1),
             jnp.full((1, k), -1, jnp.int32), jnp.zeros((1, k), F32))
    row, _, prev, bests, gains = jax.lax.fori_loop(0, k, body, carry)
    # flush: fold the final accepted winner so value(state) sees all of S
    rowout_ref[...] = R.fold_winner(row, R.read_col(mat_ref, prev), prev,
                                    rule)
    best_ref[...] = bests
    gain_ref[...] = gains


@functools.partial(jax.jit,
                   static_argnames=("k", "rule", "interpret",
                                    "cache_dtype", "vmem_limit_bytes"))
def greedy_loop_resident_pallas(ground: jax.Array, cands: jax.Array,
                                row: jax.Array, mask: jax.Array,
                                ctl: jax.Array, k: int,
                                rule: KernelRule, interpret: bool = False,
                                cache_dtype: str = "float32",
                                vmem_limit_bytes: int = 0):
    """Resident tier: ONE dispatch builds the matrix on-chip and runs all k
    steps. Feature rules: ground (N, D), cands (C, D); bitmap rules:
    ground is an ignored placeholder and cands the (C, W) bitmaps (the
    on-chip matrix is their transpose, N = W). row: (1, N) in the rule's
    row dtype, mask: (1, C); the whole working set must fit VMEM (gated
    by plans.fused_plan's resident check, dtype-aware). `cache_dtype` is
    the plan's storage dtype: 'int8'/'bfloat16' round the on-chip matrix
    to exactly what the HBM-cached tiers would store (raising the
    residency ceiling per plans.resident_fits), 'float32'/'uint32' keep
    the legacy exact build.

    ctl: (1, 3) i32 ``[kq, logical_n, logical_c]`` — a TRACED operand
    (not a static arg): `kq ≤ k` is the per-invocation step budget
    (steps ≥ kq are masked, so a k-padded call is bit-identical to a
    solo k=kq run — the serving engine's heterogeneous-k batching),
    logical_n/logical_c bound the sub-f32 rounding to the logical
    region. vmem_limit_bytes: Mosaic's scoped-VMEM limit
    (plans.vmem_limit). Returns as greedy_loop_pallas.
    """
    n = row.shape[1]
    c = cands.shape[0]
    assert mask.shape == (1, c), (row.shape, mask.shape)
    assert ctl.shape == (1, 3) and ctl.dtype == jnp.int32, \
        (ctl.shape, ctl.dtype)
    if rule.is_bitmap:
        assert cands.shape[1] == n, (cands.shape, n)
    else:
        assert ground.shape == (n, cands.shape[1])
    row_out, best, gain = pl.pallas_call(
        functools.partial(_resident_kernel, k=k, rule=rule,
                          cache_dtype=cache_dtype),
        name="greedy_loop_resident_pallas",
        out_shape=[
            jax.ShapeDtypeStruct((1, n), rule.dtype),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((1, k), F32),
        ],
        # the on-chip matrix, kept in f32 even for int8/bf16 plans
        scratch_shapes=[pltpu.VMEM((n, c), rule.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes or None),
        interpret=interpret,
    )(ground, cands, row, mask, ctl)
    return row_out[0], best[0], gain[0]
