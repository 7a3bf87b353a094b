"""Pallas TPU kernel: one batch of stream arrivals × ALL sieve levels.

The streaming engine (streaming/sieve.py, DESIGN §Streaming) maintains L
concurrent sieve levels — one partial solution per OPT guess v_l — and
must, for every arrival batch, (a) update the running max singleton gain
m and slide the exponent window {j : m ≤ (1+ε)^j ≤ 2k·m}, recycling
expired levels, and (b) decide which levels admit each arrival. Done
naively that is a separate singleton-gains pass plus B×L `gains` calls;
this kernel does the whole batch in ONE dispatch:

    1. build the (N, B) ground×arrival matrix ON-CHIP via the rule's
       pairwise op (`rules.matrix_block` — one MXU matmul for the feature
       rules, a bitmap transpose for coverage, N = W words) — it serves
       BOTH the singleton gains and the admission loop;
    2. re-anchor: (1, B) raw singleton gains vs the empty-solution row,
       then the shared `ref.sieve_reanchor` window slide (expired levels
       reset to row0 in place);
    3. `fori_loop` over the B arrivals IN ORDER (admission is sequential:
       an admitted arrival changes the state later arrivals see). Each
       iteration computes the (L, 1) raw gains of the arrival against
       every level's state row — `rules.level_gains`, the level-batched
       transpose of `rules.partial_gains` — and applies the shared
       `ref.sieve_admit` threshold rule plus the rule's fold;
    4. emit updated (L, N) rows, raw values, counts, exponents, m, the
       (L, 1) expired mask, and the (L, B) 0/1 admit matrix (the host
       wrapper resets expired id/payload slots and scatters admits).

The admission and re-anchor rules are IMPORTED from kernels/ref.py (pure
jnp) and the objective math from kernels/rules.py, so kernel and oracle
semantics cannot drift; parity is asserted bit-identically under
interpret mode. Everything lives in VMEM for the whole dispatch; the
plans.stream_plan gate falls back to the jnp oracle (ref.stream_sieve)
when the working set exceeds the VMEM budget.

Gains/values/v-grid are RAW part sums — callers normalize by the valid
ground count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rules as R
from repro.kernels.rules import KernelRule, level_gains  # noqa: F401
from repro.kernels.ref import sieve_admit, sieve_reanchor

F32 = jnp.float32


def _body(g, batch_ref, rows_ref, row0_ref, values_ref,
          counts_ref, expos_ref, m_ref, bvalid_ref, cost_refs, out_refs,
          arrivals_ref, *, k: int, eps_log: float, rule: KernelRule):
    bt = batch_ref[...]                                   # (B, D) | (B, W)
    mat = R.matrix_block(g, bt, rule)                     # (N, B), on-chip
    # arrival-major copy: the loop reads arrival i's column as row i
    # (Mosaic slices sublanes, not lanes, at a traced offset)
    arrivals_ref[...] = mat.T                             # (B, N)
    row0 = row0_ref[...]                                  # (1, N)
    bv = bvalid_ref[...].astype(F32)                      # (1, B)
    nb = bt.shape[0]
    (rowsout_ref, valout_ref, cntout_ref, admit_ref, expoout_ref,
     mout_ref, expired_ref) = out_refs[:7]

    # re-anchor on this batch's singleton gains (vs the empty solution)
    singletons = R.level_gains(row0, mat.T, rule).T       # (1, B)
    rows, values, counts, expos, m_new, expired = sieve_reanchor(
        singletons, bv, rows_ref[...], row0,
        values_ref[...].astype(F32), counts_ref[...],
        expos_ref[...], m_ref[0, 0], eps_log)
    vgrid = jnp.exp(expos.astype(F32) * eps_log)          # (L, 1)
    cost_mode = cost_refs is not None
    if cost_mode:
        costs_ref, spent_ref, budget_ref = cost_refs
        costs = costs_ref[...].astype(F32)                # (1, B)
        budget = budget_ref[0, 0]
        # expired levels restart with an empty (zero-cost) solution
        spent = jnp.where(expired, 0.0, spent_ref[...].astype(F32))
    else:
        costs = budget = None
        spent = jnp.zeros_like(vgrid)

    def body(i, carry):
        rows, values, counts, spent, admits = carry
        col = arrivals_ref[pl.ds(i, 1), :]                # (1, N)
        gains = R.level_gains(rows, col, rule)            # (L, 1)
        ok = jnp.sum(R.lane_pick(bv, i)) > 0
        if cost_mode:
            ci = jnp.sum(R.lane_pick(costs, i))
            admit = sieve_admit(gains, values, counts, vgrid, ok, k,
                                cost=ci, spent=spent, budget=budget)
            spent = spent + jnp.where(admit, ci, 0.0)
        else:
            admit = sieve_admit(gains, values, counts, vgrid, ok, k)
        upd = R.fold_cols(rows, col, rule)
        rows = jnp.where(admit, upd, rows)
        values = values + jnp.where(admit, gains, 0.0)
        counts = counts + admit.astype(jnp.int32)
        bcols = jax.lax.broadcasted_iota(jnp.int32, admits.shape, 1)
        admits = jnp.where(bcols == i, admit.astype(F32), admits)
        return rows, values, counts, spent, admits

    carry = (rows, values, counts, spent,
             jnp.zeros(admit_ref.shape, F32))
    rows, values, counts, spent, admits = jax.lax.fori_loop(0, nb, body,
                                                            carry)
    rowsout_ref[...] = rows
    valout_ref[...] = values
    cntout_ref[...] = counts
    admit_ref[...] = admits
    expoout_ref[...] = expos
    mout_ref[...] = jnp.broadcast_to(m_new, mout_ref.shape)
    expired_ref[...] = expired.astype(F32)
    if cost_mode:
        out_refs[7][...] = spent


def _kernel(ground_ref, *refs, k, eps_log, rule, quant, has_cost):
    refs = list(refs)
    if quant:
        # int8 ground features (stream_plan dtype='int8'): the resident
        # evaluation set is stored at 1 byte/entry and rescaled against
        # its (1, N) per-row scales on-chip before the shared pairwise op
        # (arrivals stay f32)
        g = R.dequant(ground_ref[...], refs.pop(0)[...])
    else:
        g = ground_ref[...]
    main, rest = refs[:8], refs[8:]
    cost_refs = None
    if has_cost:
        cost_refs, rest = tuple(rest[:3]), rest[3:]
    *outs, arrivals_ref = rest
    _body(g, *main, cost_refs, tuple(outs), arrivals_ref, k=k,
          eps_log=eps_log, rule=rule)


@functools.partial(jax.jit, static_argnames=("k", "eps_log", "rule",
                                             "interpret",
                                             "vmem_limit_bytes"))
def stream_filter_pallas(ground: jax.Array, batch: jax.Array,
                         rows: jax.Array, row0: jax.Array,
                         values: jax.Array, counts: jax.Array,
                         expos: jax.Array, m_max: jax.Array,
                         bvalid: jax.Array, k: int, eps_log: float,
                         rule: KernelRule, interpret: bool = False,
                         gscale=None, costs=None, spent=None,
                         budget=None, vmem_limit_bytes: int = 0):
    """Feature rules: ground (N, D), batch (B, D) arrivals. Bitmap rules:
    ground is an ignored placeholder and batch the (B, W) arrival bitmaps
    (N = W). rows: (L, N) level states in the rule's row dtype, row0:
    (1, N) empty-solution row, values: (L, 1) f32 raw, counts / expos:
    (L, 1) i32, m_max: (1, 1) f32, bvalid: (1, B) 0/1 f32. L must be a
    sublane multiple (SieveStreamer rounds its level count up); N/B/D
    padded to 128 lanes by the ops.py wrapper (arrival pads carry
    bvalid = 0). When
    `gscale` (1, N) f32 is given, `ground` is int8 per-row-quantized
    storage and the kernel rescales it to f32 on-chip.

    ``costs`` (1, B) f32 / ``spent`` (L, 1) f32 / ``budget`` (1, 1) f32
    (all three or none) switch admission to the knapsack cost-ratio rule
    — the per-level spent track rides the same sequential loop, so the
    batch still costs ONE dispatch — and append spent (L, 1) f32 to the
    outputs. vmem_limit_bytes: Mosaic's scoped-VMEM limit
    (plans.vmem_limit of plans.stream_need).

    Returns (rows (L, N), values (L, 1), counts (L, 1) i32, admits
    (L, B) f32 0/1, expos (L, 1) i32, m_new (1, 1) f32, expired (L, 1)
    f32 0/1[, spent (L, 1) f32]) — ONE dispatch per arrival batch,
    re-anchor included.
    """
    nb = batch.shape[0]
    l, n = rows.shape
    if rule.is_bitmap:
        assert batch.shape[1] == n, (batch.shape, n)
    else:
        assert ground.shape == (n, batch.shape[1])
    assert row0.shape == (1, n) and values.shape == (l, 1)
    assert counts.shape == (l, 1) and expos.shape == (l, 1)
    assert m_max.shape == (1, 1) and bvalid.shape == (1, nb)
    operands = [ground, batch, rows, row0, values, counts, expos, m_max,
                bvalid]
    if gscale is not None:
        assert gscale.shape == (1, ground.shape[0]), gscale.shape
        operands.insert(1, gscale)
    has_cost = costs is not None
    if has_cost:
        assert costs.shape == (1, nb) and spent.shape == (l, 1)
        assert budget.shape == (1, 1)
        operands += [costs, spent, budget]
    out_shape = [
        jax.ShapeDtypeStruct((l, n), rule.dtype),
        jax.ShapeDtypeStruct((l, 1), F32),
        jax.ShapeDtypeStruct((l, 1), jnp.int32),
        jax.ShapeDtypeStruct((l, nb), F32),
        jax.ShapeDtypeStruct((l, 1), jnp.int32),
        jax.ShapeDtypeStruct((1, 1), F32),
        jax.ShapeDtypeStruct((l, 1), F32),
    ]
    if has_cost:
        out_shape.append(jax.ShapeDtypeStruct((l, 1), F32))
    return pl.pallas_call(
        functools.partial(_kernel, k=k, eps_log=eps_log, rule=rule,
                          quant=gscale is not None, has_cost=has_cost),
        name="stream_filter_pallas",
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((nb, n), rule.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes or None),
        interpret=interpret,
    )(*operands)
