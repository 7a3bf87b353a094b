"""Pure-jnp oracles for every Pallas kernel, rule-parameterized.

These are the semantic ground truth: each kernel's test sweeps shapes and
rules and asserts allclose against the function here. They are also the
execution backend on CPU (ops.py dispatches: compiled Pallas on TPU,
interpret-mode Pallas in kernel tests, jnp reference everywhere else).

All objective math comes from the shared rule primitives
(kernels/rules.py) — the SAME functions the kernel bodies trace — so
oracle and kernel semantics cannot drift; only the tiling/accumulation
structure differs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import rules as R
from repro.kernels.rules import KernelRule

F32 = jnp.float32


def pairwise(ground, cands, rule: KernelRule) -> jax.Array:
    """Full logical cached matrix for any rule: feature rules do the
    pairwise compute; bitmap rules just transpose the payloads (the
    candidate bitmaps ARE the matrix columns)."""
    if rule.is_bitmap:
        return cands.T
    return R.matrix_block(ground, cands, rule)


def gains(ground, row, cands, cand_valid, rule: KernelRule) -> jax.Array:
    """Per-step marginal gains oracle: RAW part-sums (no normalization),
    −inf at invalid candidates.

    Feature rules: ground (N, D), row (N,) state; bitmap rules: ground is
    ignored, row (W,) covered words, cands (C, W)."""
    mat = pairwise(ground, cands, rule)                  # (N|W, C)
    raw = jnp.sum(R.gain_part(row[:, None], mat, rule), axis=0)
    return jnp.where(cand_valid, raw, -jnp.inf)


def fused_step(mat: jax.Array, row: jax.Array, mask: jax.Array,
               prev: jax.Array, rule: KernelRule):
    """Oracle for the fused selection step over a cached (N, C) matrix.

    Applies the deferred previous-winner column update to the state row,
    then computes the masked gain sums and their argmax. Returns
    (new_row, best () i32, best_gain () f32); best_gain is the RAW part
    sum (no 1/N)."""
    col = jax.lax.dynamic_slice_in_dim(mat, jnp.maximum(prev, 0), 1,
                                       axis=1)[:, 0]
    new_row = R.fold_winner(row, col, prev, rule)
    part = R.gain_part(new_row[:, None], mat, rule)
    gains_ = jnp.where(mask > 0, jnp.sum(part, axis=0), -jnp.inf)
    best = jnp.argmax(gains_).astype(jnp.int32)
    return new_row, best, gains_[best]


def greedy_loop(mat: jax.Array, row: jax.Array, mask: jax.Array, k: int,
                rule: KernelRule, kq=None):
    """Oracle for the whole-greedy megakernel (kernels/greedy_loop.py): all
    k selection steps over a cached (N, C) matrix, including the per-step
    accept rule (gain > 0), mask update, and the final winner-column flush.

    ``kq`` (traced scalar, default k) is the per-invocation step budget:
    steps ≥ kq are masked — state and mask freeze, bests/gains emit
    −1/0 — so a k-padded call matches a solo k=kq run bit-for-bit on the
    first kq steps (the serving engine's heterogeneous-k batching; same
    semantics as the resident kernel's ctl operand).

    Returns (final_row (N,), bests (k,) i32 with −1 for rejected steps,
    gains (k,) f32 raw part sums)."""
    c = mat.shape[1]
    cols = jnp.arange(c, dtype=jnp.int32)
    kq_ = jnp.asarray(k if kq is None else kq, jnp.int32)

    def step(carry, s):
        row, mask, prev = carry
        new_row, best, gain = fused_step(mat, row, mask, prev, rule)
        accept = jnp.isfinite(gain) & (gain > 0) & (s < kq_)
        best_i = jnp.where(accept, best, jnp.int32(-1))
        mask = jnp.where(accept & (cols == best), 0.0, mask)
        return (new_row, mask, best_i), (best_i,
                                         jnp.where(s < kq_, gain, 0.0))

    (row, _, prev), (bests, gains_) = jax.lax.scan(
        step, (row, mask.astype(F32), jnp.int32(-1)),
        jnp.arange(k, dtype=jnp.int32))
    col = jax.lax.dynamic_slice_in_dim(mat, jnp.maximum(prev, 0), 1,
                                       axis=1)[:, 0]
    return R.fold_winner(row, col, prev, rule), bests, gains_


def sieve_admit(gains_, values, counts, vgrid, ok, k: int,
                cost=None, spent=None, budget=None):
    """Sieve-Streaming admission rule (Badanidiyuru et al. 2014), shared
    by the Pallas stream-filter kernel and the jnp oracle so the
    threshold semantics can never drift between them: admit when |S_l| < k
    and the raw gain clears (v_l/2 − f(S_l))/(k − |S_l|). The `gain > 0`
    conjunct only skips zero-gain fills after f(S_l) has already reached
    v_l/2 (threshold ≤ 0), which never lowers the level's final value.
    Shapes broadcast; all raw units.

    With ``cost``/``spent``/``budget`` (the knapsack streaming variant,
    DESIGN §Constraints) admission switches to COST-RATIO thresholding:
    admit when the gain DENSITY gain/c(e) clears the per-cost-unit
    residual threshold (v_l/2 − f(S_l))/(B − c(S_l)) and the element fits
    the remaining budget — compared multiplied-out (gain ≥ thresh·c(e))
    so the kernel never divides by a per-arrival cost. cost: per-arrival
    scalar ≥ 0; spent: (L, 1) per-level c(S_l); budget: () B."""
    if cost is None:
        remaining = jnp.maximum(k - counts, 1).astype(F32)
        thresh = (vgrid * 0.5 - values) / remaining
        return ok & (counts < k) & (gains_ >= thresh) & (gains_ > 0.0)
    room = jnp.maximum(budget - spent, 0.0)
    thresh = (vgrid * 0.5 - values) / jnp.maximum(room, 1e-30)
    fits = (cost > 0.0) & (cost <= room)
    return (ok & (counts < k) & fits & (gains_ >= thresh * cost)
            & (gains_ > 0.0))


def sieve_reanchor(singletons, bvalid, rows, row0, values, counts, expos,
                   m_max, eps_log: float):
    """Slide the sieve exponent window up to the new max singleton gain
    (DESIGN §Streaming), recycling expired levels (v < m ⇒ provably not
    OPT's sieve) as fresh sieves at the exponents above the old window
    top — the classic create/discard at batch granularity, fixed-shape.
    Shared semantics for the kernel and oracles; all 2D operands:
    singletons/bvalid (1, B), rows (L, N|W), row0 (1, N|W) fresh level
    state, values (L, 1), counts (L, 1) i32, expos (L, 1) i32, m_max ().

    Returns (rows, values, counts, expos, m_new (), expired (L, 1))."""
    l = expos.shape[0]
    m_new = jnp.maximum(m_max, jnp.max(jnp.where(bvalid > 0, singletons,
                                                 0.0)))
    low = jnp.where(
        m_new > 0.0,
        jnp.ceil(jnp.log(jnp.maximum(m_new, 1e-30))
                 / eps_log).astype(jnp.int32),
        jnp.min(expos))
    # first anchor: every slot is still empty (an admitted element would
    # have set m_max > 0), so the whole window may jump — also DOWN, for
    # data whose raw gains are < 1
    first = (m_max == 0.0) & (m_new > 0.0)
    lidx = jax.lax.broadcasted_iota(jnp.int32, (l, 1), 0)
    base = jnp.where(first, low + lidx, expos)
    expired = base < low
    old_high = jnp.max(base)
    # distinct exponents ⇒ expired slots rank uniquely; refill the missing
    # window exponents ascending (max() covers the full-window jump where
    # even the old top fell below the new low)
    # (transposing the int32 exponents, not the bool mask: Mosaic has no
    # transpose of a mask)
    base_t = base.T
    rank = jnp.sum((base_t < low) & (base_t < base), axis=1, keepdims=True)
    expos = jnp.where(expired, jnp.maximum(old_high + 1, low) + rank, base)
    rows = jnp.where(expired, jnp.broadcast_to(row0, rows.shape), rows)
    values = jnp.where(expired, 0.0, values)
    counts = jnp.where(expired, 0, counts)
    return rows, values, counts, expos, m_new, expired


def stream_sieve(mat: jax.Array, row0: jax.Array, rows: jax.Array,
                 values: jax.Array, counts: jax.Array, expos: jax.Array,
                 m_max: jax.Array, bvalid: jax.Array, k: int,
                 eps_log: float, rule: KernelRule,
                 costs=None, spent=None, budget=None):
    """Oracle for the batched sieve-streaming kernel
    (kernels/stream_filter.py, DESIGN §Streaming): re-anchor the exponent
    window on the batch's singleton gains, then admit arrivals IN ORDER
    (admitting arrival b changes the state arrival b+1 sees — the
    sequential semantics the kernel must reproduce bit-identically).

    mat: (N, B) ground×arrival matrix (W words × B bitmaps for 'bits');
    row0: (N,) empty-solution state row; rows: (L, N) per-level state;
    values: (L,) RAW f(S_v) (part-sum/popcount units, no 1/N); counts:
    (L,) i32; expos: (L,) i32 grid exponents (v_l = e^(expos·eps_log));
    m_max: () running max singleton.

    ``costs``/``spent``/``budget`` switch admission to the knapsack
    cost-ratio rule (see `sieve_admit`): costs (B,) per-arrival, spent
    (L,) per-level c(S_v) — expired levels reset it with the rest of
    their state — budget () B. The spent track rides the same sequential
    loop, so the kernel still runs ONE dispatch per batch.

    Returns (rows (L, N), values (L,), counts (L,), admits (L, B) f32
    0/1, expos (L,), m_new (), expired (L,) f32 0/1), plus spent (L,)
    as an extra trailing output in cost mode.
    """
    l, b = rows.shape[0], mat.shape[1]
    part0 = R.gain_part(row0[:, None], mat, rule)          # (N, B)
    singletons = jnp.sum(part0, axis=0, keepdims=True)     # (1, B)
    rows, values, counts, expos, m_new, expired = sieve_reanchor(
        singletons, bvalid.astype(F32).reshape(1, b), rows,
        row0.reshape(1, -1), values.astype(F32).reshape(l, 1),
        counts.reshape(l, 1), expos.reshape(l, 1).astype(jnp.int32),
        m_max.astype(F32), eps_log)
    vgrid = jnp.exp(expos.astype(F32) * eps_log)           # (L, 1)
    cost_mode = costs is not None
    if cost_mode:
        spent = jnp.where(expired, 0.0,
                          spent.astype(F32).reshape(l, 1))
        budget = jnp.asarray(budget, F32)
    else:
        spent = jnp.zeros((l, 1), F32)

    def body(i, carry):
        rows, values, counts, spent, admits = carry
        col = jax.lax.dynamic_slice_in_dim(mat, i, 1, axis=1).T  # (1, N)
        gains_ = R.level_gains(rows, col, rule)                  # (L, 1)
        ok = jax.lax.dynamic_index_in_dim(bvalid, i, keepdims=False) > 0
        if cost_mode:
            ci = jax.lax.dynamic_index_in_dim(costs.astype(F32), i,
                                              keepdims=False)
            admit = sieve_admit(gains_, values, counts, vgrid, ok, k,
                                cost=ci, spent=spent, budget=budget)
            spent = spent + jnp.where(admit, ci, 0.0)
        else:
            admit = sieve_admit(gains_, values, counts, vgrid, ok, k)
        upd = R.fold_cols(rows, col, rule)
        rows = jnp.where(admit, upd, rows)
        values = values + jnp.where(admit, gains_, 0.0)
        counts = counts + admit.astype(jnp.int32)
        admits = jax.lax.dynamic_update_slice_in_dim(
            admits, admit.astype(F32), i, axis=1)
        return rows, values, counts, spent, admits

    rows, values, counts, spent, admits = jax.lax.fori_loop(
        0, b, body, (rows, values, counts, spent, jnp.zeros((l, b), F32)))
    out = (rows, values[:, 0], counts[:, 0], admits, expos[:, 0],
           m_new, expired.astype(F32)[:, 0])
    return out + (spent[:, 0],) if cost_mode else out
