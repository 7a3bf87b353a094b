"""Sharded cross-device leaf selection — tiled pairwise over a mesh axis.

The cached-matrix tiers (kernels/plans.py) and even the per-step path
assume ONE device holds the whole leaf pool: the (n, d) features, the
(n,) state row, and — for the cached tiers — the (n, c) interaction
matrix. The paper's memory-capped regime (§6.1/§6.4) is exactly where
that stops working. This module is the `sharded` engine tier: the ground
set of one greedy is SPLIT over the `p` devices of a mesh axis, and the
per-step candidate gains are evaluated by streaming candidate tiles
through the SAME rule-parameterized gains kernel every other tier uses
(ops.gains → kernels/pairwise.gains_pallas), exchanging only fold
reductions — no device ever materializes the (n, c) matrix or the full
feature pool.

Per selection step, for each of the ``n_s / tile_c`` candidate tiles:

  1. ``all_gather`` over the shard axis of each lane's (tile_c, d)
     candidate slice and its (tile_c,) valid-∧-unselected mask — the
     (p·tile_c, d) visible tile; every lane sees the same candidates.
  2. ONE gains-kernel dispatch of the tile against the lane's LOCAL
     (n_s, d) ground shard and (n_s,) state row → (p·tile_c,) partial
     gain sums.
  3. ``psum`` of the partials over the shard axis — each lane now holds
     the tile's GLOBAL raw gains, identical to what a single device
     computing over the whole ground set would reduce.
  4. A running first-max argmax in GLOBAL pool order (the pool is the
     lane-major concatenation of the shards), so ties break exactly like
     solo ``jnp.argmax``.

After the tiles, the winner's (d,) payload column is broadcast with one
owner-masked ``psum`` (the `_broadcast_from_root` trick) and folded into
every lane's local state row via the shared rule primitives — the "k
winner columns" of the exchange protocol. Per-device memory is
O(n_s·d + p·tile_c·d); per-step exchange is O(p·tile_c + d) floats.

Selections are BIT-IDENTICAL to solo ``greedy(engine='step')`` up to
float summation order: the accept rule (``isfinite ∧ gain > 0``), the
n_eff normalization, the first-max tie-break in pool order, and the
evals accounting all replicate core/greedy.py exactly; the only
difference is that raw gains are a psum of p partial sums instead of one
n-term reduction (tests use margin-robust pools, as the int8 tiers do).

Feature rules only: sharding a bitmap rule's ground axis would shard the
universe WORDS — the payload columns themselves — which the tile
protocol cannot stream. `plans.shard_plan` therefore never admits bitmap
rules; coverage-style objectives stay on the solo tiers.

Dispatch accounting (measured by tests/test_shard_scale.py on the
interpret backend): exactly ONE gains dispatch per (step, tile) —
``k · n_s / tile_c`` per leaf greedy, and nothing else dispatches (the
winner fold is pure jnp).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops as kernel_ops
from repro.kernels import plans
from repro.kernels import rules as R
from repro.runtime import flags, telemetry

F32 = jnp.float32
_BIG_IDX = jnp.int32(2 ** 30)


def resolve_tile_c(rule: R.KernelRule, n: int, d: int, lanes: int,
                   tile_c: int = 0, backend: Optional[str] = None) -> int:
    """The candidate tile size one lane contributes per exchange round:
    the caller's explicit choice, else the budget-gated `plans.shard_plan`
    pick, else the minimal tile (the gate refusing everything means the
    caller is already past the modeled budget — run anyway, smallest
    working set)."""
    if tile_c:
        return int(tile_c)
    sp = plans.shard_plan(rule, n, d, lanes, backend=backend)
    if sp is not None:
        return int(sp["tile_c"])
    return plans.SHARD_TILE_MIN


def pad_pool(ids: jax.Array, payloads: jax.Array, valid: jax.Array,
             lanes: int, tile_c: int
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pad the flat pool so every lane's shard is a whole number of
    candidate tiles: n → lanes · ceil(n / lanes / tile_c) · tile_c.
    Padding rows are invalid (id −1, zero payload) and can never win a
    step, so selections match the unpadded pool."""
    n = ids.shape[0]
    n_s = -(-(-(-n // lanes)) // tile_c) * tile_c
    pad = n_s * lanes - n
    if pad == 0:
        return ids, payloads, valid
    return (jnp.concatenate([ids, jnp.full((pad,), -1, ids.dtype)]),
            jnp.concatenate([payloads,
                             jnp.zeros((pad,) + payloads.shape[1:],
                                       payloads.dtype)]),
            jnp.concatenate([valid, jnp.zeros((pad,), bool)]))


def shard_greedy(objective, ids: jax.Array, payloads: jax.Array,
                 valid: jax.Array, k: int, *, axis: str, lanes: int,
                 tile_c: int = 0):
    """Lane-local body of the sharded greedy — call INSIDE shard_map (or
    nested vmap with ``axis`` as an axis_name) with ids/payloads/valid
    being THIS lane's (n_s, …) shard of the pool. Returns the GLOBAL
    Solution, replicated (bit-identically) across the shard axis.

    ``lanes`` is the static size of ``axis``; n_s must divide by the
    resolved ``tile_c`` (drivers pad via `pad_pool`).
    """
    from repro.core.greedy import Solution      # lazy: core imports kernels

    rule = objective.rule
    assert not rule.is_bitmap, \
        "sharded tier is feature-rule only (plans.shard_plan gates this)"
    n_s, d = payloads.shape
    tile_c = resolve_tile_c(rule, n_s * lanes, d, lanes, tile_c,
                            backend=objective.backend)
    tile_c = min(tile_c, n_s)
    while n_s % tile_c:          # shrink to a divisor of the lane shard;
        tile_c //= 2             # tile width never changes selections
    ntiles = n_s // tile_c
    lane = lax.axis_index(axis).astype(jnp.int32)

    # empty-solution state, with the GLOBAL normalizers of
    # RuleObjective.init_state rebuilt from psums of the lane-local terms
    row0 = R.empty_row(payloads, valid, rule)
    n_eff = jnp.maximum(lax.psum(jnp.sum(valid.astype(F32)), axis), 1.0)
    base = (lax.psum(jnp.sum(row0), axis) / n_eff
            if rule.fold == "min" else jnp.zeros((), F32))
    gather = lambda x: lax.all_gather(x, axis, axis=0, tiled=True)
    ones = jnp.ones((lanes * tile_c,), bool)
    src = lax.broadcasted_iota(jnp.int32, (lanes * tile_c,), 0)

    def step(carry, _):
        row, selected, evals = carry
        cand_mask = valid & jnp.logical_not(selected)
        n_evals = lax.psum(jnp.sum(cand_mask.astype(jnp.int32)), axis)
        best_gain, best_gidx = -jnp.inf, _BIG_IDX
        for t in range(ntiles):
            sl = slice(t * tile_c, (t + 1) * tile_c)
            tile_pay = gather(payloads[sl])              # (p·tc, d)
            tile_mask = gather(cand_mask[sl])            # (p·tc,)
            raw = kernel_ops.gains(payloads, row, tile_pay, ones, rule,
                                   backend=objective.backend)
            raw = lax.psum(raw, axis)
            g = jnp.where(tile_mask, raw / n_eff, -jnp.inf)
            # global pool index of each gathered candidate (lane-major)
            gidx = (src // tile_c) * n_s + t * tile_c + src % tile_c
            mx = jnp.max(g)
            first = jnp.min(jnp.where(g == mx, gidx, _BIG_IDX))
            better = (mx > best_gain) | ((mx == best_gain)
                                         & (first < best_gidx))
            best_gain = jnp.where(better, mx, best_gain)
            best_gidx = jnp.where(better, first, best_gidx)
        # the k-winner-columns exchange: owner-masked psum of the winner's
        # payload (and id) — one (d,) broadcast per accepted step
        local_i = best_gidx - lane * n_s
        own = (local_i >= 0) & (local_i < n_s)
        safe = jnp.clip(local_i, 0, n_s - 1)
        wpay = lax.psum(jnp.where(own, payloads[safe], 0.0), axis)
        wid = lax.psum(jnp.where(own, ids[safe],
                                 jnp.zeros((), ids.dtype)), axis)
        accept = jnp.isfinite(best_gain) & (best_gain > 0)
        new_row = R.update_row(payloads, row, wpay, rule)
        row = jnp.where(accept, new_row, row)
        selected = selected | (jax.nn.one_hot(safe, n_s, dtype=jnp.bool_)
                               & own & accept)
        out = (jnp.where(accept, wid, -1),
               jnp.where(accept, wpay, jnp.zeros_like(wpay)),
               accept)
        return (row, selected, evals + n_evals), out

    carry0 = (row0, jnp.zeros((n_s,), jnp.bool_), jnp.zeros((), jnp.int32))
    with telemetry.repeat(k):
        (row, _, evals), (out_ids, out_pay, out_valid) = lax.scan(
            step, carry0, None, length=k, unroll=flags.scan_unroll())
    tot = lax.psum(jnp.sum(jnp.where(valid, row, 0.0)), axis)
    value = base - tot / n_eff if rule.fold == "min" else tot / n_eff
    return Solution(out_ids, out_pay, out_valid, value, evals)


def shard_greedy_distributed(objective, ids: jax.Array,
                             payloads: jax.Array, valid: jax.Array, k: int,
                             mesh: Mesh, shard_axis: str = "shard",
                             tile_c: int = 0):
    """One sharded greedy over the devices of ``mesh.shape[shard_axis]``:
    the pool's leading dim is sharded over that axis, every device holds
    1/p of the features, and the replicated global Solution comes back."""
    lanes = mesh.shape[shard_axis]
    tile_c = resolve_tile_c(objective.rule, ids.shape[0],
                            payloads.shape[1], lanes, tile_c,
                            backend=objective.backend)
    ids, payloads, valid = pad_pool(ids, payloads, valid, lanes, tile_c)

    def body(i, p, v):
        return shard_greedy(objective, i, p, v, k, axis=shard_axis,
                            lanes=lanes, tile_c=tile_c)

    spec = P(shard_axis)
    from repro.core.greedy import Solution      # noqa: F811 (pytree specs)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=Solution(P(), P(), P(), P(), P()),
                         check_vma=False)(ids, payloads, valid)


def shard_greedy_sim(objective, ids: jax.Array, payloads: jax.Array,
                     valid: jax.Array, k: int, lanes: int,
                     tile_c: int = 0, axis: str = "shard"):
    """Single-device simulation of `shard_greedy_distributed`: the lanes
    become a vmapped axis with the SAME axis_name, so psum/all_gather run
    over the batch dim — bit-identical lane-local math on one CPU (the
    core.simulate / LevelDispatcher pattern). Used by tier-1 tests."""
    tile_c = resolve_tile_c(objective.rule, ids.shape[0],
                            payloads.shape[1], lanes, tile_c,
                            backend=objective.backend)
    ids, payloads, valid = pad_pool(ids, payloads, valid, lanes, tile_c)
    n_s = ids.shape[0] // lanes
    shp = lambda x: x.reshape((lanes, n_s) + x.shape[1:])

    def body(i, p, v):
        return shard_greedy(objective, i, p, v, k, axis=axis, lanes=lanes,
                            tile_c=tile_c)

    out = jax.vmap(body, axis_name=axis)(shp(ids), shp(payloads),
                                         shp(valid))
    return jax.tree.map(lambda x: x[0], out)
