"""Declarative kernel rules — the ONE place objective math lives.

Every submodular objective in this repo reduces to the same selection
algebra over a ground×candidate interaction matrix M and a per-ground-row
state vector r:

    matrix    M[x, c]  = pairwise(x, c)         'dist' | 'dot' | 'bits'
    state     r_x      = fold_{v ∈ S} M[x, v]   'min' | 'max' | 'or' | 'satsum'
    gain(c|S)          = Σ_x part(r_x, M[x, c])  the objective's marginal

A `KernelRule` captures exactly that triple (plus the row dtype/pad and
any static parameters like the saturation cap), and EVERY engine tier —
per-step gains kernel, fused cached-matrix step, whole-greedy megakernel
(streaming and resident), sieve stream-filter, and the jnp oracles —
consumes the rule through the shared primitives below instead of carrying
per-objective kernels or mode strings. Adding an objective therefore
means registering one rule (and, only for a genuinely new fold algebra,
one branch in `gain_part`/`fold_cols`); no new kernel files.

Built-in rules (DESIGN §Objective protocol):

    name        pairwise  fold     row        part(r, m)
    ---------   --------  ------   --------   --------------------------
    kmedoid     dist      min      f32 mind   relu(r − m)
    facility    dot       max      f32 curmax relu(m − r)
    coverage    bits      or       u32 words  popcount(m & ~r)
    satcover    dot       satsum   f32 cursum min(relu(m), cap − r)
    graphcut    dot       sum      f32 cursum Δh(r; m), h(t) = t − t²/2cap
    mmr         dot       sum      f32 cursum λ·relu(m) + (1−λ)·Δh(r; m)

The 'sum' fold keeps the UNCAPPED running similarity sum per ground row
and scores it through the λ-weighted potential W(r) = λ·r + (1−λ)·h(r∧cap)
with the concave quadratic h(t) = t − t²/(2·cap) clipped at its vertex
t = cap. The modular λ·r term is pure relevance; h rewards coverage but
charges a quadratic redundancy penalty (the graph-cut intra-similarity
term), so λ trades relevance against diversity exactly like MMR. Both
terms are exact potentials, so gain ≡ Δvalue holds bit-for-bit on every
tier, and W is concave nondecreasing over a nonnegative modular sum —
monotone submodular.

'bits' needs no pairwise compute at all: the candidate payloads ARE the
matrix columns (M[:, c] = bitmap of c, transposed to words-major), which
is why coverage rides every cached-matrix tier for free — `prepare` is a
transpose, not a kernel dispatch.

All primitives are pure jnp on values (not refs), so they trace inside
Pallas kernel bodies and in the oracles identically — semantics cannot
drift between backends.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
LANES = 128

# facility/satsum pad sentinel for invalid ground rows (≈ f32 max; keeps
# the per-element gain part at exactly 0)
BIG = 3.0e38

_NEG_INF = float("-inf")


@dataclasses.dataclass(frozen=True)
class KernelRule:
    """Static, hashable spec of one objective's kernel math. Frozen so it
    can be a jit/pallas static argument: equal rules hit the same compile
    cache entry."""
    name: str            # registry key (and the jit cache key)
    pairwise: str        # 'dist' | 'dot' | 'bits'
    fold: str            # 'min' | 'max' | 'or' | 'satsum' | 'sum'
    row_dtype: str       # 'float32' | 'uint32'
    row_pad: float       # pad value for ground-axis padding (0 gain)
    cap: float = 0.0     # saturation cap (satsum/sum folds only)
    lam: float = 0.0     # relevance weight λ ('sum' fold only)

    @property
    def dtype(self):
        return jnp.dtype(self.row_dtype)

    @property
    def is_bitmap(self) -> bool:
        return self.pairwise == "bits"

    def pad_row(self, dtype=None):
        return jnp.asarray(self.row_pad, dtype or self.dtype)


# ---------------------------------------------------------------------------
# built-in rules + registry
# ---------------------------------------------------------------------------

DIST_MIN = KernelRule("kmedoid", "dist", "min", "float32", 0.0)
DOT_MAX = KernelRule("facility", "dot", "max", "float32", BIG)
BITS_OR = KernelRule("coverage", "bits", "or", "uint32", 0.0)

_RULES = {r.name: r for r in (DIST_MIN, DOT_MAX, BITS_OR)}


@functools.lru_cache(maxsize=None)
def sat_sum(cap: float, name: str = "satcover") -> KernelRule:
    """Saturated-sum rule family: f(S) = Σ_x min(cap, Σ_{v∈S} relu⟨x, v⟩)
    — weighted saturated coverage over embedding similarities (Lin &
    Bilmes-style), monotone submodular because min(cap, ·) is concave
    nondecreasing over a nonnegative modular sum. Invalid ground rows pad
    at `cap` so their per-element part is exactly 0. lru_cached so equal
    caps share one jit compile-cache identity."""
    assert cap > 0.0, "satsum needs a positive saturation cap"
    return KernelRule(name, "dot", "satsum", "float32", float(cap),
                      cap=float(cap))


@functools.lru_cache(maxsize=None)
def graph_cut(alpha: float, name: str = "graphcut") -> KernelRule:
    """Graph-cut rule family: f(S) = Σ_x h(t_x ∧ cap) with the per-row
    running similarity t_x = Σ_{v∈S} relu⟨x, v⟩ and the concave quadratic
    h(t) = t − α·t²/2 (cap = 1/α, h's vertex) — the coverage term minus
    the quadratic redundancy penalty of the classic graph-cut objective,
    clipped at the vertex so the potential stays monotone. λ = 0: pure
    diversity-aware coverage. lru_cached so equal α share one jit
    compile-cache identity."""
    assert alpha > 0.0, "graph-cut needs a positive redundancy weight"
    return KernelRule(name, "dot", "sum", "float32", BIG,
                      cap=1.0 / float(alpha))


@functools.lru_cache(maxsize=None)
def mmr(lam: float, theta: float, name: str = "mmr") -> KernelRule:
    """MMR-style relevance–diversity rule family:
    f(S) = Σ_x [λ·t_x + (1−λ)·h(t_x ∧ θ)], t_x the running relu-similarity
    sum and h(t) = t − t²/(2θ) the saturating coverage term. λ → 1 is the
    pure modular relevance sum, λ → 0 pure graph-cut-style diversity —
    the MMR tradeoff as one exact potential (gain ≡ Δvalue on every
    tier). The RAG retrieval-dedup serving workload rides this spec."""
    assert 0.0 <= lam <= 1.0, "MMR λ must lie in [0, 1]"
    assert theta > 0.0, "MMR needs a positive saturation cap θ"
    return KernelRule(name, "dot", "sum", "float32", BIG,
                      cap=float(theta), lam=float(lam))


def get(name: str) -> KernelRule:
    """Look up a built-in rule by objective name."""
    return _RULES[name]


# ---------------------------------------------------------------------------
# the shared selection algebra
# ---------------------------------------------------------------------------


def gain_part(row, m, rule: KernelRule):
    """Per-element marginal-gain contribution part(r, M), broadcast over
    any (ground-axis, candidate-axis) orientation: row is the state along
    the ground axis, m the matrix slab. Returns f32 ≥ 0. The three call
    shapes in the engines:

      fused/loop kernels: row (1, BN).T × m (BN, C)   → (BN, C)
      sieve level gains:  row (L, N)    × m (1, N)    → (L, N)
      per-step gains:     row (N, 1)    × m (N, C)    → (N, C)
    """
    if rule.fold == "min":
        return jnp.maximum(row - m.astype(F32), 0.0)
    if rule.fold == "max":
        return jnp.maximum(m.astype(F32) - row, 0.0)
    if rule.fold == "satsum":
        return jnp.minimum(jnp.maximum(m.astype(F32), 0.0), rule.cap - row)
    if rule.fold == "sum":
        # exact potential increment of W(r) = λ·(r ∧ BIG) + (1−λ)·h(r ∧ cap),
        # h(t) = t − t²/(2·cap): the modular relevance term is clamped at
        # BIG so pad rows (r = BIG) contribute exactly 0, and t is clamped
        # BEFORE squaring so the f32 math never sees BIG²
        inc = jnp.maximum(m.astype(F32), 0.0)
        mod = jnp.minimum(row + inc, BIG) - jnp.minimum(row, BIG)
        t0 = jnp.minimum(row, rule.cap)
        t1 = jnp.minimum(row + inc, rule.cap)
        sat = (t1 - t0) - (t1 * t1 - t0 * t0) / (2.0 * rule.cap)
        return rule.lam * mod + (1.0 - rule.lam) * sat
    if rule.fold == "or":
        new = jnp.bitwise_and(m, jnp.bitwise_not(row))
        # via int32: Mosaic has no uint32 → f32 cast (a popcount ≤ 32 is
        # the same number either way)
        return jax.lax.population_count(new).astype(jnp.int32).astype(F32)
    raise KeyError(rule.fold)


def fold_cols(row, col, rule: KernelRule):
    """State-row fold: absorb one matrix column (an accepted element)."""
    if rule.fold == "min":
        return jnp.minimum(row, col.astype(F32))
    if rule.fold == "max":
        return jnp.maximum(row, col.astype(F32))
    if rule.fold == "satsum":
        return jnp.minimum(row + jnp.maximum(col.astype(F32), 0.0),
                           rule.cap)
    if rule.fold == "sum":
        # UNCAPPED running similarity sum — the potential W clamps at
        # score time, not the state (pad rows at BIG stay ≥ BIG)
        return row + jnp.maximum(col.astype(F32), 0.0)
    if rule.fold == "or":
        return jnp.bitwise_or(row, col)
    raise KeyError(rule.fold)


def fold_winner(row, col, prev, rule: KernelRule):
    """Deferred update: fold the previous winner's column into the state
    row; prev < 0 (no accepted winner yet) is a no-op."""
    return jnp.where(prev >= 0, fold_cols(row, col, rule), row)


def partial_gains(row, m, rule: KernelRule):
    """(1, BN) state row × (BN, C) matrix block → (1, C) gain partials."""
    return jnp.sum(gain_part(row.T, m, rule), axis=0, keepdims=True)


def level_gains(rows, col, rule: KernelRule):
    """(L, N) per-level state rows × (1, N) arrival column → (L, 1) raw
    gains — the level-batched transpose of `partial_gains` (sieve)."""
    return jnp.sum(gain_part(rows, col, rule), axis=1, keepdims=True)


def lane_pick(x, idx):
    """Column `idx` of an (R, L) value as (R, 1) f32 — (R, 1) uint32 for
    bitmap words — by a one-hot masked lane reduction: Mosaic cannot slice
    the lane axis at a traced offset. Exact for every value: floats take
    the max against −inf, words the sum against zeros."""
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == idx
    if x.dtype == jnp.uint32:
        xi = jax.lax.bitcast_convert_type(x, jnp.int32)
        col = jnp.sum(jnp.where(hit, xi, 0), axis=1, keepdims=True)
        return jax.lax.bitcast_convert_type(col, jnp.uint32)
    return jnp.max(jnp.where(hit, x.astype(F32), _NEG_INF), axis=1,
                   keepdims=True)


def read_col(mat_ref, idx, scale=None):
    """Column `idx` (clamped at 0) of a VMEM matrix ref as a (1, R) row.
    Mosaic slices the lane axis only at 128-aligned offsets, so this loads
    the aligned 128-lane window that holds the column and picks its lane
    (`lane_pick`). `scale`: the (1, R) per-row scales of int8 storage,
    applied to the window exactly as `dequant` would to the whole slab."""
    i = jnp.maximum(idx, 0)
    base = pl.multiple_of(i // LANES * LANES, LANES)
    win = mat_ref[:, pl.ds(base, LANES)]
    if scale is not None:
        win = dequant(win, scale)
    return lane_pick(win, i - base).T


def masked_argmax(gains, mask):
    """(1, C) gains + 0/1 mask → (first argmax () i32, max gain () f32)."""
    g = jnp.where(mask > 0, gains, _NEG_INF)
    mx = jnp.max(g)
    cols = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    first = jnp.min(jnp.where(g == mx, cols, jnp.int32(2 ** 30)))
    return first, mx


# ---------------------------------------------------------------------------
# int8 quantized storage (per-row f32 scale, f32 rescale-accumulate)
# ---------------------------------------------------------------------------

# quantized dist/dot entries live on a symmetric per-ground-row grid:
# scale_x = max_c |M[x, c]| / 127, q = round(M / scale) clipped to ±127.
# Gains accumulate in f32 AFTER the in-kernel rescale (dequant), so the
# selection algebra above never sees int8 — only rounded f32 values. A
# zero row (all-pad or genuinely empty) keeps scale = 1 so dequant is an
# exact 0 and padding stays gain-neutral.
_QMAX = 127.0


def cache_itemsize(dtype: str) -> int:
    """Bytes per cached-matrix entry for a storage dtype name — the ONE
    mapping the planner's budget gates use (the itemsize fix: bf16/int8
    caches must not be budgeted as if they were f32)."""
    return {"float32": 4, "uint32": 4, "bfloat16": 2, "int8": 1}[dtype]


def quantize_rows(mat):
    """(N, C) f32 matrix → (q int8 (N, C), scale f32 (1, N)) with a
    symmetric per-row scale. Rows of pure zeros get scale 1 (exact
    round-trip of the zero padding)."""
    m = mat.astype(F32)
    amax = jnp.max(jnp.abs(m), axis=1, keepdims=True)          # (N, 1)
    scale = jnp.where(amax > 0.0, amax / _QMAX, 1.0)
    q = jnp.clip(jnp.round(m / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale.T                                          # (1, N)


def dequant(q, scale):
    """(N, C) int8 + (1, N) per-row scale → (N, C) f32. Pure jnp on
    values, so it traces identically inside kernel bodies (the in-kernel
    rescale-accumulate) and in the oracles — int8 selections cannot
    drift between backends."""
    return q.astype(F32) * scale.T


# ---------------------------------------------------------------------------
# matrix construction
# ---------------------------------------------------------------------------


def cross_block(g, c):
    """(TN, TD) × (TC, TD) f32 feature blocks → their (TN, TC) inner
    products ⟨g, c⟩ over this slice of the features, f32."""
    # explicit f32 precision: XLA on the TPU would otherwise run this in
    # one bf16 pass while Mosaic does not, and the ref backend and the
    # kernels must see the same matrix
    return jax.lax.dot_general(g, c, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def sq_norms(g, c):
    """Squared norms of the same blocks over this slice of the features:
    (TN, 1) for the ground rows, (1, TC) for the candidates."""
    return (jnp.sum(g * g, axis=1, keepdims=True),
            jnp.sum(c * c, axis=1, keepdims=True).T)


def finish_block(cross, gn, cn, mode: str):
    """The (TN, TC) matrix block from inner products and squared norms
    summed over every feature: 'dot' is the cross term itself; 'dist'
    the ‖g‖²+‖c‖²−2⟨g,c⟩ expansion, its rounding noise cut to 0."""
    if mode == "dot":
        return cross
    return _dist_from_sq(gn + cn - 2.0 * cross, gn + cn)


def pairwise_block(g, c, mode: str):
    """(TN, D) × (TC, D) feature blocks → (TN, TC) matrix block, f32.

    The single source of the matrix entries — the pairwise and gains
    kernels (which sum `cross_block` and `sq_norms` over feature tiles,
    then call `finish_block`), the resident megakernel, the stream filter
    and the ref backend all finish the same way, so every engine sees
    the same expansion and the same noise cut."""
    cross = cross_block(g, c)
    if mode == "dot":
        return cross
    return finish_block(cross, *sq_norms(g, c), mode)


# Squared distances of the expansion below this share of ‖g‖²+‖c‖² are
# its f32 rounding noise, which differs between XLA's and Mosaic's
# matmuls; they count as 0, so a selected point sits at distance exactly
# 0 from itself on every backend. `scripts/kmedoid_parity.py` reads the
# noise on a TPU v5e at d=768 as at most 4.9e-7 of the scale (Mosaic;
# 2.5e-7 under XLA), on and off the diagonal, for unit-norm and offset
# data: this share is 31× that, and 12× below the closest distinct pair
# of the offset data (PERF.md).
DIST_REL_TOL = 2.0 ** -16


def _dist_from_sq(d2, scale):
    return jnp.sqrt(jnp.where(d2 > DIST_REL_TOL * scale, d2, 0.0))


def matrix_block(g, c, rule: KernelRule):
    """On-chip matrix slab in ground-major (N|W, C) orientation. For
    'bits' the candidate bitmaps ARE the columns — one transpose, no
    arithmetic; for the feature rules, one MXU matmul."""
    if rule.is_bitmap:
        return c.T                                     # (W, C) uint32
    return pairwise_block(g.astype(F32), c.astype(F32), rule.pairwise)


def bitmap_gains(cands, row, rule: KernelRule):
    """Per-step bitmap gains kernel body on a row-major block: (TC, W)
    candidate bitmaps against the (1, W) covered words → (1, TC) f32.
    The cands-major layout avoids the block transpose: part works
    elementwise either way. (A word-major (W, TC) block sums `gain_part`
    down its sublanes instead; feature rules finish a matrix block, then
    `partial_gains`.)"""
    part = gain_part(row, cands, rule)                 # (TC, W)
    return jnp.sum(part, axis=1, keepdims=True).T      # (1, TC)


# ---------------------------------------------------------------------------
# per-step (uncached) state math — the memory-capped path + oracles
# ---------------------------------------------------------------------------


def pairwise_col(ground, payload, rule: KernelRule):
    """One candidate's matrix column M[:, c] against the ground set,
    pure jnp. For 'bits' the payload IS the column."""
    if rule.is_bitmap:
        return payload
    g = ground.astype(F32)
    p = payload.astype(F32)
    if rule.pairwise == "dist":
        # the direct ‖g−c‖ has no cancellation noise to cut: it is exactly
        # 0 for a point against itself
        return jnp.sqrt(jnp.sum((g - p[None, :]) ** 2, axis=-1))
    # 'dot' family, at the matrix's f32 precision (pairwise_block)
    return jnp.dot(g, p, precision=jax.lax.Precision.HIGHEST)


def update_row(ground, row, payload, rule: KernelRule):
    """Per-step state update after accepting `payload` (the slow,
    recompute-everything path and the oracles)."""
    return fold_cols(row, pairwise_col(ground, payload, rule), rule)


def empty_row(ground, ground_valid, rule: KernelRule, words: int = 0):
    """State row of the EMPTY solution: the fold identity per ground row,
    with invalid rows pinned at the zero-gain pad value.

    'min' uses the paper's auxiliary element e0 = 0 (k-medoid §6.4), so
    the empty row is d(·, e0) = ‖x‖; 'bits' rows are all-clear words and
    need no ground features at all."""
    if rule.is_bitmap:
        return jnp.zeros((words,), jnp.uint32)
    if rule.fold == "min":
        d0 = jnp.linalg.norm(ground.astype(F32), axis=-1)
        return jnp.where(ground_valid, d0, rule.pad_row())
    zero = jnp.zeros((ground.shape[0],), F32)
    return jnp.where(ground_valid, zero, rule.pad_row())
