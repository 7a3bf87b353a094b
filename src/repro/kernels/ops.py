"""jit'd wrappers around the Pallas kernels, rule-dispatched.

Dispatch policy (``backend`` arg or REPRO_KERNEL_BACKEND env, resolved by
plans.resolve_backend):
  * 'auto'      — compiled Pallas on TPU, jnp reference elsewhere (CPU has no
                  Mosaic backend; interpret mode is for correctness tests)
  * 'pallas'    — compiled Pallas (TPU)
  * 'interpret' — Pallas interpret mode (CPU correctness validation)
  * 'ref'       — pure-jnp oracle

Every wrapper takes the objective's `KernelRule` (kernels/rules.py) —
there are no per-objective entry points and no mode strings. Wrappers own
all padding to tile multiples and validity masking so callers
(core/objective.py) see the clean mathematical signature. Pad targets on
the DRIFTING axes (ground rows N — universe words W for bitmap rules —
and candidates C; they grow level by level at accumulation nodes) are
BUCKETED to the next power-of-two multiple of the tile so repeated calls
hit the jit/pallas compile cache instead of retracing per shape (DESIGN
§Perf); fixed axes (features D, the word axis as a lane dim) keep the
plain next-multiple pad, and constant factors like 1/N are applied
OUTSIDE the kernels so they never become static compile keys. The one
exception is the per-step bitmap gains: `gains` hands the kernel the
candidate bitmaps as they are, (C, W) or their (W, C) view as
plans.bitmap_orient picks, since it runs on every step of a scan already
compiled for the logical pool shape.

Every wrapper that launches a kernel counts, while it is traced
(`runtime.telemetry`): one ``launches``, the ``relayout_bytes`` its pads
write to reshape operands, and a ``stream`` record of the operand the
kernel streams (logical shape, padded shape, bytes, and for the bitmap
gains the ``orient`` it streams in); the pairwise build
also counts the HBM bytes its planned tiling moves (``build_bytes``,
plans.feature_bytes, the full rectangular walk) and the blocks the
symmetric build's mirror fills (``mirrored_blocks``). The greedy driver
gathers them into its per-invocation record.

Engine planning (memory gates, tier selection, backend resolution) lives
in kernels/plans.py; the legacy names (`fused_plan`, `stream_plan`,
`fused_replicas`, …) are re-exported here for callers and tests.

Fused selection engine (DESIGN §Perf): ``pairwise_matrix`` builds the
(N, C) cached matrix once per greedy invocation (a transpose — not a
dispatch — for bitmap rules); ``fused_step`` performs one selection step
over it (deferred winner-column fold + masked gains + on-chip argmax);
``greedy_loop`` / ``greedy_loop_resident`` run the ENTIRE k-step
selection in one dispatch (the whole-greedy megakernel).

Streaming engine (DESIGN §Streaming): ``stream_filter`` folds one batch
of B arrivals into ALL L sieve levels in one dispatch
(kernels/stream_filter.py), gated by ``stream_plan`` with the jnp oracle
(ref.stream_sieve) as fallback and parity ground truth.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import plans, ref
from repro.kernels import rules as rules_mod
from repro.kernels.fused_step import fused_step_pallas
from repro.kernels.greedy_loop import (greedy_loop_pallas,
                                       greedy_loop_resident_pallas)
from repro.kernels.pairwise import (gains_pallas, pairwise_mirror,
                                    pairwise_pallas)
from repro.kernels.plans import (EnginePlan, FEATURE_TILE_C,  # noqa: F401
                                 FEATURE_TILE_N, RES_TILE_N,
                                 fused_block_n, fused_plan, fused_replicas,
                                 loop_block_n, resident_fits,
                                 resolve_backend, select_engine, stream_plan)
from repro.kernels.rules import KernelRule
from repro.runtime import flags, telemetry

F32 = jnp.float32

# legacy aliases (tests/benchmarks poke these)
_backend = flags.kernel_backend
_bucket_len = plans.bucket_len

# placeholder "ground" input for bitmap rules: their matrix is built from
# the candidate payloads alone, but the kernels keep one uniform signature
_DUMMY_GROUND = (8, 128)


class QuantMatrix(NamedTuple):
    """int8-quantized cached matrix: `q` (N, C) int8 storage + `scale`
    (1, N) f32 per-row scales (rules.quantize_rows). A NamedTuple, so it
    is a jax pytree and threads through jit boundaries and the greedy
    drivers exactly like a plain cached array; `.shape`/`.dtype` mirror
    the storage array so shape/itemsize probes work unchanged."""
    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


def _dequant_mat(mat):
    """Logical f32 view of a cached matrix: QuantMatrix → rescaled f32
    (bit-identical to the kernels' on-chip rescale — same primitive),
    plain arrays pass through."""
    if isinstance(mat, QuantMatrix):
        return rules_mod.dequant(mat.q, mat.scale)
    return mat


def _quantized_ground(ground):
    """(q int8, scale (1, N)) for a padded f32 ground block, plus the
    rounded f32 features the ref oracles must see so kernel and oracle
    selections stay bit-identical under int8."""
    q, scale = rules_mod.quantize_rows(ground)
    return q, scale, rules_mod.dequant(q, scale)


def _pad_to(x: jax.Array, axis: int, mult: int, value=0,
            bucket: bool = True) -> jax.Array:
    target = (_bucket_len(x.shape[axis], mult) if bucket
              else -(-x.shape[axis] // mult) * mult)
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _relayout(src, out):
    """`out`, the padded copy of operand `src`. Counts the bytes the copy
    writes (``relayout_bytes``) when the pads changed the shape; chained
    pads of one operand fuse into one copy, so they count once."""
    if out.shape != src.shape:
        telemetry.count("relayout_bytes", out.size * out.dtype.itemsize)
    return out


def _launch(kernel: str, logical, streamed, **extra) -> None:
    """Trace-time counts of one kernel launch: ``launches``, and a
    ``stream`` record of the operand the kernel streams, logical shape
    against the padded shape it is handed (and any `extra` fields, such
    as the bitmap gains' ``orient``)."""
    telemetry.count("launches")
    telemetry.record("stream", kernel=kernel,
                     logical=[int(x) for x in logical],
                     padded=[int(x) for x in streamed.shape],
                     bytes=int(streamed.size * streamed.dtype.itemsize),
                     repeat=telemetry.multiplier(), **extra)


def _dummy_ground():
    return jnp.zeros(_DUMMY_GROUND, F32)


def _row_pad_value(rule: KernelRule):
    return int(rule.row_pad) if rule.is_bitmap else rule.row_pad


def _cast_row(row, rule: KernelRule):
    return row.astype(rule.dtype)


@jax.named_scope("ops.gains")
def gains(ground, row, cands, cand_valid, rule: KernelRule, backend=None,
          orient: str = "cw"):
    """Per-step marginal gains for any rule: RAW part sums (C,) f32, −inf
    at invalid candidates. Callers normalize by the valid ground count.

    Feature rules: ground (N, D), row (N,) state (mind/curmax/cursum),
    cands (C, D). Bitmap rules: ground ignored (may be None), row (W,)
    covered words, cands the (C, W) candidate bitmaps or, with ``orient``
    'wc', their (W, C) view.

    When REPRO_FUSED_CACHE_DTYPE forces 'int8', the per-step path stores
    the ground features quantized too (per-row scale; the kernel
    rescale-accumulates in f32, quartering its dominant HBM read); the
    ref oracle sees the identically ROUNDED f32 features, so selections
    stay bit-identical across backends.
    """
    b = _backend(backend)
    quant = (not rule.is_bitmap and ground is not None
             and flags.fused_cache_dtype() == "int8")
    if b == "ref":
        if quant:
            ground = _quantized_ground(ground.astype(F32))[2]
        return ref.gains(ground, _cast_row(row, rule),
                         cands.T if orient == "wc" else cands, cand_valid,
                         rule)
    if rule.is_bitmap:
        return _bitmap_gains(row, cands, cand_valid, rule, b, orient)
    c = cands.shape[0]
    n_pad = plans.bucket_len(ground.shape[0], FEATURE_TILE_N)
    c_pad = plans.bucket_len(c, FEATURE_TILE_C)
    tiles = plans.feature_tiles("gains", n_pad, c_pad, ground.shape[1],
                                itemsize=1 if quant else 4)
    # feature axis never drifts between calls → plain pad to whole tiles
    g = _relayout(ground, _pad_to(_pad_to(ground, 0, FEATURE_TILE_N), 1,
                                  tiles.td, bucket=False))
    r = _relayout(row, _pad_to(_cast_row(row, rule), 0, FEATURE_TILE_N,
                               value=_row_pad_value(rule)))  # ⇒ zero gain
    cd = _relayout(cands, _pad_to(_pad_to(cands, 0, FEATURE_TILE_C), 1,
                                  tiles.td, bucket=False))
    gscale = None
    if quant:
        g, gscale, _ = _quantized_ground(g.astype(F32))
    _launch("gains_pallas", cands.shape, cd)
    raw = gains_pallas(g, r.reshape(1, -1), cd, rule,
                       interpret=(b == "interpret"), gscale=gscale,
                       tiles=(tiles.tn, tiles.tc, tiles.td),
                       vmem_limit_bytes=tiles.limit)[:c]
    return jnp.where(cand_valid, raw, -jnp.inf)


def _bitmap_gains(row, cands, cand_valid, rule: KernelRule, b: str,
                  given: str):
    """`gains`' bitmap branch. The bitmaps are read in place, in blocks
    of all W words, in the orientation plans.bitmap_orient picks from the
    logical (C, W): no copy per step where the caller holds the pool that
    way (the greedy's step engine does), and only a transpose of a
    gathered subset where it does not. The step runs inside the greedy's
    scan, compiled for the logical pool shape, so a bucketed pad would
    save no compile."""
    c, w = cands.shape[::-1] if given == "wc" else cands.shape
    orient = plans.bitmap_orient(c, w)
    if orient != given:
        cands = cands.T
    tc = plans.bitmap_block_c(w, orient)
    if not tc:
        raise ValueError(
            f"per-step bitmap gains: no block of 8 candidates over {w} "
            f"words fits the {flags.fused_vmem_mb()} MB VMEM budget")
    _launch("gains_pallas", cands.shape, cands, orient=orient)
    row = _cast_row(row, rule)
    raw = gains_pallas(
        _dummy_ground(), row.reshape((-1, 1) if orient == "wc" else (1, -1)),
        cands, rule, interpret=(b == "interpret"), block_c=tc, orient=orient,
        vmem_limit_bytes=plans.vmem_limit(
            plans.bitmap_gains_need(tc, w, orient)))
    return jnp.where(cand_valid, raw[:c], -jnp.inf)


# ---------------------------------------------------------------------------
# Fused selection engine (cached-matrix greedy, DESIGN §Perf)
# ---------------------------------------------------------------------------


@jax.named_scope("ops.pairwise_matrix")
def pairwise_matrix(ground, cands, rule: KernelRule, backend=None,
                    dtype: str = "float32"):
    """The cached ground×candidate matrix for any rule.

    Feature rules run the tiled pairwise kernel ((N, D) × (C, D) →
    (N, C) in ``dtype``; 'bfloat16' halves the cache's HBM footprint,
    consumers accumulate in f32; 'int8' quarters it — the result is a
    `QuantMatrix` pytree of per-row-scaled int8 storage, and consumers
    rescale-accumulate in f32 on-chip). Bitmap rules TRANSPOSE the
    candidate payloads — (C, W) uint32 → (W, C) — with zero kernel
    dispatches.

    Pallas backends return the BUCKET-PADDED (N_pad, C_pad) matrix
    (padding rows/cols carry junk that downstream masks neutralize); the
    ref backend returns the logical (N, C). `fused_step` /
    `apply_column` / `masked_col_reduce` accept either.

    When the matrix is symmetric, as it is where `greedy` selects from
    its own ground set, the build does half the work: when ``cands is
    ground`` (the same array at trace time), their padded extents are
    equal, the planned tiles are square and there are at least 2 block
    rows, the one padded array feeds both sides, `pairwise_pallas`
    computes only the blocks on and above the diagonal (its skipped
    steps keep their index maps on the next computed step's blocks, so
    they move nothing), and `pairwise_mirror` fills each block below
    the diagonal with the transpose of its partner, in place (counted as
    ``mirrored_blocks``). Every other call — distinct arrays (accumulation
    nodes, `replay_batch`), the one-tile (256, 128) blocks, non-square
    tiles — takes the full build. The int8 cache is quantized from the
    mirrored f32 matrix.
    """
    b = _backend(backend)
    if rule.is_bitmap:
        if b == "ref":
            return cands.T
        # (W_pad, C_pad)
        return _relayout(cands, _pad_to(_pad_to(cands, 0, 128), 1, 256)).T
    if b == "ref":
        m = rules_mod.matrix_block(ground, cands, rule)
        if dtype == "int8":
            return QuantMatrix(*rules_mod.quantize_rows(m))
        return m if dtype == "float32" else m.astype(jnp.dtype(dtype))
    # the int8 cache is quantized from the f32 kernel output
    out_dtype = "float32" if dtype == "int8" else dtype
    n_pad = plans.bucket_len(ground.shape[0], FEATURE_TILE_N)
    c_pad = plans.bucket_len(cands.shape[0], FEATURE_TILE_C)
    tiles = plans.feature_tiles("pairwise", n_pad, c_pad, ground.shape[1],
                                itemsize=ground.dtype.itemsize,
                                out_itemsize=jnp.dtype(out_dtype).itemsize)
    # the candidates are the ground rows, over one padded extent, on
    # square tiles of at least 2 block rows: the matrix is symmetric
    rows = n_pad // tiles.tn
    symmetric = (ground is cands and n_pad == c_pad
                 and tiles.tn == tiles.tc and rows >= 2)
    g = _relayout(ground, _pad_to(_pad_to(ground, 0, FEATURE_TILE_N), 1,
                                  tiles.td, bucket=False))
    cd = g if symmetric else _relayout(
        cands, _pad_to(_pad_to(cands, 0, FEATURE_TILE_C), 1, tiles.td,
                       bucket=False))
    _launch("pairwise_pallas", cands.shape, cd)
    telemetry.count("build_bytes", tiles.hbm_bytes)
    m = pairwise_pallas(g, cd, mode=rule.pairwise, out_dtype=out_dtype,
                        interpret=(b == "interpret"),
                        tiles=(tiles.tn, tiles.tc, tiles.td),
                        vmem_limit_bytes=tiles.limit, symmetric=symmetric)
    if symmetric:
        _launch("pairwise_mirror", (ground.shape[0], cands.shape[0]), m)
        telemetry.count("mirrored_blocks", rows * (rows - 1) // 2)
        # VMEM: the block read and the block written at the matrix's
        # width, and the block's f32 transpose
        m = pairwise_mirror(m, tile=tiles.tn, interpret=(b == "interpret"),
                            vmem_limit_bytes=plans.vmem_limit(
                                tiles.tn ** 2 * (2 * m.dtype.itemsize + 4)))
    if dtype == "int8":
        # quantization is a cheap jnp epilogue on the f32 kernel output
        # (one pass, fuses under jit) — zero extra dispatches. Pad
        # rows/cols are zeroed FIRST: per-row scales must see only the
        # logical columns, or the padded and the ref (logical) caches
        # would round differently and int8 selections could drift
        # between backends
        logical = ((jnp.arange(m.shape[0]) < ground.shape[0])[:, None]
                   & (jnp.arange(m.shape[1]) < cands.shape[0])[None, :])
        return QuantMatrix(*rules_mod.quantize_rows(
            jnp.where(logical, m, 0.0)))
    return m


@jax.named_scope("ops.fused_step")
def fused_step(mat, row, mask, prev, rule: KernelRule, backend=None,
               plan: Optional[EnginePlan] = None):
    """One fused greedy step over the cached matrix.

    mat: (N[, _pad], C[, _pad]) from `pairwise_matrix`; row: (n,) state
    in the rule's row dtype; mask: (c,) bool candidate mask; prev: ()
    int32 previous winner (-1 = none). Returns (new_row (n,), best ()
    int32, raw_gain ()). ``plan``: the EnginePlan, threaded through by
    callers so the row block is not re-derived on every one of the k
    calls.
    """
    b = _backend(backend)
    n, c = row.shape[0], mask.shape[0]
    if b == "ref":
        return ref.fused_step(_dequant_mat(mat), _cast_row(row, rule),
                              mask.astype(F32), prev, rule)
    n_pad, c_pad = mat.shape
    r = _relayout(row, _pad_to(_cast_row(row, rule), 0, n_pad,
                               value=_row_pad_value(rule), bucket=False))
    mk = _relayout(mask, _pad_to(mask.astype(F32), 0, c_pad, bucket=False))
    bn = (plan.block_n if plan is not None else 0) or fused_block_n(
        n_pad, c_pad, mat.dtype.itemsize)
    assert bn, "fused_step called without a feasible plan (select_engine)"
    quant = isinstance(mat, QuantMatrix)
    _launch("fused_step_pallas", (n, c), mat.q if quant else mat)
    new_row, best, gain = fused_step_pallas(
        mat.q if quant else mat, r, mk, prev, rule, block_n=bn,
        interpret=(b == "interpret"),
        scale=mat.scale if quant else None,
        vmem_limit_bytes=plans.vmem_limit(
            plans.fused_need(bn, n_pad, c_pad, mat.dtype.itemsize)))
    return new_row[:n], best, gain


@jax.named_scope("ops.greedy_loop")
def greedy_loop(mat, row, mask, k: int, rule: KernelRule, backend=None,
                plan: Optional[EnginePlan] = None):
    """STREAMING megakernel tier: the entire k-step greedy over an
    HBM-cached matrix in ONE dispatch (kernels/greedy_loop.py).

    mat: (N[, _pad], C[, _pad]) from `pairwise_matrix`; row: (n,) state;
    mask: (c,) bool/0-1 candidate mask. Returns (final_row (n,), bests
    (k,) i32 with −1 = rejected step, raw gains (k,) f32).
    """
    b = _backend(backend)
    n, c = row.shape[0], mask.shape[0]
    if b == "ref":
        return ref.greedy_loop(_dequant_mat(mat), _cast_row(row, rule),
                               mask.astype(F32), k, rule)
    n_pad, c_pad = mat.shape
    r = _relayout(row, _pad_to(_cast_row(row, rule), 0, n_pad,
                               value=_row_pad_value(rule),
                               bucket=False)).reshape(1, n_pad)
    mk = _relayout(mask, _pad_to(mask.astype(F32), 0, c_pad,
                                 bucket=False)).reshape(1, c_pad)
    bn = (plan.loop_block_n if plan is not None else 0) or loop_block_n(
        n_pad, c_pad, mat.dtype.itemsize)
    assert bn, "greedy_loop called without a feasible streaming plan"
    quant = isinstance(mat, QuantMatrix)
    _launch("greedy_loop_pallas", (n, c), mat.q if quant else mat)
    new_row, bests, gains_ = greedy_loop_pallas(
        mat.q if quant else mat, r, mk, k, rule, block_n=bn,
        interpret=(b == "interpret"),
        scale=mat.scale if quant else None,
        vmem_limit_bytes=plans.vmem_limit(
            plans.loop_need(bn, n_pad, c_pad, mat.dtype.itemsize)))
    return new_row[:n], bests, gains_


@jax.named_scope("ops.greedy_loop_resident")
def greedy_loop_resident(ground, cands, row, mask, k: int,
                         rule: KernelRule, backend=None,
                         cache_dtype: str = "float32",
                         kq=None, logical=None):
    """RESIDENT megakernel tier: matrix built ON-CHIP + all k steps, one
    dispatch total — the accumulation-node fast path.

    Feature rules: ground (N, D) evaluation rows, cands (C, D); bitmap
    rules: ground ignored, cands (C, W) bitmaps (N = W). row: (n,) state,
    mask: (c,) candidate mask. `cache_dtype` is the plan's storage dtype:
    'int8'/'bfloat16' make the kernel round its on-chip matrix to that
    storage (the quantized-residency ceiling of plans.resident_fits),
    matching the HBM-cached tiers' rounding exactly.

    ``kq`` (traced scalar, default k): per-invocation step budget — steps
    ≥ kq are masked inside the loop, so a k-padded call is bit-identical
    to a solo k=kq run. ``logical``: (n_logical, c_logical) when the
    INPUTS are already pre-padded (the serving engine stacks queries at
    their bucket shapes) — bounds the sub-f32 rounding to the logical
    region so quantization scales match the solo run. Both thread
    through as TRACED values, which is what makes this wrapper vmappable
    over a query axis (DESIGN §Serving). Returns as `greedy_loop`.
    Callers gate via select_engine returning 'mega_resident'.
    """
    b = _backend(backend)
    n, c = row.shape[0], mask.shape[0]
    ln, lc = logical if logical is not None else (n, c)
    kq_ = jnp.asarray(k if kq is None else kq, jnp.int32)
    if b == "ref":
        mat = ref.pairwise(ground, cands, rule)
        if not rule.is_bitmap and cache_dtype in ("int8", "bfloat16"):
            # zero pad rows/cols before rounding: pre-padded (serving)
            # and logical (solo) pools must produce identical per-row
            # int8 scales — a no-op where for solo calls (ln=n, lc=c)
            rows_i = jnp.arange(mat.shape[0])[:, None]
            cols_i = jnp.arange(mat.shape[1])[None, :]
            mat = jnp.where((rows_i < ln) & (cols_i < lc), mat, 0.0)
            if cache_dtype == "int8":
                mat = rules_mod.dequant(*rules_mod.quantize_rows(mat))
            else:
                mat = mat.astype(jnp.bfloat16).astype(F32)
        return ref.greedy_loop(mat, _cast_row(row, rule),
                               mask.astype(F32), k, rule, kq=kq_)
    if rule.is_bitmap:
        g = _dummy_ground()
        cd = _relayout(cands, _pad_to(_pad_to(cands, 0, 128), 1, 128))
        n_pad, c_pad = cd.shape[1], cd.shape[0]
        d_pad = None
        r = _relayout(row, _pad_to(_cast_row(row, rule), 0,
                                   128)).reshape(1, n_pad)
    else:
        g = _relayout(ground, _pad_to(_pad_to(ground, 0, RES_TILE_N), 1,
                                      128, bucket=False))
        cd = _relayout(cands, _pad_to(_pad_to(cands, 0, 128), 1, 128,
                                      bucket=False))
        n_pad, c_pad, d_pad = g.shape[0], cd.shape[0], g.shape[1]
        r = _relayout(row, _pad_to(_cast_row(row, rule), 0, RES_TILE_N,
                                   value=_row_pad_value(rule))
                      ).reshape(1, n_pad)
    mk = _relayout(mask, _pad_to(mask.astype(F32), 0, 128)
                   ).reshape(1, c_pad)
    ctl = jnp.stack([kq_, jnp.asarray(ln, jnp.int32),
                     jnp.asarray(lc, jnp.int32)]).reshape(1, 3)
    # the kernel holds its on-chip matrix in f32 whatever the plan's
    # storage dtype, so its working set is the f32 one
    need = plans.resident_need(n_pad, c_pad, d_pad, rule=rule)
    _launch("greedy_loop_resident_pallas", cands.shape, cd)
    new_row, bests, gains_ = greedy_loop_resident_pallas(
        g, cd, r, mk, ctl, k, rule, interpret=(b == "interpret"),
        cache_dtype=cache_dtype, vmem_limit_bytes=plans.vmem_limit(need))
    return new_row[:n], bests, gains_


def count_pallas_dispatches(jaxpr) -> int:
    """Pallas dispatches per execution, statically from a jaxpr.

    Each pallas_call eqn counts ONCE — including under `jax.vmap`, whose
    batching rule prepends a batch grid dimension to the SAME pallas_call
    eqn rather than wrapping it in an outer loop, so a vmapped kernel is
    genuinely one dispatch. That is the property the serving engine's
    1-dispatch-per-admitted-batch metric measures (DESIGN §Serving): B
    queries stacked on a vmap axis over the resident megakernel must
    count 1 here, while a per-query `lax.map`/scan loop counts B (scan
    bodies multiply by trip length). Recursion descends into every
    sub-jaxpr param (scan/while/cond/pjit/custom_* and closed calls), so
    transformed callees are never silently skipped. The measured (not
    modeled) dispatch column of bench_selection.py / bench_serve.py and
    the streaming acceptance check (one dispatch per arrival batch).

    `shard_map` contract (the vmap contract's SPMD mirror): recursion
    descends into the shard_map eqn's body jaxpr and counts its
    pallas_calls ONCE — the count is PER-LANE, not multiplied by the
    mesh size, because shard_map traces one lane's SPMD program that
    every device executes in parallel. A sharded-tier leaf greedy
    (kernels/shard_gains.py) over p lanes with T candidate tiles and k
    steps therefore counts exactly k·T dispatches — the per-device
    kernel-launch bill — NOT p·k·T, and the same body measured through
    the nested-vmap simulation (axis_name vmap over a batch dim) counts
    identically, so interpret-mode tests can assert the hardware bill
    on one CPU (tests/test_shard_scale.py)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            # the kernel-body jaxpr in params is the dispatch's OWN body —
            # recursing into it would double-count, so stop here
            total += 1
            continue
        mult = (eqn.params.get("length", 1)
                if eqn.primitive.name == "scan" else 1)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += mult * count_pallas_dispatches(inner)
    return total


# ---------------------------------------------------------------------------
# Batched sieve-streaming filter (streaming/sieve.py, DESIGN §Streaming)
# ---------------------------------------------------------------------------


@jax.named_scope("ops.stream_filter")
def stream_filter(ground, batch, rows, row0, values, counts, expos, m_max,
                  bvalid, k: int, eps_log: float, rule: KernelRule,
                  backend=None, plan: Optional[dict] = None,
                  costs=None, spent=None, budget=None):
    """One batch of B arrivals against all L sieve levels in ONE dispatch
    (kernels/stream_filter.py) — the on-chip matrix serves both the
    singleton-gain re-anchor and the admission loop.

    Feature rules: ground (N, D) fixed evaluation set, batch (B, D)
    arrival payloads. Bitmap rules: ground ignored (may be None), batch
    (B, W) arrival bitmaps (N = W). rows: (L, N) per-level state in the
    rule's row dtype; row0: (N,) empty-solution row; values: (L,) raw
    units; counts/expos: (L,) i32; m_max: () f32; bvalid: (B,) bool/0-1;
    eps_log: log(1+ε) (static). Returns (rows (L, N), values (L,),
    counts (L,), admits (L, B) bool, expos (L,), m_new (), expired (L,)
    bool). ``plan``: the stream_plan dict, threaded through so the gate
    is not re-derived per batch; a non-kernel plan (or None) routes to
    the jnp oracle. A plan dtype of 'int8' (REPRO_FUSED_CACHE_DTYPE
    forced) stores the fixed ground features per-row-quantized — the
    kernel rescale-accumulates on-chip, and the oracle sees identically
    ROUNDED features, so admissions stay bit-identical across backends.

    ``costs`` (B,) f32 / ``spent`` (L,) f32 / ``budget`` () f32 (all
    three or none) switch admission to the knapsack cost-ratio rule
    (DESIGN §Constraints) and append the updated per-level spent (L,) to
    the returned tuple — still one dispatch per batch.
    """
    from repro.kernels.stream_filter import stream_filter_pallas
    bk = _backend(backend)
    l, b = rows.shape[0], batch.shape[0]
    n = rows.shape[1]
    d = None if rule.is_bitmap else ground.shape[1]
    has_cost = costs is not None
    plan = plan if plan is not None else stream_plan(n, l, b, d,
                                                     backend=backend,
                                                     rule=rule)
    quant = (not rule.is_bitmap and plan is not None
             and plan.get("dtype") == "int8")
    if bk == "ref" or plan is None or plan.get("tier") != "kernel":
        if quant:
            ground = _quantized_ground(ground.astype(F32))[2]
        mat = ref.pairwise(ground, batch, rule)
        out = ref.stream_sieve(
            mat, _cast_row(row0, rule), _cast_row(rows, rule),
            values.astype(F32), counts, expos, m_max, bvalid.astype(F32),
            k, eps_log, rule,
            costs=costs.astype(F32) if has_cost else None,
            spent=spent.astype(F32) if has_cost else None,
            budget=budget if has_cost else None)
        rows_, values_, counts_, admits, expos_, m_new, expired = out[:7]
        res = (rows_, values_, counts_, admits > 0, expos_, m_new,
               expired > 0)
        return res + (out[7],) if has_cost else res
    assert l % RES_TILE_N == 0, \
        f"levels ({l}) must be a multiple of {RES_TILE_N} on Pallas " \
        "backends (SieveStreamer rounds up)"
    pad_val = _row_pad_value(rule)
    if rule.is_bitmap:
        g = _dummy_ground()
        bt = _relayout(batch, _pad_to(_pad_to(batch, 0, 128, bucket=False),
                                      1, 128, bucket=False))
        n_pad = bt.shape[1]
    else:
        g = _relayout(ground, _pad_to(_pad_to(ground, 0, 128,
                                              bucket=False),
                                      1, 128, bucket=False))
        bt = _relayout(batch, _pad_to(_pad_to(batch, 0, 128, bucket=False),
                                      1, 128, bucket=False))
        n_pad = g.shape[0]
    gscale = None
    if quant:
        g, gscale, _ = _quantized_ground(g.astype(F32))
    r = _relayout(rows, _pad_to(_cast_row(rows, rule), 1, n_pad,
                                value=pad_val, bucket=False))
    r0 = _relayout(row0, _pad_to(_cast_row(row0, rule), 0, n_pad,
                                 value=pad_val, bucket=False)
                   ).reshape(1, n_pad)
    vals = values.astype(F32).reshape(l, 1)
    cnt = counts.astype(jnp.int32).reshape(l, 1)
    exp_ = expos.astype(jnp.int32).reshape(l, 1)
    m_ = m_max.astype(F32).reshape(1, 1)
    bv = _relayout(bvalid.reshape(1, b),
                   _pad_to(bvalid.astype(F32).reshape(1, b), 1, 128,
                           bucket=False))
    cost_kw = {}
    if has_cost:
        # pad arrivals carry bvalid = 0, so their (zero) pad cost is inert
        cost_kw = dict(
            costs=_relayout(costs.reshape(1, b),
                            _pad_to(costs.astype(F32).reshape(1, b), 1,
                                    128, bucket=False)),
            spent=spent.astype(F32).reshape(l, 1),
            budget=jnp.asarray(budget, F32).reshape(1, 1))
    need = plans.stream_need(n, l, b, d, plan["dtype"])
    _launch("stream_filter_pallas", batch.shape, bt)
    out = stream_filter_pallas(g, bt, r, r0, vals, cnt, exp_, m_, bv, k,
                               eps_log, rule,
                               interpret=(bk == "interpret"),
                               gscale=gscale,
                               vmem_limit_bytes=plans.vmem_limit(need),
                               **cost_kw)
    rows_o, vals_o, cnt_o, admits, expos_o, m_o, expired = out[:7]
    res = (rows_o[:, :n], vals_o[:, 0], cnt_o[:, 0], admits[:, :b] > 0,
           expos_o[:, 0], m_o[0, 0], expired[:, 0] > 0)
    return res + (out[7][:, 0],) if has_cost else res


# ---------------------------------------------------------------------------
# column folds over the cached matrix (flush + batched replay)
# ---------------------------------------------------------------------------


def apply_column(mat, row, idx, rule: KernelRule):
    """Fold column `idx` of the cached matrix into the state row (flush of
    the deferred final-step update); idx < 0 is a no-op. Pure jnp — O(N).
    QuantMatrix caches rescale just the sliced column (same elementwise
    product as the in-kernel dequant — bit-identical values)."""
    if isinstance(mat, QuantMatrix):
        n = row.shape[0]
        colq = lax.dynamic_slice_in_dim(mat.q, jnp.maximum(idx, 0), 1,
                                        axis=1)[:n, 0]
        col = colq.astype(F32) * mat.scale[0, :n]
    else:
        col = lax.dynamic_slice_in_dim(mat, jnp.maximum(idx, 0), 1,
                                       axis=1)[: row.shape[0], 0]
    upd = rules_mod.fold_cols(row, col, rule)
    return jnp.where(idx >= 0, upd, row)


def masked_col_reduce(mat, col_valid, row, rule: KernelRule):
    """Batched replay: fold ALL valid columns of the cached matrix into the
    state row in one pass (replaces the sequential k-step update scan).
    Valid for every fold: min/max are idempotent reductions, OR is one
    union, and the saturated add telescopes — min(cap, min(cap, r+a)+b) ≡
    min(cap, r+a+b) for a, b ≥ 0."""
    n, c = row.shape[0], col_valid.shape[0]
    mat = _dequant_mat(mat)
    sub = mat[:n, :c]
    if rule.fold == "or":
        masked = jnp.where(col_valid[None, :], sub, jnp.uint32(0))
        union = lax.reduce(masked, jnp.uint32(0), lax.bitwise_or, [1])
        return jnp.bitwise_or(row, union)
    sub = sub.astype(F32)
    if rule.fold == "min":
        vals = jnp.where(col_valid[None, :], sub, jnp.inf)
        return jnp.minimum(row, jnp.min(vals, axis=1))
    if rule.fold == "max":
        vals = jnp.where(col_valid[None, :], sub, -jnp.inf)
        return jnp.maximum(row, jnp.max(vals, axis=1))
    if rule.fold == "satsum":
        inc = jnp.sum(jnp.where(col_valid[None, :],
                                jnp.maximum(sub, 0.0), 0.0), axis=1)
        return jnp.minimum(row + inc, rule.cap)
    if rule.fold == "sum":
        # plain uncapped add — telescopes trivially over the columns
        return row + jnp.sum(jnp.where(col_valid[None, :],
                                       jnp.maximum(sub, 0.0), 0.0), axis=1)
    raise KeyError(rule.fold)
