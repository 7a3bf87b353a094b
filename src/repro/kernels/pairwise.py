"""Pallas TPU kernels: pairwise matrix materialization + the ONE
rule-parameterized per-step gains kernel.

Three entry points, the first and last driven by a `KernelRule`
(kernels/rules.py):

  * ``pairwise_pallas`` — the fused engine's `prepare()` stage (DESIGN
    §Perf): compute the (N, C) ground×candidate matrix ONCE per greedy
    invocation for the feature rules ('dist' k-medoid, 'dot'
    facility/satcover). Bitmap rules never reach it — their matrix is a
    transpose of the candidate payloads, built by ops.py without a
    dispatch. Grid: (N/TN, C/TC, D/TD), the features innermost: each
    step adds one (TN, TD) × (TC, TD) MXU product to an f32 (TN, TC)
    cross-term accumulator (and, for 'dist', the blocks' squared norms to
    (TN, 1) and (1, TC) accumulators), and the last feature tile finishes
    the block with `rules.finish_block`. Where the candidates are the
    ground rows on square tiles (ops.pairwise_matrix decides), the matrix
    is symmetric and the build runs ``symmetric``: same grid and blocks,
    but only the blocks on and above the diagonal are computed (bit for
    bit what the full build writes), and the steps below it move nothing.

  * ``pairwise_mirror`` — fills the blocks below the diagonal of that
    symmetric build, in place, with the transposes of the blocks above
    it: one block read and one written per grid step.

  * ``gains_pallas`` — the per-step (uncached) marginal-gains pass, the
    paper's memory-capped regime. This single kernel replaces the three
    per-objective kernels (kmedoid_gains / facility_gains /
    coverage_gains) that predated the objective protocol: the rule picks
    the matrix op and the gain part, so feature rules tile (TC
    candidates × TN ground rows × TD features), accumulating the matrix
    block over the features as the pairwise build does and folding its
    gains into the revisited (1, TC) output on the last feature tile,
    and bitmap rules read the candidate bitmaps in place with AND-NOT +
    popcount, in the orientation plans.bitmap_orient picks from their
    shape: the (C, W) array in blocks of whole rows (TC × W words), or
    its (W, C) view in blocks of TC candidate lanes over all W words,
    summed down the sublanes (TC from plans.bitmap_block_c).

Tiles come from `plans.feature_tiles` (feature rules), which picks the
tiling that moves the fewest HBM bytes under the VMEM budget, with its
VMEM model `plans.feature_need` (≈ 7.6 MB at TN = TC = 512, TD = 384);
bitmap blocks from `plans.bitmap_gains_need` (≈ 8 MB at W = 515 in
either orientation).
Where the features fit one tile, each kernel computes exactly the
full-feature product it did before the contraction axis was tiled.
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


def _add_tile(ref, value, first) -> None:
    """Sum `value` into the accumulator `ref` over the feature tiles: the
    first tile stores it, so one tile gives the value itself."""
    @pl.when(first)
    def _store():
        ref[...] = value

    @pl.when(jnp.logical_not(first))
    def _add():
        ref[...] += value


def _accumulate(g, c, acc_ref, gn_ref, cn_ref, first, mode: str) -> None:
    """One feature tile of a (TN, TC) matrix block: its cross term and,
    for 'dist', the squared norms, summed in f32 scratch."""
    _add_tile(acc_ref, R.cross_block(g, c), first)
    if mode == "dist":
        gn, cn = R.sq_norms(g, c)
        _add_tile(gn_ref, gn, first)
        _add_tile(cn_ref, cn, first)


def _finish(acc_ref, gn_ref, cn_ref, mode: str):
    return R.finish_block(acc_ref[...], gn_ref[...], cn_ref[...], mode)


def _scratch(tn: int, tc: int):
    return [pltpu.VMEM((tn, tc), F32), pltpu.VMEM((tn, 1), F32),
            pltpu.VMEM((1, tc), F32)]


def _kernel(ground_ref, cands_ref, out_ref, acc_ref, gn_ref, cn_ref, *,
            mode: str, symmetric: bool):
    di = pl.program_id(2)
    last = pl.num_programs(2) - 1

    def block():
        _accumulate(ground_ref[...].astype(F32), cands_ref[...].astype(F32),
                    acc_ref, gn_ref, cn_ref, di == 0, mode)

        @pl.when(di == last)
        def _write():
            out_ref[...] = _finish(acc_ref, gn_ref, cn_ref,
                                   mode).astype(out_ref.dtype)

    if symmetric:
        # only the blocks on and above the diagonal; `pairwise_mirror`
        # fills the rest
        pl.when(pl.program_id(1) >= pl.program_id(0))(block)
    else:
        block()


def _index_maps(symmetric: bool):
    """Block indices of ground, candidates and output at grid step
    (ni, ci, di). A skipped step of the symmetric build (ci < ni) stays
    on the blocks of row ni's first computed step, the diagonal's at
    feature tile 0, so the pipeline neither fetches nor writes back for
    it, and the diagonal block is written back once, when computed."""
    if not symmetric:
        return ((lambda ni, ci, di: (ni, di)),
                (lambda ni, ci, di: (ci, di)),
                (lambda ni, ci, di: (ni, ci)))

    def feat(ni, ci, di):
        return jnp.where(ci < ni, 0, di)

    return ((lambda ni, ci, di: (ni, feat(ni, ci, di))),
            (lambda ni, ci, di: (jnp.maximum(ni, ci), feat(ni, ci, di))),
            (lambda ni, ci, di: (ni, jnp.maximum(ni, ci))))


@functools.partial(jax.jit,
                   static_argnames=("mode", "out_dtype", "interpret", "tiles",
                                    "vmem_limit_bytes", "symmetric"))
def pairwise_pallas(ground: jax.Array, cands: jax.Array, mode: str = "dist",
                    out_dtype: str = "float32", interpret: bool = False, *,
                    tiles: tuple, vmem_limit_bytes: int = 0,
                    symmetric: bool = False) -> jax.Array:
    """ground: (N, D), cands: (C, D) → (N, C) matrix in ``out_dtype``
    (compute always f32; 'bfloat16' halves the cache's HBM footprint).

    ``tiles``: (TN, TC, TD) blocks (plans.feature_tiles); N, C and D must
    be padded to multiples of them by the ops.py wrapper (zero padding:
    pad rows/cols produce ‖·‖ / 0 entries that callers mask, pad features
    add nothing). `vmem_limit_bytes`: Mosaic's scoped-VMEM limit
    (plans.vmem_limit).

    ``symmetric``: ``cands`` is ``ground`` on square tiles (TN = TC), so
    the matrix is symmetric. The grid and blocks stay as they are, but
    only the steps with ci ≥ ni compute, in the same arithmetic and
    feature order as the full build, so those blocks are bit for bit
    what it writes; the steps below the diagonal keep their index maps
    on the blocks the next computed step needs, so they move nothing
    (`_index_maps`). The blocks below the diagonal are left unwritten
    for `pairwise_mirror` to fill.
    """
    tn, tc, td = tiles
    n, d = ground.shape
    c = cands.shape[0]
    assert n % tn == 0 and c % tc == 0 and d % td == 0 and td % 128 == 0, \
        (n, c, d, tiles)
    assert not symmetric or (n, tn) == (c, tc), (n, c, tiles)
    g_map, c_map, o_map = _index_maps(symmetric)
    return pl.pallas_call(
        functools.partial(_kernel, mode=mode, symmetric=symmetric),
        name="pairwise_pallas",
        grid=(n // tn, c // tc, d // td),
        in_specs=[
            pl.BlockSpec((tn, td), g_map),
            pl.BlockSpec((tc, td), c_map),
        ],
        out_specs=pl.BlockSpec((tn, tc), o_map),
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.dtype(out_dtype)),
        scratch_shapes=_scratch(tn, tc),
        # matrix blocks are independent — Mosaic may pipeline/reorder
        # both; the innermost feature axis accumulates in scratch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes or None),
        interpret=interpret,
    )(ground, cands)


def _mirror_kernel(upper_ref, out_ref):
    # through f32: Mosaic transposes 32-bit tiles; the round trip of a
    # narrower float is exact
    out_ref[...] = upper_ref[...].astype(F32).T.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret",
                                             "vmem_limit_bytes"))
def pairwise_mirror(mat: jax.Array, *, tile: int, interpret: bool = False,
                    vmem_limit_bytes: int = 0) -> jax.Array:
    """Fill the blocks below the diagonal of a symmetric (N, N) matrix,
    in place, from the blocks above it that `pairwise_pallas(...,
    symmetric=True)` wrote: block (i, j), i > j, becomes the transpose
    of block (j, i).

    The matrix is aliased to the output, so no second (N, N) buffer
    exists. Grid (T/2, T − 1) over T = N/tile block rows (a power of
    two, so even), rows paired: step (p, q) writes block (p, q) when
    q < p, else block (T − 1 − p, q − p). Every step writes exactly one
    block below the diagonal, and none above it is written.
    """
    n = mat.shape[0]
    t = n // tile
    assert mat.shape == (n, n) and n % tile == 0 and t % 2 == 0, \
        (mat.shape, tile)

    def lower(p, q):
        first = q < p
        return jnp.where(first, p, t - 1 - p), jnp.where(first, q, q - p)

    def upper(p, q):
        i, j = lower(p, q)
        return j, i

    return pl.pallas_call(
        _mirror_kernel,
        name="pairwise_mirror",
        grid=(t // 2, t - 1),
        in_specs=[pl.BlockSpec((tile, tile), upper)],
        out_specs=pl.BlockSpec((tile, tile), lower),
        out_shape=jax.ShapeDtypeStruct(mat.shape, mat.dtype),
        input_output_aliases={0: 0},
        # every step reads one block above the diagonal and writes one
        # below it: no step touches another's block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem_limit_bytes or None),
        interpret=interpret,
    )(mat)


def _gains_kernel(*refs, rule: KernelRule, quant: bool):
    if quant:
        # int8 rescale-accumulate: the (TN, TD) ground block is 1-byte
        # storage; rescale it against the (1, TN) per-row scales on-chip,
        # then the identical f32 algebra
        ground_ref, gscale_ref, row_ref, cands_ref, out_ref, *acc = refs
        g = R.dequant(ground_ref[...], gscale_ref[...])
    else:
        ground_ref, row_ref, cands_ref, out_ref, *acc = refs
        g = ground_ref[...].astype(F32)
    ni, di = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(2) - 1

    @pl.when((ni == 0) & (di == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _accumulate(g, cands_ref[...].astype(F32), *acc, di == 0, rule.pairwise)

    @pl.when(di == last)
    def _fold():
        m = _finish(*acc, rule.pairwise)                # (TN, TC)
        out_ref[...] += R.partial_gains(row_ref[...], m, rule)


def _bitmap_gains_kernel(ground_ref, row_ref, cands_ref, out_ref, *,
                         rule: KernelRule, tc: int):
    # one (TC, W) block covers every word of its candidates: no
    # accumulation; a block under 128 rows fills the front of its
    # lane-dense output block
    del ground_ref
    out_ref[:, :tc] = R.bitmap_gains(cands_ref[...], row_ref[...], rule)


def _bitmap_gains_wc_kernel(ground_ref, col_ref, cands_ref, out_ref, *,
                            rule: KernelRule):
    # one (W, TC) block, candidates on lanes: the (W, 1) covered words
    # broadcast along the lanes, and the popcounts sum down the sublanes
    # straight into the lane-dense (1, TC) output
    del ground_ref
    part = R.gain_part(col_ref[...], cands_ref[...], rule)      # (W, TC)
    out_ref[...] = jnp.sum(part, axis=0, keepdims=True)


def _bitmap_gains(ground, row, cands, rule, interpret, block_c, orient,
                  vmem_limit_bytes):
    """`gains_pallas`' bitmap branch: grid (⌈C/TC⌉,) over blocks of
    `block_c` candidates (all of them when C is smaller), each over all W
    words. 'cw': cands (C, W), row (1, W); 'wc': cands (W, C), row
    (W, 1)."""
    wc = orient == "wc"
    w, c = cands.shape if wc else cands.shape[::-1]
    assert row.shape == ((w, 1) if wc else (1, w)) and block_c > 0, \
        (row.shape, orient, block_c)
    tc = min(block_c, c)
    lanes = tc if wc else -(-tc // 128) * 128
    blocks = pl.cdiv(c, tc)
    if wc:
        kernel = functools.partial(_bitmap_gains_wc_kernel, rule=rule)
        cand_spec = pl.BlockSpec((w, tc), lambda ci: (0, ci))
    else:
        kernel = functools.partial(_bitmap_gains_kernel, rule=rule, tc=tc)
        cand_spec = pl.BlockSpec((tc, w), lambda ci: (ci, 0))
    out = pl.pallas_call(
        kernel,
        name="gains_pallas",
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec(ground.shape, lambda ci: (0, 0)),
            pl.BlockSpec(row.shape, lambda ci: (0, 0)),
            cand_spec,
        ],
        out_specs=pl.BlockSpec((1, lanes), lambda ci: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, blocks * lanes), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes or None),
        interpret=interpret,
    )(ground, row, cands)
    return out.reshape(blocks, lanes)[:, :tc].reshape(-1)


@functools.partial(jax.jit, static_argnames=("rule", "interpret", "block_c",
                                             "orient", "tiles",
                                             "vmem_limit_bytes"))
def gains_pallas(ground: jax.Array, row: jax.Array, cands: jax.Array,
                 rule: KernelRule, interpret: bool = False,
                 gscale=None, block_c: int = 0, orient: str = "cw",
                 tiles: tuple = (), vmem_limit_bytes: int = 0) -> jax.Array:
    """RAW marginal-gain sums (C,) f32 for ANY registered rule (callers
    normalize outside the kernel so the logical N never becomes a static
    compile key).

    Feature rules: ground (N, D), row (1, N) state (mind/curmax/cursum),
    cands (C, D), in (TN, TC, TD) ``tiles`` (plans.feature_tiles) that
    divide the padded shapes; grid (C/TC, N/TN, D/TD), the features
    innermost: each (candidate block, ground block) sums its matrix block
    over the feature tiles and, on the last, adds its gains to the
    revisited output block. Padded ground rows must carry row =
    rule.row_pad (⇒ zero contribution), padded features are zero; the
    ops.py wrapper guarantees both. When `gscale` (1, N) f32 is given,
    `ground` is int8 per-row-quantized storage (rules.quantize_rows) and
    the kernel rescales each block to f32 on-chip — quartering the
    dominant per-step HBM read.

    Bitmap rules: ground is an ignored (8, 128) placeholder; the
    candidate bitmaps, of any shape, are read in place in the `orient`
    that plans.bitmap_orient picked: 'cw', cands (C, W) with row (1, W)
    covered words, blocks of `block_c` whole rows; 'wc', cands the (W, C)
    view with row (W, 1), blocks of `block_c` candidate lanes. Each block
    covers all W words (all C candidates when C is smaller than a block),
    grid (⌈C/TC⌉,). The last block may run past C; it only reaches
    entries past C, which the caller cuts. `vmem_limit_bytes`: Mosaic's
    scoped-VMEM limit (plans.vmem_limit).
    """
    if rule.is_bitmap:
        return _bitmap_gains(ground, row, cands, rule, interpret, block_c,
                             orient, vmem_limit_bytes)
    c = cands.shape[0]
    tn, tc, td = tiles
    n, d = ground.shape
    assert n % tn == 0 and c % tc == 0 and d % td == 0 and td % 128 == 0, \
        (n, c, d, tiles)
    assert row.shape == (1, n) and cands.shape[1] == d
    in_specs = [
        pl.BlockSpec((tn, td), lambda ci, ni, di: (ni, di)),
        pl.BlockSpec((1, tn), lambda ci, ni, di: (0, ni)),
        pl.BlockSpec((tc, td), lambda ci, ni, di: (ci, di)),
    ]
    operands = [ground, row, cands]
    if gscale is not None:
        assert gscale.shape == (1, n), (gscale.shape, n)
        in_specs.insert(1, pl.BlockSpec((1, tn),
                                        lambda ci, ni, di: (0, ni)))
        operands.insert(1, gscale)
    out = pl.pallas_call(
        functools.partial(_gains_kernel, rule=rule,
                          quant=gscale is not None),
        name="gains_pallas",
        grid=(c // tc, n // tn, d // td),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tc), lambda ci, ni, di: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, c), F32),
        scratch_shapes=_scratch(tn, tc),
        # candidate blocks are independent (parallel); the ground and
        # feature axes accumulate into the revisited output block and
        # the scratch (arbitrary), which Mosaic can still pipeline
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes or None),
        interpret=interpret,
    )(*operands)
    return out[0]
