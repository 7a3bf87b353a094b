"""The contraction-tiled feature kernels (`kernels/pairwise.py`), run in
interpret mode on the CPU.

`pairwise_pallas` and the feature branch of `gains_pallas` sum the matrix
block over feature tiles of width TD in f32 scratch and finish it on the
last tile. Here, over D in 1, 2 and 5 tiles (one with a zero-padded last
tile), they must match the jnp reference (`kernels/ref.py`) and float64
NumPy; a point sits at distance exactly 0 from itself; with one tile
they must give, bit for bit, the full-feature kernels they replaced; and
a whole greedy under tiles forced small by a tight VMEM budget must
select the reference backend's ids.

Where the candidates are the ground rows on square tiles, the build
computes the blocks on and above the diagonal and `pairwise_mirror`
fills the rest: those blocks must be the full build's bit for bit, the
rest their exact transpose; every other call must take the full build.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.greedy import greedy
from repro.core.objective import make_objective
from repro.kernels import ops, plans, ref
from repro.kernels import rules as R
from repro.kernels.pairwise import (gains_pallas, pairwise_mirror,
                                    pairwise_pallas)
from repro.runtime import telemetry

F32 = jnp.float32
N, C = 512, 256             # padded ground rows and candidates
TN, TC, TD = 256, 128, 128

# (features, tiles of TD): one tile, two, five, and five whose last tile
# is mostly zero padding
DIMS = [(128, 1), (256, 2), (640, 5), (600, 5)]


def _points(d, seed=0):
    """(N, d) ground rows, the first C of them also the candidates, zero
    padded to whole feature tiles."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (N, d), F32)
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return jnp.pad(x, ((0, 0), (0, -(-d // TD) * TD - d)))


def _f64_matrix(g, c, mode):
    g64, c64 = np.asarray(g, np.float64), np.asarray(c, np.float64)
    cross = g64 @ c64.T
    if mode == "dot":
        return cross
    sq = (g64 * g64).sum(1)[:, None] + (c64 * c64).sum(1)[None, :]
    d2 = sq - 2.0 * cross
    # squares under the noise cut are 0 by the matrix's definition
    return np.sqrt(np.where(d2 > R.DIST_REL_TOL * sq, d2, 0.0))


@pytest.mark.parametrize("mode", ["dist", "dot"])
@pytest.mark.parametrize("d,tiles", DIMS)
def test_pairwise_matches_references(mode, d, tiles):
    x = _points(d)
    assert x.shape[1] == tiles * TD
    m = np.asarray(pairwise_pallas(x, x[:C], mode, interpret=True,
                                   tiles=(TN, TC, TD)))
    np.testing.assert_allclose(m, np.asarray(R.pairwise_block(x, x[:C],
                                                              mode)),
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(m, _f64_matrix(x, x[:C], mode), rtol=0,
                               atol=2e-5)
    if mode == "dist":
        # a point against itself: the noise of the expansion is cut to 0
        assert np.all(np.diag(m[:C]) == 0.0)


def _rows(rule):
    row = jax.random.uniform(jax.random.PRNGKey(7), (N,), F32, 0.2, 1.6)
    return row if rule.fold == "min" else row - 0.8


def _quant(g):
    q, scale = R.quantize_rows(g)
    return q, scale, R.dequant(q, scale)


@pytest.mark.parametrize("store", ["f32", "int8"])
@pytest.mark.parametrize("name", ["kmedoid", "facility"])
@pytest.mark.parametrize("d,tiles", DIMS)
def test_gains_matches_references(name, store, d, tiles):
    rule = R.get(name)
    # candidates apart from the ground rows: an int8-rounded row and its
    # own f32 copy would sit at the noise cut, where f32 and f64 part
    x, cands = _points(d, seed=1), _points(d, seed=11)[:C]
    row = _rows(rule)
    g, gscale, seen = x, None, x
    if store == "int8":
        g, gscale, seen = _quant(x)
    got = np.asarray(gains_pallas(g, row.reshape(1, -1), cands, rule,
                                  interpret=True, gscale=gscale,
                                  tiles=(TN, TC, TD)))
    want = np.asarray(ref.gains(seen, row, cands, jnp.ones((C,), bool),
                                rule))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-3)
    m64 = _f64_matrix(seen, cands, rule.pairwise)
    r64 = np.asarray(row, np.float64)[:, None]
    part = (np.maximum(r64 - m64, 0.0) if rule.fold == "min"
            else np.maximum(m64 - r64, 0.0))
    np.testing.assert_allclose(got, part.sum(0), rtol=2e-5, atol=1e-3)


# ---------------------------------------------------------------------------
# one feature tile: the full-feature kernels, bit for bit
# ---------------------------------------------------------------------------


def _full_d_pairwise(g, c, mode):
    """The pairwise build as it was before the features were tiled: grid
    (N/256, C/128), each block one product over every feature."""
    d = g.shape[1]

    def body(g_ref, c_ref, o_ref):
        o_ref[...] = R.pairwise_block(g_ref[...].astype(F32),
                                      c_ref[...].astype(F32), mode)

    return pl.pallas_call(
        body, grid=(g.shape[0] // TN, c.shape[0] // TC),
        in_specs=[pl.BlockSpec((TN, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((TC, d), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((TN, TC), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((g.shape[0], c.shape[0]), F32),
        interpret=True)(g, c)


def _full_d_gains(g, row, c, rule, gscale=None):
    """The per-step feature gains as they were before the features were
    tiled: grid (C/128, N/256), each block one full-feature product."""
    d = g.shape[1]

    def body(*refs):
        if gscale is None:
            g_ref, r_ref, c_ref, o_ref = refs
            gb = g_ref[...]
        else:
            g_ref, s_ref, r_ref, c_ref, o_ref = refs
            gb = R.dequant(g_ref[...], s_ref[...])

        @pl.when(pl.program_id(1) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        m = R.matrix_block(gb, c_ref[...], rule)
        o_ref[...] += R.partial_gains(r_ref[...], m, rule)

    specs = [pl.BlockSpec((TN, d), lambda i, j: (j, 0)),
             pl.BlockSpec((1, TN), lambda i, j: (0, j)),
             pl.BlockSpec((TC, d), lambda i, j: (i, 0))]
    ops_ = [g, row, c]
    if gscale is not None:
        specs.insert(1, pl.BlockSpec((1, TN), lambda i, j: (0, j)))
        ops_.insert(1, gscale)
    return pl.pallas_call(
        body, grid=(c.shape[0] // TC, g.shape[0] // TN), in_specs=specs,
        out_specs=pl.BlockSpec((1, TC), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, c.shape[0]), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=True)(*ops_)[0]


@pytest.mark.parametrize("d", [128, 768])
@pytest.mark.parametrize("mode", ["dist", "dot"])
def test_one_tile_pairwise_is_the_full_feature_build(mode, d):
    x = _points(d, seed=2)
    got = pairwise_pallas(x, x[:C], mode, interpret=True,
                          tiles=(TN, TC, d))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_full_d_pairwise(x, x[:C],
                                                              mode)))


@pytest.mark.parametrize("store", ["f32", "int8"])
@pytest.mark.parametrize("name", ["kmedoid", "facility"])
def test_one_tile_gains_are_the_full_feature_gains(name, store):
    rule, d = R.get(name), 768
    x = _points(d, seed=3)
    row = _rows(rule).reshape(1, -1)
    g, gscale = x, None
    if store == "int8":
        g, gscale, _ = _quant(x)
    got = gains_pallas(g, row, x[:C], rule, interpret=True, gscale=gscale,
                       tiles=(TN, TC, d))
    want = _full_d_gains(g, row, x[:C], rule, gscale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the symmetric build: the blocks on and above the diagonal, mirrored
# ---------------------------------------------------------------------------


def _force_tiles(monkeypatch, tn, tc, td):
    """Make the planner hand every feature kernel (tn, tc, td) tiles."""
    def forced(kernel, n_pad, c_pad, d, itemsize=4, out_itemsize=4,
               budget=None):
        d_pad = -(-d // td) * td
        return plans.FeatureTiles(
            kernel, tn, tc, td, d_pad,
            plans.feature_need(kernel, tn, tc, td, itemsize),
            plans.feature_bytes(kernel, n_pad, c_pad, tn, tc, td, d_pad,
                                itemsize, out_itemsize))

    monkeypatch.setattr(plans, "feature_tiles", forced)


def _block(m, t, i, j):
    return m[i * t:(i + 1) * t, j * t:(j + 1) * t]


def _blocks(m, t):
    rows = m.shape[0] // t
    for i in range(rows):
        for j in range(rows):
            yield i, j, _block(m, t, i, j)


def _build(x, mode, dtype, cands=None):
    """pairwise_matrix(x, cands or x itself) in interpret mode, and the
    blocks the mirror filled."""
    rule = R.DIST_MIN if mode == "dist" else R.DOT_MAX
    with telemetry.span("build") as sp:
        m = ops.pairwise_matrix(x, x if cands is None else cands, rule,
                                backend="interpret", dtype=dtype)
    return m, sp.counts.get("mirrored_blocks", 0)


@pytest.mark.parametrize("rows", [2, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", ["dist", "dot"])
def test_symmetric_build_mirrors_the_upper_blocks(mode, dtype, rows,
                                                  monkeypatch):
    """T × T square tiles over two feature tiles: the blocks on and above
    the diagonal are the full build's bit for bit, those below it their
    exact transpose, and the whole matrix the reference's."""
    t, d = 256, 2 * TD
    _force_tiles(monkeypatch, t, t, TD)
    x = jax.random.normal(jax.random.PRNGKey(8), (rows * t, d), F32)
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    m, mirrored = _build(x, mode, dtype)
    assert mirrored == rows * (rows - 1) // 2
    full = pairwise_pallas(x, x, mode, dtype, interpret=True,
                           tiles=(t, t, TD))
    got = np.asarray(m.astype(F32))
    want = np.asarray(full.astype(F32))
    for i, j, block in _blocks(got, t):
        if j >= i:
            np.testing.assert_array_equal(block, _block(want, t, i, j))
        else:
            np.testing.assert_array_equal(block, _block(got, t, j, i).T)
    np.testing.assert_allclose(
        got, np.asarray(R.pairwise_block(x, x, mode)), atol=R.DIST_REL_TOL,
        rtol=0 if dtype == "float32" else 2.0 ** -8)
    if mode == "dist":
        assert np.all(np.diag(got) == 0.0)


def test_symmetric_int8_cache_quantizes_the_mirrored_matrix(monkeypatch):
    t, rows, d = 256, 4, 2 * TD
    _force_tiles(monkeypatch, t, t, TD)
    x = jax.random.normal(jax.random.PRNGKey(9), (rows * t, d), F32)
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    q, mirrored = _build(x, "dist", "int8")
    m, _ = _build(x, "dist", "float32")
    assert mirrored == rows * (rows - 1) // 2
    want = R.quantize_rows(m)
    np.testing.assert_array_equal(np.asarray(q.q), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(q.scale), np.asarray(want[1]))
    np.testing.assert_allclose(
        np.asarray(R.dequant(q.q, q.scale)),
        np.asarray(R.pairwise_block(x, x, "dist")), rtol=0, atol=2e-2)


@pytest.mark.parametrize("case", ["distinct", "non_square", "one_row"])
def test_other_builds_stay_rectangular(case, monkeypatch):
    """Distinct arrays, non-square tiles and a single block row take the
    full build, unchanged: one kernel, no block mirrored."""
    n, tiles = {"distinct": (512, (256, 256, TD)),
                "non_square": (512, (256, 128, TD)),
                "one_row": (256, (256, 256, TD))}[case]
    _force_tiles(monkeypatch, *tiles)
    x = jax.random.normal(jax.random.PRNGKey(10), (n, 2 * TD), F32)
    other = x + 0.0 if case == "distinct" else None
    m, mirrored = _build(x, "dist", "float32", cands=other)
    assert mirrored == 0
    full = pairwise_pallas(x, x, "dist", interpret=True, tiles=tiles)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(full))
    jx = jax.make_jaxpr(lambda a: ops.pairwise_matrix(
        a, a if other is None else a + 0.0, R.DIST_MIN,
        backend="interpret"))(x)
    assert ops.count_pallas_dispatches(jx.jaxpr) == 1


def test_mirror_writes_only_below_the_diagonal():
    """Every block below the diagonal is written once, from the block
    above it; the blocks on and above it keep what the build wrote."""
    t, rows = 128, 8
    upper = jnp.triu(jnp.arange(float((rows * t) ** 2), dtype=F32)
                     .reshape(rows * t, rows * t))
    got = np.asarray(pairwise_mirror(upper, tile=t, interpret=True))
    up = np.asarray(upper)
    for i, j, block in _blocks(got, t):
        want = _block(up, t, i, j) if j >= i else _block(up, t, j, i).T
        np.testing.assert_array_equal(block, want)


# ---------------------------------------------------------------------------
# a whole greedy under tiles forced small
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine,square", [
    pytest.param("auto", False, id="auto"),
    pytest.param("step", False, id="step"),
    pytest.param("auto", True, id="auto-square")])
def test_greedy_with_small_tiles_selects_the_reference_ids(engine, square,
                                                           monkeypatch):
    """n = 256 pixel-like points wide enough (d = 4,200) that the
    resident tier is refused: 'auto' builds the cache with the pairwise
    kernel, 'step' runs the gains kernel every step, both on tiles a
    1.5 MiB budget forces to split the features. With square tiles
    forced over n = 512, 'auto' builds the blocks on and above the
    diagonal and mirrors them."""
    n, d, k = (512 if square else 256), 4200, 8
    if square:
        _force_tiles(monkeypatch, 256, 256, 1152)
    else:
        small = functools.partial(plans.feature_tiles, budget=3 * 2 ** 19)
        monkeypatch.setattr(plans, "feature_tiles", small)
    x = jax.random.normal(jax.random.PRNGKey(4), (16, d), F32)
    lbl = jax.random.randint(jax.random.PRNGKey(5), (n,), 0, 16)
    x = x[lbl] + 0.35 * jax.random.normal(jax.random.PRNGKey(6), (n, d))
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    ids, valid = jnp.arange(n, dtype=jnp.int32), jnp.ones((n,), bool)
    picks = {}
    for backend in ("interpret", "ref"):
        obj = make_objective("kmedoid", backend=backend)
        sol = jax.jit(lambda i, p, v: greedy(obj, i, p, v, k,
                                             engine=engine))(ids, x, valid)
        picks[backend] = np.asarray(sol.ids)
        if backend == "interpret":
            plan = telemetry.records("plan")[-1]
            mirrored = telemetry.records("greedy")[-1]["mirrored_blocks"]
    assert plan["engine"] == ("mega_stream" if engine == "auto" else "step")
    tiles = plan["tiles"]
    assert tiles["d_pad"] // tiles["td"] >= 2, tiles
    assert mirrored == (1 if square else 0)
    if not square:
        assert tiles["need"] <= 3 * 2 ** 19
    np.testing.assert_array_equal(picks["interpret"], picks["ref"])
