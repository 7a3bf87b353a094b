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
from repro.kernels import plans, ref
from repro.kernels import rules as R
from repro.kernels.pairwise import gains_pallas, pairwise_pallas
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
# a whole greedy under tiles forced small
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["auto", "step"])
def test_greedy_with_small_tiles_selects_the_reference_ids(engine,
                                                           monkeypatch):
    """n = 256 pixel-like points wide enough (d = 4,200) that the
    resident tier is refused: 'auto' builds the cache with the pairwise
    kernel, 'step' runs the gains kernel every step, both on tiles a
    1.5 MiB budget forces to split the features."""
    n, d, k = 256, 4200, 8
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
    assert plan["engine"] == ("mega_stream" if engine == "auto" else "step")
    tiles = plan["tiles"]
    assert tiles["d_pad"] // tiles["td"] >= 2, tiles
    assert tiles["need"] <= 3 * 2 ** 19
    np.testing.assert_array_equal(picks["interpret"], picks["ref"])
