"""Operations and bytes counted from traced kernels, against hand counts
at small shapes. Tracing needs no chip: the kernels are traced for the
Pallas backend and never lowered."""
import math

import jax
import jax.numpy as jnp
import pytest

from bench.lib import counts


def _greedy_kernels(objective, n, w, k, engine="auto", universe=0):
    from repro.core.greedy import greedy
    from repro.core.objective import make_objective
    obj = make_objective(objective, universe=universe, backend="pallas")
    dt = jnp.uint32 if universe else jnp.float32
    args = (jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n, w), dt),
            jax.ShapeDtypeStruct((n,), jnp.bool_))
    jx = jax.make_jaxpr(lambda i, p, v: greedy(obj, i, p, v, k,
                                                engine=engine))(*args)
    return {kk.name: kk for kk in counts.kernels(jx)}


def test_streaming_loop_and_pairwise_build():
    n, d, k = 2048, 128, 16
    ks = _greedy_kernels("kmedoid", n, d, k)
    assert set(ks) == {"pairwise_pallas", "greedy_loop_pallas"}
    pw = ks["pairwise_pallas"]
    # ground rows fetched once per row block, candidate rows once per
    # (row block, candidate block), the (n, n) f32 matrix written once
    assert pw.nbytes == 4 * (n * d + (n // 256) * n * d + n * n)
    assert pw.ops == 2 * n * n * d + 3 * n * n
    loop = ks["greedy_loop_pallas"]
    assert loop.grid == (k + 1, n // 256)
    # the cache re-read on every grid step; the state row, mask and the
    # lane-dense (1, 128) outputs move once
    assert loop.nbytes == 4 * ((k + 1) * n * n + n + n + n + 128 + 128)
    assert loop.ops == 3 * k * n * n
    assert loop.out_shapes == ((8, 256), (1, 128), (1, 128))


def test_resident_tier_moves_each_operand_once():
    n, d, k = 400, 768, 200
    ks = _greedy_kernels("kmedoid", n, d, k)
    res = ks["greedy_loop_resident_pallas"]
    pad = 512                   # both axes bucket to a power of two
    assert res.grid == ()
    assert res.ops == 2 * pad * pad * d + 3 * k * pad * pad
    moved = sum(math.prod(b.shape) * b.itemsize
                for b in res.inputs + res.outputs)
    assert res.nbytes == moved


def test_per_step_bitmap_gains():
    n, words, k = 4096, 512, 8
    ks = _greedy_kernels("coverage", n, words, k, engine="step",
                         universe=words * 32)
    g = ks["gains_pallas"]
    # placeholder ground block, the covered-words row and the (n, words)
    # bitmaps once each, the (1, n) gains written once
    assert g.nbytes == 4 * (8 * 128 + words + n * words + n)
    assert g.ops == 4 * n * words
    assert g.inputs[2].shape == (n, words)


def test_uncounted_kernel_gets_no_bound():
    k = counts.Kernel("mystery_pallas", (), (), (), None)
    assert k.least_seconds(object()) is None


def test_match_by_name_and_result_shapes():
    ks = list(_greedy_kernels("kmedoid", 2048, 128, 16).values())
    loop = counts.match(ks, "greedy_loop_pallas",
                        ((8, 256), (1, 128), (1, 128)))
    assert loop is not None and loop.name == "greedy_loop_pallas"
    assert counts.match(ks, "greedy_loop_pallas", ((8, 256),)) is None


@pytest.mark.parametrize("dep,grid,moves", [
    ([False, False], (5, 7), 1),
    ([True, False], (5, 7), 5),
    ([False, True], (5, 7), 35),
    ([True, True], (5, 7), 35),
])
def test_block_moves(dep, grid, moves):
    assert counts._moves(dep, grid) == moves
