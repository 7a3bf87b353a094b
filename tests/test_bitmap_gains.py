"""Per-step bitmap gains read the candidate bitmaps in place.

`ops.gains` hands the (C, W) bitmaps to `gains_pallas` as they are: blocks
of whole rows, a ragged last block, no pad. Step-engine greedy selections
under the kernel (interpret mode) must equal the jnp oracle's id for id
at pool and word counts off every alignment, including the constrained
branch and the stochastic branch's gathered (sample, W) operand. The
block size comes from the operand's width through the planner's VMEM
model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.constraints import PartitionMatroid
from repro.core.functions import make_objective
from repro.core.greedy import greedy
from repro.data.synthetic import gen_kcover, pack_bitmaps
from repro.kernels import ops, plans
from repro.kernels import rules as R

K = 12


def _pool(c, words, seed):
    universe = 32 * words - 5           # a part-filled last word
    bm = jnp.asarray(pack_bitmaps(gen_kcover(c, universe, seed=seed),
                                  universe))
    assert bm.shape == (c, words)
    ids = jnp.arange(c, dtype=jnp.int32)
    valid = (jnp.arange(c) % 7) != 0
    return universe, ids, bm, valid


@pytest.mark.parametrize("case", ["plain", "constrained", "sampled"])
@pytest.mark.parametrize("words", [13, 515])
@pytest.mark.parametrize("c", [1000, 1153])
def test_step_engine_selections_match_ref(c, words, case):
    universe, ids, bm, valid = _pool(c, words, seed=c + words)
    kw = {}
    if case == "constrained":
        cats = jnp.asarray(np.arange(c) % 3, jnp.int32)
        kw["constraint"] = PartitionMatroid(cats,
                                            jnp.asarray([5, 2, 3], jnp.int32))
    if case == "sampled":               # a gathered (301, W) operand
        kw.update(sample=301, key=jax.random.PRNGKey(c))
    sols = [greedy(make_objective("kcover", universe=universe, backend=b),
                   ids, bm, valid, K, engine="step", **kw)
            for b in ("ref", "interpret")]
    ref_sol, sol = sols
    assert int(ref_sol.valid.sum()) > 0
    np.testing.assert_array_equal(np.asarray(sol.ids),
                                  np.asarray(ref_sol.ids))
    np.testing.assert_array_equal(np.asarray(sol.valid),
                                  np.asarray(ref_sol.valid))
    assert int(sol.evals) == int(ref_sol.evals)
    assert float(sol.value) == float(ref_sol.value)


@pytest.mark.parametrize("words,tc", [
    (515, 1024),        # FIMI retail: 3 × 1024 × 640 × 4 B fits 8 MiB
    (1290, 384),        # FIMI kosarak
    (164_700, 0),       # FIMI webdocs: not even 8 whole rows fit 8 MiB
])
def test_block_rows_follow_the_word_count(monkeypatch, words, tc):
    monkeypatch.delenv("REPRO_FUSED_VMEM_MB", raising=False)
    assert plans.bitmap_block_c(words) == tc
    if tc:
        assert (plans.bitmap_gains_need(tc, words)
                <= 8 * 2 ** 20 < plans.bitmap_gains_need(tc + 128, words))


def test_block_rows_under_128_fill_a_lane_dense_output(monkeypatch):
    """A budget that admits fewer than 128 rows: blocks of 64 rows still
    give the oracle's gains."""
    words = 515
    monkeypatch.setenv("REPRO_FUSED_VMEM_MB",
                       str(plans.bitmap_gains_need(64, words) / 2 ** 20))
    assert plans.bitmap_block_c(words) == 64
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    bits = jax.random.bits(k1, (200, words), dtype=jnp.uint32)
    cov = jax.random.bits(k2, (words,), dtype=jnp.uint32)
    valid = (jnp.arange(200) % 3) != 0
    np.testing.assert_array_equal(
        np.asarray(ops.gains(None, cov, bits, valid, R.BITS_OR,
                             backend="interpret")),
        np.asarray(ops.gains(None, cov, bits, valid, R.BITS_OR,
                             backend="ref")))


def test_no_block_fits_raises(monkeypatch):
    monkeypatch.setenv("REPRO_FUSED_VMEM_MB", "0.01")
    bits = jnp.zeros((16, 515), jnp.uint32)
    with pytest.raises(ValueError, match="VMEM budget"):
        ops.gains(None, bits[0], bits, jnp.ones((16,), bool), R.BITS_OR,
                  backend="interpret")
