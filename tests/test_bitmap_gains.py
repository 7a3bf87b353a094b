"""Per-step bitmap gains read the candidate bitmaps in place.

`ops.gains` hands the bitmaps to `gains_pallas` as they are, in the
orientation `plans.bitmap_orient` picks from the pool's shape: the (C, W)
array in blocks of whole rows, or its (W, C) view in blocks of candidate
lanes; a ragged last block, no pad. Step-engine greedy selections under
the kernel (interpret mode) must equal the jnp oracle's id for id at pool
and word counts off every alignment, including the constrained branch and
the stochastic branch's gathered (sample, W) operand, in each
orientation. The block size comes from the operand's width through the
planner's VMEM model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.constraints import PartitionMatroid
from repro.core.functions import make_objective
from repro.core.greedy import greedy
from repro.data.synthetic import gen_kcover, pack_bitmaps
from repro.kernels import ops, plans, ref
from repro.kernels import rules as R
from repro.kernels.pairwise import gains_pallas
from repro.runtime import telemetry

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


@pytest.mark.parametrize("orient,words,tc", [
    ("cw", 515, 1024),  # FIMI retail: 3 × 1024 × 640 × 4 B fits 8 MiB
    ("cw", 1290, 384),  # FIMI kosarak
    ("cw", 164_700, 0),  # FIMI webdocs: not even 8 whole rows fit 8 MiB
    ("wc", 515, 1280),  # retail word-major: 3 × 520 × 1280 × 4 B
    ("wc", 1290, 512),  # kosarak word-major: 3 × 1296 × 512 × 4 B
    ("wc", 164_700, 0),  # webdocs: not even 128 lanes of all words fit
])
def test_block_rows_follow_the_word_count(monkeypatch, orient, words, tc):
    monkeypatch.delenv("REPRO_FUSED_VMEM_MB", raising=False)
    assert plans.bitmap_block_c(words, orient) == tc
    if tc:
        assert (plans.bitmap_gains_need(tc, words, orient) <= 8 * 2 ** 20
                < plans.bitmap_gains_need(tc + 128, words, orient))


def test_block_rows_under_128_fill_a_lane_dense_output(monkeypatch):
    """A budget that admits fewer than 128 rows: blocks of 64 rows still
    give the oracle's gains."""
    words = 515
    monkeypatch.setenv("REPRO_FUSED_VMEM_MB",
                       str(plans.bitmap_gains_need(64, words, "cw")
                           / 2 ** 20))
    assert plans.bitmap_block_c(words, "cw") == 64
    assert plans.bitmap_orient(200, words) == "cw"
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


# ---------------------------------------------------------------------------
# the word-major (W, C) operand
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block", ["planned", 128])
@pytest.mark.parametrize("covered", ["empty", "partial", "full"])
@pytest.mark.parametrize("c", [7, 128, 1000, 3000])
@pytest.mark.parametrize("words", [1, 33, 515])
def test_word_major_kernel_matches_ref(words, c, covered, block):
    """`gains_pallas` on the (W, C) view equals `ref.gains` on the (C, W)
    bitmaps: pools narrower than one lane tile, ragged last blocks
    (3,000 over 1,280 lanes, or any C over 128), every word count."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(words * 7919 + c))
    bits = jax.random.bits(k1, (c, words), dtype=jnp.uint32)
    cov = {"empty": jnp.zeros((words,), jnp.uint32),
           "partial": jax.random.bits(k2, (words,), dtype=jnp.uint32),
           "full": jnp.full((words,), 0xFFFFFFFF, jnp.uint32)}[covered]
    valid = (jnp.arange(c) % 5) != 0
    tc = plans.bitmap_block_c(words, "wc") if block == "planned" else block
    raw = gains_pallas(jnp.zeros((8, 128), jnp.float32), cov.reshape(-1, 1),
                       bits.T, R.BITS_OR, interpret=True, block_c=tc,
                       orient="wc")
    assert raw.shape[0] >= c
    want = ref.gains(None, cov, bits, valid, R.BITS_OR)
    got = jnp.where(valid, raw[:c], -jnp.inf)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if covered == "full":
        assert float(jnp.max(got)) == 0.0


@pytest.mark.parametrize("c,words,orient", [
    (88_162, 515, "wc"),        # FIMI retail
    (990_002, 1_290, "wc"),     # FIMI kosarak
    (128, 515, "wc"),           # an accumulation node's pool
    (1000, 33, "wc"),
    (256, 512, "cw"),           # whole 128-lane word tiles: no lane pad
    (7, 640, "cw"),
    (1000, 640, "cw"),
    (88_162, 640, "cw"),
    (301, 515, "cw"),           # the stochastic branch's gathered sample
    (7, 515, "cw"),             # fewer candidates than a sublane tile
])
def test_orientation_follows_the_tiled_footprint(monkeypatch, c, words,
                                                 orient):
    monkeypatch.delenv("REPRO_FUSED_VMEM_MB", raising=False)
    assert plans.bitmap_orient(c, words) == orient


def test_orientation_needs_a_word_major_block_that_fits(monkeypatch):
    """Where 128 candidate lanes of every word bust the VMEM budget but
    whole rows fit, the pool stays row-major."""
    words = 8_000
    monkeypatch.delenv("REPRO_FUSED_VMEM_MB", raising=False)
    assert plans.bitmap_block_c(words, "wc") == 0
    assert plans.bitmap_block_c(words, "cw") > 0
    assert plans.bitmap_orient(88_162, words) == "cw"


@pytest.mark.parametrize("given", ["cw", "wc"])
@pytest.mark.parametrize("c,orient", [(1000, "wc"), (200, "cw")])
def test_stream_record_carries_the_orientation(c, orient, given):
    """The `stream` record names the orientation the kernel streams, with
    its logical and handed shapes both in it, whichever way the caller
    held the bitmaps; the gains are the oracle's either way."""
    words = 515
    k1, k2 = jax.random.split(jax.random.PRNGKey(c))
    bits = jax.random.bits(k1, (c, words), dtype=jnp.uint32)
    cov = jax.random.bits(k2, (words,), dtype=jnp.uint32)
    valid = (jnp.arange(c) % 3) != 0
    telemetry.reset()
    got = ops.gains(None, cov, bits.T if given == "wc" else bits, valid,
                    R.BITS_OR, backend="interpret", orient=given)
    (s,) = telemetry.records("stream")
    shape = [words, c] if orient == "wc" else [c, words]
    assert (s["kernel"], s["orient"]) == ("gains_pallas", orient)
    assert s["logical"] == s["padded"] == shape
    assert s["bytes"] == c * words * 4
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ops.gains(None, cov, bits, valid, R.BITS_OR,
                             backend="ref")))


@pytest.mark.parametrize("case", ["plain", "constrained", "sampled"])
@pytest.mark.parametrize("orient", ["cw", "wc"])
def test_each_orientation_picks_as_ref(monkeypatch, orient, case):
    """The chooser forced to either orientation, for the pool the step
    engine holds and for every operand `ops.gains` streams: a small
    coverage greedy picks as the `ref` backend does."""
    monkeypatch.setattr(plans, "bitmap_orient", lambda c, w: orient)
    c, words = 500, 21
    universe, ids, bm, valid = _pool(c, words, seed=c + words)
    kw = {}
    if case == "constrained":
        cats = jnp.asarray(np.arange(c) % 3, jnp.int32)
        kw["constraint"] = PartitionMatroid(cats,
                                            jnp.asarray([5, 2, 3], jnp.int32))
    if case == "sampled":
        kw.update(sample=211, key=jax.random.PRNGKey(c))
    telemetry.reset()
    sols = [greedy(make_objective("kcover", universe=universe, backend=b),
                   ids, bm, valid, K, engine="step", **kw)
            for b in ("ref", "interpret")]
    ref_sol, sol = sols
    assert int(ref_sol.valid.sum()) > 0
    assert {s["orient"] for s in telemetry.records("stream")} == {orient}
    for a, b in [(sol.ids, ref_sol.ids), (sol.payloads, ref_sol.payloads),
                 (sol.valid, ref_sol.valid)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(sol.evals) == int(ref_sol.evals)
    assert float(sol.value) == float(ref_sol.value)
