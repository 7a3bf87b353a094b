"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes, dtypes, and KernelRules as the assignment requires.
Objective-specific math lives in rule specs (kernels/rules.py); these
tests drive the ONE rule-parameterized gains kernel plus the fused-step
and planning layers through every rule family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, rules

SHAPES_NC = [(64, 32), (256, 128), (300, 150), (512, 17), (33, 260)]
DTYPES = [jnp.float32, jnp.bfloat16]

VECTOR_RULES = [rules.DIST_MIN, rules.DOT_MAX, rules.sat_sum(2.0)]


def _mk(key, n, c, d, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    ground = jax.random.normal(k1, (n, d)).astype(dtype)
    cands = jax.random.normal(k2, (c, d)).astype(dtype)
    aux = jnp.abs(jax.random.normal(k3, (n,))).astype(jnp.float32)
    valid = (jnp.arange(c) % 5) != 0
    return ground, cands, aux, valid


def _state_row(rule, ground, aux):
    """A plausible mid-run state row for the rule family."""
    if rule.fold == "min":
        return aux * 3
    if rule.fold == "satsum":
        return jnp.minimum(aux, rule.cap)
    return aux                                   # 'max': some curmax ≥ 0


@pytest.mark.parametrize("n,c", SHAPES_NC)
@pytest.mark.parametrize("d", [16, 70, 128])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rule", VECTOR_RULES, ids=lambda r: r.name)
def test_vector_gains_match_ref(rule, n, c, d, dtype):
    ground, cands, aux, valid = _mk(jax.random.PRNGKey(n * c + d), n, c, d,
                                    dtype)
    row = _state_row(rule, ground, aux)
    r = ref.gains(ground, row, cands, valid, rule)
    p = ops.gains(ground, row, cands, valid, rule, backend="interpret")
    tol = 2e-4 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(np.where(np.isfinite(r), r, 0),
                               np.where(np.isfinite(p), p, 0),
                               atol=tol, rtol=tol)
    assert bool(jnp.all(jnp.isfinite(r) == jnp.isfinite(p)))


@pytest.mark.parametrize("c,w", [(64, 16), (128, 512), (150, 100), (257, 513)])
def test_coverage_gains_matches_ref(c, w):
    k1, k2 = jax.random.split(jax.random.PRNGKey(c * w))
    bits = jax.random.bits(k1, (c, w), dtype=jnp.uint32)
    cov = jax.random.bits(k2, (w,), dtype=jnp.uint32)
    valid = (jnp.arange(c) % 3) != 0
    r = ref.gains(None, cov, bits, valid, rules.BITS_OR)
    p = ops.gains(None, cov, bits, valid, rules.BITS_OR,
                  backend="interpret")
    np.testing.assert_array_equal(np.where(np.isfinite(r), r, 0),
                                  np.where(np.isfinite(p), p, 0))


def test_coverage_gain_exact_popcount():
    # hand-computed case
    bits = jnp.asarray([[0b1111, 0], [0b1100, 0b1]], jnp.uint32)
    cov = jnp.asarray([0b0101, 0], jnp.uint32)
    valid = jnp.ones(2, bool)
    g = ops.gains(None, cov, bits, valid, rules.BITS_OR,
                  backend="interpret")
    assert g.tolist() == [2.0, 2.0]  # 1111&~0101=1010 → 2; 1100&~0101=1000 +1


def test_kernels_zero_candidates_masked():
    ground, cands, mind, _ = _mk(jax.random.PRNGKey(0), 64, 32, 16,
                                 jnp.float32)
    valid = jnp.zeros(32, bool)
    g = ops.gains(ground, mind, cands, valid, rules.DIST_MIN,
                  backend="interpret")
    assert bool(jnp.all(jnp.isneginf(g)))


def test_satsum_gain_saturates_at_cap():
    """The saturated-sum part must clip at cap − row: a candidate whose
    similarity sum exceeds the remaining headroom gains exactly the
    headroom, no more."""
    rule = rules.sat_sum(1.0)
    ground = jnp.eye(4, dtype=jnp.float32) * 10.0    # huge similarities
    cands = jnp.eye(4, dtype=jnp.float32)
    row = jnp.asarray([0.0, 0.25, 0.5, 1.0])
    g = ref.gains(ground, row, cands, jnp.ones(4, bool), rule)
    np.testing.assert_allclose(np.asarray(g), [1.0, 0.75, 0.5, 0.0])
    p = ops.gains(ground, row, cands, jnp.ones(4, bool), rule,
                  backend="interpret")
    np.testing.assert_allclose(np.asarray(p), np.asarray(g), atol=1e-6)


# ---------------------------------------------------------------------------
# Fused selection engine kernels (DESIGN §Perf)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,c", [(64, 32), (256, 128), (300, 150), (33, 260)])
@pytest.mark.parametrize("d", [16, 128])
@pytest.mark.parametrize("rule", [rules.DIST_MIN, rules.DOT_MAX],
                         ids=lambda r: r.name)
def test_pairwise_matrix_matches_ref(n, c, d, rule):
    ground, cands, _, _ = _mk(jax.random.PRNGKey(n + c + d), n, c, d,
                              jnp.float32)
    r = ops.pairwise_matrix(ground, cands, rule, backend="ref")
    p = ops.pairwise_matrix(ground, cands, rule, backend="interpret")
    assert p.shape[0] % 256 == 0 and p.shape[1] % 128 == 0  # bucketed pad
    np.testing.assert_allclose(np.asarray(r), np.asarray(p)[:n, :c],
                               atol=2e-5, rtol=2e-5)


def test_pairwise_matrix_bitmap_is_transpose():
    """Bitmap rules build the cached matrix WITHOUT any kernel: the
    padded transpose of the candidate bitmaps."""
    bits = jax.random.bits(jax.random.PRNGKey(0), (20, 7),
                           dtype=jnp.uint32)
    r = ops.pairwise_matrix(None, bits, rules.BITS_OR, backend="ref")
    np.testing.assert_array_equal(np.asarray(r), np.asarray(bits).T)
    p = ops.pairwise_matrix(None, bits, rules.BITS_OR,
                            backend="interpret")
    assert p.dtype == jnp.uint32
    assert p.shape[0] % 256 == 0 and p.shape[1] % 128 == 0
    np.testing.assert_array_equal(np.asarray(p)[:7, :20],
                                  np.asarray(bits).T)


@pytest.mark.parametrize("n,c", [(64, 32), (300, 150), (512, 17)])
@pytest.mark.parametrize("rule", [rules.DIST_MIN, rules.DOT_MAX],
                         ids=lambda r: r.name)
@pytest.mark.parametrize("prev", [-1, 0, 5])
def test_fused_step_matches_ref(n, c, rule, prev):
    ground, cands, aux, valid = _mk(jax.random.PRNGKey(n * c + prev), n, c,
                                    16, jnp.float32)
    m_ref = ops.pairwise_matrix(ground, cands, rules.DIST_MIN,
                                backend="ref")
    m_pal = ops.pairwise_matrix(ground, cands, rules.DIST_MIN,
                                backend="interpret")
    row = aux if rule.fold == "min" else jnp.zeros((n,), jnp.float32)
    prev_arr = jnp.int32(min(prev, c - 1))
    r_row, r_best, r_gain = ops.fused_step(m_ref, row, valid, prev_arr,
                                           rule, backend="ref")
    p_row, p_best, p_gain = ops.fused_step(m_pal, row, valid, prev_arr,
                                           rule, backend="interpret")
    assert int(r_best) == int(p_best)
    assert p_row.shape == (n,)
    np.testing.assert_allclose(np.asarray(r_row), np.asarray(p_row),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(float(r_gain), float(p_gain),
                               atol=1e-3, rtol=1e-4)


def test_fused_step_bitmap_matches_ref():
    """The fused step must fold OR + popcount bit-identically on the
    uint32 transposed-bitmap matrix."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    bits = jax.random.bits(k1, (40, 9), dtype=jnp.uint32)
    cov = jax.random.bits(k2, (9,), dtype=jnp.uint32)
    valid = (jnp.arange(40) % 4) != 0
    m_ref = ops.pairwise_matrix(None, bits, rules.BITS_OR, backend="ref")
    m_pal = ops.pairwise_matrix(None, bits, rules.BITS_OR,
                                backend="interpret")
    for prev in (-1, 3):
        r_row, r_best, r_gain = ops.fused_step(
            m_ref, cov, valid, jnp.int32(prev), rules.BITS_OR,
            backend="ref")
        p_row, p_best, p_gain = ops.fused_step(
            m_pal, cov, valid, jnp.int32(prev), rules.BITS_OR,
            backend="interpret")
        assert int(r_best) == int(p_best)
        assert p_row.dtype == jnp.uint32
        np.testing.assert_array_equal(np.asarray(r_row), np.asarray(p_row))
        assert float(r_gain) == float(p_gain)


def test_fused_step_all_masked_returns_neginf():
    ground, cands, aux, _ = _mk(jax.random.PRNGKey(0), 64, 32, 16,
                                jnp.float32)
    mat = ops.pairwise_matrix(ground, cands, rules.DIST_MIN,
                              backend="interpret")
    _, best, gain = ops.fused_step(mat, aux, jnp.zeros(32, bool),
                                   jnp.int32(-1), rules.DIST_MIN,
                                   backend="interpret")
    assert bool(jnp.isneginf(gain)) and int(best) == 0


def test_fused_plan_memory_gate(monkeypatch):
    assert ops.fused_plan(256, 128, backend="interpret") is not None
    monkeypatch.setenv("REPRO_FUSED_CACHE_MB", "0.05")
    assert ops.fused_plan(4096, 4096, backend="interpret") is None
    monkeypatch.delenv("REPRO_FUSED_CACHE_MB")
    monkeypatch.setenv("REPRO_FUSED_VMEM_MB", "0.001")
    assert ops.fused_plan(256, 128, backend="interpret") is None
    # ref backend ignores the VMEM gate (no Pallas block)
    assert ops.fused_plan(256, 128, backend="ref") is not None


def test_bitmap_plan_never_offers_bf16(monkeypatch):
    """Bitmap caches are uint32 words — the bf16 escape hatch must not
    apply; squeezing the budget goes straight to the memory-capped None."""
    plan = ops.fused_plan(512, 512, backend="interpret", rule=rules.BITS_OR)
    assert plan is not None and plan["dtype"] == "uint32"
    monkeypatch.setenv("REPRO_FUSED_CACHE_MB", "0.5")
    assert ops.fused_plan(512, 512, backend="interpret",
                          rule=rules.BITS_OR) is None


def test_select_engine_resolves_tiers():
    """The planner is the single engine decision point: requested engine ×
    sampling/constraint flags × budget → EnginePlan."""
    from repro.kernels import plans
    r = rules.DIST_MIN
    assert plans.select_engine(r, 512, 256, 128,
                               backend="ref").engine == "mega_resident"
    assert plans.select_engine(r, 512, 256, 128, requested="step",
                               backend="ref").engine == "step"
    assert plans.select_engine(r, 512, 256, 128, sampling=True,
                               backend="ref").engine == "step"
    assert plans.select_engine(r, 512, 256, 128, requested="fused",
                               sampling=True,
                               backend="ref").engine == "fused"
    assert plans.select_engine(r, 512, 256, 128, constrained=True,
                               backend="ref").engine == "fused"
    # bitmap rules plan over words with no feature dim
    p = plans.select_engine(rules.BITS_OR, 12, 96, None,
                            backend="interpret")
    assert p.engine == "mega_resident" and p.dtype == "uint32"
    with pytest.raises(ValueError):
        plans.select_engine(r, 8, 8, 8, requested="warp")


def test_pad_bucketing_powers_of_two():
    assert ops._bucket_len(1, 128) == 128
    assert ops._bucket_len(128, 128) == 128
    assert ops._bucket_len(129, 128) == 256
    assert ops._bucket_len(300, 128) == 512
    assert ops._bucket_len(2048, 256) == 2048
    assert ops._bucket_len(2049, 256) == 4096


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.uint32])
@pytest.mark.parametrize("idx", [0, 77, 127])
def test_lane_pick_is_exact_column(dtype, idx):
    """The kernels' winner-column read (one-hot lane reduction) returns
    x[:, idx] bit for bit: negative, infinite and full-width word values
    included."""
    x = jax.random.normal(jax.random.PRNGKey(idx), (16, 128)) * 1e3
    x = x.at[3, idx].set(-jnp.inf).at[4, idx].set(-0.5)
    if dtype == jnp.uint32:
        x = jax.lax.bitcast_convert_type(x, jnp.uint32)   # high bits set
    else:
        x = x.astype(dtype)
    col = rules.lane_pick(x, jnp.int32(idx))
    want = x[:, idx:idx + 1]
    want = want if dtype == jnp.uint32 else want.astype(jnp.float32)
    assert col.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(col), np.asarray(want))


def test_vmem_limit_from_working_set():
    """Mosaic's scoped-VMEM limit is twice the planner's working set and
    never below the compiler's own 16 MiB default."""
    from repro.kernels import plans
    assert plans.vmem_limit(1) == 16 * 2 ** 20
    need = plans.loop_need(64, 8192, 8192)
    assert need > plans.fused_need(64, 8192, 8192)
    assert plans.vmem_limit(need) == max(16 * 2 ** 20, 2 * need)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_self_distance_is_exactly_zero(backend):
    """The ‖g‖²+‖c‖²−2⟨g,c⟩ expansion leaves rounding noise where g = c;
    below `rules.DIST_REL_TOL` of the scale it counts as 0, so a point
    sits at distance exactly 0 from itself in the cached matrix and in
    the direct per-column formula, and other distances are unchanged."""
    from repro.data.synthetic import gen_images
    x = jnp.asarray(gen_images(256, 768, seed=13))      # unit-norm rows
    m = np.asarray(ops.pairwise_matrix(x, x, rules.DIST_MIN,
                                       backend=backend))[:256, :256]
    col = np.asarray(rules.pairwise_col(x, x[7], rules.DIST_MIN))
    np.testing.assert_array_equal(np.diag(m), 0.0)
    assert col[7] == 0.0
    x64 = np.asarray(x, np.float64)
    exact = np.sqrt(((x64[:, None, :] - x64[None, :, :]) ** 2).sum(-1))
    np.testing.assert_allclose(m, exact, atol=1e-5)
    np.testing.assert_allclose(col, exact[:, 7], atol=1e-5)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_small_distances_survive_the_noise_cut(backend):
    """On offset, non-unit data (‖x‖² ≈ 7000, far from unit-norm images)
    the cut zeroes only what the expansion cannot resolve: pairs whose
    squared distance is 2 to 256 times `rules.DIST_REL_TOL` of
    ‖g‖²+‖c‖² stay nonzero, within that share of the exact value, while
    self-distances are exactly 0. The direct per-column formula has no
    cut: it keeps every small distance, a quarter of the cut's included,
    to f32 accuracy."""
    from repro.data.synthetic import gen_images
    x = 3.0 + 4.0 * gen_images(16, 768, seed=5)
    u = np.random.default_rng(3).normal(size=x.shape)
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    x64 = x.astype(np.float64)
    share = np.array([0.25, 2.0, 16.0, 256.0] * 4)[:, None]
    delta = np.sqrt(share * rules.DIST_REL_TOL
                    * 2.0 * np.sum(x64 * x64, axis=1, keepdims=True))
    p = np.concatenate([x, (x64 + delta * u).astype(np.float32)])
    p64 = p.astype(np.float64)
    i, j = np.arange(16), 16 + np.arange(16)
    exact2 = np.sum((p64[i] - p64[j]) ** 2, axis=1)
    scale = np.sum(p64[i] ** 2, axis=1) + np.sum(p64[j] ** 2, axis=1)
    m = np.asarray(ops.pairwise_matrix(jnp.asarray(p), jnp.asarray(p),
                                       rules.DIST_MIN, backend=backend))
    m = m[:32, :32].astype(np.float64)
    np.testing.assert_array_equal(np.diag(m), 0.0)
    resolved = share[:, 0] >= 2.0
    ir, jr = i[resolved], j[resolved]
    assert np.all(m[ir, jr] > 0.0)
    assert np.all(np.abs(m[ir, jr] ** 2 - exact2[resolved])
                  <= rules.DIST_REL_TOL * scale[resolved])
    for a, b in zip(i, j):
        col = np.asarray(rules.pairwise_col(jnp.asarray(p),
                                            jnp.asarray(p[b]),
                                            rules.DIST_MIN))
        assert col[b] == 0.0
        np.testing.assert_allclose(col[a], np.sqrt(exact2[a]), rtol=1e-5)
