"""The plain references and the replay that compares against them."""
import math

import numpy as np
import pytest

from bench.generators import bitmaps, images
from bench.lib import check, data
from bench.references import coverage, kmedoid


@pytest.fixture(scope="module")
def points():
    return np.asarray(images.draw(data.seed_words(3), pools=1, n=1024, d=64,
                                  classes=24, noise=0.35)[0])


def test_reference_greedy_reads_no_gap_and_its_value(points):
    ones = np.ones(len(points), bool)
    picks = kmedoid.greedy(points, ones, points, ones, 20)
    assert len(picks) == 20
    r = kmedoid.replay(points, ones, points, ones, picks, [True] * 20)
    assert r["gap"] == 0.0
    # value by brute force: mean over rows of d(v, 0) - min(d(v, 0), d(v, S))
    g = points.astype(np.float64)
    d0 = np.linalg.norm(g, axis=1)
    ds = np.linalg.norm(g[:, None, :] - g[None, picks, :], axis=2).min(1)
    assert kmedoid.value(g, ones, g[picks]) == pytest.approx(
        np.mean(d0 - np.minimum(d0, ds)), rel=1e-9)


@pytest.mark.parametrize("precision", ["exact", "bf16"])
def test_screened_replay_agrees_with_the_full_one(points, precision):
    ones = np.ones(len(points), bool)
    p, ok, _ = kmedoid.device_greedy(points, ones, 40, precision=precision)
    p, ok = [int(a) for a in np.asarray(p)], [bool(b) for b in np.asarray(ok)]
    full = kmedoid.replay(points, ones, points, ones, p, ok)
    screened = kmedoid._replay_screened(points, ones, points, ones, p, ok)
    assert screened["gap"] == pytest.approx(full["gap"], rel=1e-9, abs=1e-15)


def test_bad_answers_read_infinite(points):
    ones = np.ones(len(points), bool)
    picks = kmedoid.greedy(points, ones, points, ones, 5)
    for bad, valid in ([picks[:4] + [picks[0]], [True] * 5],
                       [picks[:4] + [len(points)], [True] * 5],
                       [picks, [True, False, True, True, True]]):
        assert math.isinf(kmedoid.replay(points, ones, points, ones, bad,
                                         valid)["gap"])


def test_coverage_replay_is_exact():
    bits = np.asarray(bitmaps.draw(data.seed_words(4), pools=1, n=600,
                                   universe=900, mean=10.3, longest=76,
                                   pareto=1.5, zipf=1.3)[0])
    ones = np.ones(len(bits), bool)
    picks = coverage.greedy(None, None, bits, ones, 12)
    assert coverage.replay(None, None, bits, ones, picks,
                           [True] * len(picks))["gap"] == 0.0
    cover = np.bitwise_or.reduce(bits[picks], axis=0)
    assert coverage.value(None, None, bits[picks]) == \
        np.bitwise_count(cover).sum()
    swapped = picks[:]
    swapped[0], swapped[1] = swapped[1], swapped[0]
    assert coverage.replay(None, None, bits, ones, swapped,
                           [True] * len(picks))["gap"] > 0


def test_tree_groups_follow_the_lane_digits():
    assert [check.group(l, 0, (2, 2)) for l in range(4)] == \
        [[0, 1], [0, 1], [2, 3], [2, 3]]
    assert [check.group(l, 1, (2, 2)) for l in range(4)] == \
        [[0, 2], [1, 3], [0, 2], [1, 3]]


def test_sample_spans_the_pools():
    picked = check.sample(2 ** 40 + 1, 30, 3, 3)
    assert len(set(picked)) == 3 and {i % 3 for i in picked} == {0, 1, 2}
    assert check.sample(5, 2, 3, 3) == [0, 1]
