"""The harness end to end on the CPU: cells found by name; configurations,
generators, traffic mixes, path kinds and metrics added as files alone;
the result line; and the refusal to run without a TPU."""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import run
from bench.generators import bitmaps, images
from bench.lib import data, spec
from bench.tests.tiny import ROOT, tiny_root


def _run(root, cell, trace=False, seconds=0.3, seed=2 ** 33 + 5, **kw):
    c = spec.load_cell(root, cell)
    return run.run_cell(c, seed, seconds, trace, devices=jax.devices()[:1],
                        t0=time.perf_counter(), on_chip=False, **kw)


def test_every_cell_of_the_benchmark_resolves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                       "selection_s"}
        for metrics in (cell.end_to_end, cell.per_layer):
            assert list(spec.readers(cell, metrics)) == \
                [m["name"] for m in metrics]
        assert set(cell.config["checks"]) >= {"pick_gap"}
        spec.reference(ROOT, cell.objective)
        assert callable(cell.generator.pools) and callable(cell.generator.rows)
        assert cell.path.Program.pool_n(cell) >= cell.config["n"]


@pytest.mark.parametrize("cell", ["kmedoid_tinyimg.greedy",
                                  "kcover_retail.greedy"])
def test_one_chip_cells_run_and_are_correct(tmp_path, cell):
    root = tiny_root(str(tmp_path))
    res = _run(root, cell)
    assert res.correct and res.failed == 0 and res.attempted >= 3
    assert list(res.metrics) == ["selection_s", "peak_hbm_gib", "setup_s"]
    line = json.loads(res.line())
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["checks"]["pick_gap"]["value"] <= \
        line["checks"]["pick_gap"]["limit"]
    traced = _run(root, cell, trace=True)
    assert traced.correct
    assert traced.metrics["compiles_in_window"]["value"] == 0.0


TOY_GENERATOR = """
import jax
import jax.numpy as jnp
from bench.lib.data import key, seed_words


def pools(cfg, n, count, seed):
    x = jax.random.normal(key(seed_words(seed), 7), (count, n, cfg["d"]))
    return tuple(x / jnp.linalg.norm(x, axis=-1, keepdims=True))


def rows(cfg, n):
    return n
"""

TOY_PATH = """
import jax
import numpy as np
from bench.lib import check
from bench.lib.systems import Path


class Program(Path):
    # the per-step engine, each answer an (ids, valid) pair

    def __init__(self, cell, pools, tmp):
        super().__init__(cell, pools, tmp)
        from repro.core.greedy import greedy
        from repro.core.objective import make_objective
        obj, k = make_objective(self.cfg["objective"]), self.k
        self.fn = jax.jit(lambda i, p, v: greedy(obj, i, p, v, k,
                                                 engine="step"))

    def run(self, p):
        sol = self.fn(self.ids, self.pools[p], self.valid)
        return jax.block_until_ready((sol.ids, sol.valid))

    def check(self, ref, outs, pools):
        return {"pick_gap": max(
            check.greedy_gap(ref, self.host(p), np.asarray(i),
                             np.asarray(v))["gap"]
            for (i, v), p in zip(outs, pools))}

    def inventory(self):
        return []


Reference = Program
"""


def _files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs
            if "__pycache__" not in d]


def test_new_config_mix_and_metric_are_files_only(tmp_path):
    """A configuration with a generator of its own, a traffic mix of a new
    path kind, a new end-to-end and a new per-layer metric: new files and
    new entries in BENCHMARK.json, and no file of the harness edited."""
    root = tiny_root(str(tmp_path))
    bpath = os.path.join(root, "BENCHMARK.json")
    with open(bpath) as f:
        bench = json.load(f)
    before = {p: open(p, "rb").read() for p in _files(root) if p != bpath}
    with open(os.path.join(root, "bench", "configs",
                           "kmedoid_tinyimg.json")) as f:
        cfg = dict(json.load(f), n=192, k=6, generator="toy_gen")
    files = {
        ("configs", "toy.json"): json.dumps(cfg),
        ("generators", "toy_gen.py"): TOY_GENERATOR,
        ("traffic", "toy_mix.json"): json.dumps(
            {"path": "toy_path", "pools": 2, "check": 1}),
        ("paths", "toy_path.py"): TOY_PATH,
        ("metrics", "toy_rate.py"): "def read(r):\n    return "
        "r.selections / (r.window.end - r.window.start)\n",
        ("metrics", "toy_metric.py"):
            "def read(r):\n    return 40.0 + r.selections * 0\n",
    }
    for (sub, name), text in files.items():
        with open(os.path.join(root, "bench", sub, name), "w") as f:
            f.write(text)
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.mix", "config": "toy",
                               "traffic": "toy_mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "toy_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["toy.mix"]})
    bench["per_layer"].append({"name": "toy_metric", "unit": "count",
                               "better": "lower", "source": "host_clock",
                               "layer": "test", "moves": "selection_s",
                               "workloads": ["toy.mix"]})
    with open(bpath, "w") as f:
        json.dump(bench, f)
    res = _run(root, "toy.mix")
    assert res.correct and res.attempted >= 2
    assert list(res.metrics) == ["selection_s", "peak_hbm_gib", "setup_s",
                                 "toy_rate"]
    assert res.metrics["toy_rate"]["value"] > 0
    traced = _run(root, "toy.mix", trace=True)
    assert traced.correct
    assert traced.metrics["toy_metric"] == {"value": 40.0, "unit": "count"}
    assert "toy_metric" not in _run(root, "kcover_retail.greedy",
                                    trace=True).metrics
    assert "toy_rate" not in _run(root, "kcover_retail.greedy").metrics
    assert all(open(p, "rb").read() == text for p, text in before.items())


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "kcover_retail.greedy", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == run.NO_DEVICE
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_seed_draws_data_and_keeps_its_high_word():
    kw = dict(pools=1, n=64, d=8, classes=4, noise=0.35)
    a = images.draw(data.seed_words(7), **kw)[0]
    b = images.draw(data.seed_words(7), **kw)[0]
    c = images.draw(data.seed_words(7 + 2 ** 40), **kw)[0]
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(a), axis=1), 1.0,
                               rtol=1e-5)


def test_bitmap_lengths_are_the_same_for_every_seed():
    kw = dict(pools=2, n=5000, universe=16470, mean=10.3, longest=76,
              pareto=1.5, zipf=1.3)
    one = bitmaps.draw(data.seed_words(1), **kw)
    two = bitmaps.draw(data.seed_words(2 ** 35), **kw)
    sizes = bitmaps.set_sizes(5000, 10.3, 76, 1.5)
    assert abs(sizes.mean() - 10.3) < 1e-3 and sizes.max() == 76
    for pool in one + two:
        bits = np.asarray(pool)
        assert bits.shape == (5000, bitmaps.words_of(16470))
        # every set holds exactly its length in distinct items
        assert np.array_equal(np.sort(np.bitwise_count(bits).sum(1)),
                              np.sort(sizes))
    assert not np.array_equal(np.asarray(one[0]), np.asarray(two[0]))
    assert not np.array_equal(np.asarray(one[0]), np.asarray(one[1]))
