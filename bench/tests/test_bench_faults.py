"""`correct` comes out false when the timed path is broken underneath.

Each case drives a whole run on the CPU (the look for a chip skipped),
with the reference put in the program's place and one fault planted:

  stale        a step returns its state unchanged
  half         half of the rows left out, the mean taken over the rest
  altered      an answer altered where it is produced
  no_exchange  the exchange between chips left out (tree only)
"""
import time

import jax
import pytest

from bench import run
from bench.lib import spec, systems
from bench.tests.tiny import tiny_root

CASES = [(cell, fault)
         for cell in ("kmedoid_tinyimg.greedy", "kcover_retail.greedy",
                      "kmedoid_tinyimg.tree4")
         for fault in ("stale", "half", "altered", None)]
CASES.append(("kmedoid_tinyimg.tree4", "no_exchange"))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("spec")))


@pytest.mark.parametrize("cell,fault", CASES)
def test_planted_fault_is_caught(root, cell, fault):
    c = spec.load_cell(root, cell)
    ref = spec.reference(root, c.objective)
    make = lambda cl, pools, tmp: systems.reference(cl, pools, tmp, ref,
                                                    fault=fault)
    res = run.run_cell(c, 2 ** 32 + 99, 0.2, False,
                       devices=jax.devices()[:1], t0=time.perf_counter(),
                       make_path=make, on_chip=False)
    assert res.correct is (fault is None), res.checks
    if fault is not None:
        assert res.failed > 0
