"""The control: the reference put in the program's place one precision
step below what the configuration states comes out not correct.

k-medoid states float32 distances at `highest`; the step below,
`high` (three bf16 passes), rounded no worse than the chip's own float32
matmul when read at d=768 and changed no pick (PERF.md), so the control
that is caught is the next step, one bf16 pass. k-cover's gains are
integers in float32, at most retail's longest set (76), which bfloat16
holds exactly, so the control is the step below that, float8. Both at the tiny sizes
of `tiny.py`, on three seeds each."""
import time

import jax
import pytest

from bench import run
from bench.lib import spec, systems
from bench.tests.tiny import tiny_root

CONTROLS = [("kmedoid_tinyimg.greedy", "bf16"),
            ("kcover_retail.greedy", "fp8"),
            ("kmedoid_tinyimg.tree4", "bf16")]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("spec")))


@pytest.mark.parametrize("cell,precision", CONTROLS)
@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 11])
def test_control_is_not_correct(root, cell, precision, seed):
    c = spec.load_cell(root, cell)
    ref = spec.reference(root, c.objective)
    make = lambda cl, pools, tmp: systems.reference(
        cl, pools, tmp, ref, precision=precision)
    res = run.run_cell(c, seed, 0.2, False, devices=jax.devices()[:1],
                       t0=time.perf_counter(), make_path=make,
                       on_chip=False)
    assert not res.correct, res.checks
