"""The accumulation tree keeps its selections: the dense simulator's ids on
fixed trees, and the dispatcher on an 8-device mesh, pinned to the ids the
tree picked when the recurrence still had a whole-tree shard_map driver."""
import json
import os
import subprocess
import sys

import pytest

from repro.core.simulate import run_tree_dense
from repro.core.tree import AccumulationTree
from repro.data import synthetic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bitmaps():
    sets = synthetic.gen_kcover(512, 512, seed=2, avg_size=3.0)
    return synthetic.pack_bitmaps(sets, 512)


def _simulated(m, b, drop_leaves=()):
    def pick():
        res = run_tree_dense("kcover", _bitmaps(), 8, AccumulationTree(m, b),
                             seed=5, universe=512, drop_leaves=drop_leaves)
        return res.ids.tolist()
    return pick


MESH_SNIPPET = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core.functions import make_objective
from repro.core.greedyml import LevelDispatcher
from repro.data import synthetic
from repro.launch.mesh import make_machine_mesh

sets = synthetic.gen_kcover(512, 512, seed=2, avg_size=3.0)
bm = jnp.asarray(synthetic.pack_bitmaps(sets, 512))
obj = make_objective('kcover', universe=512)
args = (jnp.arange(512, dtype=jnp.int32), bm, jnp.ones(512, bool))
mesh = LevelDispatcher(obj, 8, (2, 2, 2),
                       mesh=make_machine_mesh(8, 2)).run(*args)
sim = LevelDispatcher(obj, 8, (2, 2, 2)).run(*args)
for a, b in zip(jax.tree.leaves(mesh), jax.tree.leaves(sim)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print(json.dumps(np.asarray(mesh.ids)[np.asarray(mesh.valid)].tolist()))
"""


def _on_eight_devices():
    """The mesh dispatcher's ids, after it matched the single-device
    dispatcher field for field (a subprocess: the test session keeps its
    one device)."""
    proc = subprocess.run(
        [sys.executable, "-c", MESH_SNIPPET], capture_output=True, text=True,
        timeout=600, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
                 JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("pick,expected", [
    pytest.param(_simulated(4, 2), [109, 72, 341, 486, 177, 130, 238, 318],
                 id="tree4x2"),
    pytest.param(_simulated(8, 2), [109, 72, 341, 486, 177, 459, 130, 238],
                 id="tree8x2"),
    pytest.param(_simulated(6, 3), [109, 72, 341, 486, 177, 459, 130, 238],
                 id="ragged6x3"),
    pytest.param(_simulated(8, 8), [109, 72, 341, 486, 177, 459, 238, 318],
                 id="randgreedi8"),
    pytest.param(_simulated(8, 2, drop_leaves=(3,)),
                 [72, 341, 486, 177, 130, 238, 318, 477], id="tree8x2_drop3"),
    pytest.param(_on_eight_devices, [109, 72, 341, 486, 177, 459, 38, 188],
                 id="mesh8"),
])
def test_tree_selects_as_before(pick, expected):
    assert pick() == expected
