"""Data summarization with the distributed GreedyML tree.

Runs `LevelDispatcher` on a mesh with one lane per device, over every
device there is: the chips of an accelerator host, or 8 simulated host
devices on the CPU; it fails with fewer than two. Selects k diverse
exemplars from a mixture-of-Gaussians image set with the k-medoid
objective, then shows the facility-location coreset used by the training
pipeline.

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/data_summarization.py
    PYTHONPATH=src python examples/data_summarization.py    # on a TPU host
"""
from repro.launch.mesh import force_host_devices

force_host_devices(8, count_flag=None)   # before jax is imported

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.functions import make_objective
from repro.core.greedy import greedy
from repro.core.greedyml import LevelDispatcher
from repro.core.simulate import global_value
from repro.data import synthetic
from repro.launch.mesh import make_machine_mesh
from repro.runtime import compile_cache

N, D, K = 2048, 256, 32

compile_cache.enable()
# the tree's machines: the largest power of two of devices present
devices = jax.devices()
machines = 1 << (len(devices).bit_length() - 1)
if machines < 2:
    raise SystemExit(f"the GreedyML tree needs at least two devices; JAX "
                     f"sees one {devices[0].platform} device (run with "
                     f"JAX_PLATFORMS=cpu to simulate 8)")
print(f"k-medoid exemplar selection: {N} images, d={D}, k={K}")
imgs = synthetic.gen_images(N, D, classes=16, seed=3)
ids = jnp.arange(N, dtype=jnp.int32)
obj = make_objective("kmedoid")
fac = make_objective("facility")

ref = greedy(obj, ids, jnp.asarray(imgs), jnp.ones(N, bool), K)
ref_sel = np.asarray(ref.ids)[np.asarray(ref.valid)]
print(f"  sequential Greedy     : "
      f"{global_value('kmedoid', imgs, ref_sel):.4f}")

mesh = make_machine_mesh(machines, 2)              # T(m, L=log2 m, b=2)
radices = (2,) * len(mesh.axis_names)
sol = LevelDispatcher(obj, K, radices, mesh=mesh).run(
    ids, jnp.asarray(imgs), jnp.ones(N, bool))
sel = np.asarray(sol.ids)[np.asarray(sol.valid)]
print(f"GreedyML over {mesh.devices.size} devices "
      f"(axes {mesh.axis_names}): picked {len(sel)} exemplars")
print(f"  global k-medoid value: "
      f"{global_value('kmedoid', imgs, sel):.4f}")

# facility-location coreset (what --data-selection greedyml:facility uses)
sol_f = LevelDispatcher(fac, K, radices, mesh=mesh).run(
    ids, jnp.asarray(imgs), jnp.ones(N, bool))
sel_f = np.asarray(sol_f.ids)[np.asarray(sol_f.valid)]
print(f"facility-location coreset: {len(sel_f)} docs, "
      f"coverage={global_value('facility', imgs, sel_f):.4f}")
