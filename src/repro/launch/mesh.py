"""Mesh construction and host-platform device-count setup.

Everything here is a FUNCTION and jax is imported lazily inside them, so
importing this module never touches jax — `force_host_devices` can (and
MUST) run before anything imports jax, because jax locks the host device
count on first init. The launchers call it in their pre-docstring
preamble instead of hand-rolling the XLA_FLAGS append.
"""
from __future__ import annotations

import math
import os
import sys
from typing import TYPE_CHECKING, Optional, Sequence, Tuple

if TYPE_CHECKING:                            # pragma: no cover - typing only
    from jax.sharding import Mesh


def force_host_devices(count: int = 512, *, trigger: Optional[str] = None,
                       count_flag: Optional[str] = "--lanes",
                       argv: Optional[Sequence[str]] = None) -> bool:
    """Append ``--xla_force_host_platform_device_count=N`` to XLA_FLAGS so
    the CPU backend simulates N devices. The flag touches only the CPU
    backend: on an accelerator host ``jax.devices()`` still lists the real
    chips (hold JAX to the CPU with ``JAX_PLATFORMS=cpu`` to simulate
    there). MUST be called before ANYTHING imports jax (this module
    deliberately does not).

    ``trigger``: only apply when this flag is present in ``argv``
    (default sys.argv) — e.g. faultrun's ``--mesh`` — None applies
    unconditionally. ``count_flag``: take the count from this flag's
    value when present (e.g. ``--lanes 8``), falling back to ``count``.
    Returns whether the flag was applied."""
    argv = list(sys.argv if argv is None else argv)
    if trigger is not None and trigger not in argv:
        return False
    n = str(count)
    if count_flag and count_flag in argv:
        n = argv[argv.index(count_flag) + 1]
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}")
    return True


def device_lanes(requested: Optional[int], flag: str) -> int:
    """Lane count of a run with one lane per device (``flag`` names the
    launcher's mesh switch): every device JAX sees — the chips, or the
    host devices forced from ``--lanes`` on the CPU. A ``--lanes`` that
    names another count is an error, never a silent resize."""
    import jax
    n = len(jax.devices())
    if requested not in (None, n):
        raise SystemExit(f"{flag} runs one lane per device: --lanes "
                         f"{requested}, but JAX sees {n} devices")
    return n


def auto_mesh(shape: Sequence[int], names: Sequence[str]) -> "Mesh":
    """`jax.make_mesh` with every axis in Auto mode: the compiler places
    the collectives, as the shard_map stages here expect (the installed
    JAX makes Explicit axes by default, under which plain indexing of a
    lane-sharded result is refused)."""
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> "Mesh":
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> "Mesh":
    """Tiny mesh over however many (CPU) devices exist — used by tests."""
    import jax
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return auto_mesh((data, model), ("data", "model"))


def make_machine_mesh(m: int, b: int, axis_prefix: str = "lvl") -> "Mesh":
    """Mesh for the GreedyML accumulation tree: m = b^L machines factored as
    an L-dim mesh (b, …, b); level-ℓ accumulation all-gathers over axis
    f"{axis_prefix}{ℓ}". Axis 0 is the innermost digit of the machine id,
    matching the paper's parent(id, i) = b^i · floor(id / b^i)."""
    if m <= 0 or b <= 1:
        raise ValueError(f"need m>0, b>1; got m={m} b={b}")
    L = int(round(math.log(m, b)))
    if b ** L != m:
        raise ValueError(f"shard_map tree driver needs m=b^L; got m={m} b={b} "
                         f"(use core.simulate for ragged trees)")
    shape = (b,) * L
    axes = tuple(f"{axis_prefix}{i}" for i in range(L))
    # NOTE: jax meshes are row-major (last axis fastest-varying); the paper's
    # machine id has level-0 groups in the LOW digits, so reverse the axes.
    return auto_mesh(shape, tuple(reversed(axes)))


def make_tree_mesh(radices: Sequence[int], shard: int = 1,
                   axis_prefix: str = "lvl",
                   shard_axis: str = "shard") -> "Mesh":
    """Mesh for a PLANNED accumulation tree (plans.plan_tree → TreePlan):
    one axis per tree level (level ℓ gathers over f"{axis_prefix}{ℓ}")
    plus, when shard > 1, an innermost ``shard_axis`` holding the lanes
    that cooperate on each leaf through the sharded engine. Device order
    has the shard digit fastest, then the level-0 digit — lane =
    machine·shard + shard_digit, LevelDispatcher's layout."""
    radices = tuple(int(r) for r in radices)
    if not radices and shard <= 1:
        raise ValueError("empty tree with no sharding needs no mesh")
    shape = tuple(reversed(radices))
    names = tuple(reversed([f"{axis_prefix}{i}"
                            for i in range(len(radices))]))
    if shard > 1:
        shape += (shard,)
        names += (shard_axis,)
    return auto_mesh(shape, names)


def mesh_devices(mesh: "Mesh") -> int:
    return math.prod(mesh.shape.values())


def factor_tree_axes(mesh: "Mesh",
                     leaf_axes: Tuple[str, ...]) -> Tuple[str, ...]:
    """Order existing mesh axes into accumulation-tree levels (innermost
    level first). Used to run GreedyML directly on the production mesh:
    512 devices = (model=16, data=16, pod=2) → mixed-radix tree, L=3."""
    return tuple(reversed([a for a in leaf_axes if a in mesh.shape]))
