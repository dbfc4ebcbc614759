"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE first init).

Graded meshes (the brief):
  * single-pod:  (16, 16)      axes ("data", "model")   = 256 chips
  * multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

Axis roles (DESIGN.md §5): FSDP/DP over ("pod", "data") — the DSU pool
serving feature data; TP/SP/EP over "model" — the VPU pool holding
resident weight shards.

Every mesh in the repo is built here, with Auto-typed axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_abstract_mesh(shape, axes):
    """Device-free mesh for spec resolution (tests, planning tools)."""
    return AbstractMesh(tuple(shape), tuple(axes),
                        axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh (tests, examples, elastic restarts)."""
    return _mk(tuple(shape), tuple(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist in this process."""
    return _mk((data, model), ("data", "model"))


# the near-memory serving axis: the UniMem page arena shards over it
MEM_AXIS = "mem"


def make_mem_mesh(shards: int | None = None) -> Mesh:
    """1-D serving mesh over the near-memory MEM_AXIS: the UniMem page
    arena shards across it (pages resident per chip, queries broadcast,
    softmax summaries reduced — DESIGN.md §2).  Defaults to every device
    in the process; a 1-device mesh degrades the sharded serving path to
    the plain single-arena one."""
    shards = shards or jax.device_count()
    return _mk((shards,), (MEM_AXIS,))


def dp_width(mesh: Mesh) -> int:
    """Data-parallel width = product of the DSU axes present."""
    w = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            w *= mesh.shape[a]
    return w
