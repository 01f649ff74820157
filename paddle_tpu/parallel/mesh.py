"""Device-mesh registry: the NCCL comm registry, TPU-native.

Replaces the reference's (ring_id, place) -> NCCLComm registry
(reference: paddle/fluid/platform/collective_helper.h:50-69
NCCLCommContext) with named `jax.sharding.Mesh` axes: a ring_id used by
`c_*` collective ops maps to a mesh axis name, and hierarchical /
multi-ring allreduce (reference: nccl_op_handle.h, `nccl_comm_num`)
becomes a multi-axis mesh (ICI within a slice × DCN across slices) that
XLA's collectives exploit natively.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class MeshRegistry:
    def __init__(self):
        self._meshes: Dict[str, "jax.sharding.Mesh"] = {}
        self._ring_axes: Dict[int, Tuple[str, str]] = {}  # ring_id -> (mesh, axis)
        self._current: Optional[str] = None

    def create_mesh(self, shape: Sequence[int], axis_names: Sequence[str],
                    name: str = "default", devices=None):
        import jax
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        n = int(np.prod(shape))
        if n > len(devices):
            raise ValueError(
                f"mesh shape {tuple(shape)} needs {n} devices, have {len(devices)}"
            )
        arr = np.array(devices[:n]).reshape(shape)
        mesh = Mesh(arr, tuple(axis_names))
        self._meshes[name] = mesh
        self._current = name
        # default ring 0 -> first data axis
        if 0 not in self._ring_axes:
            self._ring_axes[0] = (name, axis_names[0])
        return mesh

    def get(self, name: str = None):
        if name is None:
            name = self._current
        if name is None or name not in self._meshes:
            return None
        return self._meshes[name]

    def register_ring(self, ring_id: int, axis_name: str, mesh_name: str = None):
        """reference: CreateNCCLComm(collective_helper.h:69) — a comm ring
        becomes a mesh axis."""
        self._ring_axes[ring_id] = (mesh_name or self._current or "default",
                                    axis_name)

    def axis_for_ring(self, ring_id: int) -> Optional[str]:
        entry = self._ring_axes.get(ring_id)
        if entry is None:
            entry = self._ring_axes.get(0)
        return entry[1] if entry else None

    def clear(self):
        self._meshes.clear()
        self._ring_axes.clear()
        self._current = None


_registry = MeshRegistry()


def registry() -> MeshRegistry:
    return _registry


def init_mesh(shape=None, axis_names=("dp",), name="default", devices=None):
    """Create + register the default mesh.  With shape=None, a 1-D 'dp'
    mesh over all devices."""
    import jax

    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = (len(devices),)
    return _registry.create_mesh(shape, axis_names, name, devices)


def current_mesh():
    return _registry.get()


def ring_axis_size(ring_id: int = 0) -> int:
    """Size of the mesh axis a collective ring maps to (1 when no mesh
    is registered) — the `nranks` a graph pass needs to decide shard
    eligibility at compile time."""
    mesh = _registry.get()
    if mesh is None:
        return 1
    axis = _registry.axis_for_ring(ring_id)
    if axis is None or axis not in mesh.shape:
        axis = mesh.axis_names[0]
    return int(mesh.shape[axis])


def default_dp_mesh(num_devices: Optional[int] = None):
    """Get-or-create the 1-D data-parallel mesh used by
    CompiledProgram.with_data_parallel when the user didn't configure one."""
    import jax

    mesh = _registry.get()
    if mesh is not None:
        return mesh
    devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return init_mesh((len(devices),), ("dp",))


def world_size() -> int:
    mesh = current_mesh()
    return int(mesh.size) if mesh is not None else 1
