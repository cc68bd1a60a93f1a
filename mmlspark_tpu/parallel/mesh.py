"""Device mesh management — the cluster-topology layer.

Replaces the reference's ``ClusterUtil`` executor/task discovery
(core/utils/ClusterUtil.scala:13-177): where MMLSpark sizes its gang by
querying the BlockManager for executors x cores, the TPU framework sizes
SPMD programs by the JAX device mesh (hosts x chips over ICI/DCN).

Axis conventions:
- ``data``  — batch (data-parallel) axis; collectives ride ICI.
- ``model`` — tensor-parallel axis for backbones exceeding one chip's HBM.
A 1-D ``data`` mesh is the default, matching the reference's rows-only
parallelism (SURVEY.md §2.18).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

_default_mesh: Optional[Mesh] = None


def make_mesh(
    shape: Optional[dict] = None, devices: Optional[Sequence] = None
) -> Mesh:
    """Build a mesh. ``shape`` maps axis name -> size; one size may be -1
    (inferred). Default: all devices on a 1-D ``data`` axis."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not shape:
        shape = {DATA_AXIS: n}
    names = list(shape.keys())
    sizes = list(shape.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    known = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    if -1 in sizes:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(f"mesh shape {dict(zip(names, sizes))} != {n} devices")
    arr = np.array(devices).reshape(sizes)
    return Mesh(arr, axis_names=tuple(names))


def get_mesh() -> Mesh:
    """The process-wide default mesh (created on first use)."""
    global _default_mesh
    if _default_mesh is None:
        _default_mesh = make_mesh()
    return _default_mesh


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _default_mesh
    _default_mesh = mesh


def device_count() -> int:
    return len(jax.devices())


def local_device_count() -> int:
    return jax.local_device_count()


def cluster_summary() -> dict:
    """Topology report (the ``ClusterUtil.getExecutors`` analogue)."""
    devs = jax.devices()
    hosts: dict = {}
    for d in devs:
        hosts.setdefault(d.process_index, []).append(d.id)
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "num_devices": len(devs),
        "num_hosts": jax.process_count(),
        "host_devices": {str(k): v for k, v in sorted(hosts.items())},
        "process_index": jax.process_index(),
    }


def data_sharding(mesh: Mesh, ndim: int, axis: str = DATA_AXIS) -> NamedSharding:
    """Sharding that splits axis 0 (batch) over ``axis``, replicating the rest."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
