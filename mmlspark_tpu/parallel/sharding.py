"""Batch/param placement helpers for SPMD execution.

Where the reference broadcasts native models to executors and maps rows per
partition (cntk/CNTKModel.scala:411-413,515-520), here weights are
*replicated* onto the mesh once and batches are *batch-sharded* over the
``data`` axis; XLA inserts the collectives.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mmlspark_tpu.parallel.mesh import DATA_AXIS, get_mesh


def pad_batch(arr: np.ndarray, multiple: int) -> tuple:
    """Pad axis 0 up to a multiple (fixed shapes avoid XLA recompiles — the
    load-bearing TPU analogue of FixedMiniBatchTransformer). Returns
    (padded, real_n)."""
    n = arr.shape[0]
    target = max(multiple, ((n + multiple - 1) // multiple) * multiple)
    if target == n:
        return arr, n
    pad_width = [(0, target - n)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad_width), n


def shard_batch(tree: Any, mesh: Optional[Mesh] = None, axis: str = DATA_AXIS) -> Any:
    """Place a pytree of host arrays batch-sharded over the mesh.

    Axis-0 of every leaf must divide by the mesh axis size (use
    ``pad_batch`` first)."""
    mesh = mesh or get_mesh()

    def put(x: Any) -> Any:
        x = np.asarray(x)
        sh = NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1))))
        return jax.device_put(x, sh)

    return jax.tree_util.tree_map(put, tree)


def multihost_pad_target(n_local: int) -> int:
    """Common per-process row count so every process contributes an equal
    shard to a global array: max local count across processes, rounded up
    to the local device count. Assumes the data axis spans all devices
    (the default ``get_mesh()`` layout)."""
    import jax.experimental.multihost_utils as mhu

    counts = mhu.process_allgather(np.asarray([n_local], np.int64))
    ldc = jax.local_device_count()
    m = int(np.max(counts))
    return ((m + ldc - 1) // ldc) * ldc


def shard_batch_multihost(
    tree: Any, mesh: Optional[Mesh] = None, axis: str = DATA_AXIS
) -> Any:
    """Process-LOCAL rows -> one global row-sharded array per leaf.

    Each process contributes its local block; the global shape stacks the
    blocks in process order (jax.make_array_from_process_local_data). The
    multi-host counterpart of :func:`shard_batch` — the reference's
    per-machine native dataset build before its socket allreduce
    (TrainUtils.scala:26-66)."""
    mesh = mesh or get_mesh()
    nproc = jax.process_count()

    def put(x: Any) -> Any:
        x = np.asarray(x)
        global_shape = (x.shape[0] * nproc,) + x.shape[1:]
        sh = NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1))))
        return jax.make_array_from_process_local_data(sh, x, global_shape=global_shape)

    return jax.tree_util.tree_map(put, tree)


def replicate(tree: Any, mesh: Optional[Mesh] = None) -> Any:
    """Replicate a pytree (weights) across the mesh — the broadcast analogue.

    A leaf that already lives on the mesh's devices, whole on each, is
    handed back as it is: weights made on the device (gigabytes of them) are
    neither copied nor given a second buffer."""
    mesh = mesh or get_mesh()
    sh = NamedSharding(mesh, P())

    def put(x: Any) -> Any:
        if isinstance(x, jax.Array) and x.is_fully_replicated \
                and x.sharding.device_set == sh.device_set:
            return x
        return jax.device_put(x, sh)

    return jax.tree_util.tree_map(put, tree)
