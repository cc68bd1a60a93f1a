"""Collective wrappers over ICI/DCN.

The TPU-native replacement for all three of the reference's communication
backends (SURVEY.md §5.8): LightGBM's socket ring allreduce
(TrainUtils.scala:496-512), VW's driver spanning tree
(VowpalWabbitBase.scala:401-429) and the hand-rolled driver TCP rendezvous
(LightGBMUtils.scala:116-185) all collapse into XLA collectives on a named
mesh axis — gang semantics come from SPMD program launch, not barriers.

Use inside ``shard_map``-ped / ``pmap``-ped functions.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from mmlspark_tpu.parallel.mesh import DATA_AXIS, get_mesh


def allreduce_sum(x: Any, axis: str = DATA_AXIS) -> Any:
    return jax.lax.psum(x, axis_name=axis)


def allreduce_mean(x: Any, axis: str = DATA_AXIS) -> Any:
    return jax.lax.pmean(x, axis_name=axis)


def allreduce_max(x: Any, axis: str = DATA_AXIS) -> Any:
    return jax.lax.pmax(x, axis_name=axis)


def all_gather(x: Any, axis: str = DATA_AXIS, tiled: bool = True) -> Any:
    return jax.lax.all_gather(x, axis_name=axis, tiled=tiled)


def reduce_scatter(x: Any, axis: str = DATA_AXIS) -> Any:
    return jax.lax.psum_scatter(x, axis_name=axis, tiled=True)


def ring_permute(x: Any, axis: str = DATA_AXIS, shift: int = 1) -> Any:
    """Neighbor exchange on the ring (building block for ring attention /
    pipelined allreduce)."""
    n = jax.lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: str = DATA_AXIS) -> jnp.ndarray:
    return jax.lax.axis_index(axis)


def shard_apply(
    fn: Callable,
    mesh: Optional[Mesh] = None,
    in_specs: Any = P(DATA_AXIS),
    out_specs: Any = P(DATA_AXIS),
) -> Callable:
    """``shard_map`` convenience wrapper bound to the default mesh.

    Replication checking is off (as at every other shard_map site here):
    the pmean-in-scan-carry pattern (vw/learner.py) legitimately moves
    arrays between replicated and varying."""
    mesh = mesh or get_mesh()
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
