"""Vertex-partition shardings: the 1-D ``("shard",)`` mesh and the specs
that place a ``PartitionedGraph`` and its fields on it.

A partitioned job (``repro.graph.partition``) gives each device one
contiguous vertex range. Its per-shard arrays are ``[S, ...]`` blocks whose
leading dim shards over :data:`SHARD`; anything whose leading dim the shard
axis does not divide (the ``[S+1]`` owner map, scalars) replicates.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD = "shard"  #: the 1-D vertex-partition axis (``repro.graph.partition``)


def _maybe(shape: Sequence[int], mesh: Mesh) -> P:
    """The leading dim of ``shape`` over :data:`SHARD` where the shard axis
    divides it evenly, else replicated (the constraint would be rejected)."""
    return P(SHARD if shape[0] % mesh.shape[SHARD] == 0 else None)


def shard_mesh(n_shards: Optional[int] = None, devices=None) -> Mesh:
    """1-D ``("shard",)`` mesh for partitioned vertex state.

    The partitioned Pregel engine flattens whatever devices it is given
    into one shard axis — one contiguous vertex range per device. Defaults
    to every local device; pass ``n_shards`` to use a prefix of them.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    if n_shards is not None:
        if n_shards > len(devs):
            raise ValueError(
                f"n_shards={n_shards} exceeds available devices ({len(devs)})"
            )
        devs = devs[:n_shards]
    return Mesh(np.array(devs), (SHARD,))


def vertex_partition_spec(ndim: int = 2) -> P:
    """Spec for a ``[S, ...]`` per-shard block array: leading dim over
    :data:`SHARD`, everything else replicated."""
    return P(SHARD, *(None,) * (ndim - 1))


def vertex_partition_shardings(tree, mesh: Mesh):
    """Pytree of ``NamedSharding`` for partitioned per-shard arrays.

    Leading dims that the shard axis divides evenly (the ``[S, ...]``
    blocks of a ``PartitionedGraph`` and of partitioned fields) shard over
    :data:`SHARD`; everything else — the ``[S+1]`` owner map, scalars —
    replicates, per :func:`_maybe`.
    """

    def leaf_sharding(leaf):
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, _maybe(shape, mesh) if shape else P())

    return jax.tree_util.tree_map(leaf_sharding, tree)
