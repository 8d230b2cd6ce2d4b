"""Distribution layer: the vertex-partition shardings of a partitioned job.

``repro.dist.sharding`` names the one mesh axis (``SHARD``) a partitioned
Palgol job runs over, and places a ``PartitionedGraph`` and its fields on it.
"""

from repro.dist import sharding  # noqa: F401

__all__ = ["sharding"]
