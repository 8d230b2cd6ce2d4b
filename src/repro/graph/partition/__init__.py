"""Partitioned vertex state with halo exchange (`repro.graph.partition`).

Ends the replicated-state scaling wall: instead of every chip holding every
vertex field, the vertex id space is split into contiguous, edge-balanced
ranges — one per shard — and each superstep moves only *boundary* state:

* :mod:`~repro.graph.partition.partitioner` — the edge-balanced greedy
  prefix-split partitioner and the :class:`PartitionedGraph` pytree
  (per-shard local COO with remapped local ids, static halo indices,
  owner maps);
* :mod:`~repro.graph.partition.halo` — shard_map collectives:
  ``halo_exchange`` (static ghost reads), ``gather_global`` (dynamic
  request/reply reads — pointer doubling rebuilds its request set from the
  current indirection field every round), ``scatter_reduce`` (combiner-aware
  reduce-scatter for remote writes);
* :mod:`~repro.graph.partition.on_mesh` — ``partition_on_mesh``: the same
  ``PartitionedGraph`` built on the chips from an edge list already spread
  over the mesh (the host ``partition_graph`` is its plain reference);
* :mod:`~repro.graph.partition.executor` — ``PartitionedProgram`` (a
  program prepared once per graph and mesh, run many times) and
  ``run_bsp_partitioned``: the ``placement="partitioned"`` path of
  ``repro.pregel.run_bsp``, executing unchanged Palgol programs over the
  partitioned layout;
* :mod:`~repro.graph.partition.stats` — communication accounting
  (``comm_bytes_report``), and ``byte_cost_model`` — the measured
  halo/request-set figures instrumented into a
  :class:`repro.core.plan.ByteCostModel` for the byte-aware ``auto``
  schedule selector.
"""

from repro.graph.partition.partitioner import (  # noqa: F401
    HaloSpec,
    PartitionedGraph,
    edge_balanced_ranges,
    partition_field,
    partition_fields,
    partition_graph,
    unpartition_field,
    unpartition_fields,
)
from repro.graph.partition.on_mesh import (  # noqa: F401
    partition_on_mesh,
    route,
    sort_blocks,
    sorted_length,
)
from repro.graph.partition.executor import (  # noqa: F401
    PartitionedProgram,
    run_bsp_partitioned,
)
from repro.graph.partition.stats import (  # noqa: F401
    byte_cost_model,
    comm_bytes_report,
    partition_stats,
    request_dedup_report,
)
