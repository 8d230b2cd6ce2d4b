"""Graph substrate: dense, statically-shaped graph representation + segment ops.

This layer is shared by the Pregel runtime (``repro.pregel``), the Palgol
compiler's generated code (``repro.core.codegen``) and the partitioned
placement (``repro.graph.partition``). Everything here is pure JAX and jit
friendly.
"""

from repro.graph.structure import Graph, from_edge_list, symmetrize, pad_edges
from repro.graph.ops import (
    segment_reduce,
    gather,
    scatter_combine,
    out_degrees,
    in_degrees,
    COMBINE_IDENTITY,
)

__all__ = [
    "Graph",
    "from_edge_list",
    "symmetrize",
    "pad_edges",
    "segment_reduce",
    "gather",
    "scatter_combine",
    "out_degrees",
    "in_degrees",
    "COMBINE_IDENTITY",
]
