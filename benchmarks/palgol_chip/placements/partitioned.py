"""The partitioned placement: one contiguous vertex range per chip, the
program's supersteps dispatched one ``shard_map`` call each.

``prepare`` builds a ``repro.graph.partition.PartitionedProgram`` once,
over the graph's ``PartitionedGraph`` on the graph's mesh (a
``graphs/kronecker_mesh.py`` graph); each job walks the program's plan
on the host and runs every superstep on all the chips, exchanging halos,
chain reads and remote writes between them.
"""

from __future__ import annotations

import jax
import numpy as np


class Job:
    """A Palgol program prepared for one resident partitioned graph."""

    def __init__(self, text: str, graph, inputs: dict):
        from repro.core import compile_program
        from repro.graph.partition import PartitionedProgram
        from repro.graph.structure import from_edge_list

        # compile_program reads a Graph for its vertex count and the
        # fields' shapes alone: a one-edge graph of as many vertices does
        shapes = from_edge_list(np.array([0]), np.array([1]),
                                graph.n_vertices)
        self.fields = compile_program(text, shapes, initial_fields=inputs)
        self.program = PartitionedProgram(
            self.fields.prog, graph.partitioned, graph.mesh
        )
        #: the HLO text the trace reduction names fusions by: the
        #: supersteps are separate executables whose names collide, so none
        self.hlo_text = ""

    def warm(self, inputs: dict) -> None:
        """Obtain every executable a job uses (``PartitionedProgram.warm``:
        one job cut to two trips of each loop)."""
        self.program.warm(self.fields.init_fields(inputs))

    def run(self, inputs: dict, result: str):
        """One job: ``(result field on the host, trips, counters, itemsize
        of every output field)``; the counters are the superstep
        dispatches (``dispatches``), the bytes per chip the collectives
        carried (``comm_bytes``), the frontier of every trip
        (``active_sets``) and the chips (``n_shards``)."""
        with jax.profiler.TraceAnnotation("palgol.call"):
            res = self.program.run(self.fields.init_fields(inputs))
        with jax.profiler.TraceAnnotation("palgol.fetch"):
            host = np.asarray(res.fields[result])
        counts = {
            "dispatches": res.supersteps,
            "comm_bytes": res.comm_bytes,
            "active_sets": res.active_sets,
            "n_shards": self.program.pg.n_shards,
        }
        itemsize = {k: v.dtype.itemsize for k, v in res.fields.items()}
        return host, res.trips, counts, itemsize


def prepare(text: str, graph, inputs: dict) -> Job:
    return Job(text, graph, inputs)
