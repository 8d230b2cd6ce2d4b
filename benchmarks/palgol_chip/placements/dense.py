"""The fused dense placement: one jitted call per job on one device.

``compile_program(text, graph, initial_fields)`` once, then
``CompiledProgram.run(user_fields)`` per job: the whole program, its
fixed-point loops included, is one XLA computation on the device that
holds the graph.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class Job:
    """A compiled Palgol program bound to one resident graph."""

    def __init__(self, text: str, graph, inputs: dict):
        from repro.core import compile_program

        self.program = compile_program(text, graph, initial_fields=inputs)

    def warm(self, inputs: dict) -> None:
        """Compile every program a job runs, without running a job: the
        jitted program for these field shapes, and the small operations
        ``run`` applies to its inputs and to the trip counts."""
        cp = self.program
        fields = cp.init_fields(inputs)
        # CompiledProgram.run calls this very jit object: lowering and
        # compiling it here fills the cache its call looks up
        compiled = cp._jitted_fn.lower(fields, cp.graph).compile()
        #: the executable's HLO, which names what each fusion does
        self.hlo_text = compiled.as_text()
        trips = jnp.zeros((max(cp.n_iters, 1),), jnp.int32)
        [int(x) for x in trips]

    def run(self, inputs: dict, result: str):
        """One job: ``(result field on the host, trips, superstep counts,
        itemsize of every output field)``."""
        with jax.profiler.TraceAnnotation("palgol.call"):
            out, trips, counts = self.program.run(inputs)
        with jax.profiler.TraceAnnotation("palgol.fetch"):
            host = np.asarray(out[result])
        itemsize = {k: v.dtype.itemsize for k, v in out.items()}
        return host, trips, counts, itemsize


def prepare(text: str, graph, inputs: dict) -> Job:
    return Job(text, graph, inputs)
