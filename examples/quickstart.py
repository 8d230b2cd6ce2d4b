"""Quickstart: write a Palgol program, compile it, run it on a graph.

    PYTHONPATH=src python examples/quickstart.py

Single-source shortest path (the paper's Fig. 4), end to end:
parse → analyze remote-access patterns → compile to one fused XLA
computation → execute → compare superstep accounting across compilers.
"""

import numpy as np

from repro import compile_cache
from repro.core import compile_program, interpret
from repro.core import algorithms as alg
from repro.graph import generators as G


def main():
    compile_cache.enable()
    # a weighted power-law digraph (RMAT, ~1k vertices)
    g = G.rmat(10, avg_degree=8, directed=True, weighted=True, seed=7)
    print(f"graph: {g.n_vertices} vertices, {int(np.asarray(g.edge_mask).sum())} edges")

    print("\n--- Palgol source (paper Fig. 4) ---")
    print(alg.SSSP.strip())

    cp = compile_program(alg.SSSP, g)
    out, trips, counts = cp.run()
    D = np.asarray(out["D"])
    finite = np.isfinite(D)
    print(f"\nreachable vertices: {finite.sum()}; "
          f"max distance: {D[finite].max():.3f}; iterations: {trips[0]}")

    active_sets = counts.pop("active_sets")
    print(f"vertices changed per trip: {active_sets[0]}")
    print("\nsuperstep accounting (paper Table 5 analogue):")
    for k, v in counts.items():
        print(f"  {k:12} {v}")

    # cross-check against the per-vertex reference interpreter
    ref, _ = interpret(alg.SSSP, g)
    assert np.allclose(D, ref["D"], rtol=1e-4, equal_nan=True)
    print("\noracle check: compiled result == naive interpreter ✓")


if __name__ == "__main__":
    main()
