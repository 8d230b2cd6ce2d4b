"""Paper Table 4 analogue: execution time, Palgol-compiled vs manual-style.

The paper compares compiler-generated Pregel+ code against hand-written
implementations (−25.9% … +32.4%). Our analogue on one host:

* ``palgol``  — the dense compiled program: ONE fused XLA computation
  (state merging + iteration fusion taken to their limit on a
  shared-address-space machine); termination check fused into the
  while-loop (the compiled aggregator).
* ``manual``  — the staged BSP executor with the *naive* schedule:
  one device dispatch per superstep, request/reply chain reads, host-side
  aggregator round-trip per iteration — the execution shape of typical
  hand-written Pregel code.

Same runtime, same graph, same results (asserted) — the measured gap is
the cost of superstep structure, which is exactly what the paper's
compiler optimizes.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import row, time_fn
from repro.core import algorithms as alg
from repro.core import compile_program
from repro.graph import generators as G
from repro.pregel import run_bsp


def run(scale: int = 10):
    out = []
    gu = G.rmat(scale, avg_degree=8, directed=False, seed=1)
    gd = G.rmat(scale, avg_degree=8, directed=True, weighted=True, seed=2)
    cases = [
        ("sv", alg.SV, gu, None),
        ("sssp", alg.SSSP, gd, None),
        ("pagerank", alg.PAGERANK, gd, None),
    ]
    for name, src, g, fields in cases:
        cp = compile_program(src, g, initial_fields=fields)
        f0 = cp.init_fields(fields)

        import jax

        fused = jax.jit(cp.fn)
        us_palgol = time_fn(fused, f0, g, warmup=1, iters=3)
        dense_out, _ = fused(f0, g)

        def manual(f0=f0, prog=cp.prog, g=g):
            # the manual baseline has no §4.3 merging/fusion: fuse=False
            return run_bsp(prog, g, f0, schedule="naive", fuse=False).fields

        # run_bsp jits per-stage internally; warm indirectly via one call
        import time as _t

        manual_out = manual()
        times = []
        for _ in range(3):
            t0 = _t.perf_counter()
            manual(), (_t.perf_counter() - t0)
            times.append(_t.perf_counter() - t0)
        us_manual = sorted(times)[1] * 1e6

        # same results (float fields compared loosely)
        for fkey in dense_out:
            a = np.asarray(dense_out[fkey])
            b = np.asarray(manual_out[fkey])
            if a.dtype.kind == "f":
                assert np.allclose(a, b, rtol=1e-4, atol=1e-5, equal_nan=True)
            else:
                assert np.array_equal(a, b), fkey

        ratio = us_manual / max(us_palgol, 1e-9)
        out.append(row(f"table4/{name}/palgol", us_palgol,
                       f"speedup_vs_manual={ratio:.2f}x"))
        out.append(row(f"table4/{name}/manual", us_manual, ""))
    return out
