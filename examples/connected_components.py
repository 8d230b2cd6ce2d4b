"""Shiloach-Vishkin connectivity — the paper's flagship example (Fig. 6).

    PYTHONPATH=src python examples/connected_components.py

Shows the features Green-Marl/Fregel can't express (paper §5):
* chain access ``D[D[u]]`` — compiled by the logic system (§4.1.1);
* a remote accumulative write ``remote D[D[u]] <?= t``;
and the three execution regimes: fused dense (production), staged BSP with
the pull schedule, staged BSP with the naive request/reply schedule (the
hand-written-code stand-in).
"""

import time

import numpy as np

from repro import compile_cache
from repro.core import compile_program
from repro.core import algorithms as alg
from repro.core.logic import pull_rounds, push_rounds
from repro.graph import generators as G
from repro.pregel import run_bsp


def main():
    compile_cache.enable()
    print("chain-access compilation (paper §4.1.1):")
    for k in (2, 3, 4, 8):
        pat = ("D",) * k
        print(f"  D^{k}[u]: paper push schedule = {push_rounds(pat)} rounds,"
              f" pull schedule = {pull_rounds(pat)} rounds,"
              f" naive request/reply = {2 * (k - 1)} rounds")

    g = G.rmat(11, avg_degree=6, directed=False, seed=3)
    print(f"\ngraph: {g.n_vertices} vertices")
    cp = compile_program(alg.SV, g)

    t0 = time.perf_counter()
    out, trips, counts = cp.run()
    t_fused = time.perf_counter() - t0
    D = np.asarray(out["D"])
    n_components = len(np.unique(D))
    print(f"components: {n_components}; iterations: {trips[0]}")

    f0 = cp.init_fields()
    t0 = time.perf_counter()
    bsp_pull = run_bsp(cp.prog, g, f0, schedule="pull")
    t_pull = time.perf_counter() - t0
    t0 = time.perf_counter()
    # the manual-style baseline keeps the unfused request/reply expansion
    bsp_naive = run_bsp(cp.prog, g, f0, schedule="naive", fuse=False)
    t_naive = time.perf_counter() - t0

    assert np.array_equal(D, np.asarray(bsp_pull.fields["D"]))
    assert np.array_equal(D, np.asarray(bsp_naive.fields["D"]))

    print("\nexecution regimes (identical results):")
    print(f"  fused dense (palgol):   {counts['palgol_push']:3d} supersteps"
          f" (accounted) {t_fused * 1e3:9.1f} ms")
    print(f"  staged BSP, pull:       {bsp_pull.supersteps:3d} supersteps"
          f" (executed)  {t_pull * 1e3:9.1f} ms")
    print(f"  staged BSP, naive:      {bsp_naive.supersteps:3d} supersteps"
          f" (executed)  {t_naive * 1e3:9.1f} ms")
    red = 100 * (1 - counts["palgol_push"] / counts["naive"])
    print(f"\nsuperstep reduction vs naive: {red:.1f}% "
          "(paper reports 46.5–51.7% for S-V)")


if __name__ == "__main__":
    main()
