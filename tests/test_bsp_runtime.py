"""Staged BSP executor: result equality + superstep accounting validation."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import compile_program
from repro.graph import generators as G
from repro.pregel import run_bsp


def _setup(name, seed):
    fields = None
    if name in ("sssp", "pagerank", "scc"):
        g = G.erdos_renyi(40, 4.0, directed=True, weighted=True, seed=seed)
    elif name == "bipartite_matching":
        g, side = G.random_bipartite(15, 15, 3.0, seed=seed)
        fields = {"Side": jnp.asarray(side)}
    elif name == "mis":
        g = G.erdos_renyi(40, 3.0, directed=False, seed=seed)
        rng = np.random.default_rng(seed)
        fields = {"P": jnp.asarray(rng.random(g.n_vertices), jnp.float32)}
    elif name == "chain4":
        g = G.erdos_renyi(30, 2.0, directed=False, seed=seed)
        rng = np.random.default_rng(seed)
        fields = {"D": jnp.asarray(rng.integers(0, 30, 30), jnp.int32)}
    else:
        g = G.erdos_renyi(40, 3.0, directed=False, weighted=True, seed=seed)
        if name == "kcore":
            fields = {"K": jnp.full((g.n_vertices,), 2, jnp.int32)}
    return g, fields


ALGS = [
    "sssp",
    "sv",
    "wcc",
    "mis",
    "bipartite_matching",
    "mwm",
    "chain4",
    "pagerank",
    "scc",
    "bfs",
    "kcore",
]


@pytest.mark.parametrize("name", ALGS)
def test_bsp_matches_dense(name):
    g, fields = _setup(name, seed=3)
    cp = compile_program(alg.ALL[name], g, initial_fields=fields)
    dense, trips, counts = cp.run(fields)
    f0 = cp.init_fields(fields)
    for schedule in ("pull", "naive"):
        res = run_bsp(cp.prog, g, f0, schedule=schedule)
        for f in dense:
            a, b = np.asarray(dense[f]), np.asarray(res.fields[f])
            if a.dtype == np.float32:
                assert np.allclose(a, b, rtol=1e-5, equal_nan=True), (name, f)
            else:
                assert np.array_equal(a, b), (name, schedule, f)


@pytest.mark.parametrize("name", ALGS)
def test_superstep_accounting_matches_execution(name):
    """The STM cost models must predict the staged executor's actual count
    — fused (the default, ``palgol_*``/``fused_*`` models) and unfused
    (``fuse=False``, the historical per-op expansion) alike."""
    g, fields = _setup(name, seed=4)
    cp = compile_program(alg.ALL[name], g, initial_fields=fields)
    _, trips, counts = cp.run(fields)
    f0 = cp.init_fields(fields)
    exec_pull = run_bsp(cp.prog, g, f0, schedule="pull")
    assert exec_pull.supersteps == counts["palgol_pull"], name
    exec_naive = run_bsp(cp.prog, g, f0, schedule="naive")
    assert exec_naive.supersteps == counts["fused_naive"], name
    exec_pull_unfused = run_bsp(cp.prog, g, f0, schedule="pull", fuse=False)
    assert exec_pull_unfused.supersteps == counts["pull_staged"], name
    exec_naive_unfused = run_bsp(cp.prog, g, f0, schedule="naive", fuse=False)
    assert exec_naive_unfused.supersteps == counts["naive"], name


def test_sv_superstep_reduction_structure():
    """Paper Table 5: S-V compiled by Palgol takes ~half the supersteps of
    the manual (request/reply, unfused) implementation."""
    g = G.erdos_renyi(200, 4.0, directed=False, seed=9)
    cp = compile_program(alg.SV, g)
    _, trips, counts = cp.run()
    reduction = 1 - counts["palgol_push"] / counts["naive"]
    assert reduction >= 0.35  # paper reports 46–52%
    # beyond-paper pull schedule is at least as good
    assert counts["palgol_pull"] <= counts["palgol_push"]


def test_pagerank_superstep_parity():
    """Paper Table 5: PR Palgol == manual superstep count (fusion makes the
    nbr-send free; manual message-driven PR is 1/iteration too)."""
    g = G.erdos_renyi(100, 4.0, directed=True, seed=10)
    cp = compile_program(alg.PAGERANK, g)
    _, trips, counts = cp.run()
    iters = trips[0]
    # fused: init-step + iter-init merged + 1/iter
    assert counts["palgol_push"] == iters + 1
    assert counts["palgol_pull"] == iters + 1
