"""Which path each edge reduction of a compiled program takes.

A graph made from its eight edge arrays, as the on-chip benchmark makes
its Graph500 graphs on the device, has no run ends: ``compile_program``
computes them, and every order-independent reduction then scans the sorted edges.
The fields must be the scatter path's and the interpreter's, to the bit.
Under the partitioned placement the shards' edges carry their run ends
(``PartitionedGraph.in_ends``/``out_ends``), so the same reductions scan
there too.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import compile_program, interpret
from repro.graph import generators as G
from repro.graph.structure import Graph
from repro.pregel.runtime import run_bsp
from repro.trace import counted


def _bare(g) -> Graph:
    """``g`` rebuilt from its eight edge arrays, without run ends."""
    names = ("src", "dst", "weight", "edge_mask",
             "t_src", "t_dst", "t_weight", "t_mask")
    return Graph(**{k: getattr(g, k) for k in names},
                 n_vertices=g.n_vertices, n_edges=g.n_edges)


def _graph(name, seed):
    if name == "sssp":
        return G.rmat(7, 6.0, directed=True, weighted=True, seed=seed)
    return G.rmat(7, 6.0, directed=False, seed=seed)


def _same_fields(a, b):
    for f in a:
        x, y = np.asarray(a[f]), np.asarray(b[f])
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), f


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", ["sv", "wcc", "sssp"])
def test_a_graph_without_ends_gets_them_and_scans(name, seed):
    g = _bare(_graph(name, seed))
    assert g.in_ends is None and g.out_ends is None
    cp = compile_program(alg.ALL[name], g)
    assert cp.graph.in_ends is not None  # every program here reads In/Nbr
    out, trips, _ = cp.run()
    assert cp.edge_reduce_paths["scan"] >= 1
    assert cp.edge_reduce_paths["scatter"] == 0
    # the same program on the graph without ends takes the scatter, and
    # without the weights' bound SSSP keeps its filter per edge
    fields = cp.init_fields()
    scatter, scatter_trips, _ = cp.fn(fields, g)
    assert cp.edge_reduce_paths == {
        "scan": 0, "scatter": cp.edge_reduce_paths["scatter"], "fold": 0
    } and cp.edge_reduce_paths["scatter"] >= 1
    _same_fields(out, scatter)
    assert trips == np.asarray(scatter_trips).tolist()
    ref, _ = interpret(alg.ALL[name], g, None)
    _same_fields({k: v for k, v in out.items() if not k.startswith("_")},
                 {k: jnp.asarray(ref[k]) for k in out if not k.startswith("_")})


def test_a_float_sum_over_edges_keeps_the_scatter():
    # PageRank: an integer count over Out (scan) and a float sum over In
    g = _bare(G.rmat(6, 4.0, directed=True, seed=3))
    cp = compile_program(alg.PAGERANK, g)
    assert cp.graph.in_ends is not None and cp.graph.out_ends is not None
    cp.run()
    # the sum's filter Deg[e.id] > 0 folds into the value it gathers
    assert cp.edge_reduce_paths == {"scan": 1, "scatter": 1, "fold": 1}


@pytest.mark.parametrize("name", ["sv", "wcc", "sssp"])
def test_partitioned_reductions_keep_the_scatter(name):
    g = _graph(name, 2)
    cp = compile_program(alg.ALL[name], g)
    with counted("edge_reduce/") as paths:
        res = run_bsp(cp.prog, g, cp.init_fields(),
                      placement="partitioned", n_shards=1)
    assert paths["scan"] >= 1 and paths["scatter"] == 0
    dense, _, _ = cp.run()
    _same_fields({f: v for f, v in dense.items() if not f.startswith("_")},
                 res.fields)


def test_the_staged_runtime_scans_where_the_graph_has_ends():
    g = _graph("wcc", 3)
    cp = compile_program(alg.WCC, g)
    with counted("edge_reduce/") as paths:
        res = run_bsp(cp.prog, g, cp.init_fields())
    assert paths["scan"] >= 1 and paths["scatter"] == 0
    with counted("edge_reduce/") as paths:
        bare = run_bsp(cp.prog, dataclasses.replace(
            g, in_ends=None, out_ends=None), cp.init_fields())
    assert paths["scan"] == 0 and paths["scatter"] >= 1
    _same_fields(res.fields, bare.fields)
