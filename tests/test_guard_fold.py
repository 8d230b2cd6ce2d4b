"""The neighbour-guard fold of edge comprehensions.

A comprehension whose filter reads only the neighbour, such as SSSP's
``minimum [D[e.id] + e.w | e <- In[v], A[e.id]]``, forms
``T[u] = A[u] ? D[u] : inf`` once per vertex and gathers ``T`` per edge.
Its fields must be, to the bit, those of the same program written with
the filter as a ``Cond`` in the body (which does not fold) and those of
the interpreter, under the fused compiler and the staged runtime. A sum
of floats is compared with the interpreter to a tolerance, as the
scatter adds in another order. Weights that are ``-inf`` or NaN keep the
filter per edge; programs without such a filter trace as they did.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import compile_program, interpret
from repro.graph import generators as G
from repro.graph.structure import from_edge_list
from repro.pregel.runtime import run_bsp
from repro.trace import counted

#: each folded library comprehension and the same one with its filter as
#: a ``Cond`` of the body
UNFOLDED = {
    "sssp": (
        "minimum [D[e.id] + e.w | e <- In[v], A[e.id]]",
        "minimum [(A[e.id] ? D[e.id] + e.w : inf) | e <- In[v]]",
    ),
    "pagerank": (
        "sum [PR[e.id] / Deg[e.id] | e <- In[v], Deg[e.id] > 0]",
        "sum [(Deg[e.id] > 0 ? PR[e.id] / Deg[e.id] : 0.0) | e <- In[v]]",
    ),
    "bipartite_matching": (
        "minimum [e.id | e <- Nbr[v], M[e.id] == numV]",
        "minimum [(M[e.id] == numV ? e.id : 2147483647) | e <- Nbr[v]]",
    ),
    "kcore": (
        "count [1 | e <- Nbr[v], Alive[e.id]]",
        "sum [(Alive[e.id] ? 1 : 0) | e <- Nbr[v]]",
    ),
}


def _unfolded(name):
    folded, cond = UNFOLDED[name]
    src = alg.ALL[name]
    assert folded in src
    return src.replace(folded, cond)


def _case(name, seed):
    """A small graph for program ``name`` and its input fields."""
    if name == "sssp":
        return G.rmat(7, 6.0, directed=True, weighted=True, seed=seed), None
    if name == "pagerank":
        return G.rmat(7, 6.0, directed=True, seed=seed), None
    if name == "bipartite_matching":
        g, side = G.random_bipartite(40, 40, 3.0, seed=seed)
        return g, {"Side": jnp.asarray(side)}
    g = G.rmat(7, 6.0, directed=False, seed=seed)
    return g, {"K": jnp.full((g.n_vertices,), 3, jnp.int32)}


def _run(src, g, fields, executor):
    """Fields of ``src`` on ``g`` and the folds traced on the way."""
    cp = compile_program(src, g, initial_fields=fields)
    with counted("edge_reduce/") as paths:
        if executor == "dense":
            out, _, _ = cp.run(fields)
        else:
            out = run_bsp(
                cp.prog, cp.graph, cp.init_fields(fields), schedule=executor
            ).fields
    return out, paths["fold"]


def _same_bits(a, b):
    for f in a:
        x, y = np.asarray(a[f]), np.asarray(b[f])
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), f


@pytest.mark.parametrize("executor", ["dense", "pull"])
@pytest.mark.parametrize("name", sorted(UNFOLDED))
def test_folded_fields_match_the_cond_form_and_the_interpreter(
    name, executor
):
    g, fields = _case(name, seed=1)
    out, folds = _run(alg.ALL[name], g, fields, executor)
    cond, cond_folds = _run(_unfolded(name), g, fields, executor)
    assert folds >= 1 and cond_folds == 0
    _same_bits(out, cond)
    ref, _ = interpret(alg.ALL[name], g, fields)
    for f in out:
        if f.startswith("_"):
            continue
        x, y = np.asarray(out[f]), np.asarray(ref[f])
        if name == "pagerank":  # a float sum in the scatter's order
            assert np.allclose(x, y, rtol=1e-5, atol=1e-7), f
        else:
            assert x.dtype == y.dtype and np.array_equal(x, y), f


@pytest.mark.parametrize("schedule", ["push", "naive"])
def test_the_staged_schedules_prefetch_the_fold(schedule):
    # push and naive send the neighbour reads in rounds of their own kinds
    g, _ = _case("sssp", seed=2)
    out, folds = _run(alg.SSSP, g, None, schedule)
    dense, _ = _run(alg.SSSP, g, None, "dense")
    assert folds >= 1
    _same_bits(out, dense)


def _weighted(bad, seed=3):
    """A weighted graph with a few live weights set to ``bad``."""
    g = G.rmat(7, 6.0, directed=True, weighted=True, seed=seed)
    live = np.asarray(g.edge_mask)
    src = np.asarray(g.src)[live]
    dst = np.asarray(g.dst)[live]
    w = np.asarray(g.weight)[live].copy()
    w[np.random.default_rng(seed).choice(w.size, 5, replace=False)] = bad
    return from_edge_list(src, dst, g.n_vertices, w, pad_to=g.n_edges + 3)


@pytest.mark.parametrize("executor", ["dense", "pull"])
@pytest.mark.parametrize(
    "bad,folds", [(-np.inf, 0), (np.nan, 0), (np.inf, 1)]
)
def test_weights_that_are_minus_inf_or_nan_keep_the_filter(
    executor, bad, folds
):
    # inf + w is inf for a finite or +inf w only: a -inf or NaN weight
    # would turn a filtered edge into NaN
    g = _weighted(bad)
    out, n = _run(alg.SSSP, g, None, executor)
    cond, _ = _run(_unfolded("sssp"), g, None, executor)
    assert min(n, 1) == folds
    _same_bits(out, cond)


@pytest.mark.parametrize(
    "name,folds",
    [("sssp", 1), ("sv", 0), ("wcc", 0), ("mis", 0), ("scc", 0), ("mwm", 0)],
)
def test_only_a_filter_of_the_neighbour_alone_folds(name, folds):
    # S-V, WCC: no filter; MIS, SCC: filters that read the current vertex
    # too; MWM: an argmax
    g = G.erdos_renyi(30, 3.0, directed=False, weighted=True, seed=4)
    fields = None
    if name == "mis":
        fields = {"P": jnp.asarray(np.random.default_rng(4).random(30),
                                   jnp.float32)}
    cp = compile_program(alg.ALL[name], g, initial_fields=fields)
    cp.run(fields)
    assert cp.edge_reduce_paths["fold"] == folds


def _gathers(name, graph=None):
    g = G.rmat(7, 6.0, directed=name == "sssp", weighted=True, seed=0)
    cp = compile_program(alg.ALL[name], g)
    text = jax.jit(cp.fn).lower(
        cp.init_fields(), cp.graph if graph is None else graph(cp.graph)
    ).as_text()
    return len(re.findall(r'"stablehlo\.gather"\(', text))


@pytest.mark.parametrize("name,before", [("sv", 6), ("wcc", 3)])
def test_programs_without_a_filter_lower_to_as_many_gathers(name, before):
    # the counts of the trace before the fold existed
    assert _gathers(name) == before


def test_sssp_gathers_the_folded_table_in_place_of_two_reads():
    # before the loop and at the end of each trip: T where A and D were
    unfolded = _gathers(
        "sssp", lambda g: dataclasses.replace(g, weights_bounded=None)
    )
    assert unfolded == 5
    assert _gathers("sssp") == unfolded - 2


def _loop_carry(cp):
    """Avals of the fused loop's ``while`` carry."""
    jaxpr = jax.make_jaxpr(cp.fn)(cp.init_fields(), cp.graph).jaxpr
    (loop,) = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    skip = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
    return [v.aval for v in loop.invars[skip:]]


def test_the_sssp_loop_carries_one_per_edge_array():
    g = G.rmat(7, 6.0, directed=True, weighted=True, seed=5)
    cp = compile_program(alg.SSSP, g)
    per_edge = [a for a in _loop_carry(cp) if a.shape == (g.n_edges,)]
    assert [a.dtype for a in per_edge] == [jnp.float32]
    unfolded = dataclasses.replace(
        cp, graph=dataclasses.replace(cp.graph, weights_bounded=None)
    )
    per_edge = [a for a in _loop_carry(unfolded) if a.shape == (g.n_edges,)]
    assert sorted(str(a.dtype) for a in per_edge) == ["bool", "float32"]


@pytest.mark.parametrize(
    "name,folds", [("pagerank", 1), ("bipartite_matching", 1), ("sssp", 0)]
)
def test_the_partitioned_placement_folds_without_the_weight_bound(
    name, folds
):
    # a fold that adds e.w needs the graph's weight bound, which the
    # shards do not carry; the others fold there too
    g, fields = _case(name, seed=6)
    cp = compile_program(alg.ALL[name], g, initial_fields=fields)
    dense, _, _ = cp.run(fields)
    with counted("edge_reduce/") as paths:
        res = run_bsp(cp.prog, g, cp.init_fields(fields),
                      placement="partitioned", n_shards=1)
    assert min(paths["fold"], 1) == folds
    _same_bits({k: v for k, v in dense.items() if not k.startswith("_")},
               res.fields)


@pytest.mark.parametrize(
    "body,folds",
    [
        ("D[e.id] + 1.0", 1),  # a neighbour-only body
        ("D[e.id] + inf", 1),
        ("e.w + D[e.id]", 1),
        ("D[e.id] + e.w", 1),
        ("D[e.id] * e.w", 0),
        ("D[e.id] + D[v]", 0),  # nothing bounds a current-vertex term
    ],
)
def test_which_bodies_fold_under_a_minimum(body, folds):
    src = alg.SSSP.replace("D[e.id] + e.w", body)
    g = G.rmat(6, 4.0, directed=True, weighted=True, seed=7)
    cp = compile_program(src, g)
    out, _, _ = cp.run()
    assert cp.edge_reduce_paths["fold"] == folds
    ref, _ = interpret(src, g, None)
    assert np.array_equal(np.asarray(out["D"]), ref["D"])


@pytest.mark.parametrize("bad,folds", [(np.inf, 0), (-np.inf, 1)])
def test_a_maximum_needs_weights_below_inf(bad, folds):
    # maximum's identity is -inf, and -inf + inf is NaN
    def program(body):
        return alg.SSSP.replace(
            "minimum [D[e.id] + e.w | e <- In[v], A[e.id]]", body
        )

    g = _weighted(bad)
    out, n = _run(program(
        "maximum [D[e.id] + e.w | e <- In[v], A[e.id]]"), g, None, "dense")
    cond, _ = _run(program(
        "maximum [(A[e.id] ? D[e.id] + e.w : -inf) | e <- In[v]]"),
        g, None, "dense")
    assert n == folds
    _same_bits(out, cond)
