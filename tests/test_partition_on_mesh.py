"""The partitioned graph built on the mesh, and the prepared partitioned
program, on a 4-fake-device CPU mesh.

One subprocess (the device count is fixed when JAX starts) builds every
case and prints its findings as one JSON line; each test reads its part:

* ``partition_on_mesh`` equals the host ``partition_graph`` leaf for leaf,
  on a directed weighted graph and a symmetrised Kronecker graph of scale
  10 whose edges are in ``(dst, src)`` order, spread over the shards in
  three ways (all on one shard, at random, in turn), for S = 2 and 4; its
  boundaries are ``edge_balanced_ranges``'s; every per-shard leaf is split
  over the mesh and ``starts`` is replicated;
* S-V, WCC and SSSP through ``PartitionedProgram`` on the device-built
  graph equal the dense ``compile_program`` run and scipy; after
  ``warm``, a job obtains no executable, nor does a second one;
* ``run_bsp(placement="partitioned")`` gives the dense fields and the
  plan's superstep counts, from a ``Graph`` and from the device-built
  ``PartitionedGraph``.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.subprocess_mesh

SUBPROCESS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from scipy.sparse import csgraph, csr_matrix

    from repro.core import algorithms as alg, compile_program
    from repro.dist import sharding as shd
    from repro.graph import generators as G
    from repro.graph.partition import (
        PartitionedProgram, edge_balanced_ranges, partition_graph,
        partition_on_mesh,
    )
    from repro.graph.structure import from_edge_list, symmetrize
    from repro.pregel import run_bsp

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    compiles = [0]

    def listen(event, duration, **_):
        if event == BACKEND_COMPILE:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)

    def sorted_graph(src, dst, n, w=None):
        # one copy of each pair, in (dst, src) order
        key = dst.astype(np.int64) * n + src
        _, first = np.unique(key, return_index=True)
        o = first[np.lexsort((src[first], dst[first]))]
        return from_edge_list(src[o], dst[o], n, None if w is None else w[o])

    def live(g):
        m = np.asarray(g.edge_mask)
        return [np.asarray(a)[m] for a in (g.src, g.dst, g.weight)]

    def spread(g, S, rule):
        src, dst, w = live(g)
        if rule == "one":
            shard = np.zeros(src.size, int)
        elif rule == "random":
            shard = np.random.default_rng(S).integers(0, S, src.size)
        else:
            shard = np.arange(src.size) % S
        k = np.bincount(shard, minlength=S).max() + 3
        n = g.n_vertices
        out = [np.full(S * k, n, np.int32), np.full(S * k, n, np.int32),
               np.zeros(S * k, np.float32), np.zeros(S * k, bool)]
        for s in range(S):
            idx = np.flatnonzero(shard == s)
            at = slice(s * k, s * k + idx.size)
            out[0][at], out[1][at], out[2][at] = src[idx], dst[idx], w[idx]
            out[3][at] = True
        return [jnp.asarray(a) for a in out]

    er = G.erdos_renyi(300, 5.0, directed=True, weighted=True, seed=2)
    directed = sorted_graph(*live(er)[:2], 300, live(er)[2])
    r = G.rmat(10, avg_degree=16, directed=True, seed=3)
    a, b, _ = symmetrize(*live(r)[:2])
    kron = sorted_graph(a[a != b], b[a != b], 1 << 10)

    out = {"equal": {}, "bounds": {}, "split": {}}
    built = {}
    for name, g, weighted in (("directed", directed, True),
                              ("kron", kron, False)):
        for S in (2, 4):
            mesh = shd.shard_mesh(S)
            ref = partition_graph(g, S)
            for rule in ("one", "random", "turn"):
                src, dst, w, mask = spread(g, S, rule)
                pg = partition_on_mesh(src, dst, w if weighted else None,
                                       mask, g.n_vertices, mesh)
                got, tree = jax.tree_util.tree_flatten_with_path(pg)
                want, tree_ref = jax.tree_util.tree_flatten_with_path(ref)
                case = f"{name}-{S}-{rule}"
                out["equal"][case] = tree == tree_ref and [
                    jax.tree_util.keystr(p) for (p, x), (_, y)
                    in zip(got, want)
                    if np.asarray(x).shape != np.asarray(y).shape
                    or not np.array_equal(np.asarray(x), np.asarray(y))
                ]
                out["bounds"][case] = np.array_equal(
                    np.asarray(pg.starts), edge_balanced_ranges(g, S))
                out["split"][case] = [
                    jax.tree_util.keystr(p) for p, x in got
                    if not (x.sharding.is_fully_replicated
                            if p[0].name == "starts" else
                            x.sharding.spec == P("shard")
                            and len(x.sharding.device_set) == S
                            and x.addressable_shards[0].data.shape[0] == 1)
                ]
                built[(name, S)] = pg

    # the prepared program on the device-built graph
    def scipy_labels(g):
        src, dst, _ = live(g)
        n = g.n_vertices
        adj = csr_matrix((np.ones(src.size), (dst, src)), shape=(n, n))
        _, lab = csgraph.connected_components(adj, directed=False)
        _, first = np.unique(lab, return_index=True)
        return first[lab]

    def scipy_dist(g):
        src, dst, w = live(g)
        n = g.n_vertices
        adj = csr_matrix((w, (src, dst)), shape=(n, n))
        return csgraph.dijkstra(adj, indices=0)

    out["program"] = {}
    for prog, g, name, field in (("sv", kron, "kron", "D"),
                                 ("wcc", kron, "kron", "C"),
                                 ("sssp", directed, "directed", "D")):
        cp = compile_program(alg.ALL[prog], g)
        dense, trips, counts = cp.run()
        pp = PartitionedProgram(cp.prog, built[(name, 4)])
        f0 = cp.init_fields()
        pp.warm(f0)
        before = compiles[0]
        res = pp.run(f0)
        first = compiles[0] - before
        res2 = pp.run(f0)
        second = compiles[0] - first - before
        got = np.asarray(res.fields[field])
        ref = scipy_labels(g) if prog != "sssp" else scipy_dist(g)
        out["program"][prog] = {
            "dense": all(np.array_equal(np.asarray(dense[f]),
                                        np.asarray(res.fields[f]))
                         for f in dense),
            "again": all(np.array_equal(np.asarray(res.fields[f]),
                                        np.asarray(res2.fields[f]))
                         for f in dense),
            "scipy": bool(np.allclose(got, ref, rtol=1e-5)
                          if prog == "sssp" else np.array_equal(got, ref)),
            "supersteps": [res.supersteps, counts["palgol_pull"]],
            "trips": [res.trips, trips],
            "active_sets": [res.active_sets, counts["active_sets"]],
            "compiles_after_warm": first, "compiles_second": second,
            "comm": sorted(res.comm_bytes),
        }

    # run_bsp(placement="partitioned"): from a Graph and from the
    # device-built PartitionedGraph
    out["run_bsp"] = {}
    mesh = shd.shard_mesh(4)
    for prog, g, name in (("sv", kron, "kron"), ("sssp", directed,
                                                  "directed")):
        cp = compile_program(alg.ALL[prog], g)
        dense, _, counts = cp.run()
        for source, graph in (("graph", g), ("mesh", built[(name, 4)])):
            res = run_bsp(cp.prog, graph, cp.init_fields(), schedule="pull",
                          placement="partitioned", mesh=mesh)
            out["run_bsp"][f"{prog}-{source}"] = [
                all(np.array_equal(np.asarray(dense[f]),
                                   np.asarray(res.fields[f]))
                    for f in dense),
                res.supersteps, counts["palgol_pull"],
            ]
    print("RESULT " + json.dumps(out))
    """
)

CASES = [f"{g}-{s}-{rule}" for g in ("directed", "kron") for s in (2, 4)
         for rule in ("one", "random", "turn")]


@pytest.fixture(scope="module")
def found():
    res = subprocess.run(
        [sys.executable, "-c", SUBPROCESS],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    lines = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, res.stdout + res.stderr
    return json.loads(lines[-1][len("RESULT "):])


@pytest.mark.parametrize("case", CASES)
def test_device_constructor_equals_host_partitioner(found, case):
    assert found["equal"][case] == []


@pytest.mark.parametrize("case", CASES)
def test_device_bounds_are_edge_balanced_ranges(found, case):
    assert found["bounds"][case] is True


@pytest.mark.parametrize("case", CASES)
def test_every_per_shard_leaf_is_split_over_the_mesh(found, case):
    assert found["split"][case] == []


@pytest.mark.parametrize("prog", ["sv", "wcc", "sssp"])
def test_prepared_program_matches_dense_and_scipy(found, prog):
    r = found["program"][prog]
    assert r["dense"] and r["again"] and r["scipy"]
    assert r["supersteps"][0] == r["supersteps"][1]
    assert r["trips"][0] == r["trips"][1]
    assert r["active_sets"][0] == r["active_sets"][1]


@pytest.mark.parametrize("prog", ["sv", "wcc", "sssp"])
def test_prepared_program_obtains_no_executable_after_warm(found, prog):
    r = found["program"][prog]
    assert r["compiles_after_warm"] == 0
    assert r["compiles_second"] == 0


def test_prepared_program_counts_what_its_collectives_carry(found):
    # S-V reads neighbours (halo), chains (gather_global) and writes
    # remotely (scatter_reduce); WCC only reads neighbours
    both = ["padded", "payload"]
    assert found["program"]["sv"]["comm"] == sorted(
        f"{p}/{k}" for p in ("gather_global", "halo_exchange",
                             "scatter_reduce") for k in both)
    assert found["program"]["wcc"]["comm"] == [
        f"halo_exchange/{k}" for k in both]


@pytest.mark.parametrize("case", ["sv-graph", "sv-mesh", "sssp-graph",
                                  "sssp-mesh"])
def test_run_bsp_partitioned_results_and_counts_unchanged(found, case):
    same, supersteps, planned = found["run_bsp"][case]
    assert same and supersteps == planned
