"""The partitioned graph built on the mesh, and the prepared partitioned
program, on a 4-fake-device CPU mesh.

One subprocess (the device count is fixed when JAX starts) builds every
case and prints its findings as one JSON line; each test reads its part:

* ``partition_on_mesh`` equals the host ``partition_graph`` leaf for leaf,
  on a directed weighted graph, a symmetrised Kronecker graph of scale
  10 and a directed graph with isolated vertices in every range and at
  its end, whose edges are in ``(dst, src)`` order, spread over the shards
  in four ways (all on one shard, at random, in turn, and in turn beside
  a masked-off repeat of every edge, as the generator drops repeats), for
  S = 2 and 4; its boundaries are ``edge_balanced_ranges``'s; every
  per-shard leaf is split over the mesh and ``starts`` is replicated;
  both constructors' run ends are the ``searchsorted`` of their sorted
  blocks;
* S-V, WCC, SSSP, BFS, SCC (which reads ``Out``) and PageRank through
  ``PartitionedProgram`` on the device-built graph equal the dense
  ``compile_program`` run to the bit, and scipy; every order-independent
  edge reduction scans the shard's sorted edges, PageRank's float sum
  keeps the scatter; after ``warm``, a job obtains no executable, nor
  does a second one;
* ``run_bsp(placement="partitioned")`` gives the dense fields and the
  plan's superstep counts, from a ``Graph`` and from the device-built
  ``PartitionedGraph``, and scans where ``PartitionedProgram`` does.
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
    from repro.trace import counted

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
        repeats = rule == "repeats"
        if repeats:  # each edge twice, the second copy masked off
            src, dst, w = (np.concatenate([a, a]) for a in (src, dst, w))
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
            out[3][at] = (idx < src.size // 2) if repeats else True
        return [jnp.asarray(a) for a in out]

    er = G.erdos_renyi(300, 5.0, directed=True, weighted=True, seed=2)
    directed = sorted_graph(*live(er)[:2], 300, live(er)[2])
    r = G.rmat(10, avg_degree=16, directed=True, seed=3)
    a, b, _ = symmetrize(*live(r)[:2])
    kron = sorted_graph(a[a != b], b[a != b], 1 << 10)
    # edges only between ids ending in 0-5: isolated vertices in every
    # range, and the last four of the last shard's rows have no edge
    ends_in = np.flatnonzero(np.arange(200) % 10 < 6)
    pick = np.random.default_rng(5).choice(ends_in, (2, 500))
    gappy = sorted_graph(*pick[:, pick[0] != pick[1]], 200)

    def ends_faults(pg):
        # run ends that are not the searchsorted of an ascending block
        bad = []
        for o, (keys, ends) in enumerate(((pg.dst_l, pg.in_ends),
                                          (pg.t_src_l, pg.out_ends))):
            for s in range(pg.n_shards):
                k = np.asarray(keys[s])
                want = np.searchsorted(k, np.arange(pg.v_max), "right")
                if np.any(np.diff(k) < 0) or not np.array_equal(
                        np.asarray(ends[s]), want):
                    bad.append(f"{o}/{s}")
        return bad

    def empty_rows(pg):
        # [a row in range with no slot, a shard's last row with none, a
        # row past the shard's range]
        ends = np.asarray(pg.in_ends)
        run = np.diff(ends, axis=1, prepend=0) > 0
        vmask = np.asarray(pg.vmask)
        last = vmask.sum(axis=1) - 1
        return [bool(np.any(vmask & ~run)),
                bool(np.any(~run[np.arange(pg.n_shards), last])),
                bool(np.any(~vmask))]

    out = {"equal": {}, "bounds": {}, "split": {}, "ends": {}}
    built = {}
    for name, g, weighted in (("directed", directed, True),
                              ("kron", kron, False),
                              ("gappy", gappy, False)):
        for S in (2, 4):
            mesh = shd.shard_mesh(S)
            ref = partition_graph(g, S)
            for rule in ("one", "random", "turn", "repeats"):
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
                out["ends"][case] = {
                    "device": ends_faults(pg), "host": ends_faults(ref),
                    "empty_rows": empty_rows(pg),
                }
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

    def scipy_hops(g):
        src, dst, _ = live(g)
        n = g.n_vertices
        adj = csr_matrix((np.ones(src.size), (src, dst)), shape=(n, n))
        return csgraph.shortest_path(adj, unweighted=True, indices=0)

    def scipy_scc(g):
        # a vertex whose least ancestor (itself included) lies in its
        # strong component is labelled with it, the component's least id;
        # any other vertex v with numV + v
        src, dst, _ = live(g)
        n = g.n_vertices
        adj = csr_matrix((np.ones(src.size), (src, dst)), shape=(n, n))
        _, lab = csgraph.connected_components(adj, connection="strong")
        least = np.full(lab.max() + 1, n)
        np.minimum.at(least, lab, np.arange(n))
        reach = np.isfinite(csgraph.shortest_path(adj, unweighted=True))
        ancestor = np.argmax(reach, axis=0)
        return np.where(ancestor == least[lab], least[lab],
                        n + np.arange(n))

    def numpy_pagerank(g):
        src, dst, _ = live(g)
        n = g.n_vertices
        deg = np.bincount(src, minlength=n)
        pr = np.full(n, 1.0 / n)
        for _ in range(30):
            s = np.zeros(n)
            np.add.at(s, dst, pr[src] / deg[src])
            pr = 0.15 / n + 0.85 * s
        return pr

    def same_bits(a, b):
        return all(np.asarray(a[f]).dtype == np.asarray(b[f]).dtype
                   and np.asarray(a[f]).tobytes() == np.asarray(b[f]).tobytes()
                   for f in a)

    PROGRAMS = (("sv", kron, "kron", "D", scipy_labels),
                ("wcc", kron, "kron", "C", scipy_labels),
                ("sssp", directed, "directed", "D", scipy_dist),
                ("bfs", directed, "directed", "L", scipy_hops),
                ("scc", directed, "directed", "SCCid", scipy_scc),
                ("pagerank", directed, "directed", "PR", numpy_pagerank))
    out["program"] = {}
    for prog, g, name, field, reference in PROGRAMS:
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
        ref = reference(g)
        out["program"][prog] = {
            "dense": same_bits(dense, res.fields),
            "again": same_bits(res.fields, res2.fields),
            "scipy": bool(np.allclose(got, ref, rtol=1e-5)
                          if got.dtype.kind == "f"
                          else np.array_equal(got, ref)),
            "paths": pp.edge_reduce_paths,
            "dense_paths": cp.edge_reduce_paths,
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
    for prog, g, name, _, _ in PROGRAMS:
        cp = compile_program(alg.ALL[prog], g)
        dense, _, counts = cp.run()
        for source, graph in (("graph", g), ("mesh", built[(name, 4)])):
            with counted("edge_reduce/") as paths:
                res = run_bsp(cp.prog, graph, cp.init_fields(),
                              schedule="pull", placement="partitioned",
                              mesh=mesh)
            out["run_bsp"][f"{prog}-{source}"] = [
                same_bits(dense, res.fields),
                res.supersteps, counts["palgol_pull"],
                {k: paths[k] for k in ("scan", "scatter", "fold")},
            ]
    print("RESULT " + json.dumps(out))
    """
)

CASES = [f"{g}-{s}-{rule}" for g in ("directed", "kron", "gappy")
         for s in (2, 4) for rule in ("one", "random", "turn", "repeats")]
PROGRAMS = ["sv", "wcc", "sssp", "bfs", "scc", "pagerank"]
#: programs whose every edge reduction gives the same bits in any order
SCANNED = ["sv", "wcc", "sssp", "bfs", "scc"]


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


@pytest.mark.parametrize("case", CASES)
def test_run_ends_are_searchsorted_of_the_sorted_blocks(found, case):
    r = found["ends"][case]
    assert r["device"] == [] and r["host"] == []
    if case.startswith("gappy"):
        assert r["empty_rows"] == [True, True, True]


@pytest.mark.parametrize("prog", PROGRAMS)
def test_prepared_program_matches_dense_and_scipy(found, prog):
    r = found["program"][prog]
    assert r["dense"] and r["again"] and r["scipy"]
    # the shards' edges carry run ends: each reduction takes the path it
    # takes on one chip, a scan wherever the combiner allows one (SSSP's
    # filter stays unfolded: a PartitionedGraph has no weights' bound)
    assert all(r["paths"][k] == r["dense_paths"][k]
               for k in ("scan", "scatter"))
    assert r["paths"]["scan"] >= 1
    if prog in SCANNED:
        assert r["paths"]["scatter"] == 0
    else:  # PageRank's float sum
        assert r["paths"]["scatter"] >= 1
    assert r["supersteps"][0] == r["supersteps"][1]
    assert r["trips"][0] == r["trips"][1]
    assert r["active_sets"][0] == r["active_sets"][1]


@pytest.mark.parametrize("prog", PROGRAMS)
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


@pytest.mark.parametrize("case", [f"{p}-{source}" for p in PROGRAMS
                                  for source in ("graph", "mesh")])
def test_run_bsp_partitioned_results_and_counts_unchanged(found, case):
    same, supersteps, planned, paths = found["run_bsp"][case]
    assert same and supersteps == planned
    assert paths == found["program"][case.rsplit("-", 1)[0]]["paths"]
