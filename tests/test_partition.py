"""`repro.graph.partition` tests: invariants, halo closure, equivalence.

Three layers:

* host-side partitioner invariants (+ hypothesis/stub property tests):
  every edge assigned exactly once, greedy balance bound, halo closure,
  partition→unpartition identity;
* single-device (S=1) partitioned execution — the full shard_map/collective
  machinery on a 1-shard mesh, runnable in-process;
* 8-fake-device subprocess: SSSP and connected components bit-match the
  dense single-device executor with identical superstep counts, pointer
  doubling (S-V, chain4) included — the ISSUE-2 acceptance gate.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import algorithms as alg
from repro.core import compile_program
from repro.graph import generators as G
from repro.graph.partition import (
    comm_bytes_report,
    edge_balanced_ranges,
    partition_field,
    partition_graph,
    partition_stats,
    unpartition_field,
)
from repro.pregel import run_bsp
from repro.pregel.runtime import _StagedStep, read_superstep_count
from repro.core.analysis import iter_steps
from repro.core import ast as past


# bool ||= / &&= remote writes at computed and edge targets: exercises the
# or/and branch of the cross-shard scatter_reduce (int min/max transport +
# re-threshold), which no library algorithm reaches
BOOL_COMBINER_PROG = """
for v in V
    local Flag[v] := (Id[v] % 7 == 0)
    local Tgt[v] := (Id[v] * 13) % numV
    local All[v] := true
end
for v in V
    if (Flag[v])
        remote Flag[Tgt[v]] ||= true
        for (e <- Nbr[v])
            remote Flag[e.id] ||= true
    for (e <- Nbr[v])
        remote All[e.id] &&= (Id[v] % 2 == 0)
end
"""


def _real_edges(g):
    m = np.asarray(g.edge_mask)
    return list(
        zip(
            np.asarray(g.src)[m].tolist(),
            np.asarray(g.dst)[m].tolist(),
        )
    )


class TestPartitioner:
    def test_every_edge_assigned_exactly_once(self):
        g = G.erdos_renyi(60, 5.0, directed=True, weighted=True, seed=2)
        pg = partition_graph(g, 4)
        starts = np.asarray(pg.starts)
        got = []
        for s in range(pg.n_shards):
            m = np.asarray(pg.emask[s])
            src = np.asarray(pg.src_g[s])[m]
            dst = np.asarray(pg.dst_l[s])[m] + starts[s]
            # ownership: every assigned edge's dst is owned by shard s
            assert np.all((dst >= starts[s]) & (dst < starts[s + 1]))
            got += list(zip(src.tolist(), dst.tolist()))
        assert sorted(got) == sorted(_real_edges(g))
        # push ordering too
        got_t = []
        for s in range(pg.n_shards):
            m = np.asarray(pg.t_emask[s])
            src = np.asarray(pg.t_src_l[s])[m] + starts[s]
            dst = np.asarray(pg.t_dst_g[s])[m]
            assert np.all((src >= starts[s]) & (src < starts[s + 1]))
            got_t += list(zip(src.tolist(), dst.tolist()))
        assert sorted(got_t) == sorted(_real_edges(g))

    def test_edge_balance_bound(self):
        g = G.rmat(10, avg_degree=8.0, directed=True, seed=7)
        n_shards = 8
        bounds = edge_balanced_ranges(g, n_shards)
        pg = partition_graph(g, n_shards, bounds=bounds)
        stats = partition_stats(pg)
        # greedy prefix bound: shard weight ≤ total/S + max vertex weight
        dst = np.asarray(g.dst)[np.asarray(g.edge_mask)]
        t_src = np.asarray(g.t_src)[np.asarray(g.t_mask)]
        w = np.ones(g.n_vertices, np.int64)
        np.add.at(w, dst, 1)
        np.add.at(w, t_src, 1)
        per_shard = [
            int(w[bounds[s]: bounds[s + 1]].sum()) for s in range(n_shards)
        ]
        bound = w.sum() / n_shards + w.max()
        assert max(per_shard) <= bound + 1e-9
        # and the per-shard assigned-edge counts inherit the balance
        assert max(stats["pull_edges_per_shard"]) <= bound

    def test_halo_closed_under_edge_patterns(self):
        """Every neighbor id a program's edge traversals read is owned or
        in the static ghost list (halo closure for ``F[e.id]`` patterns)."""
        g = G.erdos_renyi(80, 4.0, directed=False, weighted=True, seed=3)
        pg = partition_graph(g, 5)
        starts = np.asarray(pg.starts)
        n = g.n_vertices
        for nbr, emask, halo in (
            (pg.src_g, pg.emask, pg.halo_in),
            (pg.t_dst_g, pg.t_emask, pg.halo_out),
        ):
            for s in range(pg.n_shards):
                ids = np.asarray(nbr[s])[np.asarray(emask[s])]
                own = (ids >= starts[s]) & (ids < starts[s + 1])
                ghost = np.asarray(halo.ghost_ids[s])
                ghost = ghost[ghost < n]
                assert np.all(np.isin(ids[~own], ghost)), s
                # ghosts are never owned and are sorted unique
                assert not np.any((ghost >= starts[s]) & (ghost < starts[s + 1]))
                assert np.all(np.diff(ghost) > 0)

    def test_partition_unpartition_roundtrip(self):
        g = G.erdos_renyi(57, 3.0, directed=True, seed=4)
        pg = partition_graph(g, 7)
        rng = np.random.default_rng(0)
        for arr in (
            rng.normal(size=57).astype(np.float32),
            rng.integers(0, 100, 57).astype(np.int32),
            rng.random(57) < 0.5,
        ):
            x = jnp.asarray(arr)
            assert np.array_equal(
                np.asarray(unpartition_field(pg, partition_field(pg, x))),
                arr,
            )

    def test_rejects_more_shards_than_vertices(self):
        g = G.cycle(4)
        with pytest.raises(ValueError):
            edge_balanced_ranges(g, 5)


class TestPartitionProperties:
    """Property tests (hypothesis, or the deterministic stub in hermetic
    images): invariants hold across random graph shapes and shard counts."""

    @given(
        n=st.integers(min_value=8, max_value=96),
        deg=st.integers(min_value=1, max_value=6),
        n_shards=st.integers(min_value=1, max_value=8),
        seed=st.integers(min_value=0, max_value=10_000),
    )
    @settings(max_examples=20, deadline=None)
    def test_partition_invariants(self, n, deg, n_shards, seed):
        n_shards = min(n_shards, n)
        g = G.erdos_renyi(n, float(deg), directed=True, seed=seed)
        pg = partition_graph(g, n_shards)
        starts = np.asarray(pg.starts)
        assert starts[0] == 0 and starts[-1] == n
        assert np.all(np.diff(starts) >= 1)
        # edge conservation
        total = sum(int(np.asarray(pg.emask[s]).sum()) for s in range(n_shards))
        assert total == pg.n_edges
        # round trip
        x = jnp.arange(n, dtype=jnp.int32)
        assert np.array_equal(
            np.asarray(unpartition_field(pg, partition_field(pg, x))),
            np.arange(n, dtype=np.int32),
        )


class TestSuperstepAccounting:
    """read_superstep_count must mirror the staged executor exactly — the
    partitioned path charges its supersteps through it."""

    @pytest.mark.parametrize(
        "name", ["sssp", "sv", "wcc", "mis", "mwm", "chain4", "pagerank"]
    )
    @pytest.mark.parametrize("schedule", ["pull", "naive"])
    def test_matches_staged_stage_count(self, name, schedule):
        g = G.erdos_renyi(30, 3.0, directed=False, weighted=True, seed=1)
        fields = None
        if name == "chain4":
            fields = {"D": jnp.zeros((30,), jnp.int32)}
        elif name == "mis":
            rng = np.random.default_rng(1)
            fields = {"P": jnp.asarray(rng.random(30), jnp.float32)}
        cp = compile_program(alg.ALL[name], g, initial_fields=fields)
        for step in iter_steps(cp.prog):
            if not isinstance(step, past.Step):
                continue
            staged = _StagedStep(step, g.n_vertices, schedule)
            assert read_superstep_count(step, schedule) == len(
                staged.read_stage_fns()
            ), (name, schedule)


class TestPartitionedExecutionSingleShard:
    """S=1 exercises the whole partitioned machinery in-process."""

    @pytest.mark.parametrize(
        "name",
        [
            "sssp",
            "wcc",
            "sv",
            "mwm",
            "chain4",
            "mis",
            "bipartite_matching",
            "pagerank",
            "scc",
            "bfs",
            "kcore",
        ],
    )
    def test_matches_dense(self, name):
        fields = None
        if name in ("sssp", "pagerank", "scc"):
            g = G.erdos_renyi(40, 4.0, directed=True, weighted=True, seed=3)
        elif name == "chain4":
            g = G.erdos_renyi(30, 2.0, directed=False, seed=3)
            rng = np.random.default_rng(3)
            fields = {"D": jnp.asarray(rng.integers(0, 30, 30), jnp.int32)}
        elif name == "mis":
            g = G.erdos_renyi(40, 3.0, directed=False, seed=3)
            rng = np.random.default_rng(3)
            fields = {"P": jnp.asarray(rng.random(40), jnp.float32)}
        elif name == "bipartite_matching":
            g, side = G.random_bipartite(15, 15, 3.0, seed=3)
            fields = {"Side": jnp.asarray(side)}
        else:
            g = G.erdos_renyi(40, 3.0, directed=False, weighted=True, seed=3)
            if name == "kcore":
                fields = {"K": jnp.full((40,), 2, jnp.int32)}
        cp = compile_program(alg.ALL[name], g, initial_fields=fields)
        dense, _, counts = cp.run(fields)
        f0 = cp.init_fields(fields)
        res = run_bsp(
            cp.prog, g, f0, schedule="pull",
            placement="partitioned", n_shards=1,
        )
        for f in dense:
            assert np.array_equal(
                np.asarray(dense[f]), np.asarray(res.fields[f]),
                equal_nan=True,
            ), (name, f)
        # default execution is the §4.3-fused plan — palgol_pull totals
        assert res.supersteps == counts["palgol_pull"], name
        unfused = run_bsp(
            cp.prog, g, f0, schedule="pull",
            placement="partitioned", n_shards=1, fuse=False,
        )
        assert unfused.supersteps == counts["pull_staged"], name

    def test_bool_combiner_remote_writes(self):
        g = G.erdos_renyi(40, 3.0, directed=False, seed=5)
        cp = compile_program(BOOL_COMBINER_PROG, g)
        dense, _, counts = cp.run()
        res = run_bsp(
            cp.prog, g, cp.init_fields(),
            placement="partitioned", n_shards=1,
        )
        for f in dense:
            assert np.array_equal(
                np.asarray(dense[f]), np.asarray(res.fields[f])
            ), f
        assert res.supersteps == counts["palgol_pull"]

    def test_rejects_unknown_schedule(self):
        g = G.cycle(8)
        cp = compile_program(alg.WCC, g)
        with pytest.raises(ValueError):
            run_bsp(
                cp.prog, g, cp.init_fields(), schedule="bogus",
                placement="partitioned", n_shards=1,
            )


class TestCommBytes:
    def test_partitioned_below_replicated_on_local_graph(self):
        """ISSUE-2 acceptance: on a graph with ≥ 8× more vertices than halo
        entries, the partitioned path's per-superstep bytes (padded — what
        the static-shape all_to_all actually moves) are below replicated."""
        g = G.grid2d(512, 8)
        rep = comm_bytes_report(g, 8)
        assert rep["vertices_per_halo_entry"] >= 8.0
        assert (
            rep["partitioned_padded_bytes_per_superstep"]
            < rep["replicated_bytes_per_superstep"]
        )
        assert (
            rep["partitioned_payload_bytes_per_superstep"]
            <= rep["partitioned_padded_bytes_per_superstep"]
        )

    def test_benchmark_report_shape(self):
        """comm_bytes_report carries both layouts on a range-local grid and
        on an R-MAT power-law graph."""
        graphs = (
            G.grid2d(512, 8),
            G.rmat(12, avg_degree=8.0, directed=True, seed=5),
        )
        for g in graphs:
            rec = comm_bytes_report(g, 4)
            assert rec["replicated_bytes_per_superstep"] > 0
            assert rec["partitioned_padded_bytes_per_superstep"] > 0


SUBPROCESS_TEST = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax.numpy as jnp
    from repro.core import algorithms as alg, compile_program
    from repro.graph import generators as G
    from repro.pregel import run_bsp

    # bool ||= / &&= remote writes: the or/and scatter_reduce branch only
    # engages its collective transport with more than one shard
    BOOL_PROG = '''
    for v in V
        local Flag[v] := (Id[v] % 7 == 0)
        local Tgt[v] := (Id[v] * 13) % numV
        local All[v] := true
    end
    for v in V
        if (Flag[v])
            remote Flag[Tgt[v]] ||= true
            for (e <- Nbr[v])
                remote Flag[e.id] ||= true
        for (e <- Nbr[v])
            remote All[e.id] &&= (Id[v] % 2 == 0)
    end
    '''
    import textwrap
    progs = dict(alg.ALL)
    progs["bool_comb"] = textwrap.dedent(BOOL_PROG)

    # sssp / wcc: the acceptance pair; sv + chain4: remote writes and
    # pull-mode pointer doubling across shards; mwm: argmax + stop/halted;
    # bool_comb: or/and combiners
    for name in ("sssp", "wcc", "sv", "chain4", "mwm", "bool_comb"):
        fields = None
        if name == "sssp":
            g = G.erdos_renyi(48, 4.0, directed=True, weighted=True, seed=3)
        elif name == "chain4":
            g = G.erdos_renyi(32, 2.0, directed=False, seed=3)
            rng = np.random.default_rng(3)
            fields = {"D": jnp.asarray(rng.integers(0, 32, 32), jnp.int32)}
        else:
            g = G.erdos_renyi(48, 3.0, directed=False, weighted=True, seed=3)
        cp = compile_program(progs[name], g, initial_fields=fields)
        dense, _, counts = cp.run(fields)
        f0 = cp.init_fields(fields)
        res = run_bsp(cp.prog, g, f0, schedule="pull",
                      placement="partitioned")
        for f in dense:
            a, b = np.asarray(dense[f]), np.asarray(res.fields[f])
            assert np.array_equal(a, b, equal_nan=True), (name, f)
        assert res.supersteps == counts["palgol_pull"], (
            name, res.supersteps, counts["palgol_pull"])
        print(name, "ok", res.supersteps)
    print("PARTITION_SUBPROCESS_OK")
    """
)


@pytest.mark.subprocess_mesh
def test_partitioned_multidevice_equivalence():
    """SSSP + CC (+ SV, chain4) on the 8-fake-device mesh: bit-identical
    fields and identical STM superstep counts vs the dense path."""
    res = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_TEST],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        timeout=560,
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    assert "PARTITION_SUBPROCESS_OK" in res.stdout, res.stdout + res.stderr
