"""The program's own measurement: device names per plan item, the fused
loop's frontier count, and host spans. (The names of the benchmark
programs' TPU executables are checked in ``test_tpu_compile.py``.)"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.core import algorithms as alg
from repro.core import compile_program, compiler
from repro.core import plan as plan_mod
from repro.graph import generators as G
from repro.pregel import run_bsp
from repro.pregel.runtime import _make_staged_superstep_fn, walk_plan


def _graph(name):
    if name == "SSSP":
        return G.rmat(8, 8.0, directed=True, weighted=True, seed=2)
    return G.rmat(8, 4.0, directed=False, seed=1)


def _case(name):
    """``(graph, input fields)`` on which program ``name`` runs."""
    if name == "BIPARTITE_MATCHING":
        g, side = G.random_bipartite(20, 20, 3.0, seed=4)
        return g, {"Side": jnp.asarray(side)}
    if name == "MWM":
        return G.erdos_renyi(40, 3.0, directed=False, weighted=True,
                             seed=4), {}
    g = _graph(name)
    rng = np.random.default_rng(4)
    inputs = {
        "MIS": {"P": jnp.asarray(rng.random(g.n_vertices), jnp.float32)},
        "KCORE": {"K": jnp.full((g.n_vertices,), 3, jnp.int32)},
    }
    return g, inputs.get(name, {})


class _Events:
    """The ``/palgol/`` monitoring events recorded inside the block."""

    def __enter__(self):
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._record)
        return self

    def _record(self, event, duration, **kwargs):
        if event.startswith(trace.EVENT_PREFIX):
            assert duration >= 0
            self.names.append(event)

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._record)
        return False


@pytest.mark.parametrize("name", [
    "SV", "WCC", "SSSP", "SCC", "PAGERANK", "MIS", "BIPARTITE_MATCHING",
    "MWM", "BFS", "KCORE", "LABEL_PROP",
])
def test_dense_frontier_is_the_staged_frontier(name):
    """The fused loop counts, trip for trip, the vertices the staged walk
    finds changed; a loop without fix fields (PageRank's 30 trips)
    counts nothing."""
    g, inputs = _case(name)
    cp = compile_program(getattr(alg, name), g, initial_fields=inputs)
    _, trips, counts = cp.run(inputs)
    staged = run_bsp(cp.prog, g, cp.init_fields(inputs))
    assert counts["active_sets"] == staged.active_sets
    assert staged.trips == trips
    for node, series, n in zip(
        plan_mod.iter_nodes(cp.prog), counts["active_sets"], trips
    ):
        if node.fix_fields:
            assert len(series) == n and series[-1] == 0 and max(series) > 0
        else:
            assert series == []


def test_trips_past_the_history_add_into_its_last_slot(monkeypatch):
    g = _graph("SV")
    _, trips, counts = compile_program(alg.SV, g).run()
    (full,) = counts["active_sets"]
    assert trips[0] > 2
    monkeypatch.setattr(compiler, "FRONTIER_TRIPS", 2)
    _, short_trips, short = compile_program(alg.SV, g).run()
    assert short_trips == trips
    assert short["active_sets"] == [[full[0], sum(full[1:])]]


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def test_dense_ops_are_named_after_the_plan_items():
    """S-V's fused program: the pre-loop step and the loop's prefetch
    outside ``L0``; inside it the chain read, the neighbour min, the local
    compute with its remote-write messages, the remote update and the
    fixpoint test."""
    g = _graph("SV")
    cp = compile_program(alg.SV, g)
    names = _op_names(
        jax.jit(cp.fn).lower(cp.init_fields(), g).compile().as_text()
    )
    body = "palgol/L0/while/body/"
    for prefix in [
        "palgol/s0/local/",
        "palgol/s1/chain/",
        "palgol/s1/nbr/",
        body + "s1/chain/",
        body + "s1/nbr/",
        body + "s1/local/",
        body + "s1/local/nbr/",
        body + "s1/local/remote/",
        body + "s1/remote/",
        body + "fixpoint/",
        "palgol/L0/while/cond/fixpoint/",
    ]:
        assert any(prefix in n for n in names), prefix


def test_staged_dispatches_are_named_inside_their_loops():
    g = _graph("SV")
    cp = compile_program(alg.SV, g)
    pp = plan_mod.fuse(plan_mod.lower_program(cp.prog, schedule="pull"))
    seen = []

    def record(ss, flds, loops):
        seen.append((ss.describe(), loops))
        return flds

    # fields that never change: one trip
    walk_plan(pp, cp.init_fields(), record, [0], [], max_iters=2)
    assert seen == [("Main+Init+RR[pull]", ()), ("Main", (0,)),
                    ("RU+RR[pull]", (0,))]
    (loop,) = [it for it in pp.items if isinstance(it, plan_mod.PlanLoop)]
    main, update = (
        _make_staged_superstep_fn(ss, g.n_vertices, {}, (0,))
        for ss in loop.body
    )
    fields = cp.init_fields()
    names = _op_names(main.lower(fields, {}, g).compile().as_text())
    for prefix in ["palgol/L0/s1/local/", "palgol/L0/s1/local/nbr/",
                   "palgol/L0/s1/local/remote/"]:
        assert any(prefix in n for n in names), prefix
    _, mailbox = main(fields, {}, g)
    names = _op_names(update.lower(fields, mailbox, g).compile().as_text())
    for prefix in ["palgol/L0/s1/remote/", "palgol/L0/s1/chain/",
                   "palgol/L0/s1/nbr/"]:
        assert any(prefix in n for n in names), prefix


def test_a_span_records_one_event_of_its_name():
    with _Events() as ev:
        with trace.span("outer"):
            with trace.span("inner"):
                pass
        with pytest.raises(ValueError):
            with trace.span("failed"):
                raise ValueError("the event is still recorded")
    assert ev.names == ["/palgol/inner", "/palgol/outer", "/palgol/failed"]


def test_the_program_spans_its_front_end_run_and_walk():
    g = _graph("WCC")
    with _Events() as ev:
        cp = compile_program(alg.WCC, g)
    assert ev.names == [
        "/palgol/parse", "/palgol/segment_ends", "/palgol/discover_fields",
        "/palgol/cost_models", "/palgol/compile_program",
    ]
    with _Events() as ev:
        cp.run()
    assert ev.names == [
        "/palgol/init_fields", "/palgol/execute", "/palgol/read_counters",
    ]
    with _Events() as ev:
        res = run_bsp(cp.prog, g, cp.init_fields())
    assert ev.names.count("/palgol/superstep") == res.supersteps
    assert ev.names.count("/palgol/frontier") == sum(res.trips)
    assert np.asarray(res.fields["C"]).shape == (g.n_vertices,)


def test_partitioned_dispatches_are_named_inside_their_loops():
    from repro.dist.sharding import shard_mesh
    from repro.graph.partition import partition_graph
    from repro.graph.partition.executor import _make_superstep_fn
    from repro.graph.partition.partitioner import partition_fields

    g = _graph("SV")
    cp = compile_program(alg.SV, g)
    pp = plan_mod.fuse(plan_mod.lower_program(cp.prog, schedule="pull"))
    (loop,) = [it for it in pp.items if isinstance(it, plan_mod.PlanLoop)]
    pg = partition_graph(g, 1)
    fn = _make_superstep_fn(loop.body[0], pg, shard_mesh(1), (0,))
    fields = partition_fields(pg, cp.init_fields())
    names = _op_names(fn.lower(fields, {}, pg).compile().as_text())
    for prefix in ["palgol/L0/s1/local/", "palgol/L0/s1/local/nbr/",
                   "palgol/L0/s1/local/remote/"]:
        assert any(prefix in n for n in names), prefix
