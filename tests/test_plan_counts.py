"""The plan-derived superstep counts of every library program, pinned.

The plan IR (``repro.core.plan``), the STM cost models (``repro.core.stm``)
and all three executors agree on one superstep accounting; these pins catch
a silent drift in it. For each program of ``core.algorithms.ALL`` under each
schedule of ``plan.SCHEDULES``:

* ``test_plan_counts`` — the executed supersteps, unfused (the per-op
  expansion) and §4.3-fused (what every executor dispatches), and each
  step's ``(resolved schedule, supersteps)``;
* ``test_auto_byte_regime`` — the schedule ``auto`` resolves each step to
  under the byte costs of a 512x8 grid on 8 shards, in the dense regime
  (every vertex reads its chain) and the sparse one (the request set is the
  grid's halo, a quarter of it after combining).

Counts are taken on one 64-vertex weighted Erdős–Rényi graph (seed 0); each
program is compiled once for the whole module.
"""

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import compile_program
from repro.core.plan import SCHEDULES, program_plan_records
from repro.graph import generators as G
from repro.graph.partition import byte_cost_model, comm_bytes_report

N = 64

#: schedule → the STM cost models of its (unfused, fused) executed totals
COUNT_KEYS = {
    "pull": ("pull_staged", "fused_pull"),
    "push": ("push", "fused_push"),
    "naive": ("naive", "fused_naive"),
    "auto": ("auto", "fused_auto"),
}

#: program → schedule → (unfused supersteps, fused supersteps,
#: [(resolved schedule, supersteps) per step])
PINS = {
    "sssp": {
        "pull": (22, 11, [("pull", 1), ("pull", 2)]),
        "push": (22, 11, [("push", 1), ("push", 2)]),
        "naive": (22, 11, [("naive", 1), ("naive", 2)]),
        "auto": (22, 11, [("pull", 1), ("pull", 2)]),
    },
    "sv": {
        "pull": (20, 13, [("pull", 1), ("pull", 3)]),
        "push": (26, 19, [("push", 1), ("push", 4)]),
        "naive": (32, 25, [("naive", 1), ("naive", 5)]),
        "auto": (20, 13, [("pull", 1), ("pull", 3)]),
    },
    "pagerank": {
        "pull": (62, 31, [("pull", 1), ("pull", 2)]),
        "push": (62, 31, [("push", 1), ("push", 2)]),
        "naive": (62, 31, [("naive", 1), ("naive", 2)]),
        "auto": (62, 31, [("pull", 1), ("pull", 2)]),
    },
    "wcc": {
        "pull": (16, 8, [("pull", 1), ("pull", 2)]),
        "push": (16, 8, [("push", 1), ("push", 2)]),
        "naive": (16, 8, [("naive", 1), ("naive", 2)]),
        "auto": (16, 8, [("pull", 1), ("pull", 2)]),
    },
    "mis": {
        "pull": (18, 13, [("pull", 1), ("pull", 2), ("pull", 2)]),
        "push": (18, 13, [("push", 1), ("push", 2), ("push", 2)]),
        "naive": (18, 13, [("naive", 1), ("naive", 2), ("naive", 2)]),
        "auto": (18, 13, [("pull", 1), ("pull", 2), ("pull", 2)]),
    },
    "bipartite_matching": {
        "pull": (17, 10, [("pull", 1), ("pull", 3), ("pull", 2)]),
        "push": (17, 10, [("push", 1), ("push", 3), ("push", 2)]),
        "naive": (17, 10, [("naive", 1), ("naive", 3), ("naive", 2)]),
        "auto": (17, 10, [("pull", 1), ("pull", 3), ("pull", 2)]),
    },
    "mwm": {
        "pull": (22, 13, [("pull", 1), ("pull", 2), ("pull", 2)]),
        "push": (26, 17, [("push", 1), ("push", 2), ("push", 3)]),
        "naive": (26, 17, [("naive", 1), ("naive", 2), ("naive", 3)]),
        "auto": (22, 13, [("pull", 1), ("pull", 2), ("pull", 2)]),
    },
    "scc": {
        "pull": (32, 17, [("pull", 1), ("pull", 2), ("pull", 2), ("pull", 1)]),
        "push": (32, 17, [("push", 1), ("push", 2), ("push", 2), ("push", 1)]),
        "naive": (32, 17, [("naive", 1), ("naive", 2), ("naive", 2), ("naive", 1)]),
        "auto": (32, 17, [("pull", 1), ("pull", 2), ("pull", 2), ("pull", 1)]),
    },
    "chain4": {
        "pull": (3, 3, [("pull", 3)]),
        "push": (4, 4, [("push", 4)]),
        "naive": (7, 7, [("naive", 7)]),
        "auto": (3, 3, [("pull", 3)]),
    },
    "bfs": {
        "pull": (16, 8, [("pull", 1), ("pull", 2)]),
        "push": (16, 8, [("push", 1), ("push", 2)]),
        "naive": (16, 8, [("naive", 1), ("naive", 2)]),
        "auto": (16, 8, [("pull", 1), ("pull", 2)]),
    },
    "kcore": {
        "pull": (6, 3, [("pull", 1), ("pull", 2)]),
        "push": (6, 3, [("push", 1), ("push", 2)]),
        "naive": (6, 3, [("naive", 1), ("naive", 2)]),
        "auto": (6, 3, [("pull", 1), ("pull", 2)]),
    },
    "label_prop": {
        "pull": (16, 8, [("pull", 1), ("pull", 2)]),
        "push": (16, 8, [("push", 1), ("push", 2)]),
        "naive": (16, 8, [("naive", 1), ("naive", 2)]),
        "auto": (16, 8, [("pull", 1), ("pull", 2)]),
    },
}

AUTO_REGIMES = {
    "sssp": {"dense": ["pull", "pull"], "sparse": ["pull", "pull"]},
    "sv": {"dense": ["pull", "pull"], "sparse": ["pull", "push"]},
    "pagerank": {"dense": ["pull", "pull"], "sparse": ["pull", "pull"]},
    "wcc": {"dense": ["pull", "pull"], "sparse": ["pull", "pull"]},
    "mis": {"dense": ["pull", "pull", "pull"], "sparse": ["pull", "pull", "pull"]},
    "bipartite_matching": {
        "dense": ["pull", "pull", "pull"],
        "sparse": ["pull", "pull", "pull"],
    },
    "mwm": {"dense": ["pull", "pull", "pull"], "sparse": ["pull", "pull", "push"]},
    "scc": {
        "dense": ["pull", "pull", "pull", "pull"],
        "sparse": ["pull", "pull", "pull", "pull"],
    },
    "chain4": {"dense": ["pull"], "sparse": ["push"]},
    "bfs": {"dense": ["pull", "pull"], "sparse": ["pull", "pull"]},
    "kcore": {"dense": ["pull", "pull"], "sparse": ["pull", "pull"]},
    "label_prop": {"dense": ["pull", "pull"], "sparse": ["pull", "pull"]},
}


def _inputs(name):
    """The input fields a program reads (None for those that read none)."""
    if name == "chain4":
        rng = np.random.default_rng(0)
        return {"D": jnp.asarray(rng.integers(0, N, N), jnp.int32)}
    if name == "mis":
        rng = np.random.default_rng(0)
        return {"P": jnp.asarray(rng.random(N), jnp.float32)}
    if name == "bipartite_matching":
        return {"Side": jnp.asarray(np.arange(N) >= N // 2, jnp.int32)}
    if name == "kcore":
        return {"K": jnp.full((N,), 2, jnp.int32)}
    return None


@pytest.fixture(scope="module")
def compiled():
    """``name -> (CompiledProgram, STM counts)``, compiled on first use."""
    graph = G.erdos_renyi(N, 4.0, directed=False, weighted=True, seed=0)

    @functools.cache
    def get(name):
        fields = _inputs(name)
        cp = compile_program(alg.ALL[name], graph, initial_fields=fields)
        _, _, counts = cp.run(fields)
        return cp, counts

    return get


@pytest.fixture(scope="module")
def regimes():
    """The dense and sparse byte cost models ``auto`` is judged under."""
    grid = G.grid2d(512, 8)
    halo = comm_bytes_report(grid, 8)["partition"]["halo_total"]
    return {
        "dense": byte_cost_model(grid, 8),
        "sparse": byte_cost_model(
            grid,
            8,
            request_set=max(1, halo),
            combined_request_set=max(1, halo // 4),
        ),
    }


def _steps(step_plans, costs=None):
    return [
        (r["resolved"], r["supersteps"])
        for r in program_plan_records(step_plans, costs=costs)
    ]


def test_pins_cover_every_program_and_schedule():
    assert list(PINS) == list(alg.ALL) == list(AUTO_REGIMES)
    assert all(tuple(p) == SCHEDULES for p in PINS.values())


@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("name", list(alg.ALL))
def test_plan_counts(compiled, regimes, name, schedule):
    cp, counts = compiled(name)
    unfused, fused = COUNT_KEYS[schedule]
    got = (
        counts[unfused],
        counts[fused],
        _steps(cp.step_plans(schedule), regimes["dense"]),
    )
    assert got == PINS[name][schedule]


@pytest.mark.parametrize("regime", ["dense", "sparse"])
@pytest.mark.parametrize("name", list(alg.ALL))
def test_auto_byte_regime(compiled, regimes, name, regime):
    cp, _ = compiled(name)
    costs = regimes[regime]
    steps = _steps(
        dataclasses.replace(cp, byte_costs=costs).step_plans("auto"), costs
    )
    assert [resolved for resolved, _ in steps] == AUTO_REGIMES[name][regime]
