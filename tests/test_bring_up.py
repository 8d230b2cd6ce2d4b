"""Chip bring-up on the CPU: the graph is a program argument, the host
graph build is exact, and the chip smoke test's phases pass at a small
scale against their scipy references."""

import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import compile_program
from repro.core import plan as plan_mod
from repro.graph import generators as G
from repro.graph.partition import partition_graph
from repro.graph.structure import stable_argsort
from repro.pregel import run_bsp
from repro.pregel.runtime import _make_staged_superstep_fn

ROOT = Path(__file__).resolve().parent.parent


def _graph(n_edges_per_vertex: float):
    return G.erdos_renyi(256, n_edges_per_vertex, directed=False, seed=5)


def _dense_hlo(name, graph):
    cp = compile_program(alg.ALL[name], graph)
    return jax.jit(cp.fn).lower(cp.init_fields(), graph).as_text()


def _staged_hlo(name, graph):
    cp = compile_program(alg.ALL[name], graph)
    pp = plan_mod.fuse(plan_mod.lower_program(cp.prog, schedule="pull"))
    first = next(
        it for it in pp.items if isinstance(it, plan_mod.Superstep)
    )
    fn = _make_staged_superstep_fn(first, graph.n_vertices, {})
    return fn.lower(cp.init_fields(), {}, graph).as_text()


@pytest.mark.parametrize("lower", [_dense_hlo, _staged_hlo])
@pytest.mark.parametrize("name", ["sv", "sssp"])
def test_hlo_does_not_grow_with_edge_count(lower, name):
    """A closed-over graph would be embedded as constants, so the HLO
    would grow with E; as an argument only its shape changes."""
    small, large = _graph(4.0), _graph(32.0)
    assert large.n_edges > 6 * small.n_edges
    grow = len(lower(name, large)) - len(lower(name, small))
    # 4+ bytes of constant text per extra edge if embedded
    assert grow < 0.1 * (large.n_edges - small.n_edges)


def test_compiled_run_reuses_its_executable():
    g = _graph(4.0)
    cp = compile_program(alg.WCC, g)
    cp.run()
    before = cp._jitted_fn._cache_size()
    out, _, _ = cp.run()
    assert cp._jitted_fn._cache_size() == before == 1
    assert np.asarray(out["C"]).shape == (g.n_vertices,)


@pytest.mark.parametrize(
    "key",
    [
        np.array([3, 1, 3, 2, 1, 0, 3], np.int32),
        np.array([-5, 7, -5, 0, 7, 2**40, 2**40, -5], np.int64),
        np.random.default_rng(0).integers(0, 50, 5000),
        np.random.default_rng(1).integers(0, 2**44, 5000) // 7 * 7,
        np.zeros(0, np.int64),
    ],
)
def test_stable_argsort_matches_numpy(key):
    np.testing.assert_array_equal(
        stable_argsort(key), np.argsort(key, kind="stable")
    )


def test_partitioned_run_accepts_a_partitioned_graph():
    g = G.rmat(8, 4.0, directed=False, seed=2)
    cp = compile_program(alg.SV, g)
    dense, _, _ = cp.run()
    res = run_bsp(
        cp.prog, partition_graph(g, 1), cp.init_fields(),
        placement="partitioned", n_shards=1,
    )
    np.testing.assert_array_equal(res.fields["D"], dense["D"])
    with pytest.raises(ValueError, match="shards"):
        run_bsp(
            cp.prog, partition_graph(g, 2), cp.init_fields(),
            placement="partitioned", n_shards=1,
        )


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_a_machine_without_tpu(chip_smoke):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit):
        chip_smoke.tpu_devices(1)


def test_chip_smoke_one_chip_phases_match_scipy(chip_smoke, capsys):
    # the device check is steered here: the phases get the CPU device
    chip_smoke.one_chip(10, 7, jax.devices()[:1])
    lines = capsys.readouterr().out.splitlines()
    phases = [ln for ln in lines if "match=scipy" in ln]
    assert [ln.split("]")[0] + "]" for ln in phases] == [
        "[dense/sv]", "[dense/wcc]", "[dense/sssp]",
        "[staged/sv]", "[staged/sssp]",
    ]
    assert lines[0].startswith("[graph] scale=10 ")


def test_chip_smoke_checks_detect_mismatches(chip_smoke):
    gu = G.rmat(8, 16.0, directed=False, seed=1)
    gd = G.rmat(8, 16.0, directed=True, weighted=True, seed=2)
    refs = chip_smoke.references(gu, gd)
    comp, dist = refs["components"], refs["dist"]
    chip_smoke.check_components("ok", comp, comp)
    chip_smoke.check_dist("ok", dist.astype(np.float32), dist)
    wrong = comp.copy()
    wrong[-1] += 1
    with pytest.raises(RuntimeError, match="labels differ"):
        chip_smoke.check_components("wrong", wrong, comp)
    far = dist.copy()
    far[np.isfinite(far).nonzero()[0][-1]] *= 1.001
    with pytest.raises(RuntimeError, match="distances differ"):
        chip_smoke.check_dist("wrong", far, dist)
    unreachable = dist.copy()
    unreachable[np.isfinite(unreachable).nonzero()[0][-1]] = np.inf
    with pytest.raises(RuntimeError, match="reachable set"):
        chip_smoke.check_dist("wrong", unreachable, dist)
