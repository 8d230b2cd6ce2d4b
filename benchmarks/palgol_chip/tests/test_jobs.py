"""Every cell, end to end through the harness, on the CPU at scale 10."""

import json

import numpy as np
import pytest

from conftest import SEED

CELLS = ["sv-g500-22", "sssp-g500-sssp-21", "wcc-g500-22"]


@pytest.mark.parametrize("name", CELLS)
def test_window_run_is_correct_and_compiles_nothing(name, small_cell,
                                                    run_small, capsys):
    line = run_small(small_cell(name))
    err = capsys.readouterr().err
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert " 0 executables obtained inside the window" in err
    assert set(line["metrics"]) == {"iter_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert list(line)[-1] == "checks"
    json.dumps(line)


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_metrics(name, small_cell, run_small):
    line = run_small(small_cell(name), trace=True)
    assert line["correct"] and line["attempted"] == 1
    # a CPU trace has no TPU plane: only the host and program counts read
    assert set(line["metrics"]) == {"supersteps_per_iter", "compile_s"}
    assert "busy_s" in line["device"] and "window_s" in line["device"]


def test_rooted_jobs_take_new_keys_of_degree_one_or_more(small_cell):
    import harness
    from graphs import kronecker

    cell = small_cell("sssp-g500-sssp-21")
    graph = kronecker.build_graph(SEED, cell.config)
    jobs = harness.job_stream(cell.traffic, graph, SEED)
    roots = [jobs(i)[1]["root"] for i in range(cell.traffic["keys"])]
    deg = np.bincount(np.asarray(graph.dst)[np.asarray(graph.edge_mask)],
                      minlength=graph.n_vertices)
    assert (deg[roots] > 0).all()
    assert len(set(roots)) > len(roots) // 2
    again = harness.job_stream(cell.traffic, graph, SEED)
    assert [again(i)[1]["root"] for i in range(8)] == roots[:8]
    mask = jobs(3)[0]["Root"]
    assert mask.sum() == 1 and mask[roots[3]]


def test_rooted_sssp_from_vertex_0_is_the_library_sssp(small_cell):
    from repro.core import algorithms, compile_program

    from graphs import kronecker

    cell = small_cell("sssp-g500-sssp-21")
    graph = kronecker.build_graph(SEED, cell.config)
    root = np.zeros(graph.n_vertices, bool)
    root[0] = True
    mine = compile_program(cell.program, graph, {"Root": root})
    lib = compile_program(algorithms.SSSP, graph)
    got, trips, counts = mine.run({"Root": root})
    want, lib_trips, lib_counts = lib.run()
    np.testing.assert_array_equal(np.asarray(got["D"]), np.asarray(want["D"]))
    assert trips == lib_trips
    assert counts["fused_pull"] == lib_counts["fused_pull"]


@pytest.mark.parametrize("program,field", [("SV", "D"), ("WCC", "C")])
def test_library_programs_on_the_device_graph_match_the_reference(
        program, field, small_cell):
    import harness
    from repro.core import algorithms, compile_program

    from graphs import kronecker
    from refs import components

    cell = small_cell("sv-g500-22")
    graph = kronecker.build_graph(SEED, cell.config)
    live = int(np.asarray(graph.edge_mask).sum())
    out, _, _ = compile_program(getattr(algorithms, program), graph).run()
    ref = components.reference(harness.host_edges(graph, live), {})
    assert components.compare(np.asarray(out[field]), ref) == {
        "label_mismatches": 0}
