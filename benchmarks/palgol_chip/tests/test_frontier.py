"""The fixpoint frontier the program counts: the per-layer reader and the
plan-item report, on the CPU at scale 10."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, CHECKOUT, SEED

CELLS = ["sv-g500-22", "sssp-g500-sssp-21", "wcc-g500-22"]


def _reader():
    import harness

    return harness.load_module(BENCH / "metrics" / "frontier_frac.py").read


def _record(counts, busy_s=1.0):
    return {"trace": {"busy_s": busy_s}, "n_vertices": 100,
            "jobs": [{"trips": [4], "counts": counts}]}


def test_the_share_is_changed_vertices_over_vertex_visits():
    read = _reader()
    assert read(_record({"active_sets": [[60, 30, 10, 0]]})) == 0.25
    # a program that counts no frontier, and a run with no device trace
    assert read(_record({"fused_pull": 8})) is None
    assert read(_record({"active_sets": [[60, 30, 10, 0]]}, 0.0)) is None


@pytest.mark.parametrize("name", CELLS)
def test_a_cpu_traced_run_reports_no_frontier_share(name, small_cell,
                                                    run_small):
    line = run_small(small_cell(name), trace=True)
    assert line["correct"]
    assert "frontier_frac" not in line["metrics"]


@pytest.mark.parametrize("name", CELLS)
def test_the_report_runs_one_job_traced_and_one_not(name, small_cell):
    import scope_report

    rep = scope_report.report(small_cell(name), SEED)
    assert rep["trips"] == rep["untraced_trips"] and rep["trips"][0] > 1
    (series,) = rep["frontier"]
    assert len(series) == rep["trips"][0] and series[-1] == 0
    assert 0 < rep["frontier_frac"] < 1
    assert rep["traced_job_s"] > 0 and rep["untraced_job_s"] > 0
    spans = rep["setup_spans_s"]
    assert spans["/palgol/compile_program"] >= spans["/palgol/parse"] > 0
    assert rep["device"]["platform"] == "cpu"
    json.dumps(rep)


def test_the_report_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "scope_report.py"), "--workload",
         "wcc-g500-22", "--seed", "1"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU found" in p.stderr
