"""A cell, configuration, program and metric added as files alone are
found by name: no code of the harness is edited."""

import json
import shutil
import time

import jax

from conftest import BENCH, CHECKOUT, SEED


def test_a_new_cell_made_of_new_files_runs(tmp_path):
    import harness

    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    config = json.loads((BENCH / "configs" / "g500-22.json").read_text())
    (bench / "configs" / "g500-8.json").write_text(
        json.dumps(dict(config, name="g500-8", scale=8)))
    (bench / "programs" / "hashmin.palgol").write_text(
        (BENCH / "programs" / "wcc.palgol").read_text())
    traffic = json.loads((BENCH / "traffic" / "wcc-fixed.json").read_text())
    (bench / "traffic" / "hashmin-fixed.json").write_text(
        json.dumps(dict(traffic, program="hashmin")))
    (bench / "metrics" / "jobs_run.py").write_text(
        "def read(record):\n    return len(record['jobs'])\n")

    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "g500-8", "file":
                            "bench/configs/g500-8.json"})
    spec["workloads"].append({"name": "hashmin-g500-8", "config": "g500-8",
                              "traffic": "hashmin-fixed", "chips": 1})
    spec["end_to_end"].append({"name": "jobs_run", "unit": "jobs",
                               "workloads": ["hashmin-g500-8"]})
    cell = harness.load_cell("hashmin-g500-8", spec, checkout=tmp_path,
                             root=bench)
    assert cell.config["scale"] == 8
    line = harness.run(cell, SEED, 0.1, False, jax.devices()[:1],
                       time.perf_counter(), None)
    assert line["correct"]
    assert set(line["metrics"]) == {"iter_ms", "setup_s", "jobs_run"}
    assert line["metrics"]["jobs_run"]["value"] == line["attempted"]
