"""CPU rehearsal of ``sv-part4-g500-24`` at scale 10 on 4 fake CPU devices.

The device count is fixed when JAX starts, so one subprocess runs the
cell through ``harness.run`` (a window run, a traced run, a job whose
loop runs no trip, one answer altered) and the control at scale 16, and
prints one JSON line; each test reads its part.
"""

import json
import subprocess
import sys
import textwrap

import pytest

from conftest import BENCH, CHECKOUT, SCALE, SEED

CELL = "sv-part4-g500-24"

SUBPROCESS = textwrap.dedent(
    f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import contextlib, io, json, sys, time
    sys.path[:0] = [{str(CHECKOUT / "src")!r}, {str(BENCH)!r}]
    import jax
    import numpy as np
    import control, harness
    from placements import partitioned

    spec = json.loads(open({str(CHECKOUT / "BENCHMARK.json")!r}).read())

    def cell(scale={SCALE}):
        c = harness.load_cell({CELL!r}, spec)
        c.config = dict(c.config, scale=scale)
        return c

    def run(trace=False, prepare=None):
        err = io.StringIO()
        with contextlib.redirect_stderr(err):
            line = harness.run(cell(), {SEED}, 0.2, trace, jax.devices()[:4],
                               time.perf_counter(), None, prepare=prepare)
        return dict(line=line, log=err.getvalue())

    def unchanged_state(text, graph, inputs):
        job = partitioned.prepare(text, graph, inputs)
        job.program.max_iters = 0
        return job

    class AlteredAnswer(partitioned.Job):
        def run(self, inputs, result):
            host, trips, counts, itemsize = super().run(inputs, result)
            host = host.copy()
            host[int(np.flatnonzero(host != 0)[0])] += 1
            return host, trips, counts, itemsize

    out = dict(window=run(), traced=run(trace=True),
               unchanged=run(prepare=unchanged_state),
               altered=run(prepare=AlteredAnswer),
               control=control.control_reading(cell(16), 3))
    print("RESULT " + json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def found():
    res = subprocess.run(
        [sys.executable, "-c", SUBPROCESS], capture_output=True, text=True,
        timeout=600, cwd=str(CHECKOUT),
    )
    lines = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, res.stdout + res.stderr
    return json.loads(lines[-1][len("RESULT "):])


def test_window_run_is_correct_and_compiles_nothing(found):
    line, log = found["window"]["line"], found["window"]["log"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert " 0 executables obtained inside the window" in log
    assert set(line["metrics"]) == {"iter_ms", "setup_s"}
    assert line["device"]["count"] == 4
    assert line["checks"]["label_mismatches"]["value"] == 0


def test_traced_run_reports_the_program_counters(found):
    line = found["traced"]["line"]
    assert line["correct"] and line["attempted"] == 1
    # a CPU trace has no TPU plane: only the program's counts read
    assert set(line["metrics"]) == {"dispatches_per_iter",
                                    "collective_mb_per_iter"}
    assert line["metrics"]["dispatches_per_iter"]["value"] > 1
    assert line["metrics"]["collective_mb_per_iter"]["value"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_a_broken_timed_path_is_not_correct(found, fault):
    line = found[fault]["line"]
    assert line["attempted"] >= 1
    assert line["failed"] == line["attempted"]
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_the_control_is_not_correct(found):
    # int16 ids hold every id below 2**15: the control shows from scale 16
    assert found["control"]["correct"] is False
