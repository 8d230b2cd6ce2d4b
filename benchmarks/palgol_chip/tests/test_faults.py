"""``correct`` comes out false when the timed path is broken underneath,
and when the reference's lower-precision control takes the program's
place. The faults a one-chip cell can have: a job that returns its state
unchanged, and an answer altered where it is produced. (No cell averages
over a batch or exchanges between chips.)"""

import numpy as np
import pytest

from placements import dense

CELLS = ["sv-g500-22", "sssp-g500-sssp-21", "wcc-g500-22"]


def unchanged_state(text, graph, inputs):
    """The program with its loops cut to zero trips: every job returns the
    state its first step set."""
    job = dense.prepare(text, graph, inputs)
    job.program.max_iters = 0
    return job


class AlteredAnswer(dense.Job):
    """One answer changed where the job produces it."""

    def run(self, inputs, result):
        host, trips, counts, itemsize = super().run(inputs, result)
        host = host.copy()
        i = int(np.flatnonzero(np.isfinite(host) & (host != 0))[0])
        host[i] = host[i] * 1.01 if host.dtype.kind == "f" else host[i] + 1
        return host, trips, counts, itemsize


@pytest.mark.parametrize("fault", [unchanged_state, AlteredAnswer])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(name, fault, small_cell,
                                            run_small):
    line = run_small(small_cell(name), prepare=fault)
    assert line["attempted"] >= 1
    assert line["failed"] == line["attempted"]
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("name,scale", [
    # int16 ids hold every id below 2**15: the control shows from scale 16
    ("sv-g500-22", 16), ("wcc-g500-22", 16), ("sssp-g500-sssp-21", 10)])
def test_the_control_is_not_correct(name, scale, small_cell):
    import control

    cell = small_cell(name)
    cell.config = dict(cell.config, scale=scale)
    reading = control.control_reading(cell, 3)
    assert reading["correct"] is False
