"""CPU rehearsal of the on-chip benchmark at Graph500 scale 10.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/palgol_chip/tests -q

The tests call the harness's functions, not ``run.py``, which refuses a
machine without a TPU.
"""

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parents[1]
for p in (str(CHECKOUT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest

#: every cell of BENCHMARK.json, rehearsed at this scale
SCALE = 10
#: a seed whose high 32 bits are set, as a run's --seed may be
SEED = (1 << 33) + 17


@pytest.fixture(scope="session")
def spec():
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


@pytest.fixture
def small_cell(spec):
    """``name -> Cell`` with its configuration cut to :data:`SCALE`."""
    import harness

    def make(name):
        cell = harness.load_cell(name, spec)
        cell.config = dict(cell.config, scale=SCALE)
        return cell

    return make


@pytest.fixture
def run_small():
    """Run a cell on the CPU for a fraction of a second."""
    import time

    import jax

    import harness

    def go(cell, seed=SEED, trace=False, prepare=None, seconds=0.2):
        return harness.run(cell, seed, seconds, trace, jax.devices()[:1],
                           time.perf_counter(), None, prepare=prepare)

    return go
