"""Suite-wide config: CPU pinning and deterministic seeds.

Loaded before any test module imports, so environment pins land before
jax initializes a backend.
"""

import os
import sys
from pathlib import Path

# -- CPU-only determinism ---------------------------------------------------
# Pin the platform before jax picks a backend: the suite's oracles are all
# CPU references, and CI machines must not accidentally grab a GPU/TPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SRC = Path(__file__).resolve().parent.parent / "src"
if str(SRC) not in sys.path:  # belt-and-braces next to pyproject pythonpath
    sys.path.insert(0, str(SRC))

import numpy as np
import pytest

#: the one seed every fixture derives from — change here, change everywhere
SUITE_SEED = 170309542  # arXiv 1703.09542, digits only


@pytest.fixture
def rng():
    """Fresh, fixed-seed numpy Generator (per-test, order-independent)."""
    return np.random.default_rng(SUITE_SEED)


@pytest.fixture
def prng_key():
    """Fixed jax PRNG key (imported lazily so collection never inits jax)."""
    import jax

    return jax.random.PRNGKey(SUITE_SEED)
