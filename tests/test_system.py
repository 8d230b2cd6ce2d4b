"""End-to-end behaviour tests: the full Palgol → Pregel-on-JAX pipeline."""

import jax
import numpy as np

from repro.core import algorithms as alg
from repro.core import compile_program
from repro.graph import generators as G


def test_end_to_end_sssp_pipeline():
    """Parse → analyze → compile → jit → execute → validate, in one breath."""
    g = G.rmat(8, avg_degree=8, directed=True, weighted=True, seed=0)
    cp = compile_program(alg.SSSP, g)
    out, trips, counts = cp.run()
    D = np.asarray(out["D"])
    # source at 0; reachable set must have finite nonneg distances
    assert D[0] == 0.0
    finite = np.isfinite(D)
    assert finite.sum() >= 1
    assert (D[finite] >= 0).all()
    # the compiled program is a single jittable XLA computation
    lowered = jax.jit(cp.fn).lower(cp.init_fields(), g)
    text = lowered.as_text()
    assert "while" in text  # the fixed-point iteration lowered to lax.while


def test_end_to_end_sv_on_rmat():
    g = G.rmat(8, avg_degree=4, directed=False, seed=1)
    cp = compile_program(alg.SV, g)
    out, trips, counts = cp.run()
    D = np.asarray(out["D"])
    # component representative is a fixed point of D (forest collapsed)
    assert np.array_equal(D[D], D)
    # superstep economy (the paper's headline Table-5 result, structurally)
    assert counts["palgol_push"] < counts["naive"]


def test_whole_program_is_one_xla_module():
    """Sequences + iterations fuse into one compiled module (state merging
    taken to its logical conclusion on a shared-address-space machine)."""
    g = G.erdos_renyi(64, 4.0, seed=2)
    cp = compile_program(alg.WCC, g)
    compiled = jax.jit(cp.fn).lower(cp.init_fields(), g).compile()
    assert compiled.cost_analysis() is not None
