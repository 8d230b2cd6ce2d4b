"""Superstep-plan IR tests (`repro.core.plan`).

Four layers:

* plan structure: op lists under pull/push/naive, one op == one superstep
  (`len(plan.ops)` is the accounting contract), chain4's known shapes
  (pull pointer doubling, the paper's 3-round push derivation, naive's
  six request/reply rounds);
* the ``auto`` selector: per step, its plan must equal the cheapest of
  the hand-picked pull/push/naive plans (ties pull → push → naive), and
  with a :class:`~repro.core.plan.ByteCostModel` the byte-aware metric
  must flip it to push/naive on tiny request sets at deep chains;
* the (executor × schedule) matrix in-process: partitioned(S=1) push,
  naive and auto bit-match the fused dense executor with identical
  plan-derived superstep counts — every schedule now executable on every
  executor.

One 8-fake-device subprocess case (a single representative program, see
the ``subprocess_mesh`` marker) keeps the multi-shard push/naive
collectives honest without re-paying the full subprocess matrix.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import ast as past
from repro.core import ByteCostModel, compile_program, lower_step
from repro.core.analysis import analyze_step, iter_steps
from repro.core.plan import (
    MainCompute,
    ReadRound,
    RemoteUpdate,
    SCHEDULES,
    plan_score,
)
from repro.graph import generators as G
from repro.pregel import run_bsp


def _steps(src, g, fields=None):
    cp = compile_program(src, g, initial_fields=fields)
    return [s for s in iter_steps(cp.prog) if isinstance(s, past.Step)]


def _setup(name, seed=3):
    fields = None
    if name in ("sssp", "pagerank", "scc"):
        g = G.erdos_renyi(40, 4.0, directed=True, weighted=True, seed=seed)
    elif name == "bipartite_matching":
        g, side = G.random_bipartite(15, 15, 3.0, seed=seed)
        fields = {"Side": jnp.asarray(side)}
    elif name == "mis":
        g = G.erdos_renyi(40, 3.0, directed=False, seed=seed)
        rng = np.random.default_rng(seed)
        fields = {"P": jnp.asarray(rng.random(g.n_vertices), jnp.float32)}
    elif name == "chain4":
        g = G.erdos_renyi(30, 2.0, directed=False, seed=seed)
        rng = np.random.default_rng(seed)
        fields = {"D": jnp.asarray(rng.integers(0, 30, 30), jnp.int32)}
    else:
        g = G.erdos_renyi(40, 3.0, directed=False, weighted=True, seed=seed)
        if name == "kcore":
            fields = {"K": jnp.full((g.n_vertices,), 2, jnp.int32)}
    return g, fields


def _stdlib_fields(name, g, fields):
    """Initial fields the stdlib programs need for compilation."""
    n = g.n_vertices
    if name == "mis":
        return {"P": jnp.zeros((n,), jnp.float32)}
    if name == "bipartite_matching":
        return {"Side": jnp.zeros((n,), jnp.int32)}
    if name == "kcore":
        return {"K": jnp.full((n,), 2, jnp.int32)}
    if name == "chain4":
        return {"D": jnp.zeros((n,), jnp.int32)}
    return fields


class TestPlanStructure:
    def test_chain4_pull_is_pointer_doubling(self):
        g, fields = _setup("chain4")
        (step,) = _steps(alg.CHAIN4, g, fields)
        plan = lower_step(step, schedule="pull")
        kinds = [type(op).__name__ for op in plan.ops]
        assert kinds == ["ReadRound", "ReadRound", "MainCompute"]
        # round 1 materializes D², round 2 composes D⁴ = D²∘D²
        assert plan.ops[0].chains[0].pattern == ("D", "D")
        assert plan.ops[1].chains[0].pattern == ("D",) * 4
        assert plan.ops[1].chains[0].prefix == ("D", "D")
        assert plan.ops[1].chains[0].suffix == ("D", "D")
        assert plan.read_rounds == 2 and plan.n_supersteps == 3

    def test_chain4_naive_is_request_reply_per_hop(self):
        g, fields = _setup("chain4")
        (step,) = _steps(alg.CHAIN4, g, fields)
        plan = lower_step(step, schedule="naive")
        rr = [op for op in plan.ops if isinstance(op, ReadRound)]
        # three hops (D², D³, D⁴), each a request+reply pair
        assert [op.kind for op in rr] == ["request", "reply"] * 3
        # each naive hop splits off the last field
        for op in rr:
            (ce,) = op.chains
            assert ce.prefix == ce.pattern[:-1] and ce.suffix == (ce.pattern[-1],)
        assert plan.n_supersteps == 7  # 6 read rounds + main (paper: naive)

    def test_chain4_push_is_paper_three_round_derivation(self):
        """The executable push plan reproduces the paper's §4.1.1 result:
        D⁴ in 3 message rounds (request forward, D² combined reply +
        request forward, D⁴ combined reply) — half of naive's six."""
        g, fields = _setup("chain4")
        (step,) = _steps(alg.CHAIN4, g, fields)
        plan = lower_step(step, schedule="push")
        rr = [op for op in plan.ops if isinstance(op, ReadRound)]
        assert [op.kind for op in rr] == [
            "push_request", "push_reply", "push_reply",
        ]
        # round 1 carries the address flow only; round 2 materializes D²
        # (and forwards the request to D²[u]); round 3 composes D⁴ = D²∘D²
        assert rr[0].chains == () and rr[0].sends
        assert rr[1].chains[0].pattern == ("D", "D") and rr[1].sends
        assert rr[2].chains[0].pattern == ("D",) * 4
        assert rr[2].chains[0].prefix == ("D", "D")
        assert rr[2].chains[0].suffix == ("D", "D")
        # every push round carries the message-combining op
        assert all(op.combiner == "min" for op in rr)
        # plan rounds == the PushSolver's minimal count the STM charges
        assert plan.read_rounds == analyze_step(step).push_read_rounds() == 3
        assert plan.n_supersteps == 4  # paper: 3 rounds + main

    def test_push_rounds_match_solver_across_stdlib(self):
        """The executable push plan charges exactly the PushSolver-minimal
        read rounds the paper-faithful STM (`palgol_push`) counts — the
        re-alignment contract: accounting == dispatch, now for push too."""
        for name in alg.ALL:
            g, fields = _setup(name if name in ("sssp", "chain4") else "wcc")
            fields = _stdlib_fields(name, g, fields)
            for step in _steps(alg.ALL[name], g, fields):
                plan = lower_step(step, schedule="push")
                assert plan.read_rounds == analyze_step(
                    step
                ).push_read_rounds(), name

    def test_remote_update_carries_write_descs(self):
        g, _ = _setup("sv")
        steps = _steps(alg.SV, g)
        body = steps[-1]  # the iteration body step (has the remote write)
        plan = lower_step(body, schedule="pull")
        (ru,) = [op for op in plan.ops if isinstance(op, RemoteUpdate)]
        assert ru.writes == (("D", "<?="),)
        assert plan.ops[-2] == MainCompute(emits_remote=True)

    def test_general_read_costs_read_rounds(self):
        """A computed-index ("general") read is one request/reply
        conversation in manual code and one gather round under pull; the
        plan charges those supersteps (chain-less rounds — the value is
        consumed inline in main), keeping the old STM charges AND making
        every executor actually dispatch what the model counts."""
        src = """
for v in V
    local A[v] := Id[v] % numV
    local B[v] := Id[v] * 2
end
for v in V
    local X[v] := B[(A[v] + 1) % numV]
end
"""
        g = G.erdos_renyi(24, 2.0, directed=False, seed=0)
        cp = compile_program(src, g)
        step = _steps(src, g)[-1]
        pull = lower_step(step, schedule="pull")
        naive = lower_step(step, schedule="naive")
        assert pull.read_rounds == 1 and not pull.ops[0].chains
        assert [op.kind for op in naive.ops[:-1]] == ["request", "reply"]
        # old STM charges hold and match execution on every executor
        dense, _, counts = cp.run()
        assert counts["pull_staged"] == 1 + 2  # init main + RR + main
        assert counts["naive"] == 1 + 3
        f0 = cp.init_fields()
        for sched in ("pull", "push", "naive", "auto"):
            for placement, kw in (
                ("replicated", {}), ("partitioned", {"n_shards": 1}),
            ):
                # fuse=False isolates the per-step expansion this test pins
                res = run_bsp(
                    cp.prog, g, f0, schedule=sched, placement=placement,
                    fuse=False, **kw
                )
                key = {
                    "pull": "pull_staged", "auto": "pull_staged",
                    "push": "push", "naive": "naive",
                }[sched]
                assert res.supersteps == counts[key], (sched, placement)
                assert np.array_equal(
                    np.asarray(dense["X"]), np.asarray(res.fields["X"])
                )

    def test_unknown_schedule_rejected(self):
        g, _ = _setup("wcc")
        (s0, *_) = _steps(alg.WCC, g)
        with pytest.raises(ValueError):
            lower_step(s0, schedule="bogus")

    def test_one_op_is_one_superstep_across_stdlib(self):
        """`len(plan.ops)` must equal read_rounds + main + remote-update —
        the invariant the STM cost models and all executors count on."""
        for name, src in alg.ALL.items():
            g, fields = _setup(name if name in ("sssp", "chain4") else "wcc")
            fields = _stdlib_fields(name, g, fields)
            for step in _steps(alg.ALL[name], g, fields):
                for sched in SCHEDULES:
                    plan = lower_step(step, schedule=sched)
                    assert plan.n_supersteps == (
                        plan.read_rounds
                        + 1
                        + (1 if plan.has_remote_update else 0)
                    ), (name, sched)


class TestAutoSelector:
    def test_auto_matches_cheapest_hand_picked_plan(self):
        """The selector's plan must be exactly the cheapest of the three
        hand-picked lowerings (by the plan's own op count; ties keep the
        pull → push → naive preference order)."""
        for name, src in alg.ALL.items():
            g = G.erdos_renyi(30, 3.0, directed=False, weighted=True, seed=1)
            fields = {
                "D": jnp.zeros((30,), jnp.int32),
                "P": jnp.zeros((30,), jnp.float32),
                "Side": jnp.zeros((30,), jnp.int32),
                "K": jnp.full((30,), 2, jnp.int32),
            }
            for step in _steps(src, g, fields):
                hand = [
                    lower_step(step, schedule=s)
                    for s in ("pull", "push", "naive")
                ]
                auto = lower_step(step, schedule="auto")
                best = min(hand, key=lambda p: p.n_supersteps)
                assert auto.ops == best.ops, (name, auto.describe())
                assert auto.schedule == best.schedule
                assert auto.requested == "auto"

    def test_auto_cost_model_lower_bounds(self):
        """STM: auto ≤ min(pull_staged, push, naive) on any trip vector."""
        from repro.core.parser import parse
        from repro.core.stm import superstep_report

        for name, src in alg.ALL.items():
            rep = superstep_report(parse(src))
            trips = {i: 3 for i in range(4)}
            assert rep["auto"].count(trips) <= rep["pull_staged"].count(trips)
            assert rep["auto"].count(trips) <= rep["push"].count(trips)
            assert rep["auto"].count(trips) <= rep["naive"].count(trips)

    def test_byte_aware_auto_never_costlier_and_flips_on_sparse(self):
        """With a ByteCostModel, auto's score must lower-bound every
        hand-picked schedule's score; on a deep chain with a tiny
        (combined) request set it must abandon pull — pointer doubling
        materializes intermediates at *every* vertex, so per-hop
        request/reply wins the byte model there (ROADMAP's 'naive can win
        on tiny request sets at deep chains', now selected for real)."""
        g, fields = _setup("chain4")
        (step,) = _steps(alg.CHAIN4, g, fields)
        dense = ByteCostModel(n_vertices=g.n_vertices)
        sparse = ByteCostModel(
            n_vertices=g.n_vertices, request_set=4, combined_request_set=2
        )
        for costs in (dense, sparse):
            auto = lower_step(step, schedule="auto", byte_costs=costs)
            for s in ("pull", "push", "naive"):
                hand = lower_step(step, schedule=s)
                assert plan_score(auto, costs) <= plan_score(hand, costs), s
        assert lower_step(step, schedule="auto", byte_costs=dense).schedule == "pull"
        picked = lower_step(step, schedule="auto", byte_costs=sparse)
        assert picked.schedule in ("push", "naive")
        # message combining makes push the winner of the sparse regime
        assert picked.schedule == "push"

    def test_byte_aware_auto_matches_execution_and_stm(self):
        """run_bsp(schedule="auto", byte_costs=...) must execute exactly
        the superstep count the STM auto model (built with the same costs)
        predicts, and still bit-match dense — on both placements."""
        g, fields = _setup("chain4")
        sparse = ByteCostModel(
            n_vertices=g.n_vertices, request_set=4, combined_request_set=2
        )
        cp = compile_program(
            alg.CHAIN4, g, initial_fields=fields, byte_costs=sparse
        )
        dense_out, _, counts = cp.run(fields)
        # the auto model selected push for the one step of chain4
        assert counts["auto"] == counts["push"] > counts["pull_staged"]
        f0 = cp.init_fields(fields)
        for placement, kw in (
            ("replicated", {}), ("partitioned", {"n_shards": 1}),
        ):
            for fuse_flag, key in ((True, "fused_auto"), (False, "auto")):
                res = run_bsp(
                    cp.prog, g, f0, schedule="auto", placement=placement,
                    byte_costs=sparse, fuse=fuse_flag, **kw,
                )
                assert res.supersteps == counts[key], (placement, fuse_flag)
                assert np.array_equal(
                    np.asarray(dense_out["D4"]), np.asarray(res.fields["D4"])
                )


MATRIX_ALGS = [
    "sssp",
    "wcc",
    "sv",
    "chain4",
    "mis",
    "bipartite_matching",
    "mwm",
    "pagerank",
    "scc",
    "bfs",
    "kcore",
]


class TestExecutorScheduleMatrix:
    """Every (executor × schedule) cell bit-matches the fused dense
    executor, with identical plan-derived superstep counts. S=1 exercises
    the whole partitioned machinery in-process (the 8-device subprocess
    case below keeps one multi-shard representative)."""

    #: schedule → (fused, unfused) STM cost-model keys
    SCHED_COUNTS = {
        "pull": ("palgol_pull", "pull_staged"),
        "push": ("palgol_push", "push"),
        "naive": ("fused_naive", "naive"),
        "auto": ("fused_auto", "auto"),
    }

    @pytest.mark.parametrize("name", MATRIX_ALGS)
    @pytest.mark.parametrize("schedule", ["push", "naive", "auto"])
    def test_partitioned_matches_dense(self, name, schedule):
        g, fields = _setup(name)
        cp = compile_program(alg.ALL[name], g, initial_fields=fields)
        dense, _, counts = cp.run(fields)
        f0 = cp.init_fields(fields)
        res = run_bsp(
            cp.prog, g, f0, schedule=schedule,
            placement="partitioned", n_shards=1,
        )
        for f in dense:
            assert np.array_equal(
                np.asarray(dense[f]), np.asarray(res.fields[f]),
                equal_nan=True,
            ), (name, schedule, f)
        # the default execution is the fused plan
        assert res.supersteps == counts[self.SCHED_COUNTS[schedule][0]]

    @pytest.mark.parametrize("name", MATRIX_ALGS)
    def test_staged_and_partitioned_counts_agree(self, name):
        """Both executors charge the same plan, so their executed superstep
        totals agree cell-for-cell across schedules."""
        g, fields = _setup(name)
        cp = compile_program(alg.ALL[name], g, initial_fields=fields)
        f0 = cp.init_fields(fields)
        for schedule in ("pull", "push", "naive", "auto"):
            staged = run_bsp(cp.prog, g, f0, schedule=schedule)
            part = run_bsp(
                cp.prog, g, f0, schedule=schedule,
                placement="partitioned", n_shards=1,
            )
            assert staged.supersteps == part.supersteps, (name, schedule)

    @pytest.mark.parametrize("schedule", ["push", "naive"])
    def test_fused_dense_schedule_matches_pull(self, schedule):
        """compile_program(schedule=...) folds the request/reply (or
        request/combined-reply) plan into the fused trace; results are
        bit-identical to pull (the wire term is exactly zero)."""
        for name in MATRIX_ALGS:
            g, fields = _setup(name)
            ref, _, _ = compile_program(
                alg.ALL[name], g, initial_fields=fields
            ).run(fields)
            out, _, _ = compile_program(
                alg.ALL[name], g, initial_fields=fields, schedule=schedule
            ).run(fields)
            for f in ref:
                assert np.array_equal(
                    np.asarray(ref[f]), np.asarray(out[f]), equal_nan=True
                ), (name, f)

    def test_push_executed_counts_match_both_fuse_settings(self):
        """Executed push supersteps == the `push` STM total when unfused,
        and == the paper-faithful `palgol_push` total (state merging +
        iteration fusion) by default — optimized accounting IS optimized
        execution now, not a separate model."""
        for name in MATRIX_ALGS:
            g, fields = _setup(name)
            cp = compile_program(alg.ALL[name], g, initial_fields=fields)
            _, _, counts = cp.run(fields)
            f0 = cp.init_fields(fields)
            res = run_bsp(cp.prog, g, f0, schedule="push", fuse=False)
            assert res.supersteps == counts["push"], name
            fused = run_bsp(cp.prog, g, f0, schedule="push")
            assert fused.supersteps == counts["palgol_push"], name
            assert counts["palgol_push"] <= counts["push"], name


def test_chain_mode_shim_removed():
    """PR 3's one-release deprecation window is over: the mutable
    ``codegen.CHAIN_MODE`` global must be gone for good."""
    from repro.core import codegen

    assert not hasattr(codegen, "CHAIN_MODE")
    assert not hasattr(codegen, "resolve_schedule")


SUBPROCESS_TEST = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax.numpy as jnp
    from repro.core import algorithms as alg, compile_program
    from repro.graph import generators as G
    from repro.pregel import run_bsp

    # one representative program: S-V has chain access (pointer doubling vs
    # per-hop gather_global vs the push request/combined-reply rounds),
    # neighborhood reads, and remote writes — every collective the
    # push/naive partitioned paths add
    g = G.erdos_renyi(48, 3.0, directed=False, weighted=True, seed=3)
    cp = compile_program(alg.SV, g)
    dense, _, counts = cp.run()
    f0 = cp.init_fields()
    for sched, key in (
        ("push", "palgol_push"), ("naive", "fused_naive"),
        ("auto", "fused_auto"),
    ):
        res = run_bsp(cp.prog, g, f0, schedule=sched, placement="partitioned")
        for f in dense:
            a, b = np.asarray(dense[f]), np.asarray(res.fields[f])
            assert np.array_equal(a, b, equal_nan=True), (sched, f)
        assert res.supersteps == counts[key], (
            sched, res.supersteps, counts[key])
        print(sched, "ok", res.supersteps)
    print("PLAN_SUBPROCESS_OK")
    """
)


@pytest.mark.subprocess_mesh
def test_partitioned_schedules_multidevice_single_program():
    """S-V under schedule="push"/"naive"/"auto" on the 8-fake-device mesh:
    bit-identical fields and plan-derived superstep counts vs dense."""
    res = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_TEST],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        timeout=900,
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    assert "PLAN_SUBPROCESS_OK" in res.stdout, res.stdout + res.stderr
