"""Program-plan fusion tests (`repro.core.plan.lower_program` + `fuse`).

Four layers:

* **fusion structure** — state merging fires at program-node boundaries
  (and chains), the read/write-set guard withholds it across a
  read-after-write boundary, iteration fusion duplicates the body's
  leading ReadRound into the preceding superstep and merges it into the
  body's tail;
* **former-STM equivalence** (hypothesis property): on
  randomized chain programs the fused plan's superstep totals equal the
  pre-refactor ``build_stm(..., optimize=True)`` accounting — the
  unconditional-merge + iteration-fusion logic this PR deleted from
  ``core/stm.py``, ported verbatim below as the reference;
* **fused execution** — ``fuse=True`` (the default) bit-matches
  ``fuse=False`` on SSSP/WCC/S-V/chain4 for every schedule on both
  placements, executes exactly the ``palgol_*``/``fused_*`` STM totals,
  and saves ≥ 1 superstep per iteration on S-V (the §4.3.2 claim,
  measured); per-iteration fixed-point frontiers are recorded;
* one 8-fake-device subprocess representative keeps the multi-shard fused
  collectives (merged RemoteUpdate + prefetched ReadRound in one
  dispatch, deduplicated gather_global requests) honest.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import algorithms as alg
from repro.core import ast as past
from repro.core import compile_program, fuse, lower_program
from repro.core.parser import parse
from repro.core.plan import (
    IterInit,
    MainCompute,
    PlanLoop,
    ReadRound,
    RemoteUpdate,
    StopOp,
    Superstep,
    lower_step,
)
from repro.core.stm import build_stm
from repro.graph import generators as G
from repro.pregel import run_bsp


# ---------------------------------------------------------------------------
# reference: the deleted pre-refactor STM accounting (unconditional state
# merging at sequence boundaries, iteration fusion when the body starts
# with a read state) — what `optimize=True` used to count


def _former_optimized_count(prog: past.Prog, mode: str, trips) -> int:
    iter_counter = [0]

    def step_states(step):
        out = []
        for op in lower_step(step, schedule=mode).ops:
            if isinstance(op, ReadRound):
                out.append("read")
            elif isinstance(op, MainCompute):
                out.append("main")
            else:
                out.append("update")
        return out

    def build(p):
        if isinstance(p, past.Step):
            return step_states(p)
        if isinstance(p, past.StopStep):
            return ["main"]
        if isinstance(p, past.Seq):
            out = []
            for sub in p.progs:
                states = build(sub)
                if (
                    out and states
                    and isinstance(out[-1], str) and isinstance(states[0], str)
                ):
                    states = states[1:]  # unconditional §4.3.1 merge
                out.extend(states)
            return out
        if isinstance(p, past.Iter):
            body = build(p.body)
            idx = iter_counter[0]
            iter_counter[0] += 1
            if (
                not any(isinstance(b, tuple) for b in body)
                and body and body[0] == "read"
            ):
                # §4.3.2: S1 duplicated into init, merged into S_n
                return ["main", ("loop", body[1:], idx)]
            return ["main", ("loop", body, idx)]
        raise TypeError(type(p))

    def count(items) -> int:
        total = 0
        for it in items:
            if isinstance(it, str):
                total += 1
            else:
                _, body, idx = it
                per_iter = sum(1 for b in body if isinstance(b, str))
                total += int(trips.get(idx, 0)) * per_iter
                total += count([b for b in body if isinstance(b, tuple)])
        return total

    return count(build(prog))


def _chain(depth: int, field: str = "D") -> str:
    e = "v"
    for _ in range(depth):
        e = f"{field}[{e}]"
    return e


@st.composite
def chain_programs(draw):
    """Random Seq-of-chain-steps programs (optionally loop-wrapped): each
    step writes a fresh field and reads only chains over ``D``, so the
    read/write-set guard is satisfied at every boundary — the regime where
    the new conditional merge must reproduce the old unconditional one."""
    n_steps = draw(st.integers(1, 4))
    steps = [
        f"for v in V\n    local X{i}[v] := "
        f"{_chain(draw(st.integers(2, 5)))}\nend"
        for i in range(n_steps)
    ]
    body = "\n".join(steps)
    trips = draw(st.integers(1, 4))
    if draw(st.booleans()):
        inner = textwrap.indent(body, "    ")
        return f"do\n{inner}\nuntil iter [{trips}]", {0: trips}
    return body, {}


@settings(max_examples=30, deadline=None)
@given(chain_programs())
def test_fused_totals_match_former_stm_on_chain_programs(case):
    src, trips = case
    prog = parse(src)
    for mode in ("pull", "push"):
        got = build_stm(prog, mode, optimize=True)[1].count(trips)
        want = _former_optimized_count(prog, mode, trips)
        assert got == want, (src, mode, got, want)


# ---------------------------------------------------------------------------
# fusion structure


def _flat_parts(items):
    out = []
    for it in items:
        if isinstance(it, Superstep):
            out.append(it)
        else:
            out.extend(_flat_parts(it.body))
    return out


class TestFusionStructure:
    def test_disjoint_mains_merge_unconditionally(self):
        """§4.3.1's canonical example: two adjacent local-compute steps
        collapse into one superstep (message independence — even though
        the second reads what the first wrote, the merged superstep
        sequences compute before sends)."""
        pp = fuse(lower_program(parse(
            "for v in V\n    local A[v] := 0\nend\n"
            "for v in V\n    local A[v] := A[v] + 1\nend"
        )))
        assert len(pp.items) == 1
        (ss,) = pp.items
        assert [type(r.op) for r in ss.parts] == [MainCompute, MainCompute]

    def test_raw_guard_withholds_merge_into_read_round(self):
        """A ReadRound whose gathers read fields the previous superstep
        writes does NOT merge — its outgoing request set must be derivable
        from pre-superstep state."""
        pp = fuse(lower_program(parse(
            "for v in V\n    local A[v] := Id[v]\nend\n"
            "for v in V\n    local B[v] := A[A[v]]\nend"
        )))
        # step1 Main stays alone; step2 [RR, Main] keeps its own supersteps
        assert [it.describe() for it in pp.items] == [
            "Main", "RR[pull]", "Main",
        ]
        # but with disjoint fields the same shape merges
        pp2 = fuse(lower_program(parse(
            "for v in V\n    local A[v] := Id[v]\nend\n"
            "for v in V\n    local B[v] := D[D[v]]\nend"
        )))
        assert [it.describe() for it in pp2.items] == ["Main+RR[pull]", "Main"]

    def test_iteration_fusion_prefetches_leading_read_round(self):
        """S-V: the body's leading ReadRound is duplicated into the merged
        init superstep and overlapped with the body tail's RemoteUpdate —
        one dispatch carries both collectives, one superstep per iteration
        saved."""
        pp = fuse(lower_program(parse(alg.SV)))
        init, loop = pp.items
        assert isinstance(loop, PlanLoop) and loop.fused
        # init = init-step Main + IterInit + prefetched RR
        assert [type(r.op) for r in init.parts] == [
            MainCompute, IterInit, ReadRound,
        ]
        assert [ss.describe() for ss in loop.body] == ["Main", "RU+RR[pull]"]

    def test_stop_merges_as_message_independent_target(self):
        """MWM: the stop superstep merges into the preceding main (it
        consumes no messages), and iteration fusion lands the prefetch on
        the merged tail."""
        pp = fuse(lower_program(parse(alg.MWM)))
        _, loop = pp.items
        assert loop.fused
        tail = loop.body[-1]
        kinds = [type(r.op) for r in tail.parts]
        assert kinds == [MainCompute, StopOp, ReadRound]

    def test_fused_counts_equal_execution_contract(self):
        """pp.cost() is what build_stm(optimize=True) reports — stm.py has
        no derivation of its own anymore."""
        for src in alg.ALL.values():
            prog = parse(src)
            for mode in ("pull", "push", "naive"):
                base, per_iter, _ = fuse(
                    lower_program(prog, schedule=mode)
                ).cost()
                cm = build_stm(prog, mode, optimize=True)[1]
                assert (base, per_iter) == (cm.base, cm.per_iter)

    def test_unfused_plan_counts_one_op_per_superstep(self):
        for src in alg.ALL.values():
            prog = parse(src)
            pp = lower_program(prog)
            for ss in _flat_parts(pp.items):
                assert len(ss.parts) == 1


# ---------------------------------------------------------------------------
# fused execution


def _setup(name, seed=3):
    fields = None
    if name == "sssp":
        g = G.erdos_renyi(40, 4.0, directed=True, weighted=True, seed=seed)
    elif name == "chain4":
        g = G.erdos_renyi(30, 2.0, directed=False, seed=seed)
        rng = np.random.default_rng(seed)
        fields = {"D": jnp.asarray(rng.integers(0, 30, 30), jnp.int32)}
    else:
        g = G.erdos_renyi(40, 3.0, directed=False, weighted=True, seed=seed)
    return g, fields


FUSED_KEY = {
    "pull": "palgol_pull", "push": "palgol_push",
    "naive": "fused_naive", "auto": "fused_auto",
}
UNFUSED_KEY = {
    "pull": "pull_staged", "push": "push", "naive": "naive", "auto": "auto",
}


class TestFusedExecution:
    # pull + push span the collective shapes (gather DAG vs combined
    # request/reply); naive/auto fused cells are covered by the staged
    # matrix below and tests/test_plan.py's partitioned matrix
    @pytest.mark.parametrize("name", ["sssp", "wcc", "sv", "chain4"])
    @pytest.mark.parametrize("schedule", ["pull", "push"])
    def test_fused_bitmatches_unfused_both_placements(self, name, schedule):
        g, fields = _setup(name)
        cp = compile_program(alg.ALL[name], g, initial_fields=fields)
        dense, _, counts = cp.run(fields)
        f0 = cp.init_fields(fields)
        for placement, kw in (
            ("replicated", {}), ("partitioned", {"n_shards": 1}),
        ):
            fused = run_bsp(
                cp.prog, g, f0, schedule=schedule, placement=placement, **kw
            )
            unfused = run_bsp(
                cp.prog, g, f0, schedule=schedule, placement=placement,
                fuse=False, **kw
            )
            for f in dense:
                a = np.asarray(dense[f])
                assert np.array_equal(
                    a, np.asarray(fused.fields[f]), equal_nan=True
                ), (name, schedule, placement, f, "fused")
                assert np.array_equal(
                    a, np.asarray(unfused.fields[f]), equal_nan=True
                ), (name, schedule, placement, f, "unfused")
            assert fused.supersteps == counts[FUSED_KEY[schedule]], (
                name, schedule, placement,
            )
            assert unfused.supersteps == counts[UNFUSED_KEY[schedule]], (
                name, schedule, placement,
            )

    @pytest.mark.parametrize("name", ["sssp", "wcc", "sv", "chain4"])
    @pytest.mark.parametrize("schedule", ["naive", "auto"])
    def test_fused_bitmatches_unfused_staged(self, name, schedule):
        g, fields = _setup(name)
        cp = compile_program(alg.ALL[name], g, initial_fields=fields)
        dense, _, counts = cp.run(fields)
        f0 = cp.init_fields(fields)
        fused = run_bsp(cp.prog, g, f0, schedule=schedule)
        unfused = run_bsp(cp.prog, g, f0, schedule=schedule, fuse=False)
        for f in dense:
            a = np.asarray(dense[f])
            assert np.array_equal(
                a, np.asarray(fused.fields[f]), equal_nan=True
            ), (name, schedule, f)
            assert np.array_equal(
                a, np.asarray(unfused.fields[f]), equal_nan=True
            ), (name, schedule, f)
        assert fused.supersteps == counts[FUSED_KEY[schedule]]
        assert unfused.supersteps == counts[UNFUSED_KEY[schedule]]

    def test_sv_saves_at_least_one_superstep_per_iteration(self):
        """The §4.3 acceptance claim, measured: fused S-V execution spends
        ≥ 1 fewer superstep per iteration than fuse=False, matching the
        former STM optimize=True totals."""
        g, _ = _setup("sv")
        cp = compile_program(alg.SV, g)
        f0 = cp.init_fields()
        fused = run_bsp(cp.prog, g, f0)
        unfused = run_bsp(cp.prog, g, f0, fuse=False)
        iters = fused.trips[0]
        assert fused.trips == unfused.trips
        assert unfused.supersteps - fused.supersteps >= iters
        assert fused.supersteps == _former_optimized_count(
            cp.prog, "pull", {0: iters}
        )

    def test_frontier_instrumentation(self):
        """Both executors record the per-iteration fixed-point frontier:
        one series per loop entry, one entry per trip, converging to 0."""
        g, _ = _setup("wcc")
        cp = compile_program(alg.WCC, g)
        f0 = cp.init_fields()
        for placement, kw in (
            ("replicated", {}), ("partitioned", {"n_shards": 1}),
        ):
            res = run_bsp(cp.prog, g, f0, placement=placement, **kw)
            assert len(res.active_sets) == len(res.trips) == 1
            (series,) = res.active_sets
            assert len(series) == res.trips[0]
            assert series[-1] == 0
            assert all(0 <= x <= g.n_vertices for x in series)


def test_request_dedup_report():
    from repro.graph.partition import request_dedup_report

    rep = request_dedup_report([0, 3, 3, 3, 7, 99], 10, bytes_per_value=4)
    assert rep["raw_request_slots"] == 5  # 99 is out of range
    assert rep["deduped_request_slots"] == 3
    assert rep["raw_bytes"] == 5 * 8 and rep["deduped_bytes"] == 3 * 8


SUBPROCESS_TEST = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax.numpy as jnp
    from repro.core import algorithms as alg, compile_program
    from repro.graph import generators as G
    from repro.pregel import run_bsp

    # S-V: iteration fusion overlaps the RemoteUpdate's reduce-scatter
    # with the prefetched ReadRound's gather_global in ONE shard_map
    # dispatch; chain4 (random D): duplicate-heavy request sets exercise
    # the deduplicated gather_global bucketing across shards
    for name in ("sv", "chain4"):
        fields = None
        if name == "chain4":
            g = G.erdos_renyi(32, 2.0, directed=False, seed=3)
            rng = np.random.default_rng(3)
            fields = {"D": jnp.asarray(rng.integers(0, 32, 32), jnp.int32)}
        else:
            g = G.erdos_renyi(48, 3.0, directed=False, weighted=True, seed=3)
        cp = compile_program(alg.ALL[name], g, initial_fields=fields)
        dense, _, counts = cp.run(fields)
        f0 = cp.init_fields(fields)
        fused = run_bsp(cp.prog, g, f0, placement="partitioned")
        unfused = run_bsp(cp.prog, g, f0, placement="partitioned",
                          fuse=False)
        for f in dense:
            a = np.asarray(dense[f])
            assert np.array_equal(a, np.asarray(fused.fields[f]),
                                  equal_nan=True), (name, f)
            assert np.array_equal(a, np.asarray(unfused.fields[f]),
                                  equal_nan=True), (name, f)
        assert fused.supersteps == counts["palgol_pull"], name
        assert unfused.supersteps == counts["pull_staged"], name
        print(name, "ok", fused.supersteps, "<", unfused.supersteps)
    print("FUSION_SUBPROCESS_OK")
    """
)


@pytest.mark.subprocess_mesh
def test_fused_partitioned_multidevice():
    """S-V + chain4 fused on the 8-fake-device mesh: bit-identical fields,
    fused (palgol) superstep totals, dedup'd multi-shard gather_global."""
    res = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_TEST],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        timeout=900,
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    assert "FUSION_SUBPROCESS_OK" in res.stdout, res.stdout + res.stderr
