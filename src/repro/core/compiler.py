"""Palgol program compilation: AST → executable JAX + STM cost models.

``compile_program`` produces a :class:`CompiledProgram` whose ``fn`` is a
pure, jit-able ``(fields, graph) → (fields, trips, frontier)`` function:
fixed-point iterations become ``lax.while_loop`` (termination when no
vertex's fix fields changed — Pregel's OR aggregator, counted), sequences
compose, and the whole Palgol program traces into a single XLA
computation. ``trips`` counts body executions per iteration node so the
STM cost models can report superstep totals for the paper's Table-5
accounting; ``frontier`` holds how many vertices changed on each trip.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import ast
from repro.core import parser as palgol_parser
from repro.core import plan as plan_mod
from repro.core import stm as stm_mod
from repro.core.analysis import CompileError, iter_steps
from repro.core.codegen import (
    HALTED,
    StepExecutor,
    exec_plan_part,
    frontier_count,
    make_stop_fn,
    needs_weight_bounds,
    plan_scope,
)
from repro.core.plan import ByteCostModel, SCHEDULES, lower_step
from repro.graph.structure import with_segment_ends, with_weight_bounds
from repro.trace import counted, span

# pre-order Iter list — the shared iteration-counter index order
_iter_nodes = plan_mod.iter_nodes

#: trips of one loop whose frontier ``fn`` keeps one by one; later trips
#: are added into the last slot
FRONTIER_TRIPS = 1024


@dataclasses.dataclass
class CompiledProgram:
    prog: ast.Prog
    graph: object
    field_struct: Dict[str, jax.ShapeDtypeStruct]
    n_iters: int
    max_iters: int
    cost_models: Dict[str, stm_mod.CostModel]
    # chain-access schedule the fused trace lowers under ("pull" | "push" |
    # "naive" | "auto"); None means "pull"
    schedule: Optional[str] = None
    # per-round byte estimates feeding the byte-aware ``auto`` selector
    # (None: auto selects on op count alone)
    byte_costs: Optional[ByteCostModel] = None
    # apply the §4.3 fuse pass (state merging + iteration fusion) to the
    # program plan ``fn`` folds into its trace; False keeps the unfused
    # per-op expansion for A/B comparisons
    fuse: bool = True
    #: edge reductions of the last traced ``fn`` by the path they took,
    #: ``{"scan": k, "scatter": m}`` (``StepExecutor._reduce_edges``), and
    #: under ``"fold"`` those of them whose neighbour-only filters folded
    #: into the gathered value (``StepExecutor._edge_reduce``)
    edge_reduce_paths: Optional[Dict[str, int]] = dataclasses.field(
        default=None, init=False
    )

    def step_plans(
        self, schedule: Optional[str] = None
    ) -> List[tuple]:
        """``(step, StepPlan)`` for every Step node, in program order —
        what ``fn`` folds into the trace."""
        sched = (
            schedule if schedule is not None else self.schedule
        ) or "pull"
        return [
            (s, lower_step(s, schedule=sched, byte_costs=self.byte_costs))
            for s in iter_steps(self.prog)
            if isinstance(s, ast.Step)
        ]

    def program_plan(
        self,
        schedule: Optional[str] = None,
        fuse: Optional[bool] = None,
    ) -> plan_mod.ProgramPlan:
        """The whole-program superstep schedule ``fn`` executes — fused by
        default (§4.3 state merging + iteration fusion applied for real)."""
        sched = (
            schedule if schedule is not None else self.schedule
        ) or "pull"
        pp = plan_mod.lower_program(
            self.prog, schedule=sched, byte_costs=self.byte_costs
        )
        if self.fuse if fuse is None else fuse:
            pp = plan_mod.fuse(pp)
        return pp

    def init_fields(self, user_fields: Optional[Dict[str, jax.Array]] = None):
        """Canonical field dict: user fields + zero-init for created fields."""
        fields = {}
        user_fields = user_fields or {}
        for name, sds in self.field_struct.items():
            if name in user_fields:
                arr = jnp.asarray(user_fields[name])
                if arr.shape != sds.shape or arr.dtype != sds.dtype:
                    arr = jnp.broadcast_to(arr, sds.shape).astype(sds.dtype)
                fields[name] = arr
            else:
                fields[name] = jnp.zeros(sds.shape, sds.dtype)
        for name in user_fields:
            if name not in fields:
                fields[name] = jnp.asarray(user_fields[name])
        return fields

    def fn(self, fields: Dict[str, jax.Array], graph):
        """Pure program function: (fields, graph) → (fields,
        trips[i32[n_iters]], frontier[i32[n_iters, FRONTIER_TRIPS]]).

        Folds the (by default fused) :class:`~repro.core.plan.ProgramPlan`
        into one trace: superstep parts execute in plan order against the
        program-level mailbox, and a fused loop's prefetched ReadRound
        buffers ride the ``lax.while_loop`` carry — the loop-back edge of
        §4.3.2 iteration fusion, traced for real.

        ``frontier[i, t]`` is the number of vertices whose fix fields
        changed on trip ``t`` of loop ``i`` (all entries of a nested loop
        in turn), the count whose zero ends the loop; trips from
        :data:`FRONTIER_TRIPS` on are added into the last slot, and a loop
        without fix fields counts nothing.

        ``graph`` is a traced argument (the compile-time graph or any graph
        of the same static shape): a closed-over graph would be embedded in
        the HLO as constants, which at chip scale exceeds the 2 GB
        serialization limit. Its edge reductions scan the sorted edges
        where it carries run ends (``Graph.in_ends``/``out_ends``), and a
        minimum or maximum of a neighbour value plus ``e.w`` folds its
        neighbour-only filters where it carries ``weights_bounded``, as
        ``self.graph`` does; tracing sets :attr:`edge_reduce_paths`.
        """
        with counted("edge_reduce/") as paths, plan_scope():
            out = self._fn(fields, graph)
        self.edge_reduce_paths = {
            k: paths[k] for k in ("scan", "scatter", "fold")
        }
        return out

    def _fn(self, fields, graph):
        pp = self.program_plan()
        n_loops = max(self.n_iters, 1)
        trips0 = jnp.zeros((n_loops,), jnp.int32)
        frontier0 = jnp.zeros((n_loops, FRONTIER_TRIPS), jnp.int32)

        def run_items(items, flds, mailbox, counters):
            for it in items:
                if isinstance(it, plan_mod.Superstep):
                    for ref in it.parts:
                        flds, mailbox = exec_plan_part(
                            ref, graph, None, flds, mailbox
                        )
                    continue
                # PlanLoop: the mailbox joins the while carry — prefetched
                # chain/nbr buffers are re-created by the fused body's
                # trailing ReadRound, so the carry structure is stable
                fix = it.node.fix_fields
                limit = (
                    it.node.fixed_trips
                    if it.node.fixed_trips is not None
                    else self.max_iters
                )
                for name in fix:
                    if name not in flds:
                        raise CompileError(f"fix field {name!r} undefined")

                def cond(carry, _limit=limit):
                    _, _, _, changed, k = carry
                    with jax.named_scope("fixpoint"):
                        return jnp.logical_and(changed, k < _limit)

                def body(carry, _it=it, _fix=fix):
                    f, m, c, _, k = carry
                    new_f, m, (t, fr) = run_items(_it.body, f, m, c)
                    i = _it.iter_index
                    with jax.named_scope("fixpoint"):
                        if _fix:
                            count = frontier_count(f, new_f, _fix)
                            at = (i, jnp.minimum(t[i], FRONTIER_TRIPS - 1))
                            seen = jax.lax.dynamic_slice(fr, at, (1, 1))
                            fr = jax.lax.dynamic_update_slice(
                                fr, seen + count, at
                            )
                            changed = count > 0
                        else:
                            changed = jnp.asarray(True)  # fixed-trip iteration
                        t = t.at[i].add(1)
                        return new_f, m, (t, fr), changed, k + 1

                carry = (
                    flds, mailbox, counters,
                    jnp.asarray(True), jnp.asarray(0, jnp.int32),
                )
                with jax.named_scope(f"L{it.iter_index}"):
                    flds, mailbox, counters, _, _ = jax.lax.while_loop(
                        cond, body, carry
                    )
            return flds, mailbox, counters

        out_fields, _, (trips, frontier) = run_items(
            pp.items, dict(fields), {}, (trips0, frontier0)
        )
        return out_fields, trips, frontier

    @functools.cached_property
    def _jitted_fn(self):
        # one jit per program: repeated runs reuse its compiled executable
        return jax.jit(self.fn)

    def run(
        self,
        user_fields: Optional[Dict[str, jax.Array]] = None,
        jit: bool = True,
    ):
        """Execute; returns (fields, trips, counts): the superstep counts
        per regime and, under ``"active_sets"``, the frontier of every
        trip of every loop (:meth:`fn`; the shape of
        ``BSPResult.active_sets`` for loops that are not nested)."""
        with span("init_fields"):
            fields = self.init_fields(user_fields)
        fn = self._jitted_fn if jit else self.fn
        with span("execute"):
            out, trips, frontier = fn(fields, self.graph)
        with span("read_counters"):
            trips, frontier = jax.device_get((trips, frontier))
        trips_host = trips.tolist()
        counts = {
            name: cm.count(trips_host) for name, cm in self.cost_models.items()
        }
        counts["active_sets"] = [
            frontier[i, : min(n, FRONTIER_TRIPS)].tolist()
            if node.fix_fields else []
            for i, (node, n) in enumerate(
                zip(_iter_nodes(self.prog), trips_host)
            )
        ]
        return out, trips_host, counts


def _discover_fields(prog, graph, fields_struct):
    """eval_shape pass discovering created fields + stable dtypes."""

    def step_pass(step, fs):
        def f(flds):
            # field discovery is schedule-independent (identical shapes /
            # dtypes under every schedule) — pin pull for determinism
            return StepExecutor(step, graph, schedule="pull")(flds)

        return dict(jax.eval_shape(f, fs))

    def stop_pass(stop, fs):
        def f(flds):
            return make_stop_fn(stop, graph)(flds)

        return dict(jax.eval_shape(f, fs))

    def go(p, fs):
        if isinstance(p, ast.Step):
            return step_pass(p, fs)
        if isinstance(p, ast.StopStep):
            return stop_pass(p, fs)
        if isinstance(p, ast.Seq):
            for q in p.progs:
                fs = go(q, fs)
            return fs
        if isinstance(p, ast.Iter):
            fs2 = go(p.body, fs)
            # one more pass with the enriched struct: dtypes must be stable
            fs3 = go(p.body, fs2)
            if {k: (v.shape, v.dtype) for k, v in fs2.items()} != {
                k: (v.shape, v.dtype) for k, v in fs3.items()
            }:
                raise CompileError(
                    "iteration body changes field shapes/dtypes between "
                    "iterations — not expressible as a fixed carry"
                )
            return fs2
        raise CompileError(f"unknown program node {type(p).__name__}")

    return go(prog, dict(fields_struct))


@span("compile_program")
def compile_program(
    source_or_ast: Union[str, ast.Prog],
    graph,
    initial_fields: Optional[Dict[str, jax.Array]] = None,
    max_iters: int = 100_000,
    schedule: Optional[str] = None,
    byte_costs: Optional[ByteCostModel] = None,
    fuse: bool = True,
) -> CompiledProgram:
    """Compile Palgol source (or AST) against a graph.

    ``initial_fields`` supplies dtypes/values of pre-existing fields; fields
    created by the program (via ``local F[v] := ...``) are discovered with an
    abstract-evaluation pass and zero-initialized.

    ``schedule`` selects the chain-access lowering the fused trace folds
    in (``"pull"`` — pointer-doubling gather DAG, ``"push"`` — the
    paper-faithful request/combined-reply message schedule, ``"naive"`` —
    per-hop request/reply wire-cost model, ``"auto"`` — per-step cheapest).
    ``None`` means ``"pull"``. ``byte_costs`` (a
    :class:`repro.core.plan.ByteCostModel`, e.g. from
    :func:`repro.graph.partition.byte_cost_model`) makes ``"auto"`` select
    on (supersteps, modeled wire bytes) instead of op count; the STM
    ``auto`` cost model is built with the same costs so the accounting
    tracks the selection.

    ``graph`` is a :class:`~repro.graph.structure.Graph`. Where it lacks
    the run ends of an ordering the program's edge lists read, they are
    computed once, on its device (the span ``segment_ends``), and
    :attr:`CompiledProgram.graph` is the graph that holds them. So is the
    bound of its live weights (``Graph.weights_bounded``), in the same
    span, where a comprehension folds only if it holds
    (:func:`repro.core.codegen.needs_weight_bounds`).

    ``fuse`` (default True) applies the §4.3 program-level optimizations
    (state merging + iteration fusion, :func:`repro.core.plan.fuse`) to the
    plan the trace folds in; ``fuse=False`` keeps the unfused per-op
    expansion for A/B comparisons. Results are bit-identical either way —
    fusion moves superstep boundaries, never reorders primitive ops.
    """
    with span("parse"):
        prog = (
            palgol_parser.parse(source_or_ast)
            if isinstance(source_or_ast, str)
            else source_or_ast
        )
    if schedule is not None and schedule not in SCHEDULES:
        raise CompileError(
            f"unknown schedule {schedule!r}; expected one of {SCHEDULES}"
        )
    directions = {
        e.direction for e in ast.walk_exprs(prog) if isinstance(e, ast.EdgeList)
    }
    jax.block_until_ready(graph)  # the span times the ends alone
    with span("segment_ends"):
        graph = with_segment_ends(graph, directions)
        if needs_weight_bounds(prog):
            graph = with_weight_bounds(graph)
        graph = jax.block_until_ready(graph)
    n = graph.n_vertices
    fs: Dict[str, jax.ShapeDtypeStruct] = {
        HALTED: jax.ShapeDtypeStruct((n,), jnp.bool_)
    }
    for name, arr in (initial_fields or {}).items():
        arr = jnp.asarray(arr)
        fs[name] = jax.ShapeDtypeStruct(arr.shape, arr.dtype)
    with span("discover_fields"):
        field_struct = _discover_fields(prog, graph, fs)
    with span("cost_models"):
        cost_models = stm_mod.superstep_report(prog, byte_costs=byte_costs)
    return CompiledProgram(
        prog=prog,
        graph=graph,
        field_struct=field_struct,
        n_iters=len(_iter_nodes(prog)),
        max_iters=max_iters,
        cost_models=cost_models,
        schedule=schedule,
        byte_costs=byte_costs,
        fuse=fuse,
    )
