"""Staged BSP executor: one device dispatch per Pregel superstep.

Execution model (mirrors paper Fig. 9 + §4.3): the whole Palgol program is
lowered by :func:`repro.core.plan.lower_program` to a
:class:`~repro.core.plan.ProgramPlan` and — by default — rewritten by
:func:`repro.core.plan.fuse` (state merging + iteration fusion, §4.3).
This runtime dispatches **one jitted device call per fused superstep**: a
merged superstep's parts (e.g. the previous step's RemoteUpdate plus the
next step's first ReadRound, or a fused loop's main compute plus the next
iteration's prefetched ReadRound) execute inside one dispatch, threading a
program-level mailbox (chain/neighborhood buffers, pending remote-write
payloads) between dispatches. ``fuse=False`` keeps the historical per-op
expansion — same results, more supersteps.

* ``schedule="pull"`` plans chain reads by the PullSolver gather DAG
  (this framework's optimized one-sided schedule);
* ``schedule="push"`` runs the paper-faithful message schedule: address
  flows forward along the chain while values double back; each
  ``push_request`` op combines requester ids per owner (Pregel message
  combining — a segment-combine scatter), each ``push_reply`` op ships one
  combined reply per distinct owner and materializes its chain buffers;
* ``schedule="naive"`` emulates the hand-written request/reply style: every
  chain hop costs a *request* superstep (push requester ids to the owner —
  a real scatter, matching the message traffic of manual Pregel code) and a
  *reply* superstep (the owner sends the value back — a gather);
* ``schedule="auto"`` picks the cheapest plan per step (by op count, or by
  the byte model when ``byte_costs`` is given);
* fixed-point termination is checked on host between supersteps, exactly like
  Pregel's aggregator round-trip; the per-iteration frontier size (how many
  vertices' fix fields changed, :func:`repro.core.codegen.frontier_count`,
  the count the fused dense loop keeps too) is recorded in
  ``BSPResult.active_sets`` — the live request-set instrumentation the
  byte cost model feeds on.

Each dispatch's device work carries the names of
:mod:`repro.core.codegen` (``palgol/L<i>/s<sidx>/<leaf>``), and the walk
marks each dispatch and each frontier round trip with a host span
(:func:`repro.trace.span`).

The executed-superstep count is returned and cross-checked in tests against
the STM cost models of ``repro.core.stm`` — both count the same fused plan.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core import ast
from repro.core import plan as plan_mod
from repro.core.codegen import (
    HALTED,
    StepExecutor,
    _RemoteMsg,
    _StepState,
    frontier_count,
    make_stop_fn,
    plan_scope,
)
from repro.core.plan import (
    ByteCostModel,
    ReadRound,
    RemoteUpdate,
    StepPlan,
    lower_step,
)
from repro.graph import ops as gops
from repro.trace import span


@dataclasses.dataclass
class BSPResult:
    fields: Dict[str, jax.Array]
    supersteps: int
    trips: List[int]
    # per loop entry, per iteration: number of vertices whose fix fields
    # changed that iteration (the fixed-point frontier — the measured
    # request-set size ByteCostModel.request_set models)
    active_sets: List[List[int]] = dataclasses.field(default_factory=list)
    # partitioned placement: bytes per chip the job's collectives carried,
    # ``"<primitive>/<padded|payload>"`` (repro.graph.partition.halo)
    comm_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)


class _StagedStep:
    """One Palgol step: its :class:`StepPlan` compiled to per-op superstep
    callables ``(fields, mailbox, graph) -> (fields, mailbox)``; the graph
    is an argument of every callable, never captured, so no graph array is
    embedded in a dispatch's HLO as a constant. ``ns`` prefixes
    this step's mailbox keys so supersteps from different steps can share
    the program-level mailbox of the fused plan.

    This path deliberately does NOT reuse
    :func:`repro.core.codegen.exec_plan_part` (the dense/partitioned
    consumer): the staged dispatches additionally emulate the *wire
    traffic* of each round in their lowered HLO — the naive ``:req``
    requester scatters and the push combined-request buffers — which the
    fused dense trace intentionally omits (its ``push_request`` op is
    compute-free). The replicated mailbox keys here are therefore a
    superset of codegen's; keep the two protocols in sync when adding op
    kinds or buffer classes. A round's per-edge buffers, a fold's ``T``
    among them, are the step executor's own (``StepExecutor.run_ops``).
    """

    def __init__(
        self,
        step: ast.Step,
        n_vertices: int,
        schedule: str,
        byte_costs: Optional[ByteCostModel] = None,
        plan: Optional[StepPlan] = None,
        ns: str = "",
    ):
        self.step = step
        self.n = n_vertices
        self.plan = (
            plan
            if plan is not None
            else lower_step(step, schedule=schedule, byte_costs=byte_costs)
        )
        self.info = self.plan.info
        # resolved (auto → pull/push/naive)
        self.schedule = self.plan.schedule
        self.ns = ns

    # -- mailbox keys ---------------------------------------------------------
    def _key(self, pattern) -> str:
        return self.ns + "chain:" + "/".join(pattern)

    def _pkey(self, pattern) -> str:
        return self.ns + "pushaddr:" + "/".join(pattern)

    def _nkey(self, direction, pattern) -> str:
        return f"{self.ns}nbr:{direction}:" + "/".join(pattern)

    def _fkey(self, key) -> str:
        return f"{self.ns}fold:{key}"

    # -- read supersteps -----------------------------------------------------
    def read_stage_fns(self):
        """List of jitted ``(fields, mailbox, graph) -> mailbox`` functions; one
        per ReadRound op of the plan (the accounting-mirror API)."""
        return [
            jax.jit(self._stage_fn(op))
            for op in self.plan.ops
            if isinstance(op, ReadRound)
        ]

    def _combine_requests(self, owner, combine: str):
        """Requester-id scatter by owner — the request-superstep wire
        traffic. ``combine="set"`` is the naive per-requester buffer
        (colliding requesters overwrite: no combining, as manual code);
        ``combine="min"`` is Pregel message combining (one deterministic
        slot per distinct owner). ``n_vertices`` is the empty sentinel."""
        ids = jnp.arange(self.n, dtype=jnp.int32)
        reqbuf = jnp.full_like(ids, self.n)
        if combine == "set":
            return reqbuf.at[owner].set(ids, mode="drop")
        return reqbuf.at[owner].min(ids, mode="drop")

    def _stage_fn(self, op: ReadRound):
        if op.kind == "request":

            def request(fields, mailbox, graph, _op=op):
                # requester u pushes its id to the owner vertex (real
                # scatter: the message traffic manual Pregel code pays)
                out = dict(mailbox)
                with jax.named_scope("chain"):
                    for ce in _op.chains:
                        owner = self._lookup(fields, out, ce.prefix)
                        out[self._key(ce.pattern) + ":req"] = (
                            self._combine_requests(owner, "set")
                        )
                return out

            return request

        if op.kind == "push_request":

            def push_request(fields, mailbox, graph, _op=op):
                # address-propagation round: requester ids move one hop
                # along the chain, message-combined per owner (one slot
                # per distinct owner — the scatter-min IS the combiner)
                out = dict(mailbox)
                with jax.named_scope("chain"):
                    for send in _op.sends:
                        owner = self._resolve(fields, out, send.target)
                        if owner is None:
                            continue
                        out[self._pkey(send.target) + ":req"] = (
                            self._combine_requests(
                                owner, _op.combiner or "min"
                            )
                        )
                return out

            return push_request

        def stage(fields, mailbox, graph, _op=op):
            # "pull": one gather-DAG round; "reply": the owner returns its
            # value to the requester; "push_reply": one combined reply per
            # distinct owner, fanned out to its requesters (the gather),
            # with the request set segment-combined per owner;
            # "nbr_send": per-edge buffers, the step executor's (a fold's
            # T in place of the reads only it consumes)
            out = dict(mailbox)
            with jax.named_scope("chain"):
                self._reply(fields, out, _op)
            if _op.kind == "push_reply":
                # the paired push_request's address buffers were the wire
                # accounting of *their* superstep; done — drop them so
                # later dispatches stop threading dead device buffers
                prefix = self.ns + "pushaddr:"
                for k in [k for k in out if k.startswith(prefix)]:
                    out.pop(k)
            chains = {
                p: out[self._key(p)]
                for p in self.plan.materialized
                if self._key(p) in out
            }
            ex = StepExecutor(self.step, graph, plan=self.plan)
            _, state = ex.run_ops(fields, [_op], _StepState(chain=chains))
            for (direction, npat), v in state.nbr.items():
                out[self._nkey(direction, npat)] = v
            for k, v in state.fold.items():
                out[self._fkey(k)] = v
            return out

        return stage

    def _reply(self, fields, out, op: ReadRound):
        """A value round's chain gathers, into the mailbox ``out``."""
        for ce in op.chains:
            pre = self._lookup(fields, out, ce.prefix)
            suf = self._lookup(fields, out, ce.suffix)
            val = gops.gather(suf, pre)
            if op.kind == "push_reply":
                # combine concurrent requests per owner (Pregel message
                # combining; the combiner op is plan-recorded) and fold
                # the combined buffer into the reply — the term is
                # exactly zero, but the simplifier can't prove it, so
                # the combining scatter survives into the lowering
                reqbuf = self._combine_requests(pre, op.combiner or "min")
                val = val + (
                    gops.gather(reqbuf, pre) // (self.n + 2)
                ).astype(val.dtype)
            out[self._key(ce.pattern)] = val
            out.pop(self._key(ce.pattern) + ":req", None)

    def _resolve(self, fields, mailbox, pattern):
        """Pattern value if materialized/axiomatic, else None (push address
        flows may target chains materialized later the same round)."""
        if len(pattern) <= 1 or self._key(pattern) in mailbox:
            return self._lookup(fields, mailbox, pattern)
        return None

    def _lookup(self, fields, mailbox, pattern):
        if len(pattern) == 0:
            return jnp.arange(self.n, dtype=jnp.int32)
        if len(pattern) == 1:
            if pattern[0] == "Id":
                return jnp.arange(self.n, dtype=jnp.int32)
            return fields[pattern[0]]
        return mailbox[self._key(pattern)]

    # -- per-op superstep callables -------------------------------------------
    def op_fn(self, op):
        """``(fields, mailbox, graph) -> (fields, mailbox)`` for one plan op — the
        building block the per-superstep dispatcher composes (a fused
        superstep is several of these sequenced inside one jit)."""
        if isinstance(op, ReadRound):
            stage = self._stage_fn(op)

            def read(fields, mailbox, graph):
                return fields, stage(fields, mailbox, graph)

            return read
        if isinstance(op, RemoteUpdate):
            return self._update_fn(op)
        return self._main_fn()

    def _main_fn(self):
        has_ru = self.plan.has_remote_update
        materialized = self.plan.materialized
        pending_key = self.ns + "pending"

        def main(fields, mailbox, graph):
            chain_values = {
                p: mailbox[self._key(p)]
                for p in materialized
                if self._key(p) in mailbox
            }
            nbr_values = {
                (d, p): mailbox[self._nkey(d, p)]
                for d, p in self.info.nbr_comms
                if self._nkey(d, p) in mailbox
            }
            fold_prefix = self._fkey("")  # a fold's T, keyed by its ordinal
            fold_values = {
                int(k[len(fold_prefix):]): v
                for k, v in mailbox.items() if k.startswith(fold_prefix)
            }
            # the step's read buffers are consumed here; drop them so the
            # mailbox keyset is loop-stable (fused bodies re-create the
            # prefetched entries at iteration end)
            out = {
                k: v for k, v in mailbox.items()
                if not k.startswith(self.ns)
            }
            ex = StepExecutor(self.step, graph, plan=self.plan)
            if has_ru:
                new, pending = ex(
                    fields, chain_values, split_remote=True,
                    nbr_values=nbr_values, fold_values=fold_values,
                )
                out[pending_key] = tuple(
                    (m.idx, m.values, m.mask) for m in pending
                )
                return new, out
            return ex(
                fields, chain_values, nbr_values=nbr_values,
                fold_values=fold_values,
            ), out

        return main

    def _update_fn(self, ru: RemoteUpdate):
        pending_key = self.ns + "pending"

        def update(fields, mailbox, graph):
            out = dict(mailbox)
            payload = out.pop(pending_key)
            ex = StepExecutor(self.step, graph, plan=self.plan)
            msgs = [
                _RemoteMsg(f, op, idx, val, mask)
                for (f, op), (idx, val, mask) in zip(ru.writes, payload)
            ]
            return ex.apply_remote(fields, msgs), out

        return update


def _stop_part(f, m, g, stop):
    with jax.named_scope("stop"):
        return make_stop_fn(stop, g)(f), m


def _make_staged_superstep_fn(
    ss: plan_mod.Superstep,
    n_vertices: int,
    staged: Dict[int, _StagedStep],
    loops: tuple = (),
):
    """jit of ONE fused superstep's parts in order:
    ``(fields, mailbox, graph) -> (fields, mailbox)``. ``staged`` caches
    one :class:`_StagedStep` per program step across supersteps; ``loops``
    are the iter indices of the loops the superstep runs in (its names)."""
    part_fns = []
    for ref in ss.parts:
        op = ref.op
        if isinstance(op, plan_mod.IterInit):
            continue
        if isinstance(op, plan_mod.StopOp):
            part_fns.append(
                (ref.sidx, functools.partial(_stop_part, stop=op.stop))
            )
            continue
        if ref.sidx not in staged:
            staged[ref.sidx] = _StagedStep(
                ref.plan.step, n_vertices, ref.plan.schedule,
                plan=ref.plan, ns=f"s{ref.sidx}:",
            )
        part_fns.append((ref.sidx, staged[ref.sidx].op_fn(op)))

    def ss_fn(flds, mailbox, graph):
        with plan_scope(loops):
            for sidx, fn in part_fns:
                with jax.named_scope(f"s{sidx}"):
                    flds, mailbox = fn(flds, mailbox, graph)
        return flds, mailbox

    return jax.jit(ss_fn)


def read_superstep_count(step: ast.Step, schedule: str) -> int:
    """Number of remote-reading supersteps a step costs under ``schedule``
    — ``lower_step(step).read_rounds``, the same plan every executor
    dispatches, so placements cannot diverge from the accounting."""
    return lower_step(step, schedule=schedule).read_rounds


def walk_plan(
    pp: plan_mod.ProgramPlan,
    fields,
    exec_superstep,
    counter: List[int],
    trips: List[int],
    max_iters: int,
    active_sets: Optional[List[List[int]]] = None,
    vertex_ndim: int = 1,
):
    """Host-side walk of a (fused) program plan, shared by every placement.

    ``exec_superstep(superstep, fields, loops)`` executes ONE plan
    superstep (fused parts included) inside the loops ``loops`` (iter
    indices, outermost first) and returns the new fields; this walker owns
    sequencing, trip counting, the host-side OR-aggregator fixed-point
    check, the superstep counter (one per dispatched superstep — the fused
    accounting), and the per-iteration frontier instrumentation — so
    iteration semantics cannot diverge between the replicated and
    partitioned executors.
    """

    def run(items, flds, loops=()):
        for it in items:
            if isinstance(it, plan_mod.Superstep):
                with span("superstep"):
                    flds = exec_superstep(it, flds, loops)
                counter[0] += 1
                continue
            # PlanLoop
            trips.append(0)
            slot = len(trips) - 1
            if active_sets is not None:
                active_sets.append([])
            node = it.node
            limit = (
                node.fixed_trips
                if node.fixed_trips is not None
                else max_iters
            )
            for _ in range(limit):
                before = {f: flds[f] for f in node.fix_fields}
                flds = run(it.body, flds, loops + (it.iter_index,))
                trips[slot] += 1
                if node.fix_fields:
                    # host-side aggregator round-trip (Pregel OR-aggregator)
                    with span("frontier"):
                        frontier = int(frontier_count(
                            before, flds, node.fix_fields, vertex_ndim
                        ))
                    if active_sets is not None:
                        active_sets[slot].append(frontier)
                    if frontier == 0:
                        break
        return flds

    try:
        return run(pp.items, fields)
    finally:
        # ``run`` refers to itself: empty its cell, or the cycle would keep
        # ``exec_superstep`` (and the graph and mailbox it closes over)
        # alive until the garbage collector next runs
        del run


def run_bsp(
    prog: ast.Prog,
    graph,
    fields: Dict[str, jax.Array],
    schedule: str = "pull",
    max_iters: int = 100_000,
    placement: str = "replicated",
    mesh=None,
    n_shards: Optional[int] = None,
    byte_costs: Optional[ByteCostModel] = None,
    fuse: bool = True,
) -> BSPResult:
    """Execute a Palgol program superstep-by-superstep.

    ``fields`` must be the full canonical field dict (use
    ``CompiledProgram.init_fields``). Returns final fields, the number of
    actually executed supersteps, per-iteration trip counts, and the
    per-iteration fixed-point frontier sizes.

    ``schedule`` ∈ {"pull", "push", "naive", "auto"} selects the
    chain-access lowering (see :mod:`repro.core.plan`) and applies to both
    placements; ``byte_costs`` makes ``"auto"`` select on the byte model.

    ``fuse`` (default True) executes the §4.3-fused program plan — merged
    supersteps dispatch as ONE device call, iteration-fused loops save one
    superstep per iteration; ``fuse=False`` dispatches the unfused per-op
    expansion (bit-identical results, the historical superstep counts).

    ``placement`` selects the vertex-state layout:

    * ``"replicated"`` (default) — dense single-address-space arrays; under
      an active mesh GSPMD/shard_map keep vertex state replicated per chip;
    * ``"partitioned"`` — edge-balanced contiguous-range shards with halo
      exchange (``repro.graph.partition``): each superstep moves only
      boundary state. ``mesh`` (a 1-D ``("shard",)`` mesh) or ``n_shards``
      selects the layout; defaults to one shard per local device. Fields
      are partitioned on entry and returned dense, so callers are
      placement-agnostic.
    """
    if placement == "partitioned":
        from repro.graph.partition import run_bsp_partitioned

        return run_bsp_partitioned(
            prog, graph, fields, schedule=schedule, max_iters=max_iters,
            mesh=mesh, n_shards=n_shards, byte_costs=byte_costs, fuse=fuse,
        )
    if placement != "replicated":
        raise ValueError(f"unknown placement {placement!r}")
    pp = plan_mod.lower_program(prog, schedule=schedule, byte_costs=byte_costs)
    if fuse:
        pp = plan_mod.fuse(pp)

    counter = [0]
    trips: List[int] = []
    active_sets: List[List[int]] = []
    # caches: one _StagedStep per step, one compiled dispatch per Superstep
    # — supersteps re-execute across iterations without re-tracing (as a
    # real Pregel binary would)
    staged: Dict[int, _StagedStep] = {}
    ss_fns: Dict[int, object] = {}
    mailbox_box = [{}]

    def exec_superstep(ss: plan_mod.Superstep, flds, loops):
        if id(ss) not in ss_fns:
            ss_fns[id(ss)] = _make_staged_superstep_fn(
                ss, graph.n_vertices, staged, loops
            )
        flds, mailbox_box[0] = ss_fns[id(ss)](flds, mailbox_box[0], graph)
        return flds

    fields = {k: jnp.asarray(v) for k, v in fields.items()}
    if HALTED not in fields:
        fields[HALTED] = jnp.zeros((graph.n_vertices,), jnp.bool_)
    out = walk_plan(
        pp, fields, exec_superstep, counter, trips, max_iters,
        active_sets=active_sets,
    )
    return BSPResult(
        fields=out, supersteps=counter[0], trips=trips,
        active_sets=active_sets,
    )
