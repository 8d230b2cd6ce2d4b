"""Dense (TPU-native) code generation for Palgol steps.

Every Palgol step becomes a pure function ``(fields, graph) -> fields`` over
struct-of-arrays vertex state:

* all *reads* target the step's input fields (the paper's LC-phase rule:
  reads see the input graph);
* *local writes* read-modify-write an intermediate copy in program order;
* *remote writes* are collected during traversal and applied at the end via
  ``scatter_combine`` (the RU phase) — accumulative-only, so application
  order is irrelevant, exactly the paper's safety argument;
* chain accesses are evaluated through the :class:`~repro.core.logic.PullSolver`
  gather DAG (memoized per step ⇒ each distinct sub-chain evaluated once);
* halted vertices (paper §3.4) are immutable: their local writes are masked
  and remote writes to/from them are dropped.

The emitted functions contain no data-dependent Python control flow, so a
whole program (including fixed-point iterations as ``lax.while_loop``) traces
into a single XLA computation — one compiled module per Palgol program, with
collectives inserted by GSPMD when fields are sharded.

Device work is named after the Palgol program (``jax.named_scope``, which
lands in the HLO ``op_name`` metadata and so in the device trace; it
changes no executable). Every executor opens ``palgol`` and one
``L<iter_index>`` per enclosing loop (:func:`plan_scope`), each plan part
opens ``s<sidx>`` (the step's ordinal in program order), and the step's
work falls under one of the leaves:

* ``chain``: a ReadRound's chain gathers, the naive request scatter, and
  reads by computed address;
* ``nbr``: neighbour access: per-edge gathers of neighbour values, edge
  masks, segment reductions over edges (a segmented scan and a read at
  each run's end, or a scatter: ``StepExecutor._reduce_edges``);
* ``remote``: building remote-write messages and applying them;
* ``local``: the rest of the main computation;
* ``stop``: a StopStep;

and a loop's termination test and frontier count under ``fixpoint``
(``palgol/L<i>/fixpoint``). A leaf opened inside another (a neighbour
reduction inside a local ``let``) nests, so the innermost leaf names the
work. The dense compiler's loops are ``lax.while_loop`` s, so JAX puts
``while/body`` between ``L<i>`` and the parts in its names.

An edge comprehension whose filters read only the neighbour folds them
into the value it gathers (:func:`_fold_of`): ``minimum [D[e.id] + e.w |
e <- In[v], A[e.id]]`` forms ``T[u] = A[u] ? D[u] : inf`` once per vertex
and gathers ``T`` per edge, one gather where there were two. The plan's
ReadRound prefetches ``T`` under its own mailbox key in place of the reads
only the fold used; each fold traced records the event
``/palgol/edge_reduce/fold``.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ast
from repro.core.analysis import (
    CompileError,
    chain_pattern_of,
    neighbor_pattern_of,
)
from repro.core.logic import PullSolver
from repro.core.plan import (
    HALTED,
    IterInit,
    MainCompute,
    OpRef,
    ReadRound,
    RemoteUpdate,
    StepPlan,
    StopOp,
    lower_step,
)
from repro.graph import ops as gops
from repro.trace import count

# NOTE: the deprecated ``codegen.CHAIN_MODE`` module global (PR 3's
# one-release shim) is gone; the schedule is the explicit ``schedule=``
# argument on compile_program / StepExecutor / run_bsp.

_OP_APPLY = {
    ":=": lambda cur, val: val,
    "+=": lambda cur, val: cur + val,
    "*=": lambda cur, val: cur * val,
    "<?=": jnp.minimum,
    ">?=": jnp.maximum,
    "||=": jnp.logical_or,
    "&&=": jnp.logical_and,
}

def plan_scope(loops=()):
    """``jax.named_scope`` of work inside the loops ``loops`` (iter
    indices, outermost first): ``palgol/L<i>/...``."""
    return jax.named_scope("/".join(["palgol"] + [f"L{i}" for i in loops]))


def frontier_count(before, after, fix_fields, vertex_ndim: int = 1):
    """Vertices whose fix fields changed (the fixed-point frontier), as a
    device int32 scalar; a loop has converged when it is 0.
    ``vertex_ndim`` is the number of leading per-vertex dims (1 dense, 2
    for ``[shard, row]``-blocked partitioned state)."""
    changed = None
    for f in fix_fields:
        d = after[f] != before[f]
        if d.ndim > vertex_ndim:
            d = d.reshape(d.shape[:vertex_ndim] + (-1,)).any(axis=-1)
        changed = d if changed is None else jnp.logical_or(changed, d)
    return jnp.sum(changed, dtype=jnp.int32)


_REDUCE_TO_COMBINER = {
    "count": "sum",
    "minimum": "min",
    "maximum": "max",
    "sum": "sum",
    "prod": "prod",
    "and": "and",
    "or": "or",
}


@dataclasses.dataclass(frozen=True)
class _Fold:
    """An edge comprehension whose neighbour-only filters fold into the
    value it gathers (:func:`_fold_of`): per vertex ``T[u] = guard(u) ?
    value(u) : identity``, then one gather of ``T`` per edge, with
    ``term`` added per edge where there is one."""

    reduce: ast.Reduce
    guard: Tuple[ast.Expr, ...]  # the neighbour-only filters
    rest: Tuple[ast.Expr, ...]  # the other filters, applied per edge
    value: Optional[ast.Expr]  # neighbour-only; None for count (1)
    term: Optional[ast.Expr]  # ``e.w``, added per edge
    term_first: bool  # the body is ``term + value``
    reads: FrozenSet[Tuple[str, tuple]]  # (direction, pattern) of T
    key: int = 0  # ordinal among the step's folds: its mailbox key


def _nbr_only(e: ast.Expr, edge_var: str) -> bool:
    """Whether ``e`` reads nothing but ``e.id``, fields through it and
    constants."""
    if isinstance(e, ast.Const):
        return True
    if isinstance(e, ast.Var):
        return e.name == "numV"
    if isinstance(e, ast.EdgeProp):
        return e.edge_var == edge_var and e.prop == "id"
    if isinstance(e, ast.FieldAccess):
        return neighbor_pattern_of(e, edge_var) is not None
    if isinstance(e, (ast.Cond, ast.BinOp, ast.UnOp)):
        return all(_nbr_only(x, edge_var) for x in _subtrees(e))
    return False


def _subtrees(node):
    """The expressions and statements directly under ``node``."""
    for f in dataclasses.fields(node):
        v = getattr(node, f.name)
        for c in v if isinstance(v, tuple) else (v,):
            if isinstance(c, (ast.Expr, ast.Stmt)):
                yield c


def _nbr_reads(nodes, ctx=None, folds=None) -> set:
    """``(direction, pattern)`` of every neighbour read under ``nodes``,
    leaving out what the comprehensions of ``folds`` read through their
    ``T``; ``ctx`` is the enclosing ``(direction, edge_var)``."""
    out = set()
    folds = folds or {}
    for node in nodes:
        kids, inner = None, ctx
        if isinstance(node, ast.Reduce):
            fold = folds.get(node)
            kids = (node.body,) + node.filters if fold is None else fold.rest
            inner = (node.range.direction, node.edge_var)
        elif isinstance(node, ast.ForEdges):
            kids, inner = node.body, (node.range.direction, node.edge_var)
        elif ctx is not None and isinstance(node, ast.FieldAccess):
            pat = neighbor_pattern_of(node, ctx[1])
            if pat is not None:
                out.add((ctx[0], pat))
                continue
        if kids is None:
            kids = tuple(_subtrees(node))
        out |= _nbr_reads(kids, inner, folds)
    return out


def _plus_weight(body: ast.Expr, edge_var: str):
    """``(x, weight_first)`` where ``body`` is ``x + e.w`` or ``e.w + x``
    with ``x`` a neighbour field, else ``None``."""
    if not (isinstance(body, ast.BinOp) and body.op == "+"):
        return None
    w = ast.EdgeProp(edge_var, "w")
    for x, other, first in (
        (body.left, body.right, False), (body.right, body.left, True)
    ):
        if other == w and isinstance(x, ast.FieldAccess) and (
            neighbor_pattern_of(x, edge_var) is not None
        ):
            return x, first
    return None


def _fold_of(r: ast.Reduce) -> Optional[_Fold]:
    """The fold of comprehension ``r``, or ``None`` where it keeps its
    filters per edge. It needs a neighbour-only filter (:func:`_nbr_only`)
    that reads a field, and one of:

    1. a neighbour-only body (or ``count``), under any reduction but
       ``argmin``/``argmax``: each edge then carries what it carried,
       the body where the guard holds and the identity where not;
    2. under ``minimum``/``maximum``, a body ``x + e.w`` (or ``e.w + x``)
       with ``x`` a neighbour field: an edge whose guard fails carries
       ``identity + e.w``, the identity where ``x`` is a float and no live
       weight is ``-identity`` or NaN (:meth:`StepExecutor._fold_holds`).
       A constant in place of ``e.w`` leaves the body neighbour-only: 1.
    """
    if r.func in ("argmin", "argmax"):
        return None
    ev = r.edge_var
    guard = tuple(f for f in r.filters if _nbr_only(f, ev))
    rest = tuple(f for f in r.filters if not _nbr_only(f, ev))
    ctx = (r.range.direction, ev)
    if not _nbr_reads(guard, ctx):
        return None
    value, term, first = None, None, False
    if r.func != "count":
        if _nbr_only(r.body, ev):
            value = r.body
        elif r.func in ("minimum", "maximum") and (
            split := _plus_weight(r.body, ev)
        ):
            (value, first), term = split, ast.EdgeProp(ev, "w")
        else:
            return None
    reads = _nbr_reads(guard + ((value,) if value is not None else ()), ctx)
    return _Fold(r, guard, rest, value, term, first, frozenset(reads))


def needs_weight_bounds(prog: ast.Prog) -> bool:
    """Whether a comprehension of ``prog`` folds only where the graph's
    live weights are bounded (:attr:`repro.graph.structure.Graph.weights_bounded`)."""
    for e in ast.walk_exprs(prog):
        fold = _fold_of(e) if isinstance(e, ast.Reduce) else None
        if fold is not None and fold.term is not None:
            return True
    return False


@dataclasses.dataclass
class _EdgeCtx:
    direction: str
    nbr: jax.Array  # i32[E] neighbor ids (e.id) — global, value semantics
    vid: jax.Array  # i32[E] current-vertex id per edge — global, value sem.
    w: jax.Array  # f32[E] e.w
    emask: jax.Array  # bool[E]
    # addressing (== vid/nbr densely; local under a partitioned comm):
    seg: jax.Array = None  # row index of the current vertex (segment key)
    nbr_read: jax.Array = None  # address for reading per-row arrays at e.id
    # i32[rows] one past each row's last slot of the seg-sorted edges
    # (Graph.segment_ends, or a shard's PartitionedGraph.in_ends/out_ends);
    # None where the graph carries none
    ends: Optional[jax.Array] = None

    def __post_init__(self):
        if self.seg is None:
            self.seg = self.vid
        if self.nbr_read is None:
            self.nbr_read = self.nbr


@dataclasses.dataclass
class _RemoteMsg:
    field: str
    op: str
    idx: jax.Array
    values: jax.Array
    mask: jax.Array  # same shape as idx


@dataclasses.dataclass
class _StepState:
    """One step's cross-superstep context under the fused program plan:
    what the step's remote-reading supersteps materialized and its main
    superstep emitted, threaded between the supersteps its plan ops landed
    in (the typed view of the executors' string-keyed mailbox)."""

    chain: Dict[tuple, jax.Array] = dataclasses.field(default_factory=dict)
    nbr: Dict[tuple, jax.Array] = dataclasses.field(default_factory=dict)
    fold: Dict[int, jax.Array] = dataclasses.field(default_factory=dict)
    pending: List[_RemoteMsg] = dataclasses.field(default_factory=list)
    naive_req: Dict[tuple, jax.Array] = dataclasses.field(default_factory=dict)


class StepExecutor:
    """Executes one Palgol step densely by folding its :class:`StepPlan`
    op list into one traced computation. Instantiated fresh per call so the
    expression memo-cache is scoped to the step (paper's CSE guarantee).

    ``plan`` (or ``schedule``, which lowers one) selects the superstep
    expansion — the same :func:`repro.core.plan.lower_step` plan the staged
    and partitioned executors consume, so the three can never diverge.

    ``comm`` selects the placement. ``None`` (default) is the dense /
    replicated path: fields are ``[N]`` arrays, reads are plain gathers.
    A :class:`repro.graph.partition.executor.ShardComm` makes this the
    ``placement="partitioned"`` path: the executor then runs *inside* a
    shard_map over per-shard field blocks ``[v_max]``, chain-access gathers
    route through the halo layer's dynamic request/reply exchange, neighbor
    reads through the static halo exchange, and remote-write scatters
    through the combiner-aware reduce-scatter. Vertex *values* (ids) stay
    global in both placements; only addressing changes.
    """

    def __init__(
        self,
        step: ast.Step,
        graph,
        comm=None,
        plan: Optional[StepPlan] = None,
        schedule: Optional[str] = None,
    ):
        self.step = step
        self.graph = graph
        self.comm = comm
        self.n = graph.n_vertices
        self.nrows = comm.n_rows if comm is not None else graph.n_vertices
        if plan is None:
            plan = lower_step(step, schedule=schedule or "pull")
        self.plan = plan
        self.info = plan.info
        self.pull = PullSolver()
        self._leaf: Optional[str] = None
        self._active: Optional[jax.Array] = None
        self.fold_cache: Dict[int, jax.Array] = {}
        self._folds: Dict[ast.Reduce, _Fold] = {}
        self._fold_only: set = set()

    # -- public -------------------------------------------------------------
    def __call__(
        self,
        fields: Dict[str, jax.Array],
        chain_values: Optional[Dict[tuple, jax.Array]] = None,
        split_remote: bool = False,
        nbr_values: Optional[Dict[tuple, jax.Array]] = None,
        fold_values: Optional[Dict[int, jax.Array]] = None,
    ):
        """Execute the plan's ops in order (fused into this one trace).

        ``chain_values`` seeds the chain cache with buffers materialized by
        earlier remote-reading supersteps (BSP mode) — seeded ReadRound
        work is skipped; ``nbr_values`` seeds per-edge neighborhood buffers
        keyed by ``(direction, pattern)``, ``fold_values`` the gathered
        ``T`` of each fold keyed by its ordinal. In dense mode the rounds
        inline their gathers here instead.
        With ``split_remote=True`` returns ``(fields, pending_messages)`` so
        a separate remote-updating superstep can apply them (paper Fig. 9).
        """
        self.old = dict(fields)
        self.new = dict(fields)
        self.env: Dict[str, Tuple[str, jax.Array]] = {}
        self.chain_cache: Dict[tuple, jax.Array] = dict(chain_values or {})
        self.nbr_cache: Dict[tuple, jax.Array] = dict(nbr_values or {})
        self.fold_cache = dict(fold_values or {})
        self.expr_cache: Dict[Tuple[int, ast.Expr], jax.Array] = {}
        self.pending: List[_RemoteMsg] = []
        self._naive_req: Dict[tuple, jax.Array] = {}
        self._active = None
        self._find_folds()
        for op in self.plan.ops:
            if isinstance(op, ReadRound):
                self._exec_read_round(op)
            elif isinstance(op, MainCompute):
                self._main_compute()
            elif not split_remote:  # RemoteUpdate
                self._apply_remote()
        if split_remote:
            return self.new, self.pending
        return self.new

    def apply_remote(self, fields, pending: List[_RemoteMsg]):
        """RU phase as a standalone superstep (BSP mode)."""
        self.old = dict(fields)
        self.new = dict(fields)
        self.pending = pending
        self._active = None
        self._apply_remote()
        return self.new

    def run_ops(self, fields, ops, state: Optional["_StepState"] = None):
        """Execute a slice of this step's plan ops — the per-superstep entry
        point of the fused program plan (``repro.core.plan.ProgramPlan``),
        where one fused superstep may hold ops from several steps and a
        step's ops may land in different supersteps.

        ``state`` threads the step's cross-superstep context (materialized
        chain/neighborhood buffers, pending remote messages, naive request
        buffers) between slices; results are identical to one ``__call__``
        over the whole plan because ReadRounds never write fields — each
        slice re-snapshotting ``fields`` sees exactly the state the unfused
        superstep at that position would.
        """
        state = state if state is not None else _StepState()
        self.old = dict(fields)
        self.new = dict(fields)
        self.env = {}
        self.chain_cache = dict(state.chain)
        self.nbr_cache = dict(state.nbr)
        self.fold_cache = dict(state.fold)
        self.expr_cache = {}
        self.pending = list(state.pending)
        self._naive_req = dict(state.naive_req)
        self._active = None
        self._find_folds()
        for op in ops:
            if isinstance(op, ReadRound):
                self._exec_read_round(op)
            elif isinstance(op, MainCompute):
                self._main_compute()
            else:  # RemoteUpdate
                self._apply_remote()
                self.pending = []
        out_state = _StepState(
            # axioms (vertex ids / single-field reads) must not outlive the
            # superstep — a carried copy would go stale once the field is
            # written; only materialized multi-hop buffers are the mailbox
            chain={p: v for p, v in self.chain_cache.items() if len(p) > 1},
            nbr=dict(self.nbr_cache),
            fold=dict(self.fold_cache),
            pending=list(self.pending),
            naive_req=dict(self._naive_req),
        )
        return self.new, out_state

    # -- helpers ------------------------------------------------------------
    @contextlib.contextmanager
    def _in(self, leaf: str):
        """Name the device work of the ``with`` body by ``leaf`` (a leaf
        of the module doc); nothing is added where ``leaf`` is already the
        innermost leaf."""
        if self._leaf == leaf:
            yield
            return
        outer, self._leaf = self._leaf, leaf
        try:
            with jax.named_scope(leaf):
                yield
        finally:
            self._leaf = outer

    @property
    def active(self) -> jax.Array:
        """The input rows that are not halted; made where first needed, so
        that its ops are named after the work that needs them."""
        if self._active is None:
            self._active = self._active_mask(self.old)
        return self._active

    def _main_compute(self):
        with self._in("local"):
            self._exec_stmts(self.step.body, mask=None, ectx=None)

    def _active_mask(self, fields) -> jax.Array:
        active = ~fields.get(HALTED, jnp.zeros((self.nrows,), jnp.bool_))
        if self.comm is not None:  # padding rows of a shard are never active
            active = jnp.logical_and(active, self.comm.valid)
        return active

    def _ids(self) -> jax.Array:
        if self.comm is not None:
            return self.comm.ids()
        return jnp.arange(self.n, dtype=jnp.int32)

    def _gather_rows(self, arr: jax.Array, idx: jax.Array, fill=None):
        """Read a per-row array at *global* vertex ids (possibly remote)."""
        if self.comm is not None:
            return self.comm.gather(arr, idx, fill)
        return gops.gather(arr, idx, fill)

    def _read_nbr(self, per_row: jax.Array, ectx: _EdgeCtx) -> jax.Array:
        """Read a per-row array at each edge's neighbor (static halo path)."""
        if self.comm is not None:
            return self.comm.read_edge(per_row, ectx)
        return gops.gather(per_row, ectx.nbr_read)

    def _edge_ctx(self, direction: str) -> _EdgeCtx:
        if self.comm is not None:
            return self.comm.edge_ctx(direction)
        nbr, vid, w, m = self.graph.edges(direction)
        return _EdgeCtx(
            direction, nbr, vid, w, m,
            ends=self.graph.segment_ends(direction),
        )

    def _field(self, name: str) -> jax.Array:
        if name == "Id":
            return self._ids()
        if name not in self.old:
            raise CompileError(f"read of undefined field {name!r}")
        return self.old[name]

    def _chain_value(self, pattern: tuple) -> jax.Array:
        """Evaluate a chain pattern at every vertex. The plan's ReadRound
        ops materialize every multi-hop pattern before the main compute, so
        during statement execution this resolves axioms (vertex ids, single
        fields) and cache hits; the pull-DAG fallback covers synthetic
        steps that run without plan rounds (stop conditions)."""
        if pattern in self.chain_cache:
            return self.chain_cache[pattern]
        if len(pattern) == 0:
            val = self._ids()
        elif len(pattern) == 1:
            val = self._field(pattern[0])
        else:
            # pull-mode pointer doubling: under a partitioned comm each
            # doubling round is a dynamic cross-shard gather whose request
            # set is rebuilt from the current indirection values
            plan = self.pull.solve(pattern)
            pre = self._chain_value(plan.prefix.pattern)
            suf = self._chain_value(plan.suffix.pattern)
            with self._in("chain"):
                val = self._gather_rows(suf, pre)
        self.chain_cache[pattern] = val
        return val

    # -- plan-op execution ---------------------------------------------------
    def _exec_read_round(self, op: ReadRound):
        """Fold one remote-reading superstep into the trace.

        Work whose result is already cached (seeded by a staged mailbox)
        is skipped — the op then only accounts for its superstep.
        """
        with self._in("chain"):
            self._read_chains(op)
        with self._in("nbr"):
            for fold in self._folds.values():
                if fold.key not in self.fold_cache and op == self._fold_round(
                    fold
                ):
                    self.fold_cache[fold.key] = self._read_nbr(
                        self._fold_table(fold),
                        self._edge_ctx(fold.reduce.range.direction),
                    )
            for direction, npat in op.nbr_sends:
                if (direction, npat) in self.nbr_cache or (
                    direction, npat
                ) in self._fold_only:
                    continue
                per_vertex = self._chain_value(npat)
                ectx = self._edge_ctx(direction)
                self.nbr_cache[(direction, npat)] = self._read_nbr(
                    per_vertex, ectx
                )

    def _read_chains(self, op: ReadRound):
        if op.kind == "request":
            # naive hop, requester→owner address push. Under a partitioned
            # comm the paired reply's gather_global pays the request
            # exchange for real; densely we keep the address scatter alive
            # so the lowered HLO carries the wire traffic manual code pays.
            if self.comm is not None:
                return
            for ce in op.chains:
                if ce.pattern in self.chain_cache:
                    continue
                cur = self._chain_value(ce.prefix)
                req = jnp.full((self.n + 1,), self.n, jnp.int32)
                self._naive_req[ce.pattern] = req.at[cur].set(
                    self._ids(), mode="drop"
                )[: self.n]
            return
        if op.kind == "push_request":
            # push address-propagation round: requester ids are forwarded
            # (combined per owner) along the chain. The fused dense trace
            # has no wire, so this op only accounts for its superstep;
            # under a partitioned comm the push_reply round's
            # gather_global pays the combined exchange for real.
            return
        # kind "pull", "reply" or "push_reply": gather suffix@prefix
        # (push_reply is the combined reply — one value per distinct
        # owner, fanned out to its requesters: exactly the gather)
        for ce in op.chains:
            if ce.pattern in self.chain_cache:
                continue
            pre = self._chain_value(ce.prefix)
            suf = self._chain_value(ce.suffix)
            val = self._gather_rows(suf, pre)
            req = self._naive_req.pop(ce.pattern, None)
            if req is not None:
                # fold in the request buffer: req < n+2 always, so this
                # term is exactly zero, but the algebraic simplifier can't
                # prove it — the scatter survives into the lowering
                val = val + (req // (self.n + 2)).astype(val.dtype)
            self.chain_cache[ce.pattern] = val

    # -- expression evaluation ----------------------------------------------
    def _eval(self, e: ast.Expr, ectx: Optional[_EdgeCtx]):
        key = (id(ectx), e)
        if key in self.expr_cache:
            return self.expr_cache[key]
        val = self._eval_inner(e, ectx)
        self.expr_cache[key] = val
        return val

    def _eval_inner(self, e: ast.Expr, ectx: Optional[_EdgeCtx]):
        if isinstance(e, ast.Const):
            if e.value == "inf":
                return jnp.inf
            return e.value
        if isinstance(e, ast.Var):
            if e.name == "numV":  # builtin: vertex count (global constant)
                return jnp.asarray(self.n, jnp.int32)
            if e.name == self.step.vertex_var:
                return ectx.vid if ectx is not None else self._ids()
            if e.name in self.env:
                ctx_tag, arr = self.env[e.name]
                if ctx_tag == "vertex" and ectx is not None:
                    return gops.gather(arr, ectx.seg)
                return arr
            raise CompileError(f"unbound variable {e.name!r}")
        if isinstance(e, ast.EdgeProp):
            if ectx is None:
                raise CompileError(f".{e.prop} outside edge context")
            return ectx.nbr if e.prop == "id" else ectx.w
        if isinstance(e, ast.FieldAccess):
            # chain access from current vertex
            pat = chain_pattern_of(e, self.step.vertex_var)
            if pat is not None:
                val = self._chain_value(pat)
                return gops.gather(val, ectx.seg) if ectx is not None else val
            # neighborhood chain from e.id
            if ectx is not None:
                npat = self._nbr_pattern(e)
                if npat is not None:
                    cached = self.nbr_cache.get((ectx.direction, npat))
                    if cached is not None:
                        return cached
                    per_vertex = self._chain_value(npat)
                    with self._in("nbr"):
                        return self._read_nbr(per_vertex, ectx)
            # general read
            idx = self._eval(e.index, ectx)
            with self._in("chain"):
                return self._gather_rows(
                    self._field(e.field), jnp.asarray(idx, jnp.int32)
                )
        if isinstance(e, ast.Cond):
            c = self._eval(e.cond, ectx)
            t = self._eval(e.then, ectx)
            f = self._eval(e.other, ectx)
            return jnp.where(c, t, f)
        if isinstance(e, ast.BinOp):
            lhs = self._eval(e.left, ectx)
            rhs = self._eval(e.right, ectx)
            return _binop(e.op, lhs, rhs)
        if isinstance(e, ast.UnOp):
            return _unop(e.op, self._eval(e.operand, ectx))
        if isinstance(e, ast.Reduce):
            return self._eval_reduce(e)
        raise CompileError(f"cannot evaluate {type(e).__name__}")

    def _nbr_pattern(self, e: ast.FieldAccess):
        # pattern starting from any edge var's `.id` — edge var name is the
        # enclosing loop's; analysis validated scoping, so accept any
        def rec(x):
            if isinstance(x, ast.EdgeProp) and x.prop == "id":
                return ()
            if isinstance(x, ast.FieldAccess):
                inner = rec(x.index)
                if inner is not None:
                    return inner + (x.field,)
            return None

        return rec(e)

    def _eval_reduce(self, e: ast.Reduce) -> jax.Array:
        with self._in("nbr"):
            return self._edge_reduce(e)

    def _edge_reduce(self, e: ast.Reduce) -> jax.Array:
        ectx = self._edge_ctx(e.range.direction)
        fold = self._folds.get(e)
        mask = ectx.emask
        for f in e.filters if fold is None else fold.rest:
            fv = self._eval(f, ectx)
            mask = jnp.logical_and(mask, fv)
        comb = _REDUCE_TO_COMBINER.get(e.func)
        if fold is not None:
            count("edge_reduce/fold")
            return self._reduce_edges(self._folded(fold, ectx), ectx, comb, mask)
        if e.func == "count":
            ones = jnp.ones_like(ectx.seg, dtype=jnp.int32)
            return self._reduce_edges(ones, ectx, comb, mask)
        body = self._eval(e.body, ectx)
        body = jnp.asarray(body)
        if body.ndim == 0:
            body = jnp.broadcast_to(body, ectx.seg.shape)
        if e.func in ("argmin", "argmax"):
            comb = "min" if e.func == "argmin" else "max"
            best = self._reduce_edges(body, ectx, comb, mask)
            attained = jnp.logical_and(mask, body == gops.gather(best, ectx.seg))
            ids = jnp.where(attained, ectx.nbr, self.n)
            out = self._reduce_edges(ids, ectx, "min")
            # empty segments reduce to int-max; clamp to the sentinel (numV)
            return jnp.minimum(out, self.n)
        return self._reduce_edges(body, ectx, comb, mask)

    # -- the neighbour-guard fold (module doc) --------------------------------
    def _find_folds(self):
        """The step's folds that hold over the fields and graph at hand, and
        the neighbour reads only they consume. The same for every executor
        of one step, so that a ReadRound prefetches what its main compute
        reads."""
        folds = {}
        for e in ast.walk_exprs(self.step):
            fold = _fold_of(e) if isinstance(e, ast.Reduce) else None
            if fold is not None and e not in folds and self._fold_holds(fold):
                folds[e] = dataclasses.replace(fold, key=len(folds))
        self._folds = folds
        consumed = set().union(*(f.reads for f in folds.values()))
        self._fold_only = consumed - _nbr_reads(self.step.body, folds=folds)

    def _fold_holds(self, fold: _Fold) -> bool:
        """Whether a fold that adds ``e.w`` per edge is exact here
        (:func:`_fold_of`, case 2): ``x`` a float, and no live weight
        ``-identity`` or NaN by the graph's ``weights_bounded``."""
        if fold.term is None:
            return True
        name = neighbor_pattern_of(fold.value, fold.reduce.edge_var)[-1]
        if name not in self.old or not jnp.issubdtype(
            self.old[name].dtype, jnp.floating
        ):
            return False
        bounds = getattr(self.graph, "weights_bounded", None)
        # minimum's identity +inf absorbs any w > -inf; maximum's, w < inf
        return bounds is not None and bounds[fold.reduce.func == "maximum"]

    def _fold_round(self, fold: _Fold) -> Optional[ReadRound]:
        """The last of the plan's ReadRounds that sends a read of ``T``:
        where ``T`` is formed and gathered."""
        rounds = [
            op for op in self.plan.ops
            if isinstance(op, ReadRound) and fold.reads & set(op.nbr_sends)
        ]
        return rounds[-1] if rounds else None

    def _fold_table(self, fold: _Fold) -> jax.Array:
        """``T`` at every row: the value where the guard holds, else the
        reduction's identity."""
        guard = True
        for f in fold.guard:
            guard = jnp.logical_and(guard, self._at_vertex(f))
        if fold.value is None:  # count
            value = jnp.asarray(1, jnp.int32)
        else:
            value = jnp.asarray(self._at_vertex(fold.value))
        comb = _REDUCE_TO_COMBINER[fold.reduce.func]
        table = jnp.where(guard, value, gops._identity_for(comb, value.dtype))
        return jnp.broadcast_to(table, (self.nrows,))

    def _at_vertex(self, e: ast.Expr):
        """A neighbour-only expression (:func:`_nbr_only`) at every row,
        as an edge whose neighbour is that row would read it."""
        if isinstance(e, ast.EdgeProp):
            return self._ids()
        if isinstance(e, ast.FieldAccess):
            return self._chain_value(self._nbr_pattern(e))
        if isinstance(e, ast.Cond):
            return jnp.where(*(self._at_vertex(x) for x in _subtrees(e)))
        if isinstance(e, ast.BinOp):
            return _binop(e.op, self._at_vertex(e.left), self._at_vertex(e.right))
        if isinstance(e, ast.UnOp):
            return _unop(e.op, self._at_vertex(e.operand))
        return self._eval(e, None)  # a constant, numV

    def _folded(self, fold: _Fold, ectx: _EdgeCtx) -> jax.Array:
        """The per-edge values of a folded comprehension: ``T`` at each
        edge's neighbour (prefetched, or gathered here), plus the term."""
        t = self.fold_cache.get(fold.key)
        if t is None:
            t = self._read_nbr(self._fold_table(fold), ectx)
        if fold.term is None:
            return t
        c = self._eval(fold.term, ectx)
        return _binop("+", c, t) if fold.term_first else _binop("+", t, c)

    def _reduce_edges(self, values, ectx: _EdgeCtx, op: str, mask=None):
        """Combine per-edge ``values`` into their current vertices with
        ``op``. Where the context has run ends and ``op`` gives the same
        bits in any order over ``values``' dtype, a segmented scan of the
        sorted edges (:func:`repro.graph.ops.sorted_segment_reduce`);
        otherwise the scatter. The path taken is recorded as the event
        ``/palgol/edge_reduce/<scan|scatter>`` at trace time."""
        if ectx.ends is not None and gops.is_order_independent(
            op, values.dtype
        ):
            count("edge_reduce/scan")
            return gops.sorted_segment_reduce(
                values, ectx.seg, ectx.ends, op, mask=mask
            )
        count("edge_reduce/scatter")
        return gops.segment_reduce(
            values, ectx.seg, self.nrows, op, indices_are_sorted=True,
            mask=mask,
        )

    # -- statement execution -------------------------------------------------
    def _exec_stmts(self, stmts, mask, ectx: Optional[_EdgeCtx]):
        for s in stmts:
            if isinstance(s, ast.Let):
                val = self._eval(s.value, ectx)
                val = jnp.asarray(val)
                tag = "edge" if ectx is not None else "vertex"
                if val.ndim == 0:
                    shape = ectx.seg.shape if ectx is not None else (self.nrows,)
                    val = jnp.broadcast_to(val, shape)
                self.env[s.var] = (tag, val)
            elif isinstance(s, ast.If):
                c = self._eval(s.cond, ectx)
                c = jnp.asarray(c)
                if c.ndim == 0:
                    shape = ectx.seg.shape if ectx is not None else (self.nrows,)
                    c = jnp.broadcast_to(c, shape)
                m_then = c if mask is None else jnp.logical_and(mask, c)
                self._exec_stmts(s.then, m_then, ectx)
                if s.other:
                    m_else = ~c if mask is None else jnp.logical_and(mask, ~c)
                    self._exec_stmts(s.other, m_else, ectx)
            elif isinstance(s, ast.ForEdges):
                with self._in("nbr"):
                    ec = self._edge_ctx(s.range.direction)
                    m = ec.emask
                    if mask is not None:  # lift vertex mask to edges
                        m = jnp.logical_and(
                            m, gops.gather(mask, ec.seg, fill=False)
                        )
                    self._exec_stmts(s.body, m, ec)
            elif isinstance(s, ast.LocalWrite):
                self._local_write(s, mask, ectx)
            elif isinstance(s, ast.RemoteWrite):
                self._remote_write(s, mask, ectx)
            else:
                raise CompileError(f"unknown statement {type(s).__name__}")

    def _local_write(self, s: ast.LocalWrite, mask, ectx: Optional[_EdgeCtx]):
        val = jnp.asarray(self._eval(s.value, ectx))
        if ectx is None:
            if val.ndim == 0:
                val = jnp.broadcast_to(val, (self.nrows,))
            cur = self.new.get(s.field)
            if cur is None:
                if s.op != ":=":
                    raise CompileError(
                        f"field {s.field!r} first written with accumulative op"
                    )
                cur = jnp.zeros((self.nrows,), val.dtype)
            updated = _OP_APPLY[s.op](cur, val).astype(cur.dtype)
            m = self.active if mask is None else jnp.logical_and(mask, self.active)
            self.new[s.field] = jnp.where(m, updated, cur)
        else:
            # accumulative write inside an edge loop: segment-reduce per-edge
            # contributions, then fold into the intermediate field once.
            if s.op == ":=":
                raise CompileError("`:=` inside an edge loop is order-dependent")
            comb = ast.OP_TO_COMBINER[s.op]
            if val.ndim == 0:
                val = jnp.broadcast_to(val, ectx.seg.shape)
            m = ectx.emask if mask is None else mask
            cur = self.new.get(s.field)
            if cur is None:
                raise CompileError(
                    f"field {s.field!r} must exist before accumulation in a loop"
                )
            seg = self._reduce_edges(val.astype(cur.dtype), ectx, comb, m)
            updated = _OP_APPLY[s.op](cur, seg).astype(cur.dtype)
            self.new[s.field] = jnp.where(self.active, updated, cur)

    def _remote_write(self, s: ast.RemoteWrite, mask, ectx: Optional[_EdgeCtx]):
        with self._in("remote"):
            self._emit_remote(s, mask, ectx)

    def _emit_remote(self, s: ast.RemoteWrite, mask, ectx: Optional[_EdgeCtx]):
        idx = jnp.asarray(self._eval(s.target, ectx), jnp.int32)
        val = jnp.asarray(self._eval(s.value, ectx))
        shape = ectx.seg.shape if ectx is not None else (self.nrows,)
        if idx.ndim == 0:
            idx = jnp.broadcast_to(idx, shape)
        if val.ndim == 0:
            val = jnp.broadcast_to(val, shape)
        # sender must be active
        sender_active = (
            gops.gather(self.active, ectx.seg, fill=False)
            if ectx is not None
            else self.active
        )
        m = sender_active if mask is None else jnp.logical_and(mask, sender_active)
        if ectx is not None:
            m = jnp.logical_and(m, ectx.emask)
        self.pending.append(_RemoteMsg(s.field, s.op, idx, val, m))

    def _apply_remote(self):
        with self._in("remote"):
            self._apply_messages()

    def _apply_messages(self):
        for msg in self.pending:
            if msg.field not in self.new:
                raise CompileError(
                    f"remote write to undefined field {msg.field!r}"
                )
            buf = self.new[msg.field]
            comb = ast.OP_TO_COMBINER[msg.op]
            if self.comm is not None:
                # route the scatter through the halo layer's reduce-scatter:
                # senders pre-combine locally, owners fold the delta in.
                # Receiver-activity masking is local to the owner — halted
                # receivers drop the whole combined delta, matching the
                # dense per-message drop (all messages to a halted vertex
                # are dropped together).
                delta = self.comm.scatter_reduce(
                    msg.idx, msg.values.astype(buf.dtype), comb, msg.mask
                )
                combined = _fold_combiner(comb, buf, delta)
                mshape = self.active.shape + (1,) * (buf.ndim - 1)
                self.new[msg.field] = jnp.where(
                    self.active.reshape(mshape), combined, buf
                )
                continue
            # receiver must be active
            recv_active = gops.gather(self.active, msg.idx, fill=False)
            m = jnp.logical_and(msg.mask, recv_active)
            self.new[msg.field] = gops.scatter_combine(
                buf, msg.idx, msg.values.astype(buf.dtype), comb, mask=m
            )


def _fold_combiner(op: str, cur: jax.Array, delta: jax.Array) -> jax.Array:
    """Fold a pre-combined remote-write delta into the live field.

    ``delta`` is identity-valued where no message arrived, so the fold is a
    no-op there — the partitioned equivalent of scatter's "unreduced rows
    keep their value"."""
    return gops.combine(op, cur, delta).astype(cur.dtype)


def _unop(op: str, x):
    return jnp.logical_not(x) if op == "!" else -x


def _binop(op: str, lhs, rhs):
    if op == "+":
        return lhs + rhs
    if op == "-":
        return lhs - rhs
    if op == "*":
        return lhs * rhs
    if op == "/":
        # float division unless both ints and exact context; Palgol `/` is
        # numeric division (PageRank), use true division then keep dtype rules
        return jnp.asarray(lhs) / rhs
    if op == "%":
        return jnp.asarray(lhs) % rhs
    if op == "==":
        return jnp.equal(lhs, rhs)
    if op == "!=":
        return jnp.not_equal(lhs, rhs)
    if op == "<":
        return jnp.less(lhs, rhs)
    if op == "<=":
        return jnp.less_equal(lhs, rhs)
    if op == ">":
        return jnp.greater(lhs, rhs)
    if op == ">=":
        return jnp.greater_equal(lhs, rhs)
    if op == "&&":
        return jnp.logical_and(lhs, rhs)
    if op == "||":
        return jnp.logical_or(lhs, rhs)
    raise CompileError(f"unknown operator {op!r}")


# ---------------------------------------------------------------------------
# fused-program-plan execution: one Superstep part at a time
#
# The program-level mailbox is a flat string-keyed dict so every consumer
# (the fused dense trace, the partitioned shard_map body) can thread it as
# one pytree. Keys are namespaced by step ordinal (``s<i>:``) so two steps
# materializing the same chain pattern cannot collide:
#
#   s<i>:chain:<f1>/<f2>...   materialized chain buffer (pattern-keyed)
#   s<i>:nbr:<dir>:<f1>...    per-edge neighborhood buffer
#   s<i>:fold:<k>             per-edge T of the step's fold k (_Fold.key)
#   s<i>:req:<f1>/...         naive request buffer (dense wire emulation)
#   s<i>:pending              remote-write payload (Main -> RemoteUpdate),
#                             a tuple of (idx, values, mask) triples in
#                             RemoteUpdate.writes order


def _pat_key(pattern: tuple) -> str:
    return "/".join(pattern)


def _ns_import(ns: str, mailbox, ru_writes) -> "_StepState":
    """Decode one step's mailbox entries into its typed _StepState."""
    state = _StepState()
    for k, v in mailbox.items():
        if not k.startswith(ns):
            continue
        rest = k[len(ns):]
        if rest.startswith("chain:"):
            state.chain[tuple(rest[len("chain:"):].split("/"))] = v
        elif rest.startswith("nbr:"):
            _, direction, pat = rest.split(":", 2)
            state.nbr[(direction, tuple(pat.split("/")) if pat else ())] = v
        elif rest.startswith("fold:"):
            state.fold[int(rest[len("fold:"):])] = v
        elif rest.startswith("req:"):
            state.naive_req[tuple(rest[len("req:"):].split("/"))] = v
        elif rest == "pending":
            state.pending = [
                _RemoteMsg(f, op, idx, val, mask)
                for (f, op), (idx, val, mask) in zip(ru_writes, v)
            ]
    return state


def _ns_export(ns: str, mailbox, op, state: "_StepState"):
    """Re-encode a step's post-op state into the mailbox.

    The drop policy keeps loop-carried mailbox keysets stable (a fixed
    while-carry structure for the fused dense trace, one retrace per
    superstep for the dispatching executors): MainCompute consumes the
    step's read buffers, RemoteUpdate consumes its pending payload — after
    a step's last op only prefetched entries (re-created by the fused
    loop's trailing ReadRound) remain.
    """
    out = {k: v for k, v in mailbox.items() if not k.startswith(ns)}
    pending = tuple((m.idx, m.values, m.mask) for m in state.pending)
    if isinstance(op, ReadRound):
        for p, v in state.chain.items():
            out[f"{ns}chain:{_pat_key(p)}"] = v
        for (d, p), v in state.nbr.items():
            out[f"{ns}nbr:{d}:{_pat_key(p)}"] = v
        for k, v in state.fold.items():
            out[f"{ns}fold:{k}"] = v
        for p, v in state.naive_req.items():
            out[f"{ns}req:{_pat_key(p)}"] = v
        if pending:
            out[f"{ns}pending"] = pending
    elif isinstance(op, MainCompute):
        if pending:
            out[f"{ns}pending"] = pending
    # RemoteUpdate: everything consumed
    return out


def exec_plan_part(ref: OpRef, graph, comm, fields, mailbox):
    """Execute one part of a fused :class:`~repro.core.plan.Superstep`.

    The shared per-op consumer of the program plan: the fused dense
    compiler folds these calls into its single trace (``comm=None``) and
    the partitioned executor runs them inside its per-superstep shard_map
    body (``comm=ShardComm``). Returns ``(fields, mailbox)``. The part's
    device work is named ``s<sidx>/<leaf>`` (module doc), inside the
    caller's :func:`plan_scope`.
    """
    op = ref.op
    if isinstance(op, IterInit):
        return fields, mailbox
    with jax.named_scope(f"s{ref.sidx}"):
        if isinstance(op, StopOp):
            with jax.named_scope("stop"):
                return make_stop_fn(op.stop, graph, comm=comm)(fields), mailbox
        ns = f"s{ref.sidx}:"
        plan = ref.plan
        ru = next((o for o in plan.ops if isinstance(o, RemoteUpdate)), None)
        state = _ns_import(ns, mailbox, ru.writes if ru is not None else ())
        ex = StepExecutor(plan.step, graph, comm=comm, plan=plan)
        fields, state = ex.run_ops(fields, [op], state)
        return fields, _ns_export(ns, mailbox, op, state)


def make_stop_fn(stop: ast.StopStep, graph, comm=None):
    """StopStep → fields update flipping the halted mask (paper §3.4)."""

    def stop_fn(fields):
        # reuse StepExecutor's evaluator on a synthetic empty step
        ex = StepExecutor(ast.Step(stop.vertex_var, ()), graph, comm=comm)
        ex.old = dict(fields)
        ex.new = dict(fields)
        ex.env = {}
        ex.chain_cache = {}
        ex.nbr_cache = {}
        ex.expr_cache = {}
        ex.pending = []
        cond = jnp.asarray(ex._eval(stop.cond, None))
        if cond.ndim == 0:
            cond = jnp.broadcast_to(cond, (ex.nrows,))
        halted = fields.get(HALTED, jnp.zeros((ex.nrows,), jnp.bool_))
        out = dict(fields)
        out[HALTED] = jnp.logical_or(halted, cond)
        return out

    return stop_fn
