"""`placement="partitioned"` execution of Palgol programs.

:class:`PartitionedProgram` prepares a program once per
(program, :class:`~repro.graph.partition.partitioner.PartitionedGraph`,
mesh, schedule, fuse) and runs it as many times as asked;
``run_bsp_partitioned`` builds one and runs it once. Either is the
partitioned twin of
:func:`repro.pregel.runtime.run_bsp`: the same host-side program-plan walk
(:func:`repro.pregel.runtime.walk_plan` — Seq/Iter/Stop sequencing,
fixed-point aggregator round-trips, fused superstep counting, frontier
instrumentation), but each **fused superstep** executes as ONE shard_map
dispatch over the :class:`~repro.graph.partition.partitioner.PartitionedGraph`
layout. Inside the shard_map body the unchanged
:class:`~repro.core.codegen.StepExecutor` runs one plan op at a time
(:func:`~repro.core.codegen.exec_plan_part`) with a :class:`ShardComm`,
mapping ops onto the halo collectives:

* ``ReadRound`` for neighborhood sends (``F[e.id]``) → static
  :func:`~.halo.halo_exchange` (moves only boundary state);
* ``ReadRound`` for chain accesses (``D[D[u]]``) →
  :func:`~.halo.gather_global` — once per pull round (pointer doubling
  rebuilds its request halo from the current indirection field), once
  per hop under ``schedule="naive"``, once per ``push_reply`` round under
  ``schedule="push"`` (the deduplicated request bucketing inside
  gather_global *is* the combined request set);
* ``RemoteUpdate`` → :func:`~.halo.scatter_reduce` + a local fold at the
  owner.

A *merged* superstep of the fused plan (§4.3) runs its parts inside the
same dispatch: the halo exchange of a step's first ReadRound piggybacks on
the merged RemoteUpdate's reduce-scatter — one barrier, both collectives —
and the per-shard mailbox (chain/neighborhood buffers, pending remote
payloads) crosses dispatch boundaries as sharded ``[S, ...]`` arrays.

Superstep accounting is the walk itself — one count per dispatched (fused)
superstep, the identical plan the staged dense executor dispatches — so
STM cross-checks carry over by construction, for every schedule and both
``fuse`` settings.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import plan as plan_mod
from repro.core.codegen import HALTED, _EdgeCtx, exec_plan_part, plan_scope
from repro.core.plan import ByteCostModel
from repro.graph import ops as gops
from repro.graph.partition import halo
from repro.graph.partition.partitioner import (
    PartitionedGraph,
    join_rows,
    partition_graph,
    split_rows,
)
from repro.pregel.runtime import BSPResult, walk_plan
from repro.trace import counted, summed

AXIS = halo.AXIS


class ShardComm:
    """Per-shard communication context (lives inside a shard_map body).

    Implements the addressing contract of
    :class:`~repro.core.codegen.StepExecutor`: ``n_rows`` local rows per
    shard (``v_max``), global vertex ids as values, halo-layer collectives
    for every access that leaves the shard.
    """

    def __init__(self, pg: PartitionedGraph, axis: str = AXIS):
        self.pg = pg
        self.axis = axis
        self.n_rows = pg.v_max
        self.valid = pg.vmask
        self.start = pg.starts[jax.lax.axis_index(axis)]

    def ids(self) -> jax.Array:
        """Global ids of this shard's rows (padding rows run past the
        range; they are masked inactive everywhere)."""
        return (self.start + jnp.arange(self.n_rows, dtype=jnp.int32)).astype(
            jnp.int32
        )

    def gather(self, arr: jax.Array, idx: jax.Array, fill=None) -> jax.Array:
        """``arr[idx]`` for arbitrary *global* ids (dynamic exchange)."""
        idx = jnp.asarray(idx, jnp.int32)
        flat = halo.gather_global(
            arr,
            idx.reshape(-1),
            self.pg.starts,
            self.pg.n_vertices,
            self.pg.v_max,
            fill=fill,
            axis=self.axis,
        )
        return flat.reshape(idx.shape + arr.shape[1:])

    def _halo_for(self, direction: str):
        return self.pg.halo_in if direction in ("in", "nbr") else self.pg.halo_out

    def read_edge(self, per_row: jax.Array, ectx: _EdgeCtx) -> jax.Array:
        """Per-edge neighbor values via the static halo (boundary-only)."""
        spec = self._halo_for(ectx.direction)
        ghost = halo.halo_exchange(
            per_row, spec.send_local, spec.recv_pos, spec.n_ghost, self.axis
        )
        ext = jnp.concatenate([per_row, ghost], axis=0)
        return gops.gather(ext, ectx.nbr_read)

    def edge_ctx(self, direction: str) -> _EdgeCtx:
        pg = self.pg
        if direction in ("in", "nbr"):
            seg, nbr_g, nbr_h, w, m, ends = (
                pg.dst_l, pg.src_g, pg.src_h, pg.w, pg.emask, pg.in_ends,
            )
        elif direction == "out":
            seg, nbr_g, nbr_h, w, m, ends = (
                pg.t_src_l, pg.t_dst_g, pg.t_dst_h, pg.t_w, pg.t_emask,
                pg.out_ends,
            )
        else:
            raise ValueError(f"unknown edge direction {direction!r}")
        vid = (self.start + seg).astype(jnp.int32)
        return _EdgeCtx(
            direction, nbr=nbr_g, vid=vid, w=w, emask=m, seg=seg,
            nbr_read=nbr_h, ends=ends,
        )

    def scatter_reduce(self, idx, values, op: str, mask) -> jax.Array:
        """Pre-combined remote-write delta for this shard's owned rows."""
        return halo.scatter_reduce(
            jnp.asarray(idx, jnp.int32),
            values,
            op,
            self.pg.starts,
            self.pg.n_vertices,
            self.pg.v_max,
            mask=mask,
            axis=self.axis,
        )


# ---------------------------------------------------------------------------
# shard_map plumbing


_SHARDED_PG_FIELDS = (
    "vmask", "src_g", "src_h", "dst_l", "w", "emask",
    "t_dst_g", "t_dst_h", "t_src_l", "t_w", "t_emask", "in_ends", "out_ends",
)
_SHARDED_HALO_FIELDS = ("ghost_ids", "send_local", "recv_pos")


def pg_partition_specs(pg: PartitionedGraph) -> PartitionedGraph:
    """PartitionSpec tree matching ``pg``: every per-shard leading dim over
    the ``shard`` axis, the owner map (``starts``) replicated."""
    sh = {f: P(AXIS) for f in _SHARDED_PG_FIELDS}
    hs = {f: P(AXIS) for f in _SHARDED_HALO_FIELDS}
    return dataclasses.replace(
        pg,
        starts=P(),
        halo_in=dataclasses.replace(pg.halo_in, **hs),
        halo_out=dataclasses.replace(pg.halo_out, **hs),
        **sh,
    )


def _local_view(pg: PartitionedGraph) -> PartitionedGraph:
    """Squeeze the per-shard leading dim off a shard_map block of ``pg``."""
    sq = {f: getattr(pg, f)[0] for f in _SHARDED_PG_FIELDS}
    return dataclasses.replace(
        pg,
        halo_in=dataclasses.replace(
            pg.halo_in, **{f: getattr(pg.halo_in, f)[0] for f in _SHARDED_HALO_FIELDS}
        ),
        halo_out=dataclasses.replace(
            pg.halo_out, **{f: getattr(pg.halo_out, f)[0] for f in _SHARDED_HALO_FIELDS}
        ),
        **sq,
    )


def _make_superstep_fn(
    ss: plan_mod.Superstep, pg: PartitionedGraph, mesh, loops: tuple = ()
):
    """jit(jax.shard_map(...)) executing ONE fused superstep's parts in order,
    named inside the loops ``loops`` (:func:`repro.core.codegen.plan_scope`).

    ``(fields, mailbox, pg) -> (fields, mailbox)`` over per-shard blocks;
    the specs are pytree prefixes (every fields/mailbox leaf is a
    ``[S, ...]`` block over the ``shard`` axis), so mailbox keysets may
    differ between supersteps without bespoke spec plumbing. A merged
    superstep's collectives (e.g. a RemoteUpdate's reduce-scatter plus the
    next step's halo exchange) land in this one dispatch.
    """

    tmap = jax.tree_util.tree_map

    def body(flds, mbox, pgb):
        pgl = _local_view(pgb)
        comm = ShardComm(pgl)
        local_f = {k: v[0] for k, v in flds.items()}
        local_m = tmap(lambda v: v[0], mbox)
        with plan_scope(loops):
            for ref in ss.parts:
                local_f, local_m = exec_plan_part(
                    ref, pgl, comm, local_f, local_m
                )
        return (
            {k: v[None] for k, v in local_f.items()},
            tmap(lambda v: v[None], local_m),
        )

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), pg_partition_specs(pg)),
            out_specs=(P(AXIS), P(AXIS)),
            check_vma=False,
        )
    )


# ---------------------------------------------------------------------------
# the runtime


def _mesh_of(pg: PartitionedGraph):
    """The 1-D ``shard`` mesh ``pg``'s blocks are split over, or ``None``."""
    sharding = getattr(pg.src_g, "sharding", None)
    mesh = getattr(sharding, "mesh", None)
    if mesh is not None and tuple(mesh.axis_names) == (AXIS,):
        return mesh
    return None


class PartitionedProgram:
    """A Palgol program prepared for one :class:`PartitionedGraph` on one
    mesh: the (fused) plan, lowered once, and one jitted ``shard_map`` per
    plan superstep, each traced and compiled on its first dispatch and
    reused by every later job. :meth:`run` walks the plan with
    :func:`~repro.pregel.runtime.walk_plan`; a second ``run`` over the same
    graph obtains no executable.

    ``mesh`` defaults to the mesh ``pg``'s blocks already lie on (as
    :func:`~repro.graph.partition.partition_on_mesh` leaves them), else a
    1-D mesh over the first ``pg.n_shards`` devices. Fields go in and come
    out dense (``[N, ...]``), as with ``run_bsp``.

    :attr:`edge_reduce_paths` tallies the edge reductions of every
    superstep traced so far by the path each took (``{"scan": k,
    "scatter": m, "fold": f}``, as ``CompiledProgram.edge_reduce_paths``):
    an order-independent reduction scans the shard's sorted edges, which
    carry run ends.
    """

    def __init__(
        self,
        prog,
        pg: PartitionedGraph,
        mesh=None,
        schedule: str = "pull",
        byte_costs: Optional[ByteCostModel] = None,
        fuse: bool = True,
        max_iters: int = 100_000,
    ):
        from repro.dist import sharding as shd

        self.plan = plan_mod.lower_program(
            prog, schedule=schedule, byte_costs=byte_costs
        )
        if fuse:
            self.plan = plan_mod.fuse(self.plan)
        if mesh is None:
            mesh = _mesh_of(pg) or shd.shard_mesh(pg.n_shards)
        if mesh.shape[AXIS] != pg.n_shards:
            raise ValueError(
                f"graph has {pg.n_shards} shards, mesh has {mesh.shape[AXIS]}"
            )
        self.mesh = mesh
        self.max_iters = max_iters
        self.pg = jax.device_put(pg, shd.vertex_partition_shardings(pg, mesh))
        bounds = tuple(int(b) for b in np.asarray(pg.starts))
        self._partition = jax.jit(
            lambda fields: {k: split_rows(bounds, pg.v_max, v)
                            for k, v in fields.items()},
            out_shardings=NamedSharding(mesh, P(AXIS)),
        )
        self._unpartition = jax.jit(
            lambda fields: {k: join_rows(bounds, v) for k, v in fields.items()}
        )
        self._fns: Dict[int, object] = {}
        #: per superstep: bytes its collectives carry per chip per dispatch
        self._carried: Dict[int, Dict[str, float]] = {}
        self._paths: collections.Counter = collections.Counter()

    def _dispatch(self, ss: plan_mod.Superstep, loops, flds, mailbox):
        key = id(ss)
        if key in self._fns:
            return self._fns[key](flds, mailbox, self.pg)
        self._fns[key] = _make_superstep_fn(ss, self.pg, self.mesh, loops)
        # the first dispatch traces the superstep: its collectives record
        # what their operands carry, its edge reductions the path they take
        with summed("comm/") as carried, counted("edge_reduce/") as paths:
            out = self._fns[key](flds, mailbox, self.pg)
        self._carried[key] = dict(carried)
        self._paths.update(paths)
        return out

    @property
    def edge_reduce_paths(self) -> Dict[str, int]:
        return {k: self._paths[k] for k in ("scan", "scatter", "fold")}

    def run(
        self, fields: Dict[str, jax.Array], max_iters: Optional[int] = None
    ) -> BSPResult:
        """One job from the canonical dense field dict ``fields``; a loop
        without a fixed trip count stops after ``max_iters`` trips (default:
        the program's ``max_iters``)."""
        fields = {k: jnp.asarray(v) for k, v in fields.items()}
        if HALTED not in fields:
            fields[HALTED] = jnp.zeros((self.pg.n_vertices,), jnp.bool_)
        counter = [0]
        trips: List[int] = []
        active_sets: List[List[int]] = []
        mailbox_box = [{}]
        comm_bytes: collections.Counter = collections.Counter()

        def exec_superstep(ss: plan_mod.Superstep, flds, loops):
            flds, mailbox_box[0] = self._dispatch(
                ss, loops, flds, mailbox_box[0]
            )
            comm_bytes.update(self._carried[id(ss)])
            return flds

        out = walk_plan(
            self.plan, self._partition(fields), exec_superstep, counter,
            trips, self.max_iters if max_iters is None else max_iters,
            active_sets=active_sets, vertex_ndim=2,
        )
        return BSPResult(
            fields=self._unpartition(out),
            supersteps=counter[0],
            trips=trips,
            active_sets=active_sets,
            comm_bytes=dict(comm_bytes),
        )

    def warm(self, fields: Dict[str, jax.Array]) -> None:
        """Obtain every executable a job from ``fields`` uses by running
        one job cut to two trips of each loop: every superstep is
        dispatched, on the first trip and on one that follows a trip, and
        so are the walk's frontier counts and the partitioning of the
        fields in and out."""
        jax.block_until_ready(self.run(fields, max_iters=2).fields)


def run_bsp_partitioned(
    prog,
    graph,
    fields: Dict[str, jax.Array],
    schedule: str = "pull",
    max_iters: int = 100_000,
    mesh=None,
    n_shards: int = None,
    byte_costs: Optional[ByteCostModel] = None,
    fuse: bool = True,
) -> BSPResult:
    """Execute a Palgol program over partitioned vertex state: build a
    :class:`PartitionedProgram` and run it once.

    Same contract as :func:`repro.pregel.runtime.run_bsp` (canonical field
    dict in, final *dense* fields + superstep count + trips + frontier
    sizes out); the graph is partitioned over ``mesh`` (default: a 1-D
    mesh over all local devices, built by
    :func:`repro.dist.sharding.shard_mesh`). ``graph`` may already be a
    :class:`PartitionedGraph` with one shard per mesh device, so a graph
    partitioned once serves many jobs (a :class:`PartitionedProgram` also
    keeps the compiled supersteps between them). Every schedule runs here
    (``pull``/``push``/``naive``/``auto`` — build byte costs from this
    layout with :func:`repro.graph.partition.byte_cost_model`), and
    ``fuse=True`` (default) dispatches the §4.3-fused program plan — one
    shard_map call per *fused* superstep, merged collectives combined in
    one dispatch; ``fuse=False`` dispatches the unfused per-op expansion.
    """
    from repro.dist import sharding as shd

    if mesh is None:
        mesh = shd.shard_mesh(n_shards)
    n_shards = mesh.shape[AXIS]
    if isinstance(graph, PartitionedGraph):
        if graph.n_shards != n_shards:
            raise ValueError(
                f"graph has {graph.n_shards} shards, mesh has {n_shards}"
            )
        pg = graph
    else:
        pg = partition_graph(graph, n_shards)
    return PartitionedProgram(
        prog, pg, mesh, schedule=schedule, byte_costs=byte_costs, fuse=fuse,
        max_iters=max_iters,
    ).run(fields)
