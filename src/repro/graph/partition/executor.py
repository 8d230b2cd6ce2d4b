"""`placement="partitioned"` execution of Palgol programs.

``run_bsp_partitioned`` is the partitioned twin of
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

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core import plan as plan_mod
from repro.core.codegen import HALTED, _EdgeCtx, exec_plan_part, plan_scope
from repro.core.plan import ByteCostModel
from repro.graph import ops as gops
from repro.graph.partition import halo
from repro.graph.partition.partitioner import (
    PartitionedGraph,
    partition_fields,
    partition_graph,
    unpartition_fields,
)
from repro.pregel.runtime import BSPResult, walk_plan

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
            seg, nbr_g, nbr_h, w, m = pg.dst_l, pg.src_g, pg.src_h, pg.w, pg.emask
        elif direction == "out":
            seg, nbr_g, nbr_h, w, m = (
                pg.t_src_l, pg.t_dst_g, pg.t_dst_h, pg.t_w, pg.t_emask,
            )
        else:
            raise ValueError(f"unknown edge direction {direction!r}")
        vid = (self.start + seg).astype(jnp.int32)
        return _EdgeCtx(
            direction, nbr=nbr_g, vid=vid, w=w, emask=m, seg=seg, nbr_read=nbr_h
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
    "t_dst_g", "t_dst_h", "t_src_l", "t_w", "t_emask",
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
    """Execute a Palgol program over partitioned vertex state.

    Same contract as :func:`repro.pregel.runtime.run_bsp` (canonical field
    dict in, final *dense* fields + superstep count + trips + frontier
    sizes out); the graph is partitioned over ``mesh`` (default: a 1-D
    mesh over all local devices, built by
    :func:`repro.dist.sharding.shard_mesh`). ``graph`` may already be a
    :class:`PartitionedGraph` with one shard per mesh device, so a graph
    partitioned once serves many jobs. Every schedule runs here
    (``pull``/``push``/``naive``/``auto`` — build byte costs from this
    layout with :func:`repro.graph.partition.byte_cost_model`), and
    ``fuse=True`` (default) dispatches the §4.3-fused program plan — one
    shard_map call per *fused* superstep, merged collectives combined in
    one dispatch; ``fuse=False`` dispatches the unfused per-op expansion.
    """
    from repro.dist import sharding as shd

    pp = plan_mod.lower_program(prog, schedule=schedule, byte_costs=byte_costs)
    if fuse:
        pp = plan_mod.fuse(pp)

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
    fields = {k: jnp.asarray(v) for k, v in fields.items()}
    if HALTED not in fields:
        fields[HALTED] = jnp.zeros((pg.n_vertices,), jnp.bool_)
    pfields = partition_fields(pg, fields)
    pfields = jax.device_put(
        pfields, shd.vertex_partition_shardings(pfields, mesh)
    )
    pg = jax.device_put(pg, shd.vertex_partition_shardings(pg, mesh))

    counter = [0]
    trips: List[int] = []
    active_sets: List[List[int]] = []
    ss_fns: Dict[int, object] = {}
    mailbox_box = [{}]

    def exec_superstep(ss: plan_mod.Superstep, flds, loops):
        if id(ss) not in ss_fns:
            ss_fns[id(ss)] = _make_superstep_fn(ss, pg, mesh, loops)
        flds, mailbox_box[0] = ss_fns[id(ss)](flds, mailbox_box[0], pg)
        return flds

    out = walk_plan(
        pp, pfields, exec_superstep, counter, trips, max_iters,
        active_sets=active_sets, vertex_ndim=2,
    )
    return BSPResult(
        fields=unpartition_fields(pg, out),
        supersteps=counter[0],
        trips=trips,
        active_sets=active_sets,
    )
