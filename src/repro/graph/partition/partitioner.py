"""Edge-balanced contiguous-range vertex partitioning (host-side).

The partitioner splits the vertex id space ``[0, N)`` into ``S`` contiguous
ranges by a greedy prefix split on the degree CSR: walking vertices in id
order, a range boundary is cut whenever the cumulative edge-endpoint count
crosses the next multiple of ``total/S``. Contiguous ranges keep the
owner map a tiny ``[S+1]`` boundary array (owner lookup is a searchsorted,
not an ``[N]`` table) and make every per-shard edge block a *slice* of the
globally sorted COO — local ids stay sorted, so segment reductions keep
``indices_are_sorted=True``. Fancier strategies (METIS-style min-cut,
degree-aware relabeling) plug in by replacing :func:`edge_balanced_ranges`;
everything downstream consumes only the boundary array.

Edge assignment follows ownership of the *segment* vertex so reductions
never cross shards:

* pull ordering (sorted by ``dst``): an edge lives with ``dst``'s owner;
* push ordering (sorted by ``src``): with ``src``'s owner.

The neighbor endpoint of each local edge is remapped to *halo-local*
addressing: owned vertices keep their local row id ``g - start``, foreign
vertices get ``v_max + position`` in the shard's sorted ghost list. The
ghost lists and the per-(owner, reader) exchange indices are static — built
once per graph — so a superstep's halo exchange is two precomputed gathers
around one ``all_to_all`` (see :mod:`repro.graph.partition.halo`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HaloSpec:
    """Static exchange plan for one edge ordering's ghost vertices.

    ``ghost_ids[s]`` are the global ids shard ``s`` reads but does not own,
    sorted ascending (padding: ``n_vertices``). ``send_local[i, j]`` are
    owner-``i``-local row ids of the values shard ``j`` needs (padding:
    ``v_max`` — clipped reads, never consumed); ``recv_pos[j, i]`` are the
    slots in ``j``'s ghost buffer where values from owner ``i`` land
    (padding: ``n_ghost`` — a dump slot sliced off after scatter).
    """

    ghost_ids: jax.Array  # i32[S, H]
    send_local: jax.Array  # i32[S, S, Hp]  indexed [owner, reader, slot]
    recv_pos: jax.Array  # i32[S, S, Hp]  indexed [reader, owner, slot]
    n_ghost: int = dataclasses.field(metadata=dict(static=True))  # H
    pair_cap: int = dataclasses.field(metadata=dict(static=True))  # Hp


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PartitionedGraph:
    """Per-shard graph blocks + owner maps + halo plans (a pytree).

    All per-shard arrays carry a leading ``[S]`` dimension so the whole
    structure shards over a 1-D ``("shard",)`` mesh with ``P("shard")`` on
    that dimension (``starts`` is replicated). Vertex fields partition to
    ``[S, v_max]`` via :func:`partition_field`.
    """

    starts: jax.Array  # i32[S+1] contiguous range boundaries (owner map)
    vmask: jax.Array  # bool[S, v_max] valid local rows
    # pull ordering: edges assigned to dst's owner, sorted by local dst
    src_g: jax.Array  # i32[S, e_max] global src (value semantics)
    src_h: jax.Array  # i32[S, e_max] halo-local src (local row | v_max+pos)
    dst_l: jax.Array  # i32[S, e_max] local dst row (ascending; pad v_max)
    w: jax.Array  # f32[S, e_max]
    emask: jax.Array  # bool[S, e_max]
    # push ordering: edges assigned to src's owner, sorted by local src
    t_dst_g: jax.Array  # i32[S, e_max]
    t_dst_h: jax.Array  # i32[S, e_max]
    t_src_l: jax.Array  # i32[S, e_max]
    t_w: jax.Array  # f32[S, e_max]
    t_emask: jax.Array  # bool[S, e_max]
    # run ends, the contract of Graph.in_ends/out_ends per shard: one past
    # each local row's last slot of dst_l (in_ends) and t_src_l (out_ends);
    # a row with no slot takes the end of the row before, or 0
    in_ends: jax.Array  # i32[S, v_max]
    out_ends: jax.Array  # i32[S, v_max]
    halo_in: HaloSpec  # ghosts read by the pull ordering (srcs)
    halo_out: HaloSpec  # ghosts read by the push ordering (dsts)
    # static metadata
    n_vertices: int = dataclasses.field(metadata=dict(static=True))
    n_edges: int = dataclasses.field(metadata=dict(static=True))
    n_shards: int = dataclasses.field(metadata=dict(static=True))
    v_max: int = dataclasses.field(metadata=dict(static=True))
    e_max: int = dataclasses.field(metadata=dict(static=True))

    @property
    def sentinel(self) -> int:
        return self.n_vertices


def edge_balanced_ranges(graph, n_shards: int) -> np.ndarray:
    """Greedy prefix split on the degree CSR → boundaries ``i64[S+1]``.

    Balances the per-shard *assigned edge* count: each vertex weighs its
    in-degree (pull edges it owns) + out-degree (push edges) + 1 (so
    isolated vertices still spread). The greedy cut guarantees every
    shard's weight ≤ ``total/S + max_vertex_weight`` (the classic prefix
    bound), and each shard owns at least one vertex.
    """
    n = graph.n_vertices
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    if n < n_shards:
        raise ValueError(
            f"cannot give each of {n_shards} shards a vertex: only {n} exist"
        )
    dst = np.asarray(graph.dst)[np.asarray(graph.edge_mask)]
    t_src = np.asarray(graph.t_src)[np.asarray(graph.t_mask)]
    weight = (
        1
        + np.bincount(dst, minlength=n)[:n]
        + np.bincount(t_src, minlength=n)[:n]
    )
    cum = np.cumsum(weight)
    total = int(cum[-1])
    bounds = np.zeros(n_shards + 1, dtype=np.int64)
    bounds[n_shards] = n
    for k in range(1, n_shards):
        target = total * k / n_shards
        cut = int(np.searchsorted(cum, target, side="left")) + 1
        # keep ≥1 vertex per shard on both sides of the cut
        cut = max(cut, int(bounds[k - 1]) + 1)
        cut = min(cut, n - (n_shards - k))
        bounds[k] = cut
    return bounds


def _build_halo(
    nbr_global: np.ndarray,  # [S, e_max] global neighbor ids (pad: N)
    emask: np.ndarray,  # [S, e_max]
    bounds: np.ndarray,  # [S+1]
    n: int,
    v_max: int,
):
    """Ghost lists + exchange plan + halo-local remap for one ordering.

    Returns ``(halo_spec_arrays, nbr_halo)`` where ``nbr_halo[s, e]`` is the
    halo-local address of ``nbr_global[s, e]`` on shard ``s``.
    """
    S = len(bounds) - 1
    ghosts = []
    for s in range(S):
        ids = np.unique(nbr_global[s][emask[s]])
        own = (ids >= bounds[s]) & (ids < bounds[s + 1])
        ghosts.append(ids[~own].astype(np.int64))
    H = max((len(g) for g in ghosts), default=0)
    ghost_ids = np.full((S, H), n, dtype=np.int32)
    for s, g in enumerate(ghosts):
        ghost_ids[s, : len(g)] = g

    # per-(owner, reader) slices of each reader's sorted ghost list
    pair_count = np.zeros((S, S), dtype=np.int64)
    pair_lo = np.zeros((S, S), dtype=np.int64)
    for j in range(S):
        lo = np.searchsorted(ghosts[j], bounds[:-1], side="left")
        hi = np.searchsorted(ghosts[j], bounds[1:], side="left")
        pair_lo[:, j] = lo
        pair_count[:, j] = hi - lo
    Hp = int(pair_count.max(initial=0))
    send_local = np.full((S, S, Hp), v_max, dtype=np.int32)
    recv_pos = np.full((S, S, Hp), H, dtype=np.int32)
    for i in range(S):
        for j in range(S):
            c = int(pair_count[i, j])
            if c == 0:
                continue
            lo = int(pair_lo[i, j])
            ids = ghosts[j][lo : lo + c]
            send_local[i, j, :c] = ids - bounds[i]
            recv_pos[j, i, :c] = np.arange(lo, lo + c)

    # halo-local remap of the neighbor endpoints: a dense id → ghost-slot
    # table (every foreign neighbor is in its shard's ghost list)
    nbr_halo = np.full(nbr_global.shape, v_max + H, dtype=np.int32)
    slot = np.zeros(n, dtype=np.int64)
    for s in range(S):
        m = emask[s]
        g = nbr_global[s][m]
        own = (g >= bounds[s]) & (g < bounds[s + 1])
        slot[ghosts[s]] = np.arange(len(ghosts[s]))
        loc = np.where(own, g - bounds[s], v_max + slot[g])
        nbr_halo[s, m] = loc.astype(np.int32)
    return (ghost_ids, send_local, recv_pos, H, Hp), nbr_halo


def _shard_edges(key, other, w, mask, bounds, v_max):
    """Slice one globally key-sorted COO into per-shard blocks.

    Returns (key_local [S,e_max], other_global [S,e_max], w, mask) with the
    padding conventions of :class:`PartitionedGraph`.
    """
    S = len(bounds) - 1
    key = np.asarray(key)[np.asarray(mask)]
    other = np.asarray(other)[np.asarray(mask)]
    w = np.asarray(w)[np.asarray(mask)]
    lo = np.searchsorted(key, bounds[:-1], side="left")
    hi = np.searchsorted(key, bounds[1:], side="left")
    counts = hi - lo
    e_max = int(counts.max(initial=0))
    n = int(bounds[-1])
    key_l = np.full((S, e_max), v_max, dtype=np.int32)
    oth_g = np.full((S, e_max), n, dtype=np.int32)
    w_p = np.zeros((S, e_max), dtype=np.float32)
    m_p = np.zeros((S, e_max), dtype=bool)
    for s in range(S):
        c = int(counts[s])
        key_l[s, :c] = key[lo[s] : hi[s]] - bounds[s]
        oth_g[s, :c] = other[lo[s] : hi[s]]
        w_p[s, :c] = w[lo[s] : hi[s]]
        m_p[s, :c] = True
    return key_l, oth_g, w_p, m_p, e_max


def _run_ends(key_l: np.ndarray, v_max: int) -> np.ndarray:
    """``i32[S, v_max]`` run ends of the ascending per-shard keys
    ``key_l`` (padding ``v_max`` sorts last and ends no row)."""
    rows = np.arange(v_max)
    return np.stack(
        [np.searchsorted(k, rows, side="right") for k in key_l]
    ).astype(np.int32)


def partition_graph(
    graph, n_shards: int, bounds: Optional[np.ndarray] = None
) -> PartitionedGraph:
    """Partition a dense :class:`~repro.graph.structure.Graph` into ``S``
    edge-balanced contiguous-range shards with static halo plans."""
    n = graph.n_vertices
    if bounds is None:
        bounds = edge_balanced_ranges(graph, n_shards)
    bounds = np.asarray(bounds, dtype=np.int64)
    if len(bounds) != n_shards + 1 or bounds[0] != 0 or bounds[-1] != n:
        raise ValueError("bounds must be [0, ..., n_vertices] of length S+1")
    v_max = int(np.max(bounds[1:] - bounds[:-1]))

    dst_l, src_g, w_p, m_p, e_pull = _shard_edges(
        graph.dst, graph.src, graph.weight, graph.edge_mask, bounds, v_max
    )
    tsrc_l, tdst_g, tw_p, tm_p, e_push = _shard_edges(
        graph.t_src, graph.t_dst, graph.t_weight, graph.t_mask, bounds, v_max
    )
    e_max = max(e_pull, e_push, 1)

    def repad(key_l, oth_g, w, m):
        S, e = key_l.shape
        if e == e_max:
            return key_l, oth_g, w, m
        pad = e_max - e
        return (
            np.pad(key_l, ((0, 0), (0, pad)), constant_values=v_max),
            np.pad(oth_g, ((0, 0), (0, pad)), constant_values=n),
            np.pad(w, ((0, 0), (0, pad))),
            np.pad(m, ((0, 0), (0, pad))),
        )

    dst_l, src_g, w_p, m_p = repad(dst_l, src_g, w_p, m_p)
    tsrc_l, tdst_g, tw_p, tm_p = repad(tsrc_l, tdst_g, tw_p, tm_p)

    (gi, sl, rp, H_in, Hp_in), src_h = _build_halo(src_g, m_p, bounds, n, v_max)
    halo_in = HaloSpec(
        ghost_ids=jnp.asarray(gi), send_local=jnp.asarray(sl),
        recv_pos=jnp.asarray(rp), n_ghost=H_in, pair_cap=Hp_in,
    )
    (gi_o, sl_o, rp_o, H_out, Hp_out), tdst_h = _build_halo(
        tdst_g, tm_p, bounds, n, v_max
    )
    halo_out = HaloSpec(
        ghost_ids=jnp.asarray(gi_o), send_local=jnp.asarray(sl_o),
        recv_pos=jnp.asarray(rp_o), n_ghost=H_out, pair_cap=Hp_out,
    )

    sizes = (bounds[1:] - bounds[:-1])[:, None]
    vmask = np.arange(v_max)[None, :] < sizes
    return PartitionedGraph(
        starts=jnp.asarray(bounds, jnp.int32),
        vmask=jnp.asarray(vmask),
        src_g=jnp.asarray(src_g),
        src_h=jnp.asarray(src_h),
        dst_l=jnp.asarray(dst_l),
        w=jnp.asarray(w_p),
        emask=jnp.asarray(m_p),
        t_dst_g=jnp.asarray(tdst_g),
        t_dst_h=jnp.asarray(tdst_h),
        t_src_l=jnp.asarray(tsrc_l),
        t_w=jnp.asarray(tw_p),
        t_emask=jnp.asarray(tm_p),
        in_ends=jnp.asarray(_run_ends(dst_l, v_max)),
        out_ends=jnp.asarray(_run_ends(tsrc_l, v_max)),
        halo_in=halo_in,
        halo_out=halo_out,
        n_vertices=n,
        n_edges=int(np.asarray(graph.edge_mask).sum()),
        n_shards=n_shards,
        v_max=v_max,
        e_max=e_max,
    )


# ---------------------------------------------------------------------------
# field (de)partitioning — host-side layout shuffles


def _bounds(pg: PartitionedGraph) -> tuple:
    return tuple(int(b) for b in np.asarray(pg.starts))


def split_rows(bounds: tuple, v_max: int, x: jax.Array) -> jax.Array:
    """``[N, ...]`` → ``[S, v_max, ...]`` blocks of the contiguous ranges
    ``bounds`` (host ints), padding rows zero; traceable under ``jit``."""
    return jnp.stack([
        jnp.pad(x[lo:hi], [(0, v_max - (hi - lo))] + [(0, 0)] * (x.ndim - 1))
        for lo, hi in zip(bounds[:-1], bounds[1:])
    ])


def join_rows(bounds: tuple, y: jax.Array) -> jax.Array:
    """``[S, v_max, ...]`` blocks of the ranges ``bounds`` → ``[N, ...]``."""
    return jnp.concatenate([
        y[s, : hi - lo] for s, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:]))
    ])


def partition_field(pg: PartitionedGraph, x) -> jax.Array:
    """``[N, ...]`` dense vertex field → ``[S, v_max, ...]`` shard blocks
    (padding rows zero-filled; they are masked inactive by the executor)."""
    return split_rows(_bounds(pg), pg.v_max, jnp.asarray(x))


def unpartition_field(pg: PartitionedGraph, y) -> jax.Array:
    """``[S, v_max, ...]`` shard blocks → ``[N, ...]`` dense vertex field."""
    return join_rows(_bounds(pg), jnp.asarray(y))


def partition_fields(pg: PartitionedGraph, fields: Dict) -> Dict:
    return {k: partition_field(pg, v) for k, v in fields.items()}


def unpartition_fields(pg: PartitionedGraph, fields: Dict) -> Dict:
    return {k: unpartition_field(pg, v) for k, v in fields.items()}
