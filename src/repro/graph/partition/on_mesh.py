"""Build a :class:`PartitionedGraph` on the chips, from edges spread over them.

:func:`partition_on_mesh` is the device twin of the host
:func:`~repro.graph.partition.partitioner.partition_graph`, which stays
the plain reference for it. Its input is a directed edge list laid out as
``[S·k]`` arrays over a 1-D ``("shard",)`` mesh: each chip holds any
``k`` edges, live where ``mask``. Nothing of it is copied to the host or
gathered on one chip. Three passes, each a ``shard_map`` over the mesh:

1. *sizes* — vertex in- and out-degrees, a ``psum`` of per-chip
   histograms, kept on every chip for the route pass; the range
   boundaries by :func:`edge_balanced_ranges`'s rule (weight
   1 + in-degree + out-degree, greedy prefix cut); per (chip, owner) edge
   counts of both orderings; and, for each reader shard, the bitmap of the
   vertices its edges read (a ``psum_scatter`` of per-chip bitmaps). The
   host reads the small counts once, the set-up's one round trip, and
   fixes every static size from them: ``e_max``, the per-pair routing
   capacity, ``v_max``, ``n_ghost`` and ``pair_cap``;
2. *route* — each edge goes to its destination's owner (pull ordering)
   and to its source's owner (push ordering), one ``all_to_all`` each
   (:func:`route`), and each shard sorts its block by ``(key, other
   endpoint[, weight])`` (:func:`sort_blocks`, one executable for both
   orderings); a shard's run ends are the running count of its rows'
   degrees, as the block is sorted by row (no scatter over the block);
3. *halo* — the ghost list of each shard is the sorted nonzero set of
   its bitmap, less its own range (a static-size ``nonzero``); the
   halo-local remap reads a ghost's slot from the bitmap's running count,
   a dense table with the same result as a ``searchsorted`` into the ghost
   list; ``send_local`` is one ``all_to_all`` of each reader's per-owner
   ghost slices; ``recv_pos`` their slots in its ghost buffer.

The result is the pytree ``partition_graph`` builds, leaf for leaf, for a
graph whose edges are in ``(dst, src)`` order with no repeated pair (a
repeated pair's copies come out in weight order): every per-shard leaf
is ``P("shard")`` on the mesh, ``starts`` is replicated. With
``size_bits`` the static sizes are rounded up to that many significant
bits instead (more padding, same conventions), so that graphs drawn alike
share their shapes, and with them every compiled executable. The spans
``palgol/partition`` (with ``partition/sizes``, ``partition/route`` and
``partition/halo`` inside) time the passes; each waits for its results.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.graph.partition.halo import AXIS
from repro.graph.partition.partitioner import HaloSpec, PartitionedGraph
from repro.trace import span

#: the two edge orderings, pull then push: (key endpoint, other endpoint)
_ORDERINGS = (("dst", "src"), ("src", "dst"))
#: significant bits a sorted block's length keeps (:func:`sorted_length`)
SORT_BITS = 3


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The static sizes the sizing pass fixes (host ints)."""

    n_edges: int  # live directed edges
    e_max: int  # edge slots per shard and ordering
    v_max: int  # rows per shard
    route_cap: int  # per-pair routing capacity, both orderings
    n_ghost: Tuple[int, int]  # halo sizes H, pull (halo_in) and push
    pair_cap: Tuple[int, int]  # Hp, pull and push


def _owner(ids: jax.Array, bounds: jax.Array, n_shards: int) -> jax.Array:
    """Owner shard of each id: how many interior boundaries lie at or
    below it (ids at or past the last boundary go to the last shard)."""
    own = jnp.zeros(ids.shape, jnp.int32)
    for s in range(1, n_shards):
        own = own + (ids >= bounds[s]).astype(jnp.int32)
    return own


def _count_below(csum: jax.Array, b: jax.Array) -> jax.Array:
    """``sum(flags[:b])`` from the running count ``csum = cumsum(flags)``."""
    return jnp.where(b > 0, csum[jnp.maximum(b - 1, 0)], 0)


def route(
    dest: jax.Array,
    arrays: Sequence[jax.Array],
    fills: Sequence,
    cap: int,
    n_shards: int,
    axis: str = AXIS,
) -> Tuple[jax.Array, ...]:
    """Send element ``i`` of each of ``arrays`` to chip ``dest[i]``
    (``n_shards``: not sent); runs inside a ``shard_map`` over ``axis``.

    Returns the ``[n_shards·cap]`` arrays this chip receives, in sender
    order and, within a sender, in the order the elements had there;
    slots no element filled hold ``fills``. ``cap`` bounds what one chip
    sends to one other: it must be at least the largest such count. Each
    element's slot in its destination's bucket is its rank among the
    elements bound there (a running count), one scatter per array fills
    the ``[n_shards, cap]`` send buffer and one ``all_to_all`` moves it.
    """
    S = n_shards
    k = dest.shape[0]
    slot = jnp.zeros((k,), jnp.int32)
    for o in range(S):
        bound = dest == o
        slot = jnp.where(bound, jnp.cumsum(bound, dtype=jnp.int32) - 1, slot)
    # unsent elements get slots of their own past the buffer: dropped
    pos = jnp.where(dest < S, dest * cap + slot,
                    S * cap + jnp.arange(k, dtype=jnp.int32))
    out = []
    for a, fill in zip(arrays, fills):
        buf = jnp.full((S * cap,), fill, a.dtype).at[pos].set(
            a, mode="drop", unique_indices=True)
        with jax.named_scope("route"):
            recv = jax.lax.all_to_all(
                buf.reshape(S, cap), axis, split_axis=0, concat_axis=0)
        out.append(recv.reshape(S * cap))
    return tuple(out)


# ---------------------------------------------------------------------------
# pass 1: sizes


def _sizes_body(src, dst, mask, *, n: int, n_shards: int):
    S = n_shards
    src = jnp.where(mask, src, n)
    dst = jnp.where(mask, dst, n)

    def hist(ids):
        return jnp.zeros((n + 1,), jnp.int32).at[ids].add(1, mode="drop")[:n]

    with jax.named_scope("degrees"):
        degrees = jax.lax.psum(jnp.stack([hist(dst), hist(src)]), AXIS)
    cum = jnp.cumsum(1 + degrees[0] + degrees[1])
    total = cum[-1]
    q, r = total // S, total % S
    bounds = [jnp.int32(0)]
    for k in range(1, S):
        # the host's float target total*k/S, as an exact integer ceiling
        target = q * k + (r * k + S - 1) // S
        cut = jnp.sum(cum < target, dtype=jnp.int32) + 1
        cut = jnp.maximum(cut, bounds[-1] + 1)
        bounds.append(jnp.minimum(cut, n - (S - k)))
    bounds.append(jnp.int32(n))
    bounds = jnp.stack(bounds).astype(jnp.int32)

    me = jax.lax.axis_index(AXIS)
    ids = jnp.arange(n, dtype=jnp.int32)
    counts, below, rows = [], [], []
    edges = {"src": src, "dst": dst}
    for key_name, other_name in _ORDERINGS:
        key, other = edges[key_name], edges[other_name]
        own = _owner(jnp.minimum(key, n - 1), bounds, S)
        counts.append(jnp.stack([jnp.sum(mask & (own == o), dtype=jnp.int32)
                                 for o in range(S)]))
        # reads[s, u]: an edge owned by shard s reads vertex u
        flat = jnp.where(mask, own * n + other, S * n)
        reads = jnp.zeros((S * n,), jnp.int32).at[flat].set(1, mode="drop")
        row = jax.lax.psum_scatter(
            reads.reshape(S, n), AXIS, scatter_dimension=0, tiled=True
        )[0] > 0
        ghost = row & ((ids < bounds[me]) | (ids >= bounds[me + 1]))
        csum = jnp.cumsum(ghost.astype(jnp.int32))
        below.append(jnp.stack([_count_below(csum, bounds[i])
                                for i in range(S + 1)]))
        rows.append(row[None])
    return (bounds, jnp.stack(counts)[None], jnp.stack(below)[None],
            tuple(rows), (degrees[0], degrees[1]))


@functools.lru_cache(maxsize=None)
def _sizes_fn(mesh, n: int):
    S = mesh.shape[AXIS]
    return jax.jit(jax.shard_map(
        functools.partial(_sizes_body, n=n, n_shards=S), mesh=mesh,
        in_specs=(P(AXIS),) * 3,
        out_specs=(P(), P(AXIS), P(AXIS), (P(AXIS),) * 2, (P(),) * 2),
        check_vma=False,
    ))


def round_up(x: int, bits: Optional[int]) -> int:
    """``x`` rounded up to ``bits`` significant bits (``None``: exact)."""
    if bits is None:
        return x
    step = 1 << max(x.bit_length() - bits, 0)
    return -(-x // step) * step


def _sizes(bounds, counts, below, bits: Optional[int]) -> Sizes:
    """Static sizes from the sizing pass's host copies: ``bounds[S+1]``,
    ``counts[chip, ordering, owner]``, ``below[reader, ordering, S+1]``
    (ghosts of the reader below each boundary), each rounded up to
    ``bits`` significant bits."""
    bounds = np.asarray(bounds, np.int64)
    counts = np.asarray(counts, np.int64)
    below = np.asarray(below, np.int64)
    owned = counts.sum(axis=0)  # [ordering, owner]
    pairs = np.diff(below, axis=-1)  # [reader, ordering, owner]
    r = functools.partial(round_up, bits=bits)
    return Sizes(
        n_edges=int(owned[0].sum()),
        e_max=r(max(int(owned.max(initial=0)), 1)),
        v_max=r(int(np.max(bounds[1:] - bounds[:-1]))),
        route_cap=r(max(int(counts.max(initial=0)), 1)),
        n_ghost=tuple(r(int(below[:, o, -1].max(initial=0)))
                      for o in range(2)),
        pair_cap=tuple(r(int(pairs[:, o].max(initial=0))) for o in range(2)),
    )


# ---------------------------------------------------------------------------
# pass 2: route and sort


def _route_body(key, other, mask, bounds, *w, n, n_shards, cap, length):
    """Each live edge to its key's owner; the received block padded to
    ``length`` with dropped slots, which carry ``(n, n[, 0])`` and so sort
    after every live edge."""
    S = n_shards
    own = _owner(jnp.minimum(key, n - 1), bounds, S)
    dest = jnp.where(mask, own, S)
    fills = (n, n) + (0.0,) * len(w)
    recv = route(dest, (key, other) + w, fills, cap, S)
    return tuple(jnp.concatenate([a, jnp.full((length - S * cap,), f, a.dtype)])
                 for a, f in zip(recv, fills))


@functools.lru_cache(maxsize=None)
def _route_fn(mesh, n, cap, length, weighted):
    S = mesh.shape[AXIS]
    return jax.jit(jax.shard_map(
        functools.partial(_route_body, n=n, n_shards=S, cap=cap,
                          length=length),
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P()) + (P(AXIS),) * weighted,
        out_specs=(P(AXIS),) * (2 + weighted), check_vma=False,
    ))


@functools.lru_cache(maxsize=None)
def _sort_fn(mesh, n_arrays: int):
    return jax.jit(jax.shard_map(
        lambda *a: jax.lax.sort(a, num_keys=len(a)), mesh=mesh,
        in_specs=(P(AXIS),) * n_arrays, out_specs=(P(AXIS),) * n_arrays,
        check_vma=False,
    ))


def sort_blocks(mesh, *arrays):
    """Sort each chip's block of the ``[S·L]`` arrays ``arrays`` (split over
    ``mesh``) lexicographically by all of them, in one executable per
    shape: a sort of large blocks takes minutes to compile, so every sort
    of a build goes through this one, at a length rounded up by
    :func:`sorted_length`. Elements equal in every array are
    interchangeable."""
    return _sort_fn(mesh, len(arrays))(*arrays)


def sorted_length(length: int) -> int:
    """``length`` rounded up to :data:`SORT_BITS` significant bits: the
    block length :func:`sort_blocks` is given, so that the sorts of one
    build, and of a generator that pads as this module does, share it."""
    return round_up(length, SORT_BITS)


def _finish_body(key, other, bounds, degree, *w, n, e_max, v_max):
    """A shard's sorted block → its first ``e_max`` slots in the padding
    conventions of :class:`PartitionedGraph`, its run ends and its
    ``vmask`` row. ``degree[u]`` counts the live edges keyed ``u``: the
    block is sorted by key, so a row's run ends where the running count
    of its rows' degrees does."""
    k, o = key[:e_max], other[:e_max]
    m = k < n
    me = jax.lax.axis_index(AXIS)
    start = bounds[me]
    key_l = jnp.where(m, k - start, v_max).astype(jnp.int32)
    wt = jnp.where(m, w[0][:e_max], 0.0) if w else m.astype(jnp.float32)
    vmask = jnp.arange(v_max) < bounds[me + 1] - start
    rows = jax.lax.dynamic_slice(jnp.pad(degree, (0, v_max)), (start,),
                                 (v_max,))
    ends = jnp.cumsum(jnp.where(vmask, rows, 0), dtype=jnp.int32)
    return key_l[None], o[None], wt[None], m[None], ends[None], vmask[None]


@functools.lru_cache(maxsize=None)
def _finish_fn(mesh, n, e_max, v_max, weighted):
    return jax.jit(jax.shard_map(
        functools.partial(_finish_body, n=n, e_max=e_max, v_max=v_max),
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(), P()) + (P(AXIS),) * weighted,
        out_specs=(P(AXIS),) * 6, check_vma=False,
    ))


# ---------------------------------------------------------------------------
# pass 3: halo plans


def _halo_body(nbr, emask, row, bounds, *, n, n_shards, v_max, n_ghost,
               pair_cap):
    S, H, Hp = n_shards, n_ghost, pair_cap
    nbr, emask, row = nbr[0], emask[0], row[0]
    me = jax.lax.axis_index(AXIS)
    start, end = bounds[me], bounds[me + 1]
    ids = jnp.arange(n, dtype=jnp.int32)
    ghost = row & ((ids < start) | (ids >= end))
    csum = jnp.cumsum(ghost.astype(jnp.int32))
    ghost_ids = jnp.nonzero(ghost, size=H, fill_value=n)[0].astype(jnp.int32)
    own = (nbr >= start) & (nbr < end)
    slot = csum[jnp.clip(nbr, 0, n - 1)] - 1
    nbr_h = jnp.where(
        emask, jnp.where(own, nbr - start, v_max + slot), v_max + H
    ).astype(jnp.int32)
    lo = jnp.stack([_count_below(csum, bounds[i]) for i in range(S + 1)])
    count = lo[1:] - lo[:-1]
    t = jnp.arange(Hp, dtype=jnp.int32)
    ok = t[None, :] < count[:, None]  # [owner, slot]
    pos = lo[:-1, None] + t[None, :]
    if H:
        wanted = ghost_ids[jnp.clip(pos, 0, H - 1)] - bounds[:-1, None]
    else:
        wanted = jnp.zeros(pos.shape, jnp.int32)
    request = jnp.where(ok, wanted, v_max).astype(jnp.int32)
    recv_pos = jnp.where(ok, pos, H).astype(jnp.int32)
    if Hp:
        with jax.named_scope("halo_plan"):
            send_local = jax.lax.all_to_all(
                request, AXIS, split_axis=0, concat_axis=0)
    else:
        send_local = request
    return nbr_h[None], ghost_ids[None], send_local[None], recv_pos[None]


@functools.lru_cache(maxsize=None)
def _halo_fn(mesh, n, v_max, n_ghost, pair_cap):
    S = mesh.shape[AXIS]
    return jax.jit(jax.shard_map(
        functools.partial(_halo_body, n=n, n_shards=S, v_max=v_max,
                          n_ghost=n_ghost, pair_cap=pair_cap),
        mesh=mesh, in_specs=(P(AXIS),) * 3 + (P(),),
        out_specs=(P(AXIS),) * 4, check_vma=False,
    ))


# ---------------------------------------------------------------------------


def partition_on_mesh(
    src: jax.Array,
    dst: jax.Array,
    w: Optional[jax.Array],
    mask: jax.Array,
    n_vertices: int,
    mesh,
    size_bits: Optional[int] = None,
) -> PartitionedGraph:
    """The :class:`PartitionedGraph` of the directed edges ``(src[i],
    dst[i], w[i])`` where ``mask[i]``, built on ``mesh`` (1-D, axis
    ``"shard"``) from ``[S·k]`` arrays split over it; see the module doc.
    ``w=None`` gives every live edge weight 1; ``size_bits`` rounds the
    static sizes up to that many significant bits (default: exact)."""
    S = mesh.shape[AXIS]
    n = int(n_vertices)
    if n < S:
        raise ValueError(
            f"cannot give each of {S} shards a vertex: only {n} exist"
        )
    if S * n >= 2**31 or n + 2 * src.shape[0] >= 2**31:
        raise ValueError("partition_on_mesh counts in int32: graph too large")
    split = NamedSharding(mesh, P(AXIS))
    src, dst, mask = (jax.device_put(a, split) for a in (src, dst, mask))
    if w is not None:
        w = jax.device_put(w, split)
    with span("partition"):
        with span("partition/sizes"):
            bounds, counts, below, rows, degrees = _sizes_fn(mesh, n)(
                src, dst, mask)
            sizes = _sizes(*jax.device_get((bounds, counts, below)),
                           bits=size_bits)
        edges = {"src": src, "dst": dst}
        with span("partition/route"):
            weighted = w is not None
            length = sorted_length(max(S * sizes.route_cap, sizes.e_max))
            route_fn = _route_fn(mesh, n, sizes.route_cap, length, weighted)
            finish_fn = _finish_fn(mesh, n, sizes.e_max, sizes.v_max,
                                   weighted)
            blocks = []
            for (key, other), degree in zip(_ORDERINGS, degrees):
                recv = route_fn(edges[key], edges[other], mask, bounds,
                                *((w,) if weighted else ()))
                key_s, other_s, *w_s = sort_blocks(mesh, *recv)
                blocks.append(finish_fn(key_s, other_s, bounds, degree,
                                        *w_s))
            jax.block_until_ready(blocks)
        with span("partition/halo"):
            halos = []
            for o in range(2):
                _, nbr_g, _, emask, _, _ = blocks[o]
                fn = _halo_fn(mesh, n, sizes.v_max, sizes.n_ghost[o],
                              sizes.pair_cap[o])
                halos.append(fn(nbr_g, emask, rows[o], bounds))
            jax.block_until_ready(halos)
    ((dst_l, src_g, w_p, m_p, in_ends, vmask),
     (tsrc_l, tdst_g, tw_p, tm_p, out_ends, _)) = blocks

    def spec(halo, o):
        _, ghost_ids, send_local, recv_pos = halo
        return HaloSpec(ghost_ids=ghost_ids, send_local=send_local,
                        recv_pos=recv_pos, n_ghost=sizes.n_ghost[o],
                        pair_cap=sizes.pair_cap[o])

    return PartitionedGraph(
        starts=bounds, vmask=vmask,
        src_g=src_g, src_h=halos[0][0], dst_l=dst_l, w=w_p, emask=m_p,
        t_dst_g=tdst_g, t_dst_h=halos[1][0], t_src_l=tsrc_l, t_w=tw_p,
        t_emask=tm_p, in_ends=in_ends, out_ends=out_ends,
        halo_in=spec(halos[0], 0), halo_out=spec(halos[1], 1),
        n_vertices=n, n_edges=sizes.n_edges, n_shards=S,
        v_max=sizes.v_max, e_max=sizes.e_max,
    )

