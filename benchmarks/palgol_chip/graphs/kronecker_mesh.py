"""Graph500 Kronecker graphs drawn, deduplicated and partitioned on a mesh
of chips, never whole on one chip or on the host.

Each of the first ``config["shards"]`` devices draws its share of the
Graph500 edge list from the seed (``kronecker.kronecker_edges`` with
``edgefactor / shards`` edges per vertex, so the shares together have the
count and distribution of one draw, though not one draw's list) and
relabels the ids by one pseudorandom permutation that every chip draws
alike: a keyed four-round Feistel network on the id bits (:func:`permute`),
computed id by id, where ``kronecker.py``'s ``jax.random.permutation``
sorts, which takes over a minute to compile at scale 24. Each chip then
drops self-loops and stores each edge in both directions. Copies of a pair
meet on one chip (edges are routed by ``dst mod shards`` with ``repro``'s
:func:`~repro.graph.partition.route`), where one copy is kept (the
lightest, where edges are weighted), as ``kronecker.build_arrays`` does on
one chip; the sort goes through ``repro``'s
:func:`~repro.graph.partition.sort_blocks`, whose executable the
constructor's own sorts then reuse. The edges then go to the program's device constructor,
:func:`repro.graph.partition.partition_on_mesh`. Every static size is
rounded up to :data:`SIZE_BITS` significant bits, so that graphs of one
configuration drawn from other seeds mostly share their shapes, and the
executables compiled for them.

:func:`build_graph` returns a :class:`MeshGraph`: the ``PartitionedGraph``
and its mesh, read by ``placements/partitioned.py``, and what the harness
reads of a graph. Its ``edge_mask`` is the live edge count of each shard;
its ``src``, ``dst`` and ``weight`` are the live edges on the host in
``(dst, src)`` order, copied from the chips on first use, which the
harness makes after the window.
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro.graph.partition import (
    partition_on_mesh, route, sort_blocks, sorted_length,
)
from repro.graph.partition.on_mesh import round_up

import harness

kronecker = harness.load_module(Path(__file__).with_name("kronecker.py"))

AXIS = "shard"
#: significant bits kept of each static size (rounded up)
SIZE_BITS = 6
#: rounds of the Feistel network that permutes the ids
ROUNDS = 4


def _mix(x):
    """A 32-bit integer hash (multiply-xorshift; uint32 arithmetic wraps)."""
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def permute(ids, key, scale: int):
    """A pseudorandom bijection of ``[0, 2**scale)`` drawn from ``key``: a
    balanced Feistel network on ``scale`` bits rounded up to even, with
    cycle walking back into range where ``scale`` is odd."""
    half = (scale + 1) // 2
    low = jnp.uint32((1 << half) - 1)
    round_keys = jax.random.bits(key, (ROUNDS,), jnp.uint32)

    def feistel(x):
        left, right = x >> half, x & low
        for k in range(ROUNDS):
            left, right = right, left ^ (_mix(right ^ round_keys[k]) & low)
        return (left << half) | right

    x = feistel(ids.astype(jnp.uint32))
    if 2 * half != scale:
        n = jnp.uint32(1 << scale)
        x = jax.lax.while_loop(
            lambda x: jnp.any(x >= n),
            lambda x: jnp.where(x >= n, feistel(x), x), x)
    return x.astype(jnp.int32)


def _draw(key, *, n_shards, scale, edgefactor, a, b, c, relabel, weighted):
    """This chip's share, symmetrised: ``(src, dst[, w])`` with self-loops
    as ``(n, n)``, and how many live edges go to each chip (by
    ``dst mod n_shards``)."""
    n = 1 << scale
    me = jax.lax.axis_index(AXIS)
    k_edges, k_w = jax.random.split(key)
    u, v = kronecker.kronecker_edges(
        jax.random.fold_in(k_edges, me), scale, edgefactor // n_shards,
        a, b, c, permute=False,
    )
    if relabel:
        k_perm = jax.random.split(k_edges)[1]
        u, v = permute(u, k_perm, scale), permute(v, k_perm, scale)
    loop = u == v
    u = jnp.where(loop, n, u)
    v = jnp.where(loop, n, v)
    src = jnp.concatenate([u, v])
    dst = jnp.concatenate([v, u])
    dest = jnp.where(dst < n, dst % n_shards, n_shards)
    counts = jnp.stack([jnp.sum(dest == o, dtype=jnp.int32)
                        for o in range(n_shards)])
    out = (src, dst)
    if weighted:
        w = jax.random.uniform(jax.random.fold_in(k_w, me), u.shape,
                               jnp.float32)
        out += (jnp.concatenate([w, w]),)
    return out + (counts[None],)


def _gather_copies(src, dst, *w, n, n_shards, cap, length):
    """Bring the copies of each pair to one chip (by ``dst mod
    n_shards``): ``(dst, src[, w])`` blocks padded to ``length`` with
    ``(n, n[, 0])``, which sort last."""
    dest = jnp.where(dst < n, dst % n_shards, n_shards)
    fills = (n, n) + (0.0,) * len(w)
    recv = route(dest, (dst, src) + w, fills, cap, n_shards)
    return tuple(jnp.concatenate([a, jnp.full((length - a.shape[0],), f,
                                              a.dtype)])
                 for a, f in zip(recv, fills))


def _first_copies(dst, src, *w, n):
    """Of a block sorted by ``(dst, src[, w])``, the first copy of each
    pair (the lightest): ``(src, dst[, w], mask)``."""
    repeat = jnp.concatenate([
        jnp.zeros((1,), bool), (dst[1:] == dst[:-1]) & (src[1:] == src[:-1])
    ])
    return (src, dst) + w + ((dst < n) & ~repeat,)


@functools.lru_cache(maxsize=None)
def _draw_fn(mesh, **static):
    outs = 3 + static["weighted"]
    return jax.jit(jax.shard_map(
        functools.partial(_draw, n_shards=mesh.shape[AXIS], **static),
        mesh=mesh, in_specs=P(), out_specs=(P(AXIS),) * outs,
        check_vma=False,
    ))


@functools.lru_cache(maxsize=None)
def _gather_copies_fn(mesh, n, cap, length, weighted):
    k = 2 + weighted
    return jax.jit(jax.shard_map(
        functools.partial(_gather_copies, n=n, n_shards=mesh.shape[AXIS],
                          cap=cap, length=length),
        mesh=mesh, in_specs=(P(AXIS),) * k, out_specs=(P(AXIS),) * k,
        check_vma=False,
    ))


@functools.lru_cache(maxsize=None)
def _first_copies_fn(mesh, n, weighted):
    k = 2 + weighted
    return jax.jit(jax.shard_map(
        functools.partial(_first_copies, n=n), mesh=mesh,
        in_specs=(P(AXIS),) * k, out_specs=(P(AXIS),) * (k + 1),
        check_vma=False,
    ))


class MeshGraph:
    """A partitioned graph on its mesh, read by the harness like a
    ``Graph`` (see the module doc)."""

    def __init__(self, partitioned, mesh):
        self.partitioned = partitioned
        self.mesh = mesh
        self.n_vertices = partitioned.n_vertices
        #: edge slots of one ordering, over all shards
        self.n_edges = partitioned.n_shards * partitioned.e_max
        #: live edges of each shard; their sum is the live edge count
        self.edge_mask = jnp.sum(partitioned.emask, axis=1, dtype=jnp.int32)

    def block_until_ready(self):
        jax.block_until_ready((self.partitioned, self.edge_mask))
        return self

    @functools.cached_property
    def _host(self):
        pg = self.partitioned
        starts = np.asarray(pg.starts)
        live = np.asarray(pg.emask)
        dst = (np.asarray(pg.dst_l) + starts[:-1, None])[live]
        return np.asarray(pg.src_g)[live], dst, np.asarray(pg.w)[live]

    @property
    def src(self):
        return self._host[0]

    @property
    def dst(self):
        return self._host[1]

    @property
    def weight(self):
        return self._host[2]


def build_graph(seed: int, config: dict) -> MeshGraph:
    """The partitioned graph of ``config`` (a ``configs/*.json`` dict) for
    ``seed``, built on the first ``config["shards"]`` devices."""
    if config.get("directed", False):
        raise ValueError("kronecker_mesh: only undirected graphs are built")
    S = config["shards"]
    if config["edgefactor"] % S:
        raise ValueError("kronecker_mesh: shards must divide the edgefactor")
    mesh = shd.shard_mesh(S)
    n = 1 << config["scale"]
    weighted = config["weights"] == "uniform_0_1"
    *edges, counts = _draw_fn(
        mesh, scale=config["scale"], edgefactor=config["edgefactor"],
        a=config["a"], b=config["b"], c=config["c"],
        relabel=config["permute_vertices"], weighted=weighted,
    )(kronecker.seed_key(seed))
    cap = round_up(max(int(np.asarray(counts).max()), 1), SIZE_BITS)
    length = sorted_length(S * cap)
    edges = _gather_copies_fn(mesh, n, cap, length, weighted)(*edges)
    edges = sort_blocks(mesh, *edges)
    src, dst, *w, mask = _first_copies_fn(mesh, n, weighted)(*edges)
    del edges
    pg = partition_on_mesh(src, dst, w[0] if w else None, mask, n, mesh,
                           size_bits=SIZE_BITS)
    graph = MeshGraph(pg, mesh)
    print(f"kronecker_mesh: bounds {np.asarray(pg.starts).tolist()}, "
          f"live edges per shard {np.asarray(graph.edge_mask).tolist()}, "
          f"e_max {pg.e_max}, v_max {pg.v_max}, n_ghost "
          f"{pg.halo_in.n_ghost}/{pg.halo_out.n_ghost}, pair_cap "
          f"{pg.halo_in.pair_cap}/{pg.halo_out.pair_cap}, dedup cap {cap}",
          file=sys.stderr, flush=True)
    return graph
