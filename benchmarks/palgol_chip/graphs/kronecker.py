"""Graph500 Kronecker graphs built on the device from a seed.

The generator follows the Graph500 reference (``kronecker_generator.m`` of
the specification): ``edgefactor * 2**scale`` edges, one initiator
quadrant per bit level with probabilities A, B, C and 1-A-B-C, then a
random relabelling of the vertex ids. Graph500 keeps self-loops and
repeated edges in its edge list; the graph a job runs on drops
self-loops, stores each undirected edge in both directions and keeps one
copy of each directed pair (the lightest, where edges are weighted), as
LDBC Graphalytics' ``graph500-*`` datasets do.

Everything runs in one jitted call on the device: the edge list is
sorted into the destination order ``repro.graph.structure.Graph`` holds
and padded to a static slot count with the sentinel id ``n_vertices`` and
``edge_mask = False``. :func:`build_graph` is the one place that builds a
``Graph`` from its dataclass fields, so the benchmark depends on those
fields here and nowhere else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps all 64 bits of ``seed`` (``jax.random.key``
    alone drops the high word of a Python int)."""
    seed %= 1 << 64
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def kronecker_edges(key, scale: int, edgefactor: int, a: float, b: float,
                    c: float, permute: bool):
    """Graph500 edge list ``(u, v)``, int32[edgefactor * 2**scale] each."""
    m = edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    k_bits, k_perm = jax.random.split(key)

    def level(i, carry):
        u, v = carry
        k1, k2 = jax.random.split(jax.random.fold_in(k_bits, i))
        u_bit = jax.random.uniform(k1, (m,)) > ab
        v_bit = jax.random.uniform(k2, (m,)) > jnp.where(u_bit, c_norm, a_norm)
        u = u | (u_bit.astype(jnp.int32) << i)
        v = v | (v_bit.astype(jnp.int32) << i)
        return u, v

    zeros = jnp.zeros((m,), jnp.int32)
    u, v = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    if permute:
        perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
        u, v = perm[u], perm[v]
    return u, v


@functools.partial(
    jax.jit,
    static_argnames=("scale", "edgefactor", "a", "b", "c", "permute",
                     "weighted"),
)
def build_arrays(key, *, scale, edgefactor, a, b, c, permute, weighted):
    """The eight edge arrays of ``Graph``: ``(src, dst, weight, mask)`` of
    the symmetrised, deduplicated graph sorted by ``(dst, src)``, live
    edges first, in ``2 * edgefactor * 2**scale`` slots; then the same for
    the source-sorted ordering. Weights are uniform in [0, 1) where
    ``weighted``, else 1.

    For a symmetric edge set the source-sorted ordering is the same slots
    with the two id arrays swapped; each ordering is returned as arrays of
    its own, as ``from_edge_list`` builds it."""
    n = 1 << scale
    k_edges, k_w = jax.random.split(key)
    u, v = kronecker_edges(k_edges, scale, edgefactor, a, b, c, permute)
    if weighted:
        w = jax.random.uniform(k_w, u.shape, jnp.float32)
    else:
        w = jnp.ones(u.shape, jnp.float32)
    loop = u == v
    u = jnp.where(loop, n, u)
    v = jnp.where(loop, n, v)
    src = jnp.concatenate([u, v])
    dst = jnp.concatenate([v, u])
    w = jnp.concatenate([w, w])
    # (dst, src, weight) order: the first copy of a pair is its lightest
    dst, src, w = jax.lax.sort((dst, src, w), num_keys=3)
    repeat = jnp.concatenate([
        jnp.zeros((1,), bool), (dst[1:] == dst[:-1]) & (src[1:] == src[:-1])
    ])
    drop = repeat | (dst == n)
    src = jnp.where(drop, n, src)
    dst = jnp.where(drop, n, dst)
    w = jnp.where(drop, 0.0, w)
    # dropped slots carry (n, n, 0) and sort after every live edge
    dst, src, w = jax.lax.sort((dst, src, w), num_keys=2)
    mask = dst < n
    return src, dst, w, mask, dst, src, w, mask


def build_graph(seed: int, config: dict):
    """The ``repro`` ``Graph`` of ``config`` (a ``configs/*.json`` dict)
    for ``seed``, built on the default device."""
    from repro.graph.structure import Graph

    if config.get("directed", False):
        raise ValueError("kronecker: only undirected graphs are built")
    arrays = build_arrays(
        seed_key(seed), scale=config["scale"],
        edgefactor=config["edgefactor"], a=config["a"], b=config["b"],
        c=config["c"], permute=config["permute_vertices"],
        weighted=config["weights"] == "uniform_0_1",
    )
    names = ("src", "dst", "weight", "edge_mask",
             "t_src", "t_dst", "t_weight", "t_mask")
    return Graph(
        **dict(zip(names, arrays)),
        n_vertices=1 << config["scale"],
        n_edges=int(arrays[0].shape[0]),
    )
