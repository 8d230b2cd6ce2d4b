"""Dense, statically-shaped graph representation.

Pregel stores per-vertex adjacency lists; on TPU we use a struct-of-arrays
sorted-COO layout (``src``, ``dst``, ``weight``) padded to a static edge count,
plus an explicit validity mask. Edges are stored sorted by ``dst`` so that
"receive messages along incoming edges" is a sorted segment reduction (the
MXU-friendly hot path); the transpose ordering (sorted by ``src``) is
maintained lazily for algorithms that push along outgoing edges.

Conventions
-----------
* An edge ``(src[i], dst[i])`` means: ``dst[i]`` can *pull* data from
  ``src[i]`` (i.e. ``src[i]`` is an in-neighbor of ``dst[i]``). For Palgol's
  ``In[v]`` the neighbor id ``e.id`` is ``src``; for ``Out[v]`` we use the
  transposed arrays; for undirected ``Nbr[v]`` the edge list must be
  symmetric (see :func:`symmetrize`) and ``In``/``Out`` coincide.
* Padding edges carry ``src = dst = n_vertices`` (an out-of-range sentinel)
  and ``mask = False``. All consumers either segment-reduce with explicit
  ``num_segments=n_vertices`` (sentinel rows are dropped by scatter's
  ``mode="drop"``) or mask messages to the combiner identity first.
* ``in_ends``/``out_ends`` give each vertex's run of slots in the ``dst``
  and ``t_src`` orderings: ``ends[v]`` is one past its last slot, so its
  run is ``ends[v-1]:ends[v]``. They let an order-independent reduction
  scan the sorted slots instead of scattering
  (:func:`repro.graph.ops.sorted_segment_reduce`). ``None`` where not
  computed: :func:`with_segment_ends` fills them.
* ``weights_bounded`` is a static fact about the live weights of both
  orderings: ``(every one is > -inf, every one is < +inf)`` (a NaN is
  neither). A minimum of ``x + e.w`` may fold a filter into ``x`` as
  ``+inf`` only where no live ``e.w`` is ``-inf`` or NaN, so that
  ``inf + e.w`` is ``inf`` (``repro.core.codegen``). ``None`` where not
  computed: :func:`with_weight_bounds` fills it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Static-shape graph. ``n_vertices``/``n_edges`` are trace-static."""

    # --- data (pytree leaves) ---
    src: jax.Array  # i32[E]  edge source, sorted by dst
    dst: jax.Array  # i32[E]  edge destination (ascending)
    weight: jax.Array  # f32[E] edge weight (1.0 if unweighted)
    edge_mask: jax.Array  # bool[E] False on padding rows
    # transpose ordering (sorted by src) for push-style traversal
    t_src: jax.Array  # i32[E]
    t_dst: jax.Array  # i32[E]
    t_weight: jax.Array  # f32[E]
    t_mask: jax.Array  # bool[E]

    # --- static metadata ---
    n_vertices: int = dataclasses.field(metadata=dict(static=True))
    n_edges: int = dataclasses.field(metadata=dict(static=True))

    # run ends (module doc), i32[n_vertices] each, or None
    in_ends: Optional[jax.Array] = None  # in the dst ordering
    out_ends: Optional[jax.Array] = None  # in the t_src ordering

    # the live weights' bounds (module doc), or None
    weights_bounded: Optional[Tuple[bool, bool]] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )

    @property
    def sentinel(self) -> int:
        return self.n_vertices

    def segment_ends(self, direction: str) -> Optional[jax.Array]:
        """The run ends of the ordering :meth:`edges` gives for
        ``direction``, or ``None`` where they are not computed."""
        return self.out_ends if direction == "out" else self.in_ends

    def in_edges(self):
        """(neighbor_id, self_id, weight, mask) for pull-along-In traversal."""
        return self.src, self.dst, self.weight, self.edge_mask

    def out_edges(self):
        """(neighbor_id, self_id, weight, mask) for traversal of Out[v].

        For ``Out[v]`` the "current vertex" is the edge *source*; the
        neighbor (``e.id``) is the destination. We return the transposed
        arrays so the segment key (second element) is sorted.
        """
        return self.t_dst, self.t_src, self.t_weight, self.t_mask

    def edges(self, direction: str):
        if direction in ("in", "nbr"):
            return self.in_edges()
        if direction == "out":
            return self.out_edges()
        raise ValueError(f"unknown edge direction {direction!r}")


@functools.partial(jax.jit, static_argnums=1)
def _run_ends(sorted_ids: jax.Array, n_vertices: int) -> jax.Array:
    """``searchsorted(sorted_ids, arange(n_vertices), side="right")``
    without a search per vertex: one sort brings the slots where a run
    ends to the front, in order, each writes one past itself at its
    vertex, and a vertex with no slot takes the end of the vertex before."""
    e = sorted_ids.shape[0]
    if e == 0:
        return jnp.zeros((n_vertices,), jnp.int32)
    last = jnp.concatenate(
        [sorted_ids[1:] != sorted_ids[:-1], jnp.ones((1,), jnp.bool_)]
    )
    last = jnp.logical_and(last, sorted_ids < n_vertices)
    # at most n_vertices runs end; every other slot sorts after them as e
    at = jnp.sort(jnp.where(last, jnp.arange(e, dtype=jnp.int32), e))
    at = at[:n_vertices]
    vertex = jnp.where(at < e, jnp.take(sorted_ids, at, mode="clip"), n_vertices)
    ends = jnp.zeros((n_vertices,), jnp.int32).at[vertex].set(
        at + 1, mode="drop"
    )
    return jax.lax.cummax(ends)


def with_segment_ends(graph: Graph, directions) -> Graph:
    """``graph`` with the run ends of every ordering that an edge list of
    ``directions`` (``"in"``, ``"nbr"``, ``"out"``) reads, computed on the
    graph's device where they are missing."""
    ends = {}
    if graph.in_ends is None and {"in", "nbr"} & set(directions):
        ends["in_ends"] = _run_ends(graph.dst, graph.n_vertices)
    if graph.out_ends is None and "out" in directions:
        ends["out_ends"] = _run_ends(graph.t_src, graph.n_vertices)
    return dataclasses.replace(graph, **ends) if ends else graph


@jax.jit
def _weight_bounds(weight, mask, t_weight, t_mask):
    def live(w, m, ok):
        return jnp.all(jnp.where(m, ok(w), True))

    return tuple(
        jnp.logical_and(live(weight, mask, ok), live(t_weight, t_mask, ok))
        for ok in (lambda w: w > -jnp.inf, lambda w: w < jnp.inf)
    )


def with_weight_bounds(graph: Graph) -> Graph:
    """``graph`` with :attr:`Graph.weights_bounded`, computed on the
    graph's device and read back once where it is missing."""
    if graph.weights_bounded is not None:
        return graph
    below, above = jax.device_get(_weight_bounds(
        graph.weight, graph.edge_mask, graph.t_weight, graph.t_mask
    ))
    return dataclasses.replace(
        graph, weights_bounded=(bool(below), bool(above))
    )


def stable_argsort(key) -> np.ndarray:
    """``np.argsort(key, kind="stable")`` for integer keys, several times
    faster on large edge lists.

    Each pass value-sorts ``(digit << 32) | position`` composites: they are
    unique, so any sort order is the stable one, and numpy's SIMD value
    sort beats its stable argsort. One pass per 31-bit key digit, least
    significant first (LSD radix order)."""
    key = np.asarray(key, np.int64)
    if key.size >= 1 << 32:
        return np.argsort(key, kind="stable")
    if key.size:
        key = key - key.min()
    pos = np.arange(key.size, dtype=np.int64)
    bits = max(int(key.max(initial=0)).bit_length(), 1)
    order = None
    for shift in range(0, bits, 31):
        k = key if order is None else key[order]
        digit = (k >> shift) & ((1 << 31) - 1)
        o = np.sort((digit << 32) | pos) & 0xFFFFFFFF
        order = o if order is None else order[o]
    return order


def _sort_by(key: np.ndarray, *arrays: np.ndarray):
    order = stable_argsort(key)
    return tuple(a[order] for a in arrays)


def from_edge_list(
    src,
    dst,
    n_vertices: int,
    weight=None,
    pad_to: Optional[int] = None,
) -> Graph:
    """Build a :class:`Graph` from host-side edge arrays.

    This runs on host (numpy) at graph-construction time; the result is a
    pytree of device arrays. ``pad_to`` rounds the edge count up to a static
    size (useful to keep recompilation away when streaming graphs).
    """
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    if weight is None:
        weight = np.ones(src.shape, dtype=np.float32)
    weight = np.asarray(weight, dtype=np.float32)
    if src.shape != dst.shape or src.shape != weight.shape:
        raise ValueError("src/dst/weight must have identical shapes")
    if src.ndim != 1:
        raise ValueError("edge arrays must be rank-1")
    e = src.shape[0]
    n_edges = int(pad_to) if pad_to is not None else e
    if n_edges < e:
        raise ValueError(f"pad_to={pad_to} smaller than edge count {e}")

    sentinel = n_vertices
    pad = n_edges - e
    src_p = np.concatenate([src, np.full((pad,), sentinel, np.int32)])
    dst_p = np.concatenate([dst, np.full((pad,), sentinel, np.int32)])
    w_p = np.concatenate([weight, np.zeros((pad,), np.float32)])
    mask_p = np.concatenate([np.ones((e,), bool), np.zeros((pad,), bool)])

    # pull ordering: sorted by dst
    dst_s, src_s, w_s, m_s = _sort_by(dst_p, dst_p, src_p, w_p, mask_p)
    # push ordering: sorted by src
    tsrc_s, tdst_s, tw_s, tm_s = _sort_by(src_p, src_p, dst_p, w_p, mask_p)
    vertices = np.arange(n_vertices)

    return Graph(
        src=jnp.asarray(src_s),
        dst=jnp.asarray(dst_s),
        weight=jnp.asarray(w_s),
        edge_mask=jnp.asarray(m_s),
        t_src=jnp.asarray(tsrc_s),
        t_dst=jnp.asarray(tdst_s),
        t_weight=jnp.asarray(tw_s),
        t_mask=jnp.asarray(tm_s),
        n_vertices=int(n_vertices),
        n_edges=int(n_edges),
        in_ends=jnp.asarray(
            np.searchsorted(dst_s, vertices, side="right").astype(np.int32)
        ),
        out_ends=jnp.asarray(
            np.searchsorted(tsrc_s, vertices, side="right").astype(np.int32)
        ),
    )


def symmetrize(src, dst, weight=None):
    """Host-side: return the symmetric closure of an edge list (deduplicated).

    Palgol's ``Nbr`` field assumes every undirected edge is stored on both
    endpoints; the compiler relies on this symmetry (paper §3.2).
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if weight is None:
        weight = np.ones(src.shape, dtype=np.float32)
    weight = np.asarray(weight, dtype=np.float32)
    a = np.concatenate([src, dst])
    b = np.concatenate([dst, src])
    w = np.concatenate([weight, weight])
    # dedup parallel edges, keep first weight
    key = a * (max(int(b.max(initial=0)) + 1, 1)) + b
    order = stable_argsort(key)
    ks = key[order]
    first = np.ones(ks.shape, bool)
    np.not_equal(ks[1:], ks[:-1], out=first[1:])
    idx = np.sort(order[first])
    return a[idx].astype(np.int32), b[idx].astype(np.int32), w[idx]


def pad_edges(graph: Graph, n_edges: int) -> Graph:
    """Re-pad a graph to a larger static edge count (host-side)."""
    if n_edges < graph.n_edges:
        raise ValueError("cannot shrink edge array")
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    w = np.asarray(graph.weight)
    m = np.asarray(graph.edge_mask)
    keep = m
    return from_edge_list(
        src[keep], dst[keep], graph.n_vertices, w[keep], pad_to=n_edges
    )
