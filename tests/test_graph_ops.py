"""Property tests for the graph substrate (hypothesis)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.graph import generators as G
from repro.graph import ops as gops
from repro.graph.structure import from_edge_list, with_segment_ends


@st.composite
def small_graph(draw):
    n = draw(st.integers(2, 24))
    m = draw(st.integers(0, 60))
    src = draw(
        st.lists(st.integers(0, n - 1), min_size=m, max_size=m)
    )
    dst = draw(
        st.lists(st.integers(0, n - 1), min_size=m, max_size=m)
    )
    pad = draw(st.integers(0, 8))
    return from_edge_list(
        np.array(src, np.int32),
        np.array(dst, np.int32),
        n,
        pad_to=m + pad,
    )


@settings(max_examples=40, deadline=None)
@given(small_graph(), st.integers(0, 2**31 - 1))
def test_segment_sum_matches_numpy(g, seed):
    rng = np.random.default_rng(seed)
    vals = rng.normal(size=g.n_edges).astype(np.float32)
    out = gops.segment_reduce(
        jnp.asarray(vals), g.dst, g.n_vertices, "sum",
        indices_are_sorted=True, mask=g.edge_mask,
    )
    expect = np.zeros(g.n_vertices, np.float32)
    dst, m = np.asarray(g.dst), np.asarray(g.edge_mask)
    for i in range(g.n_edges):
        if m[i]:
            expect[dst[i]] += vals[i]
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-5)


@settings(max_examples=40, deadline=None)
@given(small_graph(), st.sampled_from(["min", "max", "or", "and"]))
def test_segment_reduce_identities_on_empty(g, op):
    """Empty segments must yield the combiner identity."""
    if op in ("or", "and"):
        vals = jnp.ones((g.n_edges,), jnp.bool_)
    else:
        vals = jnp.ones((g.n_edges,), jnp.float32)
    out = gops.segment_reduce(
        vals, g.dst, g.n_vertices, op, indices_are_sorted=True, mask=g.edge_mask
    )
    deg = np.asarray(gops.in_degrees(g))
    o = np.asarray(out)
    for v in range(g.n_vertices):
        if deg[v] == 0:
            if op == "min":
                assert o[v] == np.inf
            elif op == "max":
                assert o[v] == -np.inf
            elif op == "or":
                assert not o[v]
            else:
                assert o[v]


@settings(max_examples=40, deadline=None)
@given(small_graph(), st.integers(0, 2**31 - 1), st.sampled_from(["sum", "min", "max"]))
def test_scatter_combine_matches_loop(g, seed, op):
    rng = np.random.default_rng(seed)
    vals = rng.normal(size=g.n_edges).astype(np.float32)
    buf0 = rng.normal(size=g.n_vertices).astype(np.float32)
    out = gops.scatter_combine(
        jnp.asarray(buf0), g.dst, jnp.asarray(vals), op, mask=g.edge_mask
    )
    expect = buf0.copy()
    dst, m = np.asarray(g.dst), np.asarray(g.edge_mask)
    f = {"sum": lambda a, b: a + b, "min": min, "max": max}[op]
    for i in range(g.n_edges):
        if m[i]:
            expect[dst[i]] = f(expect[dst[i]], vals[i])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-5)


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


#: (vertices, live edges, slots of them on vertex 0, sentinel padding slots)
_LAYOUTS = {
    "under-one-row": (5, 7, 0, 2),
    "runs-cross-rows": (40, 900, 0, 50),
    # 20,000 slots on one vertex: longer than a row of 128 and than the
    # 128 x 128 slots one row of row carries spans
    "hub-past-every-block": (300, 40_000, 20_000, 77),
    "only-padding": (6, 0, 0, 200),
    "no-slots": (4, 0, 0, 0),
}
_VALUE_OPS = [
    (np.int32, "min"), (np.int32, "max"), (np.int32, "sum"),
    (np.float32, "min"), (np.float32, "max"),
    (np.bool_, "and"), (np.bool_, "or"),
]


_scatter = jax.jit(gops.segment_reduce, static_argnums=(2, 3, 4))
_scan = jax.jit(gops.sorted_segment_reduce, static_argnums=3)


def _sorted_layout(rng, n, live, hub, pad):
    """Ascending segment ids: ``live`` slots over ``n`` vertices (skewed,
    so many vertices have none), ``hub`` more of them on vertex 0, then
    ``pad`` slots of the sentinel ``n``."""
    ids = np.minimum((n * rng.random(live - hub) ** 3).astype(np.int64), n - 1)
    ids = np.sort(np.concatenate([np.zeros(hub, np.int64), ids]))
    return np.concatenate([ids, np.full(pad, n)]).astype(np.int32)


def _values(rng, dtype, size):
    if dtype == np.bool_:
        return rng.random(size) < 0.5
    if dtype == np.int32:
        return rng.integers(-(2**31), 2**31 - 1, size).astype(np.int32)
    vals = rng.normal(size=size).astype(np.float32)
    vals[rng.random(size) < 0.1] = np.inf
    vals[rng.random(size) < 0.05] = -np.inf
    return vals


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("dtype,op", _VALUE_OPS)
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_sorted_segment_reduce_is_the_scatter_bit_for_bit(layout, dtype, op,
                                                           masked):
    n, live, hub, pad = _LAYOUTS[layout]
    rng = np.random.default_rng(live * 7 + pad)
    seg = _sorted_layout(rng, n, live, hub, pad)
    ends = np.searchsorted(seg, np.arange(n), side="right").astype(np.int32)
    vals = jnp.asarray(_values(rng, dtype, seg.size))
    mask = jnp.asarray(rng.random(seg.size) < 0.7) if masked else None
    expect = _scatter(vals, jnp.asarray(seg), n, op, True, mask)
    got = _scan(vals, jnp.asarray(seg), jnp.asarray(ends), op, mask)
    assert _same_bits(got, expect)


@settings(max_examples=25, deadline=None)
@given(small_graph(), st.integers(0, 2**31 - 1), st.sampled_from(_VALUE_OPS))
def test_sorted_segment_reduce_on_graphs(g, seed, value_op):
    dtype, op = value_op
    vals = jnp.asarray(_values(np.random.default_rng(seed), dtype, g.n_edges))
    for dst, ends, mask in [(g.dst, g.in_ends, g.edge_mask),
                            (g.t_src, g.out_ends, g.t_mask)]:
        expect = _scatter(vals, dst, g.n_vertices, op, True, mask)
        assert _same_bits(_scan(vals, dst, ends, op, mask), expect)


def test_sorted_segment_reduce_keeps_trailing_dims():
    n, live, hub, pad = _LAYOUTS["hub-past-every-block"]
    rng = np.random.default_rng(11)
    seg = _sorted_layout(rng, n, live, hub, pad)
    ends = np.searchsorted(seg, np.arange(n), side="right").astype(np.int32)
    vals = jnp.asarray(rng.normal(size=(seg.size, 3)).astype(np.float32))
    mask = jnp.asarray(rng.random(seg.size) < 0.7)
    expect = _scatter(vals, jnp.asarray(seg), n, "max", True, mask)
    got = _scan(vals, jnp.asarray(seg), jnp.asarray(ends), "max", mask)
    assert _same_bits(got, expect)


def test_a_float_sum_keeps_the_scatter():
    assert gops.is_order_independent("sum", jnp.int32)
    for op in ("min", "max", "and", "or"):
        assert gops.is_order_independent(op, jnp.float32)
    for op, dtype in [("sum", jnp.float32), ("prod", jnp.float32),
                      ("prod", jnp.int32)]:
        assert not gops.is_order_independent(op, dtype)
        with pytest.raises(ValueError, match="order"):
            gops.sorted_segment_reduce(
                jnp.ones((3,), dtype), jnp.zeros((3,), jnp.int32),
                jnp.array([3], jnp.int32), op,
            )


@settings(max_examples=40, deadline=None)
@given(small_graph())
def test_from_edge_list_ends_are_searchsorted(g):
    vertices = np.arange(g.n_vertices)
    for ids, ends in [(g.dst, g.in_ends), (g.t_src, g.out_ends)]:
        expect = np.searchsorted(np.asarray(ids), vertices, side="right")
        assert _same_bits(ends, expect.astype(np.int32))


@settings(max_examples=40, deadline=None)
@given(small_graph())
def test_device_ends_are_the_host_ends(g):
    bare = dataclasses.replace(g, in_ends=None, out_ends=None)
    done = with_segment_ends(bare, {"in", "out"})
    assert _same_bits(done.in_ends, g.in_ends)
    assert _same_bits(done.out_ends, g.out_ends)


def test_with_segment_ends_fills_only_what_is_read():
    g = G.erdos_renyi(60, 4.0, directed=True, seed=5)
    bare = dataclasses.replace(g, in_ends=None, out_ends=None)
    nbr = with_segment_ends(bare, {"nbr"})
    assert _same_bits(nbr.in_ends, g.in_ends) and nbr.out_ends is None
    out = with_segment_ends(bare, {"out"})
    assert out.in_ends is None and _same_bits(out.out_ends, g.out_ends)
    both = with_segment_ends(bare, {"in", "out"})
    assert _same_bits(both.in_ends, g.in_ends)
    assert _same_bits(both.out_ends, g.out_ends)
    assert with_segment_ends(g, {"in", "out"}) is g


def test_symmetrize_produces_symmetric_graph():
    g = G.erdos_renyi(40, 4.0, directed=False, seed=2)
    src, dst, m = map(np.asarray, (g.src, g.dst, g.edge_mask))
    edges = set(zip(src[m].tolist(), dst[m].tolist()))
    assert all((d, s) in edges for s, d in edges)
