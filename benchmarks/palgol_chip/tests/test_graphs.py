"""The device generator against a plain host build of the same edges."""

import jax
import numpy as np
import pytest

from graphs import kronecker
from conftest import SCALE, SEED

CFG = dict(scale=SCALE, edgefactor=16, a=0.57, b=0.19, c=0.19,
           permute_vertices=True, weights="uniform_0_1")


@pytest.fixture(scope="module")
def graph():
    return kronecker.build_graph(SEED, CFG)


def host_graph(seed, permute):
    """The graph the generator's raw edges make, built with numpy: self-loops
    dropped, both directions stored, each pair once at its lightest."""
    k_edges, k_w = jax.random.split(kronecker.seed_key(seed))
    u, v = kronecker.kronecker_edges(k_edges, SCALE, 16, 0.57, 0.19, 0.19,
                                     permute)
    w = np.asarray(jax.random.uniform(k_w, u.shape, np.float32))
    u, v = np.asarray(u), np.asarray(v)
    keep = u != v
    best = {}
    for a, b, x in zip(np.r_[u[keep], v[keep]], np.r_[v[keep], u[keep]],
                       np.r_[w[keep], w[keep]]):
        best[(b, a)] = min(best.get((b, a), np.inf), x)
    return best


def live(graph):
    m = np.asarray(graph.edge_mask)
    return (np.asarray(graph.src)[m], np.asarray(graph.dst)[m],
            np.asarray(graph.weight)[m])


def test_slots_padding_and_order(graph):
    n = 1 << SCALE
    assert graph.n_vertices == n
    assert graph.n_edges == 2 * 16 * n
    m = np.asarray(graph.edge_mask)
    k = int(m.sum())
    assert m[:k].all() and not m[k:].any()
    assert (np.asarray(graph.src)[k:] == n).all()
    assert (np.asarray(graph.dst)[k:] == n).all()
    src, dst, _ = live(graph)
    key = dst.astype(np.int64) * n + src
    assert (np.diff(key) > 0).all()  # sorted by (dst, src), no repeats
    assert (src != dst).all()


def test_edges_are_the_symmetric_lightest_pairs(graph):
    src, dst, w = live(graph)
    got = {(int(b), int(a)): float(x) for a, b, x in zip(src, dst, w)}
    want = host_graph(SEED, True)
    assert got == {(int(b), int(a)): float(x) for (b, a), x in want.items()}
    assert all(got[(a, b)] == got[(b, a)] for (a, b) in got)


def test_transposed_ordering_is_its_own_arrays(graph):
    for t, d in (("t_src", "dst"), ("t_dst", "src"), ("t_weight", "weight"),
                 ("t_mask", "edge_mask")):
        np.testing.assert_array_equal(np.asarray(getattr(graph, t)),
                                      np.asarray(getattr(graph, d)))


def test_ids_are_permuted():
    def degrees(permute):
        g = kronecker.build_graph(SEED, dict(CFG, permute_vertices=permute))
        _, dst, _ = live(g)
        return np.bincount(dst, minlength=g.n_vertices)

    plain, permuted = degrees(False), degrees(True)
    # the same degrees, on other ids: unpermuted, the hubs sit at low ids
    assert sorted(plain) == sorted(permuted)
    assert plain.argmax() == 0 and permuted.argmax() != 0


def test_seeds_differ_in_their_high_word():
    a = kronecker.build_graph(5, CFG)
    b = kronecker.build_graph(5 + (1 << 32), CFG)
    assert not np.array_equal(np.asarray(a.src), np.asarray(b.src))
