"""Plain reference for connectivity: each vertex's component, labelled
with the lowest vertex id in it (what S-V and HashMin WCC compute), by
``scipy.sparse.csgraph.connected_components``.

Compared number: ``label_mismatches``, the count of vertices whose label
differs from the reference's. Labels are exact integers: its limit is 0.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph


def adjacency(edges: dict, data=None) -> sp.csr_matrix:
    """CSR matrix of the live edges, one row per destination (they are
    sorted by destination, so no sort is needed)."""
    n, src, dst = edges["n"], edges["src"], edges["dst"]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=n), out=indptr[1:])
    if data is None:
        data = np.ones(src.size, np.int8)
    return sp.csr_matrix((data, src, indptr), shape=(n, n))


def _components(edges: dict) -> np.ndarray:
    _, labels = csgraph.connected_components(
        adjacency(edges), directed=False
    )
    return labels


def reference(edges: dict, job: dict) -> np.ndarray:
    labels = _components(edges)
    # vertices scan in id order, so each label's first index is its lowest id
    _, first = np.unique(labels, return_index=True)
    return first[labels].astype(np.int32)


def control(edges: dict, job: dict) -> np.ndarray:
    """The reference with vertex ids held in int16, the integer type below
    the configuration's int32 ids."""
    labels = _components(edges)
    ids = np.arange(edges["n"]).astype(np.int16)
    low = np.full(labels.max() + 1, np.iinfo(np.int16).max, np.int16)
    np.minimum.at(low, labels, ids)
    return low[labels].astype(np.int32)


def compare(got: np.ndarray, ref: np.ndarray) -> dict:
    if got.shape != ref.shape:
        return {"label_mismatches": int(ref.size)}
    return {"label_mismatches": int(np.count_nonzero(got != ref))}
