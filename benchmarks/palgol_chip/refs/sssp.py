"""Plain reference for single-source shortest paths: distances from the
job's root along the live edges, by ``scipy.sparse.csgraph.dijkstra`` in
float64 over the float32 weights the graph holds.

Compared numbers:

* ``reach_mismatches``: vertices reached by one side and not the other;
  exact, limit 0;
* ``dist_rel_gap``: the largest ``|got - ref| / ref`` over vertices both
  reach (0 where both are 0). The program sums float32 weights; its limit
  lies between the program's readings and the control's.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
from scipy.sparse import csgraph

from refs.components import adjacency


def _dijkstra(edges: dict, root: int, weights) -> np.ndarray:
    adj = adjacency(edges, weights.astype(np.float64))
    # rows are destinations; the edge set is symmetric, so the matrix is
    # its own transpose and rows may stand for sources
    return csgraph.dijkstra(adj, directed=True, indices=root)


def reference(edges: dict, job: dict) -> np.ndarray:
    return _dijkstra(edges, job["root"], edges["weight"])


def control(edges: dict, job: dict) -> np.ndarray:
    """The reference in bfloat16, the type below the configuration's
    float32: weights rounded to bfloat16, distances rounded to bfloat16."""
    w = edges["weight"].astype(ml_dtypes.bfloat16).astype(np.float64)
    dist = _dijkstra(edges, job["root"], w)
    return dist.astype(ml_dtypes.bfloat16).astype(np.float64)


def compare(got: np.ndarray, ref: np.ndarray) -> dict:
    got = np.asarray(got, np.float64)
    if got.shape != ref.shape:
        return {"reach_mismatches": int(ref.size), "dist_rel_gap": np.inf}
    reach = np.isfinite(ref)
    both = reach & np.isfinite(got)
    diff = np.abs(got[both] - ref[both])
    rel = np.where(diff == 0, 0.0, diff / np.maximum(ref[both], 1e-300))
    return {
        "reach_mismatches": int(np.count_nonzero(np.isfinite(got) != reach)),
        "dist_rel_gap": float(rel.max(initial=0.0)),
    }
