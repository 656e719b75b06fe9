"""Plain breadth-first search: scipy's unweighted shortest paths (levels).

Levels are whole numbers, so the comparison is exact: the number of
vertices whose level differs (unreached on both sides agree).
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csgraph

from bench.reference.common import rounded
from bench.reference.sssp import as_distances


def solve(mats, sources, params, dtype=None) -> np.ndarray:
    """(k, n) float64 levels, inf where unreached."""
    del params
    return rounded(csgraph.dijkstra(mats[0], indices=list(sources),
                                    unweighted=True), dtype)


def compare(got, want, ctx) -> dict:
    del ctx
    return {"bfs_wrong_vertices": int(np.sum(as_distances(got) != want))}
