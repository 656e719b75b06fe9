"""Plain single-source shortest paths: scipy's Dijkstra in float64.

The program sums float32 weights, so its distances sit within float32
rounding of these; they are compared by the largest relative gap over all
vertices, and a vertex reached by one side only counts 1. The control
(`dtype=bfloat16`) runs on weights and returns distances rounded to
bfloat16.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csgraph

from bench.reference.common import rounded

#: the program marks an unreached vertex with a huge finite value
UNREACHED = 1e30


def solve(mats, sources, params, dtype=None) -> np.ndarray:
    """(k, n) float64 distances, inf where unreached."""
    del params
    adj = mats[0]
    if dtype is not None:
        adj = adj.copy()
        adj.data = rounded(adj.data, dtype)
    return rounded(csgraph.dijkstra(adj, indices=list(sources)), dtype)


def as_distances(result) -> np.ndarray:
    got = np.asarray(result, np.float64).copy()
    got[got > UNREACHED] = np.inf
    return got


def compare(got, want, ctx) -> dict:
    """{'sssp_rel_err': largest |got - want| / want over every vertex}."""
    del ctx
    got = as_distances(got)
    both = np.isfinite(got) & np.isfinite(want)
    one = np.isfinite(got) != np.isfinite(want)
    rel = np.abs(got[both] - want[both]) / np.maximum(want[both], 1e-30)
    worst = float(rel.max()) if rel.size else 0.0
    return {"sssp_rel_err": 1.0 if one.any() else worst}
