"""Shared plumbing of the plain references: the benchmark's edge list as
host sparse matrices, and rounding to the control's precision.

The references read only the benchmark's own edge list (`bench.gen`), never
the program's `Graph`, and import nothing of the program. They are the
scipy/numpy references of `chip_smoke.py`, copied.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

import ml_dtypes


def matrices(edges):
    """(weighted adjacency, walk matrix) of the symmetric edge list, in
    float64: the list has one entry per vertex pair and direction, so no
    entry is a sum of parallel edges; the walk matrix carries 1/deg(v) on
    each edge v -> u, as PPR spreads mass."""
    n = edges.n
    adj = sp.csr_matrix((edges.w.astype(np.float64), (edges.src, edges.dst)),
                        shape=(n, n))
    deg = np.maximum(np.bincount(edges.src, minlength=n), 1)
    walk = sp.csr_matrix((1.0 / deg[edges.src], (edges.dst, edges.src)),
                         shape=(n, n))
    return adj, walk


def rounded(x, dtype):
    """`x` rounded to `dtype` and back to float64 (identity for float64)."""
    if dtype is None or np.dtype(dtype) == np.float64:
        return x
    return np.asarray(x, np.float64).astype(dtype).astype(np.float64)


BFLOAT16 = ml_dtypes.bfloat16
