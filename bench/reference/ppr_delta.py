"""Plain personalised PageRank: a float64 power iteration, one source per
host thread (scipy's sparse product releases the GIL).

  r <- (1 - d) e_s + d W r,   W[u, v] = 1 / deg(v) for each edge v -> u

from r = e_s until no entry's L1 change reaches `STOP`; what remains of the
error is then under STOP * d / (1 - d), 6e-9 at d = 0.85.

The program (`ppr_delta`, residual push) stops once every residual r_u is
within tol·deg(u) of zero. Its rank then falls short of the exact PPR at x
by sum_u r_u ppr_u(x), and on an undirected graph deg(u) ppr_u(x) =
deg(x) ppr_x(u), so that sum is at most tol·deg(x) (Andersen, Chung, Lang,
FOCS 2006). A cold run's residuals are non-negative, so per vertex

  0 <= ppr(x) - rank(x) <= tol·deg(x).

Both sides are compared in units of tol·deg(x): `ppr_gap_ratio` (the
configuration's limit is 1) and `ppr_excess_ratio` (the limit is 0, less
the rounding both sides carry). The control (`dtype=bfloat16`) rounds the
iterate to bfloat16 after each round.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.reference.common import rounded

STOP = 1e-9
MAX_ITERS = 1000


def power(walk, source: int, damping: float, dtype=None) -> np.ndarray:
    pref = np.zeros(walk.shape[0])
    pref[source] = 1.0
    r = pref.copy()
    for _ in range(MAX_ITERS):
        nxt = rounded((1 - damping) * pref + damping * (walk @ r), dtype)
        change = np.abs(nxt - r).sum()
        r = nxt
        if change < STOP:
            break
    return r


def solve(mats, sources, params, dtype=None) -> np.ndarray:
    """(k, n) float64 PPR vectors."""
    walk, damping = mats[1], float(params["damping"])
    with ThreadPoolExecutor(max(1, min(os.cpu_count() or 1,
                                       len(sources)))) as pool:
        rows = list(pool.map(lambda s: power(walk, s, damping, dtype),
                             sources))
    return np.stack(rows) if rows else np.zeros((0, walk.shape[0]))


def compare(got, want, ctx) -> dict:
    """Worst gap on each side of the residual bound, in units of tol·deg."""
    bound = ctx["params"]["tol"] * np.maximum(ctx["deg"], 1)
    ratio = (want - np.asarray(got, np.float64)) / bound
    return {"ppr_gap_ratio": float(ratio.max()),
            "ppr_excess_ratio": float(-ratio.min())}
