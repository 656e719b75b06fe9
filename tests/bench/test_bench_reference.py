"""The benchmark's plain references (bench/reference/) on the CPU: against
an independent level-by-level BFS and a sparse power iteration, against the
program served through GraphServer, and their bfloat16 control, which the
comparison must refuse."""

import sys
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp


ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check  # noqa: E402
from bench.gen import graph500  # noqa: E402
from bench.reference import bfs, common, ppr_delta, sssp  # noqa: E402

INIT = (0.57, 0.19, 0.19, 0.05)
PPR = {"damping": 0.85, "tol": 1e-5}
CFG = {"programs": {"bfs": {}, "sssp": {}, "ppr_delta": PPR},
       "checks": {"bfs": {"bfs_wrong_vertices": 0},
                  "sssp": {"sssp_rel_err": 1e-4},
                  "ppr_delta": {"ppr_gap_ratio": 1.0,
                                "ppr_excess_ratio": 0.01}}}


@pytest.fixture(scope="module")
def graph():
    e = graph500.generate(2**31 + 5, 10, 16, INIT, 20)
    srcs = [int(s) for s in np.nonzero(e.degrees())[0][::97][:18]]
    return e, common.matrices(e), srcs


def test_bfs_levels_and_sssp_distances_hold_on_every_edge(graph):
    e, mats, srcs = graph
    levels = bfs.solve(mats, srcs, {})
    for row, s in zip(levels, srcs):
        want = np.full(e.n, np.inf)
        want[s], frontier, lvl = 0, [s], 0
        while frontier:
            lvl += 1
            nbr = e.dst[np.isin(e.src, frontier)]
            new = np.unique(nbr[~np.isfinite(want[nbr])])
            want[new] = lvl
            frontier = list(new)
        np.testing.assert_array_equal(row, want)
    dist = sssp.solve(mats, srcs, {})
    for row, s in zip(dist, srcs):
        # feasible on every edge, and tight on one edge into each vertex
        fin = np.isfinite(row[e.src])
        assert np.all(row[e.dst][fin] <= row[e.src][fin] + e.w[fin] + 1e-12)
        tight = np.isclose(row[e.dst], row[e.src] + e.w) & fin
        reached = np.nonzero(np.isfinite(row))[0]
        assert set(e.dst[tight]) | {s} == set(reached)


def test_ppr_is_the_fixpoint(graph):
    e, mats, srcs = graph
    deg = np.maximum(e.degrees(), 1)
    walk = sp.csr_matrix((1.0 / deg[e.src], (e.dst, e.src)),
                         shape=(e.n, e.n))
    pref = np.zeros((e.n, len(srcs)))
    pref[srcs, np.arange(len(srcs))] = 1.0
    r = pref.copy()
    for _ in range(300):
        r = 0.15 * pref + 0.85 * (walk @ r)
    got = ppr_delta.solve(mats, srcs, PPR)
    assert np.abs(got - r.T).max() < 1e-8


def test_the_program_served_agrees_with_the_references(graph):
    from repro.core import algorithms as alg
    from repro.graph import csr, pack_ell
    from repro.serving import GraphServer, default_config

    e, _dg, srcs = graph
    s, d, w = e.one_direction()
    g = csr.from_edges(s, d, e.n, w, directed=False)
    progs = {"bfs": alg.bfs(0), "sssp": alg.sssp(0),
             "ppr_delta": alg.ppr_delta(0, **PPR)}
    srv = GraphServer(g, pack_ell(g.inc), progs, slots=4,
                      cfg=default_config(g))
    for prog in progs:
        for src in srcs[:6]:
            srv.submit(prog, src)
    srv.drain()
    answers = [(c.algo, c.source, c.result) for c in srv.completions]
    assert len(answers) == 18
    checks = check.judge(check.compare(e, CFG, answers),
                         check.limits_of(CFG))
    assert set(checks) == set(check.limits_of(CFG))
    assert check.passed(checks), checks
    assert checks["bfs_wrong_vertices"]["value"] == 0


def test_the_bfloat16_control_fails(graph):
    e, _dg, srcs = graph
    worst = check.control(e, CFG, {"sssp": srcs[:4], "ppr_delta": srcs[:4]})
    checks = check.judge(worst, check.limits_of(CFG))
    assert checks["sssp_rel_err"]["value"] > checks["sssp_rel_err"]["limit"]
    assert (checks["ppr_gap_ratio"]["value"] > 1.0
            or checks["ppr_excess_ratio"]["value"] > 0.01)


def test_a_wrong_answer_fails(graph):
    e, mats, srcs = graph
    rows = bfs.solve(mats, srcs[:2], {})
    bad = rows[0].copy()
    bad[np.isfinite(bad) & (bad > 0)] += 1
    worst = check.compare(e, CFG, [("bfs", srcs[0], bad),
                                   ("bfs", srcs[1], rows[1])])
    assert worst["bfs_wrong_vertices"] > 0
    # at the source of fewest edges, where tol * deg is smallest
    src = min(srcs, key=lambda s: e.degrees()[s])
    ppr = ppr_delta.solve(mats, [src], PPR)[0]
    worst = check.compare(e, CFG, [("ppr_delta", src,
                                    (ppr * 0.999).astype(np.float32))])
    assert worst["ppr_gap_ratio"] > 1.0
