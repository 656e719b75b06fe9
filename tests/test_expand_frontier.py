"""`expand_frontier`'s slot-owner lookup (a scatter of each frontier vertex's
first edge slot and a running max) against the binary search it replaced:
the same src/dst/w/valid/total bit for bit, on frontiers built to hit every
edge of the lookup, and the same answers and push/pull sequence through the
batched engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.sparse import csgraph, csr_matrix

from repro.core import algorithms as A
from repro.core.engine import EngineConfig, expand_frontier
from repro.graph import generators, pack_ell
from repro.graph.csr import CSR
from repro.serving import batch_engine as B


def _searchsorted_rows(a, v):
    if a.ndim == 1:
        return jnp.searchsorted(a, v, side="right").astype(jnp.int32)
    flat = a.reshape((-1, a.shape[-1]))
    out = jax.vmap(lambda row: jnp.searchsorted(row, v, side="right"))(flat)
    return out.reshape(a.shape[:-1] + (v.shape[-1],)).astype(jnp.int32)


def reference_expand(csr, ids, count, edge_cap):
    """The binary-search expansion: slot e's owner is the first frontier
    index whose inclusive degree prefix exceeds e."""
    n = csr.n_nodes
    cap = ids.shape[-1]
    count = jnp.asarray(count)
    valid_v = jnp.arange(cap, dtype=jnp.int32) < count[..., None]
    safe = jnp.where(valid_v, jnp.minimum(ids, n - 1), 0)
    deg = jnp.where(valid_v, csr.row_ptr[safe + 1] - csr.row_ptr[safe], 0)
    cum = jnp.cumsum(deg, axis=-1)
    total = cum[..., -1]
    e = jnp.arange(edge_cap, dtype=jnp.int32)
    owner = jnp.minimum(_searchsorted_rows(cum, e), cap - 1)
    start = (jnp.take_along_axis(cum, owner, -1)
             - jnp.take_along_axis(deg, owner, -1))
    within = e - start
    src = jnp.take_along_axis(safe, owner, -1)
    ptr = jnp.minimum(csr.row_ptr[src] + within, csr.n_edges - 1)
    valid_e = e < jnp.minimum(total, edge_cap)[..., None]
    valid_e = jnp.broadcast_to(valid_e, src.shape)
    dst = jnp.where(valid_e, csr.col_idx[ptr], n)
    w = jnp.where(valid_e, csr.weights[ptr], 0.0)
    src = jnp.where(valid_e, src, n)
    return src, dst, w, valid_e, total


N, CAP, EDGE_CAP = 48, 16, 48
_new = jax.jit(expand_frontier, static_argnums=3)
_ref = jax.jit(reference_expand, static_argnums=3)


def _graph() -> CSR:
    """N vertices, a third of them without edges, the rest of degree 1-9."""
    r = np.random.default_rng(7)
    deg = np.where(r.random(N) < 0.33, 0, r.integers(1, 10, N))
    row_ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    m = int(row_ptr[-1])
    return CSR(jnp.asarray(row_ptr),
               jnp.asarray(r.integers(0, N, m), jnp.int32),
               jnp.asarray(r.random(m), jnp.float32),
               jnp.asarray(np.repeat(np.arange(N), deg), jnp.int32))


GRAPH = _graph()
DEG = np.diff(np.asarray(GRAPH.row_ptr))
ZERO = np.flatnonzero(DEG == 0)
LOW = np.flatnonzero((DEG > 0) & (DEG <= 3))
HIGH = np.argsort(-DEG, kind="stable")


def _pad(ids, fill=N):
    ids = np.asarray(ids, np.int32)
    return np.concatenate([ids, np.full(CAP - ids.size, fill, np.int32)])


def _cases():
    """name -> (ids (CAP,), count); ids past `count` hold sentinels or
    leftovers, which the expansion must ignore."""
    r = np.random.default_rng(11)
    zero_first = np.concatenate([ZERO[:3], LOW[:2], ZERO[3:5], HIGH[:3]])
    return {
        "zero_degree": (_pad(zero_first), zero_first.size),
        "all_zero_degree": (_pad(ZERO[:6]), 6),
        "count_0": (_pad(HIGH[:5]), 0),
        "count_eq_cap_fits": (_pad(np.concatenate([LOW, ZERO])[:CAP]), CAP),
        "count_eq_cap_truncated": (_pad(HIGH[:CAP]), CAP),
        "total_over_budget": (_pad(HIGH[4:12]), 8),
        "tail_starts_past_budget": (_pad(HIGH[:CAP]), 12),
        "unsorted": (r.permutation(N)[:CAP].astype(np.int32), 13),
        "leftovers_past_count": (r.integers(0, N, CAP).astype(np.int32), 4),
    }


CASES = _cases()


def _assert_identical(got, want):
    for name, a, b in zip(("src", "dst", "w", "valid_e", "total"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("case", [*CASES, "leading_batch_axes",
                                  "random_frontiers"])
def test_matches_binary_search(case):
    if case == "leading_batch_axes":
        ids = np.stack([c[0] for c in CASES.values()])
        counts = np.array([c[1] for c in CASES.values()], np.int32)
        ids = np.concatenate([ids, ids[::-1]]).reshape(3, 6, CAP)
        counts = np.concatenate([counts, counts[::-1]]).reshape(3, 6)
    elif case == "random_frontiers":
        r = np.random.default_rng(5)
        ids = np.stack([r.permutation(N)[:CAP] for _ in range(200)])
        counts = r.integers(0, CAP + 1, 200)
    else:
        ids, counts = CASES[case]
    ids = jnp.asarray(ids, jnp.int32)
    counts = jnp.asarray(counts, jnp.int32)
    got = _new(GRAPH, ids, counts, EDGE_CAP)
    _assert_identical(got, _ref(GRAPH, ids, counts, EDGE_CAP))
    total = np.asarray(got[4])
    valid = np.asarray(got[3])
    assert (valid.sum(-1) == np.minimum(total, EDGE_CAP)).all()


def test_cases_reach_what_they_name():
    """The fixed frontiers truncate, start tails past the budget and fit, as
    their names say."""
    def starts(case):
        ids, count = CASES[case]
        d = DEG[ids[:count]]
        return np.cumsum(d) - d, d.sum()

    assert starts("count_eq_cap_fits")[1] < EDGE_CAP
    assert starts("count_eq_cap_truncated")[1] > EDGE_CAP
    assert starts("total_over_budget")[1] > EDGE_CAP
    tail, _ = starts("tail_starts_past_budget")
    assert (tail >= EDGE_CAP).sum() >= 3
    assert starts("all_zero_degree")[1] == 0


# ---------------------------------------------------------------------------
# through the batched engine
# ---------------------------------------------------------------------------


def _scipy_dist(g, sources, weighted):
    n = g.n_nodes
    rp = np.asarray(g.out.row_ptr)
    ci = np.asarray(g.out.col_idx)
    w = np.asarray(g.out.weights, np.float64) if weighted else np.ones(ci.size)
    adj = csr_matrix((w, ci, rp), shape=(n, n))
    return csgraph.dijkstra(adj, indices=list(sources))


@pytest.mark.parametrize("algo", ["bfs", "sssp"])
def test_batched_engine_answers_and_modes(monkeypatch, algo):
    """A small edge budget makes the controller leave push for pull by the
    budget alone (alpha never fires); answers match scipy and the push/pull
    sequence matches the binary-search expansion's."""
    g = generators.rmat(9, 8, seed=3)
    pack = pack_ell(g.inc)
    n = g.n_nodes
    cfg = EngineConfig(frontier_cap=n, edge_cap=256, alpha=2.0)
    sources = [0, 5, 17, 100, 311, 480]
    program = getattr(A, algo)(0)
    m_new, st_new = B.run_batch(program, g, pack, cfg, sources)
    # fusion="none" jits a fresh step closure, so the patch is traced
    monkeypatch.setattr(B, "expand_frontier", reference_expand)
    m_old, st_old = B.run_batch(program, g, pack, cfg, sources, fusion="none")

    trace = np.asarray(st_new["mode_trace"])
    assert np.array_equal(trace, np.asarray(st_old["mode_trace"]))
    assert (trace == 0).any() and (trace == 1).any()
    assert np.array_equal(np.asarray(st_new["switches"]),
                          np.asarray(st_old["switches"]))
    assert np.array_equal(np.asarray(m_new["dist"]), np.asarray(m_old["dist"]))

    got = np.asarray(m_new["dist"][:n], np.float64).T
    got[got > 1e30] = np.inf
    want = _scipy_dist(g, sources, weighted=(algo == "sssp"))
    assert np.array_equal(np.isfinite(got), np.isfinite(want))
    fin = np.isfinite(want)
    assert np.allclose(got[fin], want[fin], rtol=1e-5, atol=0)
