"""Streaming subsystem: delta overlay, incremental recomputation, selective
cache invalidation (DESIGN.md §8).

Contracts:
  (a) an empty overlay is a no-op: overlaid runs bit-match plain runs;
  (b) PROPERTY: after any random update batch, incremental recomputation is
      bit-identical to full recomputation on the updated graph, for monotone
      (BFS/SSSP) and non-monotone (PPR) programs, across chained batches;
  (c) deletions repair exactly (a cut chain reports unreachable);
  (d) the serving layer never serves a stale result after `apply_updates`,
      while retaining clean cache entries (no wholesale invalidation) and
      re-enqueueing dirtied in-flight queries;
  (e) insertion-buffer overflow compacts into a rebuilt CSR, transparently;
  (f) the kernel-level deletion overlay equals sentinel-neutralized slices;
  (g) the frontier-aware masked pull is exact for min programs and
      tol-bounded for PPR.
"""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import algorithms as alg
from repro.graph import generators, pack_ell
from repro.graph.csr import empty_delta
from repro.graph.packing import delta_ell_slice
from repro.serving import GraphServer, default_config, query_result, run_batch
from repro.streaming import StreamingGraph, incremental_batch, is_monotone


CASES = [
    ("bfs", alg.bfs, "dist"),
    ("sssp", alg.sssp, "dist"),
    ("ppr", alg.ppr, "rank"),
]


def _rand_updates(rng, g, n_ins, n_del):
    n = g.n_nodes
    ins = [(int(rng.integers(0, n)), int(rng.integers(0, n)),
            float(rng.integers(1, 65))) for _ in range(n_ins)]
    eidx = rng.integers(0, g.n_edges, size=n_del)
    dels = [(int(g.out.src_idx[i]), int(g.out.col_idx[i])) for i in eidx]
    return ins, dels


# ---------------------------------------------------------------------------
# (a) empty overlay is the identity
# ---------------------------------------------------------------------------


def test_overlay_noop_matches_plain(rmat_graph, rmat_pack):
    g = rmat_graph
    sg = StreamingGraph(g, delta_cap=32)
    cfg = default_config(g, max_iters=64)
    sources = [0, 7, g.n_nodes - 1]
    prog = alg.bfs(0)
    m_ov, _ = run_batch(prog, sg.graph, sg.pack, cfg, sources, delta=sg.delta)
    m_pl, _ = run_batch(prog, g, rmat_pack, cfg, sources)
    for k in m_pl:
        assert np.array_equal(np.asarray(m_ov[k]), np.asarray(m_pl[k]))


# ---------------------------------------------------------------------------
# (b) property: incremental == full recompute, bit for bit, chained batches
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,factory,field", CASES)
def test_incremental_bitmatches_full_property(name, factory, field):
    g = generators.rmat(8, 4, seed=11)           # 256 nodes
    sg = StreamingGraph(g, delta_cap=128)
    cfg = default_config(g, max_iters=64)
    rng = np.random.default_rng(23)
    sources = rng.integers(0, g.n_nodes, size=6).tolist()
    prog = factory(0)
    prev, _ = run_batch(prog, sg.graph, sg.pack, cfg, sources, delta=sg.delta)
    assert is_monotone(prog) == (name in ("bfs", "sssp"))
    for batch in range(3):                       # chained random batches
        ins, dels = _rand_updates(rng, g, n_ins=5, n_del=4)
        sg.apply(inserts=ins, deletes=dels)
        full, _ = run_batch(prog, sg.graph, sg.pack, cfg, sources,
                            delta=sg.delta)
        inc, info = incremental_batch(prog, sg, cfg, sources, prev)
        for k in full:
            assert np.array_equal(np.asarray(full[k]), np.asarray(inc[k])), (
                f"{name} batch {batch}: incremental diverges on field {k} "
                f"(info={info})"
            )
        prev = inc


# ---------------------------------------------------------------------------
# (c) deletions repair exactly
# ---------------------------------------------------------------------------


def test_deletion_cuts_chain():
    n = 64
    g = generators.chain(n, weighted=False)
    sg = StreamingGraph(g, delta_cap=8)
    cfg = default_config(g, max_iters=256)
    prog = alg.bfs(0)
    prev, _ = run_batch(prog, sg.graph, sg.pack, cfg, [0], delta=sg.delta)
    assert float(query_result(prev, "dist", 0)[n - 1]) == n - 1

    cut = n // 2
    rep = sg.apply(deletes=[(cut, cut + 1)])
    assert rep.n_deleted == 2                    # both directions
    inc, _ = incremental_batch(prog, sg, cfg, [0], prev)
    d = np.asarray(query_result(inc, "dist", 0))
    big = float(jnp.finfo(jnp.float32).max / 4)
    assert np.all(d[: cut + 1] == np.arange(cut + 1))
    assert np.all(d[cut + 1:] == big), "beyond the cut must be unreachable"

    # re-inserting restores connectivity (insert goes to the delta buffer)
    sg.apply(inserts=[(cut, cut + 1)])
    inc2, _ = incremental_batch(prog, sg, cfg, [0], inc)
    full2, _ = run_batch(prog, sg.graph, sg.pack, cfg, [0], delta=sg.delta)
    assert np.array_equal(np.asarray(inc2["dist"]), np.asarray(full2["dist"]))
    assert float(query_result(inc2, "dist", 0)[n - 1]) == n - 1


# ---------------------------------------------------------------------------
# (d) serving: no stale results, partial retention, in-flight re-enqueue
# ---------------------------------------------------------------------------


def _fresh_reference(srv, factory, cfg, sources):
    sg = srv.sg
    prog = factory(0)
    m, _ = run_batch(prog, sg.graph, sg.pack, cfg, sources, delta=sg.delta)
    return m


@pytest.mark.parametrize("refresh", ["incremental", "drop"])
def test_apply_updates_never_serves_stale(refresh):
    # two components: a connected grid plus guaranteed-isolated vertices
    # (sources there stay clean -> cache retention must be > 0)
    g = generators.grid2d(8, seed=5)             # vertices 0..63 connected
    import repro.graph.csr as csr_mod
    src = np.asarray(g.out.src_idx)
    dst = np.asarray(g.out.col_idx)
    w = np.asarray(g.out.weights)
    g = csr_mod.from_edges(src, dst, 80, w, directed=False)  # 64..79 isolated
    cfg = default_config(g, max_iters=256)
    srv = GraphServer(g, None, {"bfs": alg.bfs(0), "ppr": alg.ppr(0)},
                      slots=4, cfg=cfg, cache_capacity=64, delta_cap=32,
                      result_fields={"ppr": "rank"})
    sources = [0, 9, 33, 70, 75]                 # mixed: grid + isolated
    for s in sources:
        srv.submit("bfs", s)
        srv.submit("ppr", s)
    srv.drain()
    assert len(srv.cache) == 2 * len(sources)

    st = srv.apply_updates(
        inserts=[(1, 62)], deletes=[(0, 1)], refresh=refresh)
    assert st["version"] == 1
    # clean sources (isolated vertices) survive the selective invalidation
    assert st["cache_retained"] >= 4, st
    if refresh == "incremental":
        assert st["cache_refreshed"] > 0, st
    # every post-update serve must match a fresh run on the updated graph
    for algo, factory, field in [("bfs", alg.bfs, "dist"),
                                 ("ppr", alg.ppr, "rank")]:
        rids = [srv.submit(algo, s) for s in sources]
        comps = {c.rid: c for c in srv.drain()}
        ref = _fresh_reference(srv, factory, cfg, sources)
        for i, rid in enumerate(rids):
            got = comps[rid].result
            want = np.asarray(query_result(ref, field, i))
            assert np.array_equal(got, want), (
                f"stale {algo} result for source {sources[i]} "
                f"(from_cache={comps[rid].from_cache}, refresh={refresh})"
            )


def test_lane_chunks_pad_each_batch_to_a_power_of_two():
    from repro.serving.scheduler import _lane_chunks

    assert list(_lane_chunks(list(range(11)), 8)) == [
        (list(range(8)), list(range(8))), ([8, 9, 10], [8, 9, 10, 10])]
    assert list(_lane_chunks(list(range(5)), 3)) == [
        ([0, 1, 2], [0, 1, 2]), ([3, 4], [3, 4])]


@pytest.mark.parametrize("refresh_lanes", [1, 3, 64])
def test_refresh_lanes_bound_each_batch(refresh_lanes, monkeypatch):
    """Dirty cached entries refresh in batches of at most `refresh_lanes`
    lanes, and every width serves the fresh answer."""
    import repro.streaming as streaming

    widths = []
    real = streaming.incremental_batch

    def counted(program, sg, cfg, sources, prev_m, *a, **kw):
        widths.append(len(sources))
        return real(program, sg, cfg, sources, prev_m, *a, **kw)

    monkeypatch.setattr(streaming, "incremental_batch", counted)
    g = generators.grid2d(8, seed=5)
    cfg = default_config(g, max_iters=256)
    srv = GraphServer(g, None, {"sssp": alg.sssp(0)}, slots=4, cfg=cfg,
                      delta_cap=32, refresh_lanes=refresh_lanes)
    sources = [0, 9, 20, 33, 47, 63]
    for s in sources:
        srv.submit("sssp", s)
    srv.drain()
    st = srv.apply_updates(inserts=[(1, 62, 1.0)], deletes=[(0, 1)])
    assert st["cache_refreshed"] == len(sources), st
    assert max(widths) <= refresh_lanes
    assert len(widths) == -(-len(sources) // refresh_lanes)
    rids = [srv.submit("sssp", s) for s in sources]
    comps = {c.rid: c for c in srv.drain()}
    ref = _fresh_reference(srv, alg.sssp, cfg, sources)
    for i, rid in enumerate(rids):
        assert comps[rid].from_cache
        assert np.array_equal(comps[rid].result,
                              np.asarray(query_result(ref, "dist", i)))


def test_apply_updates_resumes_inflight_ppr_delta():
    """Version-swap with RESIDUAL-PUSH lanes in flight: `apply_updates` must
    RESUME dirty `ppr_delta` lanes from Maiter-corrected residuals (not
    restart them — `readmit` would bump engine_queries and zero the lane's
    iteration counters) while clean cached entries re-key to the new
    version, and every post-update completion must agree with a fresh run
    on the updated graph."""
    # connected grid + guaranteed-isolated vertices (clean cache entries)
    g = generators.grid2d(8, seed=5)
    import repro.graph.csr as csr_mod
    src = np.asarray(g.out.src_idx)
    dst = np.asarray(g.out.col_idx)
    w = np.asarray(g.out.weights)
    g = csr_mod.from_edges(src, dst, 80, w, directed=False)  # 64..79 isolated
    cfg = default_config(g, max_iters=256)
    srv = GraphServer(g, None, {"ppr_delta": alg.ppr_delta(0)}, slots=2,
                      cfg=cfg, cache_capacity=64, delta_cap=32,
                      result_fields={"ppr_delta": "rank"})
    for s in [70, 75]:                           # isolated: stay clean
        srv.submit("ppr_delta", s)
    srv.drain()
    assert len(srv.cache) == 2

    srv.submit("ppr_delta", 0)
    srv.submit("ppr_delta", 33)
    srv.pump()                                   # admit + one step: in flight
    pool = srv.pools["ppr_delta"]
    assert any(r is not None for r in pool.lane_rid)
    queries_before = pool.engine_queries
    it_before = np.asarray(pool.state.it).copy()

    st = srv.apply_updates(inserts=[(1, 62)], deletes=[(0, 1)])
    assert st["resumed_inflight"] >= 1, st
    assert st["reenqueued_inflight"] == 0, "residual lanes must not restart"
    assert st["cache_retained"] == 2, st         # clean entries re-keyed
    assert pool.engine_queries == queries_before, "resume is not a readmit"
    assert (np.asarray(pool.state.it) >= it_before).all(), (
        "iteration counters must survive the resume")

    comps = {c.source: c for c in srv.drain()}
    ref = _fresh_reference(srv, alg.ppr_delta, cfg, [0, 33])
    for i, s in enumerate([0, 33]):
        got = comps[s].result
        want = np.asarray(query_result(ref, "rank", i))
        # resumed mid-flight trajectories are tol-accurate, not bitwise
        assert np.abs(got - want).max() < 1e-3, s
    # a clean cached source still hits under the NEW version
    rid = srv.submit("ppr_delta", 70)
    comp = [c for c in srv.drain() if c.rid == rid][0]
    assert comp.from_cache


def test_apply_updates_reenqueues_dirty_inflight():
    g = generators.grid2d(10, seed=3)            # 100 nodes, slow BFS
    cfg = default_config(g, max_iters=256)
    srv = GraphServer(g, None, {"sssp": alg.sssp(0)}, slots=2, cfg=cfg,
                      cache_capacity=0, delta_cap=16)
    srv.submit("sssp", 0)
    srv.submit("sssp", 99)
    srv.pump()                                   # admit + one step: in flight
    assert any(r is not None for r in srv.pools["sssp"].lane_rid)
    st = srv.apply_updates(deletes=[(0, 1)])
    assert st["reenqueued_inflight"] >= 1, st
    comps = srv.drain()
    ref = _fresh_reference(srv, alg.sssp, cfg, [0, 99])
    by_src = {c.source: c for c in comps}
    for i, s in enumerate([0, 99]):
        assert np.array_equal(by_src[s].result,
                              np.asarray(query_result(ref, "dist", i)))


# ---------------------------------------------------------------------------
# (e) overflow -> rebuild/compaction
# ---------------------------------------------------------------------------


def test_delta_overflow_triggers_rebuild():
    g = generators.grid2d(6, seed=1)             # 36 nodes
    sg = StreamingGraph(g, delta_cap=4)          # room for 2 undirected edges
    cfg = default_config(g, max_iters=256)
    prog = alg.bfs(0)
    rng = np.random.default_rng(2)
    inserted = []
    for k in range(4):                           # 4 batches x 2 directed each
        u, v = rng.integers(0, 36, size=2)
        while u == v:
            u, v = rng.integers(0, 36, size=2)
        rep = sg.apply(inserts=[(int(u), int(v))])
        if rep.n_inserted:
            inserted.append((int(u), int(v)))
    assert sg.rebuilds >= 1, "delta buffer should have overflowed"
    # post-rebuild overlay still answers correctly vs a from-scratch graph
    full, _ = run_batch(prog, sg.graph, sg.pack, cfg, [0], delta=sg.delta)
    import repro.graph.csr as csr_mod
    src = np.concatenate([np.asarray(g.out.src_idx),
                          np.asarray([e[0] for e in inserted])])
    dst = np.concatenate([np.asarray(g.out.col_idx),
                          np.asarray([e[1] for e in inserted])])
    g2 = csr_mod.from_edges(src, dst, 36, None, directed=False, dedupe=True)
    ref, _ = run_batch(prog, g2, pack_ell(g2.inc), cfg, [0])
    assert np.array_equal(np.asarray(full["dist"]), np.asarray(ref["dist"]))


# ---------------------------------------------------------------------------
# (e2) update batches arriving while a rebuild is IN FLIGHT merge into it
#      (streaming round 3(d)) — no loss, no double-count
# ---------------------------------------------------------------------------


def test_mid_rebuild_update_batches_merge_exactly_once():
    """Interleave apply() with begin_compact()/finish_compact(): batches
    landing mid-rebuild must (1) stay live in the overlay (serving reads
    stay coherent), (2) be replayed into the rebuilt base exactly ONCE —
    the pre-begin overlay is already folded in, so a naive re-fold would
    double-count its insertion COO lanes — and (3) surface as one merged
    UpdateReport from the finish."""
    import repro.graph.csr as csr_mod

    g = generators.rmat(9, 8, seed=11, directed=True)
    n = g.n_nodes
    sg = StreamingGraph(g, delta_cap=16)
    cfg = default_config(g, max_iters=256)
    prog = alg.bfs(0)

    sg.apply(inserts=[(1, 2), (3, 4)])                  # pre-begin overlay
    sg.begin_compact()
    # mid-flight: new inserts, a deletion of a PRE-BEGIN pending insert
    # (folded into the rebuild snapshot — replay must remove it), and a
    # base-edge deletion
    r1 = sg.apply(inserts=[(5, 6), (7, 8)], deletes=[(1, 2)])
    base_del = (int(g.out.src_idx[0]), int(g.out.col_idx[0]))
    r2 = sg.apply(deletes=[base_del])
    # mid-flight views are already coherent (old base + overlay)
    mid, _ = run_batch(prog, sg.graph, sg.pack, cfg, [0], delta=sg.delta)
    merged = sg.finish_compact()

    assert sg.rebuilds == 1
    assert merged.rebuild
    assert merged.n_inserted == r1.n_inserted + r2.n_inserted == 2
    assert merged.n_deleted == r1.n_deleted + r2.n_deleted == 2
    assert np.array_equal(
        merged.dirty_src, r1.dirty_src | r2.dirty_src)
    assert set(merged.touched) == set(r1.touched) | set(r2.touched)

    # post-finish graph == fold-everything-from-scratch reference, bitwise
    src = np.asarray(g.out.src_idx)
    dst = np.asarray(g.out.col_idx)
    w = np.asarray(g.out.weights)
    keep = np.ones(src.shape[0], bool)
    keep[0] = False                                     # base_del
    src2 = np.concatenate([src[keep], [3, 5, 7]])       # (1,2) net-zero
    dst2 = np.concatenate([dst[keep], [4, 6, 8]])
    w2 = np.concatenate([w[keep], [1.0, 1.0, 1.0]])
    g_ref = csr_mod.from_edges(src2, dst2, n, w2, directed=True,
                               dedupe=False)
    assert np.array_equal(sg.live_out_degrees(),
                          np.bincount(src2, minlength=n)[:n]), \
        "an edge counted twice (or lost) across the merge"
    full, _ = run_batch(prog, sg.graph, sg.pack, cfg, [0], delta=sg.delta)
    ref, _ = run_batch(prog, g_ref, pack_ell(g_ref.inc), cfg, [0])
    assert np.array_equal(np.asarray(full["dist"]), np.asarray(ref["dist"]))
    # and the finish changed nothing logically: mid-flight result still holds
    assert np.array_equal(np.asarray(mid["dist"]), np.asarray(full["dist"]))


def test_mid_rebuild_overflowing_batch_finishes_the_rebuild():
    """A batch that overflows the overlay while a rebuild is in flight must
    merge into THAT rebuild (one fold), not serialize a second one."""
    g = generators.grid2d(6, seed=1)                    # 36 nodes
    sg = StreamingGraph(g, delta_cap=4)
    sg.apply(inserts=[(0, 7)])                          # 2 directed lanes
    sg.begin_compact()
    rep = sg.apply(inserts=[(1, 8), (2, 9)])            # 4 more: overflow
    assert rep.rebuild
    assert sg._rebuild_inflight is None, "finish must have run"
    assert sg.rebuilds == 1, "merged into the in-flight fold, not a second"
    assert sg.n_live_edges() == g.n_edges + 6
    cfg = default_config(g, max_iters=256)
    full, _ = run_batch(alg.bfs(0), sg.graph, sg.pack, cfg, [0],
                        delta=sg.delta)
    import repro.graph.csr as csr_mod
    src = np.concatenate([np.asarray(g.out.src_idx), [0, 7, 1, 8, 2, 9]])
    dst = np.concatenate([np.asarray(g.out.col_idx), [7, 0, 8, 1, 9, 2]])
    g_ref = csr_mod.from_edges(src, dst, 36, None, directed=True,
                               dedupe=False)
    ref, _ = run_batch(alg.bfs(0), g_ref, pack_ell(g_ref.inc), cfg, [0])
    assert np.array_equal(np.asarray(full["dist"]), np.asarray(ref["dist"]))


# ---------------------------------------------------------------------------
# (e3) dirty cached ppr_delta entries REFRESH incrementally (round 3(e))
# ---------------------------------------------------------------------------


def test_cached_ppr_delta_survives_update_incrementally():
    """REGRESSION (ROADMAP streaming 3(e)): a dirty cached `ppr_delta`
    entry used to DROP — the cache held only the (n,) rank, which is not
    resumable. Entries now carry (rank, resid), so an insert+delete batch
    refreshes them via the Maiter correction + residual reseed instead of
    dropping, and the refreshed entry serves a correct hit."""
    g = generators.grid2d(8, seed=5)
    import repro.graph.csr as csr_mod
    src = np.asarray(g.out.src_idx)
    dst = np.asarray(g.out.col_idx)
    w = np.asarray(g.out.weights)
    g = csr_mod.from_edges(src, dst, 80, w, directed=False)  # 64..79 isolated
    cfg = default_config(g, max_iters=256)
    srv = GraphServer(g, None, {"ppr_delta": alg.ppr_delta(0)}, slots=2,
                      cfg=cfg, cache_capacity=64, delta_cap=32,
                      result_fields={"ppr_delta": "rank"})
    sources = [0, 33, 70]                       # two dirty-able + one clean
    for s in sources:
        srv.submit("ppr_delta", s)
    srv.drain()
    assert len(srv.cache) == 3

    st = srv.apply_updates(inserts=[(1, 62)], deletes=[(0, 1)])
    assert st["cache_refreshed"] == 2, st       # grid sources refresh
    assert st["cache_retained"] == 1, st        # isolated source re-keys
    assert st["cache_dropped"] == 0, st         # NOTHING drops (the fix)

    rids = {s: srv.submit("ppr_delta", s) for s in sources}
    comps = {c.rid: c for c in srv.drain()}
    sg = srv.sg
    ref, _ = run_batch(alg.ppr_delta(0), sg.graph, sg.pack, cfg, sources,
                       delta=sg.delta)
    for i, s in enumerate(sources):
        c = comps[rids[s]]
        assert c.from_cache, s                  # refresh kept it cached
        want = np.asarray(query_result(ref, "rank", i))
        # resumed-from-correction fixpoints are tol-accurate, not bitwise
        assert np.abs(c.result - want).max() < 1e-3, s


def test_cached_ppr_delta_refreshes_through_edge_sharded_pool():
    """REGRESSION (review finding): edge-sharded sum pools tag their cache
    keys with the placement param, and the dirty-entry filter used to admit
    only params == () — so their (rank, resid) entries silently dropped.
    Tagged entries must refresh and re-key under the SAME tag."""
    from repro.serving import make_serving_mesh

    g = generators.grid2d(8, seed=5)
    import repro.graph.csr as csr_mod
    src = np.asarray(g.out.src_idx)
    dst = np.asarray(g.out.col_idx)
    w = np.asarray(g.out.weights)
    g = csr_mod.from_edges(src, dst, 80, w, directed=False)
    cfg = default_config(g, max_iters=256)
    mesh = make_serving_mesh(1, 1)
    srv = GraphServer(g, None, {"ppr_delta": alg.ppr_delta(0)}, slots=2,
                      cfg=cfg, cache_capacity=64, delta_cap=32,
                      result_fields={"ppr_delta": "rank"},
                      mesh=mesh,
                      placements={"ppr_delta": ("edge_sharded", 1)})
    tag = srv.pools["ppr_delta"].cache_params
    assert tag == ((("placement", "edge_sharded"),))
    for s in [0, 33]:
        srv.submit("ppr_delta", s)
    srv.drain()
    st = srv.apply_updates(inserts=[(1, 62)], deletes=[(0, 1)])
    assert st["cache_refreshed"] == 2, st
    assert st["cache_dropped"] == 0, st
    # refreshed entries live under the pool's tag and serve correct hits
    keys = list(srv.cache._entries)
    assert all(k[3] == tag for k in keys), keys
    rid = srv.submit("ppr_delta", 0)
    comp = [c for c in srv.drain() if c.rid == rid][0]
    assert comp.from_cache
    sg = srv.sg
    ref, _ = run_batch(alg.ppr_delta(0), sg.graph, sg.pack, cfg, [0],
                       delta=sg.delta)
    assert np.abs(comp.result
                  - np.asarray(query_result(ref, "rank", 0))).max() < 1e-3


def test_materialize_is_identity_stable_across_batches():
    """The diff-shipping contract (DESIGN.md §11): an update batch re-creates
    ONLY the view arrays whose backing state it touched."""
    g = generators.rmat(9, 8, seed=3, directed=True)
    sg = StreamingGraph(g, delta_cap=16)
    col0 = sg.graph.out.col_idx
    d0 = sg.delta.src
    s0 = sg.pack.slices[0].nbr
    sg.apply(inserts=[(1, 2)])                  # insert-only: base untouched
    assert sg.graph.out.col_idx is col0
    assert sg.pack.slices[0].nbr is s0
    assert sg.delta.src is not d0               # delta view did change
    d1 = sg.delta.src
    sg.apply(deletes=[(int(g.out.src_idx[5]), int(g.out.col_idx[5]))])
    assert sg.graph.out.col_idx is not col0     # deletion dirties the CSR
    assert sg.delta.src is d1                   # pending inserts untouched


# ---------------------------------------------------------------------------
# (f) kernel-level deletion overlay
# ---------------------------------------------------------------------------


def test_ell_combine_dead_overlay_matches_neutralized():
    from repro.kernels import ell_spmv

    rng = np.random.default_rng(9)
    r, w, n = 32, 8, 100
    nbr = rng.integers(0, n + 1, size=(r, w)).astype(np.int32)
    wgt = rng.random((r, w)).astype(np.float32)
    vals = rng.random(n + 1).astype(np.float32)
    dead = (rng.random((r, w)) < 0.3)
    neut = np.where(dead, n, nbr).astype(np.int32)
    compute = lambda v, ww: v + ww
    for combine in ("min", "sum"):
        a = ell_spmv.ell_combine(
            jnp.asarray(nbr), jnp.asarray(wgt), jnp.asarray(vals),
            jnp.asarray(dead), compute_fn=compute, combine=combine,
            interpret=True)
        b = ell_spmv.ell_combine(
            jnp.asarray(neut), jnp.asarray(wgt), jnp.asarray(vals),
            compute_fn=compute, combine=combine, interpret=True)
        assert np.array_equal(np.asarray(a), np.asarray(b)), combine


def test_delta_buffers_keep_static_shapes():
    n, cap = 50, 16
    empty = delta_ell_slice(np.zeros(0), np.zeros(0), np.zeros(0), n, cap)
    filled = delta_ell_slice(
        np.asarray([1, 2, 3]), np.asarray([4, 5, 6]),
        np.asarray([1.0, 1.0, 1.0]), n, cap)
    assert empty.nbr.shape == filled.nbr.shape
    assert empty.row_id.shape == filled.row_id.shape
    d = empty_delta(n, cap)
    assert d.src.shape == (cap,) and bool(jnp.all(d.src == n))


# ---------------------------------------------------------------------------
# (g) frontier-aware masked pull
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,factory,field", CASES)
def test_masked_pull(served_graph_masked, name, factory, field):
    g, pack = served_graph_masked
    cfg = default_config(g, max_iters=64)
    cfgm = dataclasses.replace(cfg, masked_pull=True)
    rng = np.random.default_rng(5)
    srcs = rng.integers(0, g.n_nodes, size=6).tolist()
    prog = factory(0)
    md, _ = run_batch(prog, g, pack, cfg, srcs)
    mm, _ = run_batch(prog, g, pack, cfgm, srcs)
    a, b = np.asarray(md[field]), np.asarray(mm[field])
    if name in ("bfs", "sssp"):
        assert np.array_equal(a, b), (
            "masked pull must be exact for min programs")
    else:
        # tol-thresholded program: sub-tolerance drift outside the frontier
        # is frozen (push-mode semantics) — O(tol)-bounded deviation
        assert np.abs(a - b).max() < 5e-5


@pytest.fixture(scope="module")
def served_graph_masked():
    g = generators.rmat(9, 8, seed=3)
    return g, pack_ell(g.inc)


# ---------------------------------------------------------------------------
# (h) device affected-region sweeps == host sweeps (streaming round 2b)
# ---------------------------------------------------------------------------


def test_reach_sweeps_device_equals_host_property():
    """PROPERTY: the on-device batched-BFS fixpoint sweeps produce exactly
    the host python sweeps' dirty-source and affected-region sets, across
    random graphs, random insert+delete batches, and chained batches."""
    rng = np.random.default_rng(42)
    for trial in range(4):
        directed = bool(trial % 2)
        g = generators.rmat(8 + trial % 2, 6, seed=trial, directed=directed)
        sg_h = StreamingGraph(g, delta_cap=64, sweep="host")
        sg_d = StreamingGraph(g, delta_cap=64, sweep="device")
        for _batch in range(3):
            ins, dels = _rand_updates(rng, sg_h.graph, n_ins=7, n_del=4)
            rh = sg_h.apply(ins, dels)
            rd = sg_d.apply(ins, dels)
            assert np.array_equal(rh.dirty_src, rd.dirty_src), (
                trial, _batch, "dirty_src")
            assert np.array_equal(rh.affected_del, rd.affected_del), (
                trial, _batch, "affected_del")
            assert np.array_equal(rh.boundary, rd.boundary)


def test_reach_sweep_auto_routes_by_size(rmat_graph):
    """'auto' keeps small graphs on the host path and big ones on device."""
    sg = StreamingGraph(rmat_graph, delta_cap=8)      # scale-9: host regime
    assert rmat_graph.n_edges < sg.DEVICE_SWEEP_MIN_EDGES
    sg.apply(inserts=[(1, 2)])
    assert not sg._sweep_dev, "small graph must not upload sweep residents"
    sg.sweep = "device"
    sg.apply(inserts=[(3, 4)])
    assert "reverse" in sg._sweep_dev


# ---------------------------------------------------------------------------
# (i) delta-aware single-query engine (ROADMAP item): solo push sees the
#     insertion COO without a rebuild
# ---------------------------------------------------------------------------


def test_solo_engine_delta_bitwise_vs_rebuild():
    """`core.engine.run(..., delta=sg.delta)` over the overlay views is
    BIT-IDENTICAL to a from-scratch run on the compacted (rebuilt) graph for
    the monotone programs — insertions reach the solo push path now, not
    just the batched one."""
    from repro.core import engine as E
    from repro.graph.csr import from_edges

    g = generators.rmat(10, 8, seed=7, directed=True)
    rng = np.random.default_rng(1)
    ins, dels = _rand_updates(rng, g, n_ins=12, n_del=6)

    sg = StreamingGraph(g, delta_cap=64)
    sg.apply(ins, dels)
    # reference: fold live base + pending insertions into a fresh graph
    live = ~sg._dead_out
    src = sg._base_src_host()[live]
    dst = sg._out_ci[live]
    w = sg._out_w[live]
    if sg._ins:
        extra = np.asarray(sg._ins, dtype=np.float64).reshape(-1, 3)
        src = np.concatenate([src, extra[:, 0].astype(np.int64)])
        dst = np.concatenate([dst, extra[:, 1].astype(np.int64)])
        w = np.concatenate([w, extra[:, 2].astype(np.float32)])
    g_ref = from_edges(src, dst, g.n_nodes, w, directed=True, dedupe=False)
    pack_ref = pack_ell(g_ref.inc)

    cfg = default_config(g, max_iters=256)
    for name, factory, field in CASES[:2]:        # monotone: bfs, sssp
        for source in (0, 17, 333, g.n_nodes - 1):
            m_ov, _ = E.run(factory(0), sg.graph, sg.pack, cfg,
                            delta=sg.delta, source=jnp.int32(source))
            m_rb, _ = E.run(factory(0), g_ref, pack_ref, cfg,
                            source=jnp.int32(source))
            assert np.array_equal(np.asarray(m_ov[field]),
                                  np.asarray(m_rb[field])), (name, source)


def test_solo_engine_delta_matches_batched_overlay(rmat_graph):
    """Solo-with-delta and batched-with-delta agree lane for lane (the two
    engines read the same overlay views)."""
    from repro.core import engine as E

    g = rmat_graph
    sg = StreamingGraph(g, delta_cap=32)
    sg.apply(inserts=[(0, 9), (9, 41), (200, 3)])
    cfg = default_config(g, max_iters=64)
    sources = [0, 9, 200]
    m_b, _ = run_batch(alg.bfs(0), sg.graph, sg.pack, cfg, sources,
                       delta=sg.delta)
    for lane, s in enumerate(sources):
        m_s, _ = E.run(alg.bfs(0), sg.graph, sg.pack, cfg, delta=sg.delta,
                       source=jnp.int32(s))
        assert np.array_equal(
            np.asarray(query_result(m_b, "dist", lane)),
            np.asarray(m_s["dist"][:-1])), s


def test_device_sweep_survives_overflow_batch():
    """REGRESSION: the sweeps run BEFORE the overflow-rebuild decision, so a
    batch pushing pending insertions past delta_cap must route around the
    device path's static extra-COO pad (host fallback), not crash — and the
    report must equal the host-swept one."""
    g = generators.rmat(9, 8, seed=11, directed=True)
    ins = [(i, (3 * i + 7) % g.n_nodes) for i in range(1, 9)]   # 8 > cap 4
    sg_d = StreamingGraph(g, delta_cap=4, sweep="device")
    sg_h = StreamingGraph(g, delta_cap=4, sweep="host")
    rd = sg_d.apply(inserts=ins)
    rh = sg_h.apply(inserts=ins)
    assert rd.rebuild and rh.rebuild
    assert np.array_equal(rd.dirty_src, rh.dirty_src)
    assert sg_d.stats()["rebuilds"] == 1
