#!/usr/bin/env python3
"""Chip smoke test: serve BFS, SSSP and PPR at RMAT-20 through GraphServer.

  python3 chip_smoke.py              # one TPU chip
  python3 chip_smoke.py --chips 4    # sharded pools on four chips vs one

One chip: generates a Graph500 RMAT graph (scale 20, edge factor 16,
initiator 0.57/0.19/0.19) from --seed, serves a cold and a warm batch of
point queries through bfs / sssp / ppr_delta pools of 8 slots each, applies
one insert/delete edge batch through `GraphServer.apply_updates`, queries
again, and checks every completion against scipy.sparse.csgraph (BFS levels,
Dijkstra) and a numpy power iteration (PPR) on the graph version it was
served under.

Four chips: serves the cold batch through query-sharded
(`Placement('replicated', 4)`, 4x1 mesh) and edge-partitioned
(`Placement('edge_sharded', 4)`, 1x4 mesh) pools, prints each placement's
shard-to-device map, and compares the answers with one-chip pools run in
the same process: bit for bit, except sum programs on the edge partition,
which DESIGN.md §9 holds to FP tolerance.

Everything runs in this one process (a chip belongs to one process). With
no TPU, or when any phase fails, it exits non-zero and prints no result
line. The last line of stdout is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Times printed here are smoke numbers from a handful of queries, not a
benchmark.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ALGOS = ("bfs", "sssp", "ppr_delta")
SCALE = 20                 # log2 of the RMAT vertex count
SLOTS = 8                  # per pool: RMAT-20 at 32 slots exceeds 16 GB HBM
#: lanes per cache-refresh batch: at RMAT-20, 16 need 13.97 GB temporaries
REFRESH_LANES = 8
EDGE_FACTOR = 16
INITIATOR = (0.57, 0.19, 0.19)   # Graph500 Kronecker a, b, c
QUERIES = 24               # per batch, round-robin over ALGOS
HOT_FRAC = 0.25            # share of sources drawn from a small hot set
DELTA_CAP = 256            # streaming insert capacity (stream_graph default)
UPDATE_EDGES = 32          # undirected inserts and deletes in the batch
#: float32 rounding allowance on each side of ppr_delta's per-vertex bound
PPR_FP_SLACK = 1e-6
#: DESIGN.md §9: edge-partitioned sum programs match to FP tolerance
SUM_RTOL, SUM_ATOL = 1e-5, 1e-7


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds jax spends compiling (or loading from the persistent cache)
    executables, and how many of them came from that cache."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.secs, self.compiles, self.cache_hits

    def since(self, mark) -> str:
        s, c, h = mark
        return (f"{self.secs - s:.2f}s compiling {self.compiles - c} "
                f"executables ({self.cache_hits - h} from the cache)")


# -- graph and queries ---------------------------------------------------------


def host_edges(g):
    """(src, dst, w) host arrays of a graph's directed edge list."""
    return (np.asarray(g.out.src_idx).astype(np.int64),
            np.asarray(g.out.col_idx).astype(np.int64),
            np.asarray(g.out.weights).astype(np.float64))


def build_graph(scale: int, seed: int):
    import jax
    from repro.graph import generators

    t0 = time.perf_counter()
    a, b, c = INITIATOR
    g = generators.rmat(scale, EDGE_FACTOR, a=a, b=b, c=c, seed=seed)
    jax.block_until_ready(g)
    log(f"graph: RMAT scale {scale} edge factor {EDGE_FACTOR} seed {seed}: "
        f"{g.n_nodes} vertices, {g.n_edges} directed edges; host generation "
        f"+ CSR upload {time.perf_counter() - t0:.2f}s")
    return g


def draw_queries(rng, sources, hot, count):
    """`count` (algo, source) pairs, round-robin over ALGOS, a HOT_FRAC share
    of them from the hot set (repeats the result cache can serve)."""
    out = []
    for i in range(count):
        pool = hot if rng.random() < HOT_FRAC else sources
        out.append((ALGOS[i % len(ALGOS)], int(rng.choice(pool))))
    return out


def update_batch(rng, edges, n, k):
    """`k` undirected inserts of absent vertex pairs and `k` deletes of
    present edges, all distinct — so the reference graph after the batch is
    the old edge list minus both directions of each delete plus both
    directions of each insert, with no parallel edges."""
    src, dst, _ = edges
    present = set()
    keys = np.sort(src * n + dst)
    ins = []
    while len(ins) < k:
        u, v = (int(x) for x in rng.integers(0, n, size=2))
        key = u * n + v
        i = np.searchsorted(keys, key)
        if (u == v or (i < keys.size and keys[i] == key)
                or (min(u, v), max(u, v)) in present):
            continue
        present.add((min(u, v), max(u, v)))
        ins.append((u, v, float(rng.integers(1, 65))))
    upper = np.nonzero(src < dst)[0]
    dels = [(int(src[e]), int(dst[e]))
            for e in rng.choice(upper, size=k, replace=False)]
    return ins, dels


def apply_batch(edges, ins, dels, n):
    """The reference edge list after one undirected update batch."""
    src, dst, w = edges
    gone = {u * n + v for u, v in dels} | {v * n + u for u, v in dels}
    keep = ~np.isin(src * n + dst, np.fromiter(gone, np.int64))
    ins_u = np.array([e[0] for e in ins], np.int64)
    ins_v = np.array([e[1] for e in ins], np.int64)
    ins_w = np.array([e[2] for e in ins], np.float64)
    return (np.concatenate([src[keep], ins_u, ins_v]),
            np.concatenate([dst[keep], ins_v, ins_u]),
            np.concatenate([w[keep], ins_w, ins_w]))


# -- serving ------------------------------------------------------------------


def make_server(g, programs, *, delta_cap=0, mesh=None, placements=None):
    """A GraphServer as the launch drivers build one (`serve_graph` packs
    the in-CSR itself; with `delta_cap` the streaming overlay packs it)."""
    from repro.graph import pack_ell
    from repro.serving import GraphServer, default_config

    pack = None if delta_cap else pack_ell(g.inc)
    return GraphServer(g, pack, programs, slots=SLOTS, cfg=default_config(g),
                       delta_cap=delta_cap, refresh_lanes=REFRESH_LANES,
                       mesh=mesh, placements=placements)


def serve(srv, queries):
    """Submit every query, pump until all have completed. Returns (the new
    completions, per-query seconds from submit to harvest, wall seconds).
    Completions carry host numpy results, so the device work is done."""
    start = len(srv.completions)
    t0 = time.perf_counter()
    t_sub = {}
    for algo, src in queries:
        rid = srv.submit(algo, src, strict=True)
        t_sub[rid] = time.perf_counter()
    lat = {}

    def collect():
        now = time.perf_counter()
        for c in srv.completions[start + len(lat):]:
            lat[c.rid] = now - t_sub[c.rid]

    collect()
    while len(lat) < len(queries):
        srv.pump()
        collect()
    wall = time.perf_counter() - t0
    comps = srv.completions[start:]
    dropped = [c.rid for c in comps if c.result is None]
    if dropped:
        raise RuntimeError(f"dropped completions {dropped}")
    return comps, np.array([lat[c.rid] for c in comps]), wall


def report_batch(name, comps, lat, wall):
    hits = sum(c.from_cache for c in comps)
    log(f"{name}: {len(comps)} queries in {wall:.3f}s "
        f"({len(comps) / wall:.2f} q/s), latency p50 "
        f"{np.percentile(lat, 50) * 1e3:.1f}ms p99 "
        f"{np.percentile(lat, 99) * 1e3:.1f}ms, {hits} cache hits")


# -- reference -----------------------------------------------------------------


def graph_matrices(edges, n):
    """Host CSR matrices of one graph version: the weighted adjacency (the
    generator dedupes and the update batch adds only absent pairs, so no
    entry is a sum of parallel edges) and the walk matrix of PPR (dangling
    mass dropped, as in the engine)."""
    import scipy.sparse as sp

    src, dst, w = edges
    adj = sp.csr_matrix((w, (src, dst)), shape=(n, n))
    deg = np.maximum(np.bincount(src, minlength=n), 1)
    walk = sp.csr_matrix((1.0 / deg[src], (dst, src)), shape=(n, n))
    return adj, walk


def shortest_paths(adj, sources, algo):
    """(k, n) scipy BFS levels or Dijkstra distances, inf where unreached."""
    from scipy.sparse import csgraph

    return csgraph.dijkstra(adj, indices=sources, unweighted=(algo == "bfs"))


def ppr_power(walk, sources, damping):
    """(k, n) float64 power iteration r = (1-d)·e_s + d·walk·r, run until
    no column's L1 change reaches 1e-8."""
    pref = np.zeros((walk.shape[0], len(sources)))
    pref[sources, np.arange(len(sources))] = 1.0
    r = pref.copy()
    for _ in range(1000):
        nxt = (1 - damping) * pref + damping * (walk @ r)
        change = np.abs(nxt - r).sum(axis=0).max()
        r = nxt
        if change < 1e-8:
            break
    return r.T


def check(comp, want, ppr_bound) -> bool:
    """BFS/SSSP as the tier-1 oracle tests compare; PPR within the residual
    formulation's per-vertex guarantee `ppr_bound` (see `verify`)."""
    if comp.algo == "ppr_delta":
        miss = want - np.asarray(comp.result, np.float64)
        # only a cached answer served after an update can carry a fixpoint
        # resumed across it, with residuals of either sign (`serve` drains
        # before each update, so no lane is in flight across one)
        low = -ppr_bound if comp.from_cache and comp.graph_version else 0.0
        return bool(np.all(miss >= low - PPR_FP_SLACK)
                    and np.all(miss <= ppr_bound + PPR_FP_SLACK))
    got = np.asarray(comp.result, np.float64).copy()
    got[got > 1e30] = np.inf           # the engine's BIG marks unreached
    return bool(np.allclose(got, want))


def verify(comps, edges_by_version, n, ppr) -> int:
    """Check every completion against the reference on its graph version;
    returns the number that matched. The reference solves run on host
    threads (scipy's sparse products release the GIL).

    ppr_delta stops pushing once every residual r_u is within tol·deg(u)
    of zero, and the exact PPR at x is its rank plus Σ_u r_u·ppr_u(x). On
    an undirected graph deg(u)·ppr_u(x) = deg(x)·ppr_x(u), so that sum is
    at most tol·deg(x) in size (Andersen-Chung-Lang). A cold run's
    residuals are non-negative, so its rank may only fall short:
    0 <= ppr - rank <= tol·deg(x). A fixpoint resumed across an update
    carries Maiter-corrected residuals, negative where edges were deleted,
    so there |ppr - rank| <= tol·deg(x). Both are held per vertex; a fixed
    absolute bound fits only small graphs, where no degree is large."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    tol, damping = ppr.param("tol"), ppr.param("damping")
    t0 = time.perf_counter()
    bound, jobs, mats = {}, {}, {}
    for ver, edges in edges_by_version.items():
        bound[ver] = tol * np.maximum(np.bincount(edges[0], minlength=n), 1)
        mats[ver] = graph_matrices(edges, n)
    srcs = {(ver, algo): sorted({c.source for c in comps
                                 if c.algo == algo and c.graph_version == ver})
            for ver in edges_by_version for algo in ALGOS}
    with ThreadPoolExecutor(os.cpu_count()) as pool:
        # the power iterations first, one source per thread: Dijkstra holds
        # the GIL, so it runs after them
        for (ver, algo), ss in srcs.items():
            if algo == "ppr_delta":
                for s in ss:
                    jobs[(ver, algo, (s,))] = pool.submit(
                        ppr_power, mats[ver][1], [s], damping)
        for (ver, algo), ss in srcs.items():
            if algo != "ppr_delta" and ss:
                jobs[(ver, algo, tuple(ss))] = pool.submit(
                    shortest_paths, mats[ver][0], ss, algo)
        want = {(ver, algo, s): row
                for (ver, algo, ss), job in jobs.items()
                for s, row in zip(ss, job.result())}
    log(f"reference: {len(want)} (version, algorithm, source) solves in "
        f"{time.perf_counter() - t0:.1f}s")
    ok = 0
    for c in comps:
        if check(c, want[(c.graph_version, c.algo, c.source)],
                 bound[c.graph_version]):
            ok += 1
        else:
            log(f"MISMATCH rid={c.rid} {c.algo}(src={c.source}) "
                f"v{c.graph_version} cache={c.from_cache}")
    return ok


# -- phases -------------------------------------------------------------------


def smoke_one_chip(scale: int, seed: int) -> None:
    """Serve, stream and verify on the default device; raises on failure."""
    import jax
    from repro.launch.catalog import make_catalog

    clock = CompileClock()
    g = build_graph(scale, seed)
    n = g.n_nodes
    edges = {0: host_edges(g)}
    deg = np.bincount(edges[0][0], minlength=n)
    rng = np.random.default_rng(seed)
    sources = np.nonzero(deg)[0]
    hot = rng.choice(sources, size=3, replace=False)

    catalog = make_catalog()
    t0 = time.perf_counter()
    srv = make_server(g, {a: catalog[a] for a in ALGOS}, delta_cap=DELTA_CAP)
    slots = sum(int(s.nbr.size) for s in srv.sg.pack.slices)
    log(f"server: {len(ALGOS)} pools x {SLOTS} slots, ELL {slots} slots; "
        f"build (ELL pack + upload) {time.perf_counter() - t0:.2f}s")

    mark = clock.mark()
    comps, lat, wall = serve(srv, draw_queries(rng, sources, hot, QUERIES))
    log(f"compile: {clock.since(mark)}, paid by the first query of each pool")
    report_batch("cold batch (compile included)", comps, lat, wall)
    mark = clock.mark()
    comps, lat, wall = serve(srv, draw_queries(rng, sources, hot, QUERIES))
    report_batch("warm batch", comps, lat, wall)
    log(f"warm batch compile: {clock.since(mark)}")

    ins, dels = update_batch(rng, edges[0], n, UPDATE_EDGES)
    t0 = time.perf_counter()
    rep = srv.apply_updates(ins, dels)
    log(f"update v{rep['version']}: +{rep['inserted']} -{rep['deleted']} "
        f"directed edges in {time.perf_counter() - t0:.2f}s; cache retained "
        f"{rep['cache_retained']} refreshed {rep['cache_refreshed']} dropped "
        f"{rep['cache_dropped']}")
    if (rep["inserted"], rep["deleted"]) != (2 * len(ins), 2 * len(dels)):
        raise RuntimeError(f"the update batch was not applied whole: {rep}")
    edges[rep["version"]] = apply_batch(edges[0], ins, dels, n)
    # re-query: half the sources served before the update (cache entries
    # refreshed or re-keyed), half fresh draws
    before = [(c.algo, c.source) for c in srv.completions]
    again = [before[i] for i in rng.choice(len(before), QUERIES // 2,
                                          replace=False)]
    comps, lat, wall = serve(
        srv, again + draw_queries(rng, sources, hot, QUERIES - len(again)))
    report_batch(f"after update (v{rep['version']})", comps, lat, wall)

    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak device memory: "
        f"{stats.get('peak_bytes_in_use', 'not reported')} bytes")

    comps = srv.completions
    ok = verify(comps, edges, n, catalog["ppr_delta"])
    log(f"verify: {ok}/{len(comps)}")
    if ok != len(comps):
        raise RuntimeError(f"{len(comps) - ok} completions mismatched")


def shard_map_of(arr) -> str:
    """'device id -> index' for every shard of a placed array."""
    idx = arr.sharding.devices_indices_map(arr.shape)
    parts = []
    for dev, sl in sorted(idx.items(), key=lambda kv: kv[0].id):
        spans = ",".join(
            ":" if s == slice(None) else f"{s.start or 0}:{s.stop}"
            for s in sl)
        parts.append(f"dev{dev.id}[{spans}]")
    return " ".join(parts)


def smoke_four_chips(scale: int, seed: int, chips: int) -> None:
    """Sharded pools on `chips` devices vs one-chip pools, one process."""
    import jax
    from repro.launch.catalog import make_catalog
    from repro.serving import Placement, make_serving_mesh

    clock = CompileClock()
    g = build_graph(scale, seed)
    n = g.n_nodes
    deg = np.bincount(np.asarray(g.out.src_idx), minlength=n)
    rng = np.random.default_rng(seed)
    sources = np.nonzero(deg)[0]
    hot = rng.choice(sources, size=3, replace=False)
    queries = draw_queries(rng, sources, hot, QUERIES)
    catalog = make_catalog()
    programs = {a: catalog[a] for a in ALGOS}

    def run(label, **kw):
        mark = clock.mark()
        srv = make_server(g, programs, **kw)
        comps, lat, wall = serve(srv, queries)
        report_batch(label, comps, lat, wall)
        log(f"{label} compile: {clock.since(mark)}")
        res = {(c.algo, c.source): c.result for c in comps}
        return srv, res

    srv, base = run("one chip")
    del srv
    gc.collect()
    failures = 0
    for kind, shape in (("replicated", (chips, 1)), ("edge_sharded", (1, chips))):
        mesh = make_serving_mesh(*shape)
        srv, res = run(f"{kind} {shape[0]}x{shape[1]}", mesh=mesh,
                       placements={a: Placement(kind, chips) for a in ALGOS})
        pool = srv.pools["bfs"]
        plane = pool.state.m[pool.result_field]
        log(f"{kind} lane plane {plane.shape} shards: {shard_map_of(plane)}")
        if kind == "edge_sharded":
            es = pool.engine.esrc
            log(f"{kind} edge rows {es.shape} shards: {shard_map_of(es)}")
        if len(plane.sharding.device_set) != chips:
            raise RuntimeError(f"{kind} placed on {plane.sharding.device_set}, "
                               f"not {chips} chips")
        for (algo, src), want in base.items():
            got = res[(algo, src)]
            if kind == "edge_sharded" and catalog[algo].combiner.name == "sum":
                same = np.allclose(got, want, rtol=SUM_RTOL, atol=SUM_ATOL)
                rule = f"allclose rtol={SUM_RTOL} atol={SUM_ATOL}"
            else:
                same = np.array_equal(got, want)
                rule = "bit-equal"
            diff = float(np.abs(np.asarray(got, np.float64) - want).max())
            log(f"{kind} {algo}(src={src}) vs one chip: "
                f"{'OK' if same else 'MISMATCH'} ({rule}, max|diff| {diff:.3g})")
            failures += not same
        del srv, pool, plane
        gc.collect()
    if failures:
        raise RuntimeError(f"{failures} sharded answers differ from one chip")
    log(f"sharded vs one chip: all {2 * len(base)} answers agree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded pools vs one-chip pools")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 2

    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    t0 = time.perf_counter()
    if args.chips == 1:
        smoke_one_chip(SCALE, args.seed)
    else:
        smoke_four_chips(SCALE, args.seed, args.chips)
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
