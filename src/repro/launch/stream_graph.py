"""Streaming graph serving driver: replay an update trace against queries.

The dynamic-graph extension of `launch/serve_graph.py` (DESIGN.md §8): an
irregular stream of point queries is served by the batched engine while the
graph itself mutates underneath — every `--update-every` submitted queries,
a batch of random edge insertions/deletions is applied through
`GraphServer.apply_updates`, which swaps the delta overlay into the pools,
selectively invalidates the result cache (clean sources keep their entries,
dirty monotone entries are refreshed incrementally), and restarts dirtied
in-flight queries.

  PYTHONPATH=src python -m repro.launch.stream_graph --requests 24 --slots 4

`--mesh DxS` streams through SHARDED pools (DESIGN.md §9/§11) — updates
then exercise the touched-delta slice shipping and, with
`--placement edge_sharded`, the frontier-compacted per-shard expansion and
CSR-free admission; needs D*S jax devices (forced host mesh, see
serve_graph).

With `--verify`, every completion is checked against a from-scratch run on
the graph version it was served under (slow; testing only).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.launch import compile_cache
from repro.launch.catalog import algos_argtype, make_catalog, result_fields
from repro.obs.trace import add_obs_cli_args, finish_obs_cli, obs_from_cli
from repro.streaming.incremental import is_residual
from repro.serving import (
    GraphServer,
    Placement,
    default_config,
    make_serving_mesh,
    query_result,
    run_batch,
)
from repro.launch.serve_graph import build_graph


def random_update_batch(rng, sg, n_ins, n_del):
    """Inserts are uniform random pairs; deletes sample LIVE base edges."""
    n = sg.n
    ins = [(int(rng.integers(0, n)), int(rng.integers(0, n)),
            float(rng.integers(1, 65))) for _ in range(n_ins)]
    live = np.nonzero(~sg._dead_out)[0]
    dels = []
    if live.size and n_del:
        for e in rng.choice(live, size=min(n_del, live.size), replace=False):
            dels.append((int(sg._base_src_host()[e]), int(sg._out_ci[e])))
    return ins, dels


def main(argv=None):
    catalog = make_catalog()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--graph", default="rmat", choices=("rmat", "uniform", "road"))
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--algos", default="bfs,sssp,ppr",
                    type=algos_argtype(catalog),
                    help=f"comma list from the registered catalog: "
                         f"{', '.join(sorted(catalog))}")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--update-every", type=int, default=8,
                    help="apply an update batch every N submitted queries")
    ap.add_argument("--inserts", type=int, default=4, help="insertions per batch")
    ap.add_argument("--deletes", type=int, default=2, help="deletions per batch")
    ap.add_argument("--delta-cap", type=int, default=256)
    ap.add_argument("--cache-cap", type=int, default=256)
    ap.add_argument("--hot-frac", type=float, default=0.25)
    ap.add_argument("--refresh", default="incremental",
                    choices=("incremental", "drop"))
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="stream through sharded pools on a DxS ('data' x "
                         "'model') mesh, e.g. 8x1 or 1x4; empty = "
                         "single-device pools")
    ap.add_argument("--placement", default="replicated",
                    choices=("replicated", "edge_sharded"),
                    help="pool placement on the --mesh")
    add_obs_cli_args(
        ap, trace_help="write per-request lifecycle spans as JSON lines "
                       "to this path (implies --telemetry); spans carry "
                       "the graph version each request completed on")
    args = ap.parse_args(argv)
    compile_cache.enable()

    g = build_graph(args.graph, args.scale, args.edge_factor, args.seed)
    n = g.n_nodes
    print(f"[stream_graph] {args.graph} scale={args.scale}: "
          f"{n} nodes, {g.n_edges} directed edges, delta_cap={args.delta_cap}")

    algos = args.algos                       # validated at argparse time
    programs = {a: catalog[a] for a in algos}

    mesh = None
    placements = None
    if args.mesh:
        try:
            d, s = (int(x) for x in args.mesh.lower().split("x"))
        except ValueError:
            ap.error(f"--mesh must look like DxS (e.g. 8x1), got {args.mesh!r}")
        mesh = make_serving_mesh(d, s)
        n_shards = d if args.placement == "replicated" else s
        placements = {a: Placement(args.placement, n_shards) for a in algos}
        if args.slots % d:
            ap.error(f"--slots {args.slots} must divide over {d} query shards")
        print(f"[stream_graph] sharded pools: mesh {d}x{s}, "
              f"placement={args.placement}")

    srv = GraphServer(
        g, None, programs, slots=args.slots, cfg=default_config(g),
        cache_capacity=args.cache_cap, delta_cap=args.delta_cap,
        # pools default each algo's served field from its declared
        # 'result' param
        mesh=mesh, placements=placements,
        obs=obs_from_cli(args),
    )
    # version -> overlay views, for --verify of historical completions.
    # Only kept under --verify: each version pins full-size device arrays,
    # so an unbounded replay must not retain them.
    snapshots = {0: (srv.sg.graph, srv.sg.pack, srv.sg.delta)} \
        if args.verify else None

    rng = np.random.default_rng(args.seed)
    hot = rng.integers(0, n, size=max(1, args.requests // 8))
    t0 = time.time()
    for i in range(args.requests):
        algo = algos[i % len(algos)]
        src = int(rng.choice(hot)) if rng.random() < args.hot_frac \
            else int(rng.integers(0, n))
        rid = srv.submit(algo, src)
        while rid is None:
            srv.pump()
            rid = srv.submit(algo, src)
        srv.pump()                       # keep lanes busy while submitting
        if (i + 1) % args.update_every == 0:
            ins, dels = random_update_batch(
                rng, srv.sg, args.inserts, args.deletes)
            st = srv.apply_updates(ins, dels, refresh=args.refresh)
            if snapshots is not None:
                snapshots[st["version"]] = (
                    srv.sg.graph, srv.sg.pack, srv.sg.delta)
            print(f"[stream_graph] update v{st['version']}: "
                  f"+{st['inserted']}/-{st['deleted']} edges, "
                  f"cache retained {st['cache_retained']} "
                  f"refreshed {st['cache_refreshed']} "
                  f"dropped {st['cache_dropped']}, "
                  f"re-enqueued {st['reenqueued_inflight']}, "
                  f"resumed {st['resumed_inflight']}, "
                  f"rebuild={st['rebuild']}")
    comps = srv.drain()
    dt = time.time() - t0

    stats = srv.stats()
    finish_obs_cli(srv, args, "stream_graph")
    print(f"[stream_graph] {len(comps)} completions in {dt:.2f}s "
          f"({len(comps) / dt:.1f} q/s) across "
          f"{stats['updates']} update batches "
          f"(graph now v{stats['graph_version']}, "
          f"{srv.sg.stats()['rebuilds']} rebuilds)")
    cache = stats["cache"]
    print(f"[stream_graph] cache: {cache['hits']} hits / {cache['misses']} "
          f"misses (hit rate {cache['hit_rate']:.0%}), size {cache['size']}")

    if args.verify:
        fields = result_fields(programs)
        bad = 0
        for c in comps:
            ver = c.graph_version
            gv, pv, dv = snapshots[ver]
            ref, _ = run_batch(programs[c.algo], gv, pv,
                               default_config(g), [c.source], delta=dv)
            want = np.asarray(query_result(ref, fields[c.algo], 0))
            if is_residual(programs[c.algo]):
                # residual lanes RESUMED across an update are tol-accurate
                # (mid-run Maiter correction, DESIGN.md §10), not bitwise —
                # metadata dispatch: ANY residual-form program, by contract
                ok = np.abs(c.result - want).max() < 1e-3
            else:
                ok = np.array_equal(c.result, want)
            if not ok:
                bad += 1
                print(f"  MISMATCH rid={c.rid} {c.algo}({c.source}) v{ver}")
        print(f"[stream_graph] verify: {len(comps) - bad}/{len(comps)} OK")
        return 1 if bad else 0
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
