"""Open-loop SLO benchmark: arrival processes x deadline policy + isolation.

An open-loop benchmark (a closed loop can never overrun the server): seeded
multi-tenant workloads (repro.slo) are
fired at `GraphServer` on the wall clock, submission times taken from the
arrival spec — never from completions — so overload shows up as shed/dropped
queries and p99 inflation instead of a self-throttled arrival clock.

Two experiments (DESIGN.md §13), one BENCH_slo.json record:

  * **arrivals x policy** — a paid (bfs+sssp, tight deadline, hot-set skew)
    + batch (ppr_delta, loose deadline) tenant mix replayed under both a
    Poisson and a bursty MMPP clock, each against a baseline server
    (deadlines accounted, no enforcement) and a policy server
    (expired/hopeless drops + degraded ppr_delta shadow pool + lane
    preemption). Reports p50/p95/p99 latency, goodput, and the full
    shed/drop/degrade/preempt accounting per cell.
  * **isolation** — one ppr_delta pool shared by a light tenant (uniform
    sources, deadline-bearing) and a heavy tenant (hub sources,
    best-effort). The SAME seeded arrival list replays against pooled
    consensus (one 32-lane batch) and tenant-affine cohorts (8 leaves;
    heavy pinned to cohort 0, light to cohorts 1-2, with
    `cohort_burst=2` / `best_effort_stride=2` cadence). The measured cost
    model drives the design: a batched step prices by ALLOCATED lanes Q
    plus an m-bound constant — never by live content — so the pooled
    batch charges every light query the full-Q step price for as long as
    ANY lane is live, while affine cohorts serve light queries from a
    narrow leaf and spend step rounds preferentially on deadline-bearing
    leaves (best-effort leaves stride). `pass_isolation` gates on the
    light tenant's p99 (or overall goodput) improving. Both cells run the
    SAME SLOPolicy — pooled serving is structurally unable to use the
    cadence knobs (one leaf), which is the point.

The MMPP+policy cell also writes its lifecycle spans (slo outcomes
included) to a JSONL trace validated against scripts/trace_schema.py
(`pass_spans_valid`).

  PYTHONPATH=src python benchmarks/slo_bench.py [--small]

Writes BENCH_slo.json (linted by scripts/bench_schema.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from repro.core import algorithms as alg
from repro.graph import generators, pack_ell
from repro.serving import GraphServer, default_config
from repro.slo import (
    SLOPolicy,
    TenantClass,
    Workload,
    describe,
    generate,
    replay,
    warmup,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
import trace_schema            # noqa: E402

MIX_ALGOS = ("bfs", "sssp", "ppr_delta")


def _programs(algos):
    factories = {"bfs": alg.bfs(0), "sssp": alg.sssp(0),
                 "ppr_delta": alg.ppr_delta(0)}
    return {a: factories[a] for a in algos}


def _server(g, pack, algos, *, slots, tenant_weights, policy=None,
            cohorts=None, affinity=None, trace=None):
    return GraphServer(
        g, pack, _programs(algos), slots=slots, cfg=default_config(g),
        queue_cap=256, result_fields={"ppr_delta": "rank"},
        tenant_weights=tenant_weights,
        cohorts=cohorts, slo=policy, cohort_affinity=affinity,
        telemetry=trace is not None, trace=trace,
    )


def _cell(srv, arrivals, *, max_wall_s):
    warmup(srv, {a: 1 for a in srv.pools})
    report = replay(srv, arrivals, max_wall_s=max_wall_s)
    slo_stats = srv.stats()["slo"]
    srv.obs.close()
    rec = report.to_json()
    rec["slo_counts_total"] = {k: slo_stats[k]
                              for k in ("deadline_missed", "dropped",
                                        "degraded", "preempted")}
    return rec, report


def _fmt(tag, r):
    t = r.total or {}
    p = (f"p50={t.get('p50_seconds', 0) * 1e3:7.1f}ms "
         f"p99={t.get('p99_seconds', 0) * 1e3:7.1f}ms") if r.total else "n=0"
    print(f"[slo_bench] {tag:24s} offered={r.offered:4d} good={r.good:4d} "
          f"shed={r.shed:3d} drop={r.dropped:3d} degr={r.degraded:3d} "
          f"pre={r.preempted:2d} goodput={r.goodput:.3f} {p}")


def run_mix(g, pack, args, trace_path):
    """arrivals x policy grid on the paid/batch tenant mix."""
    tenants = (
        TenantClass("paid", 2.0, (("bfs", 2.0), ("sssp", 1.0)),
                    deadline_ms=args.deadline_ms, hot_frac=0.3),
        TenantClass("batch", 1.0, (("ppr_delta", 1.0),),
                    deadline_ms=4 * args.deadline_ms),
    )
    weights = {"paid": 2.0, "batch": 1.0}
    policy = SLOPolicy(
        hopeless_margin=1.0,
        degrade_algos=("ppr_delta",),
        degrade_slots=max(2, args.slots // 4),
        degrade_queue_depth=max(2, args.slots // 2),
        preempt=True,
        preempt_slack_s=args.deadline_ms / 1e3 / 4,
        preempt_min_resident_s=args.deadline_ms / 1e3 / 4,
    )
    out = {}
    wl_desc = None
    for arrival in ("poisson", "mmpp"):
        w = Workload(arrival=arrival, rate_qps=args.rate,
                     duration_s=args.duration, burst_factor=6.0,
                     tenants=tenants, seed=args.seed)
        arrivals = generate(w, g.n_nodes)
        wl_desc = wl_desc or describe(w)
        cells = {}
        for label, pol in (("baseline", None), ("slo", policy)):
            srv = _server(g, pack, MIX_ALGOS, slots=args.slots,
                          tenant_weights=weights, policy=pol)
            rec, rep = _cell(srv, arrivals,
                             max_wall_s=4 * args.duration + 60)
            _fmt(f"{arrival}/{label}", rep)
            cells[label] = rec
        out[arrival] = {"n_arrivals": len(arrivals), **cells}

    # dedicated traced replay (mmpp + policy): telemetry/span recording has
    # its own cost, so it stays OUT of the baseline-vs-policy comparison —
    # this cell exists to validate slo span plumbing end-to-end under load
    w = Workload(arrival="mmpp", rate_qps=args.rate / 2,
                 duration_s=args.duration / 2, burst_factor=6.0,
                 tenants=tenants, seed=args.seed + 1)
    srv = _server(g, pack, MIX_ALGOS, slots=args.slots,
                  tenant_weights=weights, policy=policy, trace=trace_path)
    traced_rec, traced_rep = _cell(srv, generate(w, g.n_nodes),
                                   max_wall_s=4 * args.duration + 60)
    _fmt("mmpp/traced", traced_rep)
    return out, traced_rec, wl_desc, policy.describe()


def run_isolation(args):
    """Same seeded heavy+light ppr_delta stream, pooled vs affine cohorts.

    Runs on its OWN graph scale (`--iso-scale`, default 15): the cohort win
    needs the per-lane `b*Q` step-cost term to dominate the m-bound
    constant `a` (cost model in the module docstring) — at small scales
    `a` dominates and fragmenting the batch only multiplies it."""
    g = generators.rmat(args.iso_scale, args.edge_factor, seed=args.seed,
                        directed=True)
    pack = pack_ell(g.inc)
    print(f"[slo_bench] isolation graph: rmat scale={args.iso_scale} "
          f"({g.n_nodes} nodes, {g.n_edges} edges), slots={args.iso_slots}, "
          f"{args.cohorts} cohorts, {args.iso_rate:.0f} q/s x "
          f"{args.iso_duration:.0f}s")
    deg = np.asarray(g.out.degrees())
    hubs = tuple(int(v) for v in np.argsort(deg)[-4:])
    tenants = (
        TenantClass("light", 6.0, (("ppr_delta", 1.0),),
                    deadline_ms=2 * args.deadline_ms),
        TenantClass("heavy", 1.0, (("ppr_delta", 1.0),), sources=hubs),
    )
    weights = {"light": 1.0, "heavy": 1.0}
    w = Workload(arrival="mmpp", rate_qps=args.iso_rate,
                 duration_s=args.iso_duration, burst_factor=6.0,
                 tenants=tenants, seed=args.seed + 7)
    arrivals = generate(w, g.n_nodes)
    # no drop/degrade/preempt: the comparison isolates the cohort knobs —
    # every query completes, so latency samples cover identical query sets
    policy = SLOPolicy(drop_expired=False, cohort_burst=2,
                       best_effort_stride=2)
    affinity = {"heavy": [0], "light": [1, 2]}
    cells = {}
    for label, cohorts, aff in (
            ("pooled", None, None),
            ("cohorts", {"ppr_delta": args.cohorts}, affinity)):
        srv = _server(g, pack, ("ppr_delta",), slots=args.iso_slots,
                      tenant_weights=weights, policy=policy,
                      cohorts=cohorts, affinity=aff)
        rec, rep = _cell(srv, arrivals,
                         max_wall_s=4 * args.iso_duration + 60)
        lt = rec["per_tenant"].get("light")
        _fmt(f"isolation/{label}", rep)
        if lt:
            print(f"[slo_bench]   light tenant: "
                  f"p50={lt['p50_seconds'] * 1e3:.1f}ms "
                  f"p99={lt['p99_seconds'] * 1e3:.1f}ms (n={lt['n']})")
        cells[label] = rec
    p99 = {k: (c["per_tenant"].get("light") or {}).get("p99_seconds")
           for k, c in cells.items()}
    p99_improved = (p99["pooled"] is not None and p99["cohorts"] is not None
                    and p99["cohorts"] < p99["pooled"])
    goodput_improved = cells["cohorts"]["goodput"] > cells["pooled"]["goodput"]
    return {
        "workload": describe(w),
        "graph": {"kind": "rmat", "scale": args.iso_scale,
                  "n_nodes": int(g.n_nodes), "n_edges": int(g.n_edges)},
        "hub_sources": list(hubs),
        "cohorts_k": args.cohorts,
        "slots": args.iso_slots,
        "cohort_affinity": affinity,
        "policy": policy.describe(),
        "pooled": cells["pooled"],
        "cohorts": cells["cohorts"],
        "light_p99_pooled_vs_cohorts": [p99["pooled"], p99["cohorts"]],
        "p99_improved": bool(p99_improved),
        "goodput_improved": bool(goodput_improved),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="time-averaged q/s — chosen so the bursty MMPP "
                         "phases genuinely overload the server (poisson at "
                         "the same average stays within capacity)")
    ap.add_argument("--duration", type=float, default=12.0,
                    help="per-cell replay window; long enough to average "
                         "several MMPP burst cycles (short windows make "
                         "the overload cells bistable run-to-run)")
    ap.add_argument("--deadline-ms", type=float, default=300.0)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cohorts", type=int, default=8)
    ap.add_argument("--iso-scale", type=int, default=15,
                    help="graph scale for the isolation experiment (large "
                         "enough that per-lane step cost dominates the "
                         "m-bound constant)")
    ap.add_argument("--iso-slots", type=int, default=32)
    ap.add_argument("--iso-rate", type=float, default=10.0)
    ap.add_argument("--iso-duration", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="smoke-size run (scale 9, 3s, 30 q/s; shorter "
                         "isolation replay at the same scale — the cohort "
                         "win is scale-dependent)")
    ap.add_argument("--out", default="BENCH_slo.json")
    args = ap.parse_args(argv)
    if args.small:
        args.scale, args.duration, args.rate = 9, 3.0, 30.0
        args.iso_duration = 5.0

    g = generators.rmat(args.scale, args.edge_factor, seed=args.seed,
                        directed=True)
    pack = pack_ell(g.inc)
    print(f"[slo_bench] rmat scale={args.scale}: {g.n_nodes} nodes, "
          f"{g.n_edges} edges; {args.rate:.0f} q/s x {args.duration:.0f}s "
          f"per cell, deadline {args.deadline_ms:.0f}ms")

    trace_path = "/tmp/repro_slo_bench_trace.jsonl"
    arrivals_grid, traced_rec, wl_desc, pol_desc = run_mix(
        g, pack, args, trace_path)
    isolation = run_isolation(args)

    n_spans, span_errs = trace_schema.check(trace_path)
    print(f"[slo_bench] trace mmpp/slo: {n_spans} spans, "
          f"{len(span_errs)} problems")

    cells = [proc[k] for proc in arrivals_grid.values()
             for k in ("baseline", "slo")]
    cells += [isolation["pooled"], isolation["cohorts"], traced_rec]
    goodput_ok = all(c["goodput"] > 0 and c["crashed_lanes"] == 0
                     for c in cells)
    rec = {
        "bench": "slo_open_loop",
        "graph": {"kind": "rmat", "scale": args.scale,
                  "n_nodes": int(g.n_nodes), "n_edges": int(g.n_edges)},
        "workload": wl_desc,
        "policy": pol_desc,
        "arrivals": arrivals_grid,
        "traced_run": traced_rec,
        "isolation": isolation,
        "pass_goodput_positive": bool(goodput_ok),
        "pass_isolation": bool(isolation["p99_improved"]
                               or isolation["goodput_improved"]),
        "pass_spans_valid": not span_errs,
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    print(f"[slo_bench] wrote {args.out} "
          f"(goodput_positive={rec['pass_goodput_positive']}, "
          f"isolation={rec['pass_isolation']}, "
          f"spans_valid={rec['pass_spans_valid']})")
    return 0 if (rec["pass_goodput_positive"] and rec["pass_isolation"]
                 and rec["pass_spans_valid"]) else 1


if __name__ == "__main__":
    raise SystemExit(main())
