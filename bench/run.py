#!/usr/bin/env python3
"""One benchmark run of one cell on the chip this process finds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from `BENCHMARK.json`; its configuration from the file that
names (`bench/configs/`), its traffic mix from `bench/traffic/<mix>.json`,
each metric's reader from `bench/metrics/<metric>.py` and each program's
plain reference from `bench/reference/<program>.py`: a new cell is new files
and one `workloads` entry.

A run: generate the Graph500 graph and the traffic from `--seed`; build the
server through the program's public constructors (`from_edges`, `pack_ell`,
`GraphServer`); warm up every shape the cell uses; measure for `--seconds`;
drain; read the peak memory of each device the server uses and the lanes'
push/pull modes; free the server; compare a sample of the answers with the
reference. With `--trace 1` the window runs under the profiler and the run
reports the per-layer metrics instead of the end-to-end ones. The last line
of stdout is the result as one JSON object; the last lines of stderr are
the compared numbers beside their limits.

A configuration places its pools on a mesh with `"mesh": [D, 1]`: every
program's pool is then replicated over D query shards, each chip holding the
whole graph and `lanes` / D of the pool's lanes. D must equal the cell's
`chips`; edge shards (S > 1) are refused until a cell needs them. Without
the key the pools live on the first device.

With no TPU, fewer chips than the cell asks for, or a mesh that is not the
cell's chips, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: longest the drain after the window may take before a request is failed
DRAIN_S = 120.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_spec(root: Path, workload: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, cfg, mix


def metrics_of(bench: dict, cell: dict, trace: bool):
    """The metric entries this cell reports in this kind of run."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def reader(root: Path, metric: str):
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def build_programs(cfg: dict):
    """The cell's programs through the program's public constructors, at
    the parameters the configuration states."""
    from repro.core import algorithms as alg

    return {name: getattr(alg, name)(0, **params)
            for name, params in cfg["programs"].items()}


def mesh_of(cfg: dict, chips: int):
    """The query shards of a configuration that places its pools on a mesh,
    None of one that does not; ValueError where `mesh` is not a replicated
    mesh of the cell's `chips`."""
    if "mesh" not in cfg:
        return None
    d, s = (int(x) for x in cfg["mesh"])
    if d * s != chips:
        raise ValueError(f"mesh {d}x{s} holds {d * s} chips, the cell "
                         f"asks for {chips}")
    if s != 1:
        raise ValueError(f"mesh {d}x{s}: no cell shards edges yet, so a "
                         "mesh is [D, 1], D query shards")
    if int(cfg["lanes"]) % d:
        raise ValueError(f"{cfg['lanes']} lanes do not divide over {d} "
                         "query shards")
    return d


def build_server(cfg: dict, edges, shards=None):
    """The server of `cfg` on `edges`; `shards` is `mesh_of(cfg, ...)`."""
    from repro.graph import csr, pack_ell
    from repro.serving import GraphServer, default_config

    import jax

    t0 = time.perf_counter()
    src, dst, w = edges.one_direction()
    g = csr.from_edges(src, dst, edges.n, w, directed=False)
    jax.block_until_ready(g)
    t1 = time.perf_counter()
    if g.n_edges != edges.src.size:
        raise RuntimeError(f"the program's graph has {g.n_edges} edges, the "
                           f"benchmark's {edges.src.size}")
    delta_cap = int(cfg["delta_cap"])
    pack = None if delta_cap else pack_ell(g.inc)
    programs = build_programs(cfg)
    placed = {}
    if shards is not None:
        from repro.serving import Placement, make_serving_mesh

        placed = {"mesh": make_serving_mesh(shards, 1),
                  "placements": {p: Placement("replicated", shards)
                                 for p in programs}}
    srv = GraphServer(g, pack, programs, slots=int(cfg["lanes"]),
                      cfg=default_config(g),
                      cache_capacity=int(cfg["cache_capacity"]),
                      delta_cap=delta_cap,
                      refresh_lanes=int(cfg["refresh_lanes"]), **placed)
    for grp in srv.pool_groups.values():
        for pool in grp:
            jax.block_until_ready(pool.state)
    return srv, t1 - t0, time.perf_counter() - t1


def server_devices(srv) -> list:
    """The devices the server's pools live on: its mesh's, else the first."""
    import jax

    return (list(srv.mesh.devices.flat) if srv.mesh is not None
            else jax.devices()[:1])


def memory_peaks(devices) -> list:
    """Each device's peak bytes in use so far (0 where it reports none)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def enable_compile_cache() -> None:
    """The program's persistent compile cache (`JAX_COMPILATION_CACHE_DIR`
    where it is set, else the checkout's `.jax_cache`), with every program
    in it, so that only a checkout's first run of a cell compiles."""
    import jax
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run(args, root: Path = ROOT, chip: bool = True) -> int:
    """One run; `chip=False` (the CPU tests) skips the look for a TPU and
    the compile cache."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    bench, cell, cfg, mix = load_spec(root, args.workload)
    try:
        shards = mesh_of(cfg, int(cell["chips"]))
    except ValueError as e:
        print(f"bench: {cell['name']}: {e}", file=sys.stderr)
        return 2
    if chip and dev.platform != "tpu":
        print(f"bench: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < int(cell["chips"]):
        print(f"bench: {cell['name']} needs {cell['chips']} chips; JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    if chip:
        enable_compile_cache()

    from bench import check, drive, trace_phases, traffic, trace_reduce
    from bench.gen import graph500

    clock = drive.CompileClock()
    parts = {}
    t = time.perf_counter()
    edges = graph500.for_config(cfg, args.seed)
    parts["generate"] = time.perf_counter() - t
    log(f"graph: Graph500 scale {cfg['scale']} edge factor "
        f"{cfg['edge_factor']} seed {args.seed}: {edges.n} vertices, "
        f"{edges.src.size} directed edges")
    srv, parts["from_edges"], parts["server_build"] = build_server(
        cfg, edges, shards)
    lanes = int(cfg["lanes"])
    plan = traffic.for_graph(mix, edges, int(cfg["graph"]["structure_seed"]),
                             lanes, args.seconds)
    drv = drive.Client(srv)
    mark = clock.mark()
    t = time.perf_counter()
    drive.warm_up(drv, plan[traffic.WARM], time.perf_counter() + 600)
    parts["warm"] = time.perf_counter() - t
    parts["compile"] = clock.since(mark)["seconds"]
    log("set-up parts (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in parts.items())
        + f"; warm-up compiles {clock.since(mark)}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    closed_at = {}

    pools = [p for grp in srv.pool_groups.values() for p in grp]

    def on_close():
        closed_at["t"] = drive.now()
        closed_at["steps"] = [p.steps - s0 for p, s0 in zip(pools, steps0)]
        if trace_dir:
            jax.profiler.stop_trace()

    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the host's own spans only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    steps0 = [p.steps for p in pools]
    mark = clock.mark()
    start = drive.now()
    setup_s = start - T_PROCESS
    drain_until = start + args.seconds + DRAIN_S
    if mix["loop"] == "closed":
        drive.closed_loop(drv, plan["queues"], int(mix["outstanding"]),
                          start, args.seconds, on_close, drain_until)
    else:
        drive.open_loop(drv, plan["arrivals"], start, args.seconds,
                        on_close, drain_until)
    end = start + args.seconds
    in_window = clock.since(mark)
    reqs = drv.requests
    failed = sum(r.done is None or r.completion.result is None
                 for r in reqs)
    late = [r.submit - r.due for r in reqs]
    log(f"window: {args.seconds} s; compiles inside the window and drain: "
        f"{in_window['compiles']} ({in_window['seconds']:.3f} s)")
    log(f"requests: attempted {len(reqs)}, answered "
        f"{sum(r.done is not None for r in reqs)} ("
        f"{sum(r.done is not None and r.done <= end for r in reqs)} within "
        f"the window), failed {failed}, "
        f"from the cache {sum(r.from_cache for r in reqs)}; drained "
        f"{drive.now() - end:.3f} s after the window")
    if late:
        log(f"load generator late by mean {sum(late) / len(late):.6f} s, "
            f"max {max(late):.6f} s")
    peaks = memory_peaks(server_devices(srv))
    split = check.split_steps(pools)

    view = SimpleNamespace(
        requests=reqs, pumps=[p for p in drv.pumps if p.start < end],
        start=start, end=end, seconds=float(args.seconds), setup_s=setup_s,
        elapsed=drive.now() - start, steps=sum(closed_at["steps"]),
        trace=None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": max(peaks),
              "memory_peak_bytes_per_device": peaks}
    breakdown = None
    if trace_dir:
        log(f"traced window {closed_at['t'] - start:.3f} s; steps per pool "
            f"{dict(zip((p.name for p in pools), closed_at['steps']))}")
        path = trace_reduce.trace_file(trace_dir)
        if args.keep_trace:
            shutil.copy(path, args.keep_trace)
        red = trace_reduce.reduce(path, closed_at["t"] - start,
                                  closed_at["steps"])
        red.update(trace_phases.metrics(trace_phases.reduce(path)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        view.trace = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
    metrics = {}
    for m in metrics_of(bench, cell, bool(args.trace)):
        v = reader(root, m["name"])(view)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the reference runs once the server and its device state are gone
    picked = check.sample(reqs, int(mix["check_per_program"]), args.seed)
    answers = [(r.program, r.source, r.completion.result) for r in picked
               if r.completion.result is not None]
    del srv, drv, view, pools
    gc.collect()
    t = time.perf_counter()
    limits = check.limits_of(cfg)
    worst = check.compare(edges, cfg, answers)
    worst[check.SPLIT] = split
    checks = check.judge(worst, limits)
    log(f"reference: {len(answers)} answers compared in "
        f"{time.perf_counter() - t:.3f} s "
        f"({sum(r.from_cache for r in picked)} served from the cache)")
    correct = (failed == 0 and set(checks) == set(limits)
               and check.passed(checks))
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": len(reqs), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="with --trace 1: also copy the .xplane.pb here")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
