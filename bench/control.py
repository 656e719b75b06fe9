#!/usr/bin/env python3
"""The control of `correct`: the reference in bfloat16 in the program's place.

  python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed: the cell's graph and the sources its traffic draws for the
comparison (as many per program as a run compares), the reference in
float32 and again in bfloat16, and the numbers the comparison gives for the
bfloat16 answers beside the configuration's limits. Each seed must fail at
least one limit; the smallest reading of each number over the seeds is the
upper reading its limit was set below (PERF.md). Needs a TPU, as a run does.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from bench import check, traffic
    from bench.gen import graph500
    from bench.run import enable_compile_cache, load_spec

    enable_compile_cache()
    _bench, _cell, cfg, mix = load_spec(ROOT, args.workload)
    limits = check.limits_of(cfg)
    failed_all = True
    for seed in args.seeds:
        edges = graph500.for_config(cfg, seed)
        plan = traffic.for_graph(mix, edges,
                                 int(cfg["graph"]["structure_seed"]),
                                 int(cfg["lanes"]), 51.0)
        per = int(mix["check_per_program"])
        if "queues" in plan:
            sources = {p: q[:per] for p, q in plan["queues"].items()}
        else:
            sources = {}
            for a in plan["arrivals"]:
                got = sources.setdefault(a.program, [])
                if len(got) < per and a.source not in got:
                    got.append(a.source)
        checks = check.judge(check.control(edges, cfg, sources), limits)
        fails = not check.passed(checks)
        failed_all &= fails
        print(json.dumps({"seed": seed, "control_fails": fails,
                          "checks": checks}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
