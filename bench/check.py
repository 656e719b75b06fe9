"""How `correct` is decided: the served answers against the plain reference.

Once the window has closed and the server is freed, a sample of the
answered requests, drawn from the seed, is compared with the reference of
its program (`bench/reference/<program>.py`, found by name): for each
program the answer with the most iterations and the one that waited
longest, every cache-served answer up to a quarter of the sample, and the
rest at random. Each reference module gives its numbers per answer; the
worst over the sample is held to the limit that the configuration file
states under `checks`.

A configuration whose pools take one push/pull decision per step for all
their lanes states the limit 0 for `consensus_split_steps`: the pool steps
at which lanes of one pool ran under different modes, read once the drain
is over from each lane's last query (`split_steps`). On a mesh that is the
guarantee that the chips exchange their frontiers every step: a chip that
decides from its own lanes alone splits the steps its lanes share with
another chip's.

`control` is the same comparison with the reference itself, computed in
bfloat16, in the program's place: the step below the float32 the programs
compute in. It must fail.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

from bench.reference.common import BFLOAT16, matrices


def reference(program: str):
    """The plain reference module of a program, by its name."""
    return importlib.import_module(f"bench.reference.{program}")


def sample(requests, per_program: int, seed: int) -> List:
    """Up to `per_program` answered requests of each program."""
    rng = np.random.default_rng([seed, 1])
    out = []
    for prog in sorted({r.program for r in requests}):
        done = [r for r in requests
                if r.program == prog and r.completion is not None]
        picked: Dict[int, object] = {}
        for r in (max(done, key=lambda r: r.iterations, default=None),
                  max(done, key=lambda r: r.latency, default=None)):
            if r is not None:
                picked[id(r)] = r
        hits = [r for r in done if r.from_cache]
        for i in rng.permutation(len(hits))[:max(1, per_program // 4)]:
            if len(picked) < per_program:
                picked[id(hits[i])] = hits[i]
        for i in rng.permutation(len(done)):
            if len(picked) >= per_program:
                break
            picked[id(done[i])] = done[i]
        out.extend(picked.values())
    return out


#: the name of `split_steps`'s number under a configuration's `checks`
SPLIT = "consensus_split_steps"


def split_steps(pools) -> int:
    """Steps of a pool at which its lanes ran under different push/pull
    modes, summed over `pools`. A lane's last query was admitted at pool
    step `lane_admit_step`, after `lane_it_base` iterations, and its
    iteration i ran during step `lane_admit_step + 1 + i - lane_it_base`
    with the mode `mode_trace[lane, i]`; the trace's last column holds
    whichever iteration came last, so it is left out."""
    split = 0
    for pool in pools:
        trace = np.asarray(pool.state.mode_trace)
        its = np.asarray(pool.state.it)
        modes: Dict[int, set] = {}
        for lane in range(trace.shape[0]):
            base = pool.lane_it_base[lane]
            for i in range(base, min(int(its[lane]), trace.shape[1] - 1)):
                step = pool.lane_admit_step[lane] + 1 + i - base
                modes.setdefault(step, set()).add(int(trace[lane, i]))
        split += sum(len(m) > 1 for m in modes.values())
    return split


def _worst(into: dict, nums: dict) -> None:
    for k, v in nums.items():
        into[k] = max(into.get(k, -np.inf), float(v))


def compare(edges, cfg: dict, answers) -> Dict[str, float]:
    """Worst number over `answers`, a list of (program, source, result)."""
    mats = matrices(edges)
    deg = edges.degrees()
    worst: Dict[str, float] = {}
    for prog in sorted({a[0] for a in answers}):
        mod = reference(prog)
        params = cfg["programs"][prog]
        srcs = sorted({a[1] for a in answers if a[0] == prog})
        want = dict(zip(srcs, mod.solve(mats, srcs, params)))
        ctx = {"params": params, "deg": deg}
        for p, s, result in answers:
            if p == prog:
                _worst(worst, mod.compare(result, want[s], ctx))
    return worst


def control(edges, cfg: dict,
            sources: Dict[str, List[int]]) -> Dict[str, float]:
    """The reference in bfloat16 compared as if it were the program."""
    mats = matrices(edges)
    answers = []
    for prog, srcs in sources.items():
        rows = reference(prog).solve(mats, srcs, cfg["programs"][prog],
                                     BFLOAT16)
        answers += [(prog, s, row) for s, row in zip(srcs, rows)]
    return compare(edges, cfg, answers)


def judge(worst: Dict[str, float],
          limits: Dict[str, float]) -> Dict[str, dict]:
    """{number: {"value", "limit"}} for every limit of the configuration."""
    return {k: {"value": worst[k], "limit": limits[k]}
            for k in sorted(limits) if k in worst}


def passed(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def limits_of(cfg: dict) -> Dict[str, float]:
    """The limit of every number the configuration's programs give."""
    return {k: float(v) for per in cfg["checks"].values()
            for k, v in per.items()}
