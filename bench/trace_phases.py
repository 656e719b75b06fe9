"""The served step read from inside, from a profiler trace (`.xplane.pb`).

Device: the operations of a step carry the jax name scopes of
`serving/batch_engine.py` in their `tf_op` (read by `bench/xplane_ops.py`):
`push` (`compact`, `expand`, `compute`), `pull` (`slices`), `combine`,
`apply`, `policy`. An operation's scope path keeps those names alone, in
order (`.../cond/branch_0_fun/push/expand/jit(searchsorted)/while` ->
`push/expand`). A step run (one `XLA Modules` event) is a push run when an
operation inside it ran under `push`, a pull run under `pull`. Self time is
an operation's device time less that of the operations nested in it (a
`while` holds its body's).

Host: `GraphServer.pump` records `serve.pump`, `serve.admit`, `serve.step`
(one per pool stepped), `serve.sync` and `serve.fetch` (`repro.obs.span`),
inside the harness's `bench.*` spans. A stepping pump is a `serve.pump`
that holds a `serve.step`.

Idle gaps: each gap between device operations goes to the innermost host
span that covers more than half of it, else to the span that covers most
of it, else to "host: other".

A trace of a program without these scopes or spans reads None, or
nothing, where they would be.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import xplane_ops
from bench.trace_reduce import DEVICE_PREFIX, MODULES_LINE, OPS_LINE, union

SCOPES = ("push", "pull", "compact", "expand", "compute", "slices",
          "combine", "apply", "policy")
HOST_PREFIXES = ("bench.", "serve.")


def scope_of(tf_op: str) -> str:
    """The named-scope path of an operation's `tf_op`; "" outside every
    scope."""
    first = tf_op.split(";", 1)[0]
    return "/".join(p for p in first.split("/") if p in SCOPES)


def self_times(ops) -> List[Tuple[object, int]]:
    """(event, nanoseconds of self time) for every operation."""
    out, stack = [], []
    for ev in sorted(ops, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0].end_ns <= ev.start_ns:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= ev.end_ns - ev.start_ns
        stack.append([ev, ev.end_ns - ev.start_ns])
    return out + [tuple(x) for x in stack]


def host_spans(planes) -> List[Tuple[str, int, int]]:
    """The harness's and the server's spans, by start."""
    spans = [(e.name, e.start_ns, e.end_ns)
             for p in planes if p.name.startswith("/host:")
             for evs in p.lines.values() for e in evs
             if e.name.startswith(HOST_PREFIXES)]
    return sorted(spans, key=lambda h: (h[1], -h[2]))


def _device(dev: Dict[str, list], acc: dict) -> None:
    ops = dev.get(OPS_LINE, [])
    timed = sorted(self_times(ops), key=lambda t: t[0].start_ns)
    starts = [ev.start_ns for ev, _ in timed]
    for mod in dev.get(MODULES_LINE, []):
        lo = bisect.bisect_left(starts, mod.start_ns)
        hi = bisect.bisect_right(starts, mod.end_ns)
        inside = [(ev, t) for ev, t in timed[lo:hi] if ev.end_ns <= mod.end_ns]
        paths = [(scope_of(str(ev.stats.get("tf_op", ""))), t)
                 for ev, t in inside]
        tops = {p.split("/", 1)[0] for p, _t in paths}
        mode = "push" if "push" in tops else "pull" if "pull" in tops else None
        if mode is None:
            continue
        acc[f"{mode}_runs"] += 1
        acc[f"{mode}_step_s"] += (mod.end_ns - mod.start_ns) / 1e9
        for p, t in paths:
            acc["scoped"][p or "(none)"] += t / 1e9


def _serve(spans) -> Tuple[int, Dict[str, float]]:
    """Stepping pumps, and the seconds of each `serve.*` span inside them."""
    starts = [h[1] for h in spans]
    pumps, total = 0, defaultdict(float)
    for name, s, e in spans:
        if name != "serve.pump":
            continue
        lo = bisect.bisect_left(starts, s)
        hi = bisect.bisect_right(starts, e)
        kids = [h for h in spans[lo:hi]
                if h[0].startswith("serve.") and h[2] <= e]
        if not any(k[0] == "serve.step" for k in kids):
            continue
        pumps += 1
        for kn, ks, ke in kids:
            total[kn] += (ke - ks) / 1e9
    return pumps, dict(total)


def gap_owner(spans, starts, longest: int, s: int, e: int) -> str:
    """The innermost span covering more than half of [s, e]; else the one
    covering most; else "host: other"."""
    half, best, best_ov, inner = (e - s) / 2, "host: other", 0, None
    i = bisect.bisect_right(starts, e) - 1
    while i >= 0 and spans[i][1] >= s - longest:
        name, hs, he = spans[i]
        ov = min(he, e) - max(hs, s)
        if ov > best_ov:
            best, best_ov = name, ov
        if ov > half and (inner is None or he - hs < inner[1]):
            inner = (name, he - hs)
        i -= 1
    return inner[0] if inner else best


def reduce(path: str) -> dict:
    """Push and pull step runs with their device time, self time per scope
    path in those runs, `serve.*` seconds in stepping pumps, and the idle
    gaps by the host span that owns them. Times are per device (averaged
    over devices); run counts too."""
    planes = xplane_ops.read(path)
    devices = [p.lines for p in planes if p.name.startswith(DEVICE_PREFIX)]
    acc = {"push_runs": 0, "pull_runs": 0, "push_step_s": 0.0,
           "pull_step_s": 0.0, "scoped": defaultdict(float)}
    spans = host_spans(planes)
    starts = [h[1] for h in spans]
    longest = max((h[2] - h[1] for h in spans), default=0)
    gaps: Dict[str, float] = defaultdict(float)
    for dev in devices:
        _device(dev, acc)
        busy = union((ev.start_ns, ev.end_ns)
                     for ev in dev.get(OPS_LINE, []))
        for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
            gaps[gap_owner(spans, starts, longest, e0, s1)] += (s1 - e0) / 1e9
    n = max(len(devices), 1)
    pumps, serve = _serve(spans)
    return {
        "push_runs": acc["push_runs"] / n,
        "pull_runs": acc["pull_runs"] / n,
        "push_step_s": acc["push_step_s"] / n,
        "pull_step_s": acc["pull_step_s"] / n,
        "scope_s": {k: v / n for k, v in sorted(acc["scoped"].items())},
        "stepping_pumps": pumps,
        "serve_s": serve,
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


def metrics(red: dict) -> Dict[str, Optional[float]]:
    """The reduction in the units of the per-layer metrics it feeds."""
    push, pull = red["push_runs"], red["pull_runs"]
    expand = sum(t for p, t in red["scope_s"].items()
                 if p.startswith("push/expand"))
    pumps, serve = red["stepping_pumps"], red["serve_s"]
    sync = serve.get("serve.sync", 0.0)
    return {
        "push_step_ms": 1e3 * red["push_step_s"] / push if push else None,
        "pull_step_ms": 1e3 * red["pull_step_s"] / pull if pull else None,
        "expand_share": (100.0 * expand / red["push_step_s"]
                         if push else None),
        "pump_wait_ms": 1e3 * sync / pumps if pumps else None,
        "pump_self_ms": (1e3 * (serve["serve.pump"] - sync) / pumps
                         if pumps else None),
    }
