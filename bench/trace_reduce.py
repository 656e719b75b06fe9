"""From a profiler trace (`.xplane.pb`) to device metrics and a breakdown.

Read with `jax.profiler.ProfileData`, nothing else. The planes of the
devices are those named `/device:TPU:<i>`; on each, the line `XLA Ops`
holds one event per operation run, and `XLA Modules` one per executable
run. The host's plane holds the benchmark's own `TraceAnnotation` spans
(`bench.pump`, `bench.submit`, `bench.wait`), on the same clock.

- busy: the union of the operation intervals of a device, in seconds,
  averaged over the devices; idle share = 1 - busy / window.
- step time: the summed device time of the executables that ran the pools'
  steps. A module event is named by its jitted function and program id,
  `jit__lambda(8048503026195580290)`, and a pool's step and its admission
  are both lambdas; so for each pool the step is the executable that ran
  exactly as many times as the pool stepped in the window (the harness
  counts that), the one with most device time where two did. See
  `step_modules`.
- collective time: the summed device time of the operations that move data
  between chips (`COLLECTIVES`, by the HLO opcode in the operation's name:
  a replicated pool's union-mask psum runs as
  `%psum.7 = s32[1048577]{...} all-reduce(...)`), averaged over the
  devices; 0 on one chip.
- breakdown: the ten operations with most device self time (an operation's
  time less that of the operations nested in it), and the idle gaps
  between device work summed by the host span that covered most of each
  gap ("host: other" where none did), each averaged over the devices.
"""

from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
#: HLO opcodes of the operations that move data between chips
COLLECTIVES = frozenset(
    f"{op}{half}" for op in ("all-reduce", "all-gather",
                             "collective-permute")
    for half in ("", "-start", "-done")) | {"reduce-scatter", "all-to-all"}


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def read_planes(pd):
    """(device planes: [{line name: events}], host spans: [(name, s, e)])."""
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append({line.name: _events(line) for line in plane.lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in _events(line)
                         if ev[0].startswith(HOST_SPAN_PREFIX)]
    return devices, host


def step_modules(modules, step_counts) -> set:
    """The executables that ran as the pools' steps: for each pool's step
    count, the unchosen executable launched that many times with the most
    device time. Empty if some pool's step is not found."""
    runs: Dict[str, int] = defaultdict(int)
    total: Dict[str, float] = defaultdict(float)
    for name, s, e in modules:
        runs[name] += 1
        total[name] += e - s
    chosen: set = set()
    for count in sorted(step_counts, reverse=True):
        if not count:
            continue
        fits = [n for n in runs if runs[n] == count and n not in chosen]
        if not fits:
            return set()
        chosen.add(max(fits, key=total.get))
    return chosen


def self_times(events, modules=()) -> Dict[str, float]:
    """Seconds of device time per operation, less nested operations',
    each named after the executable it ran in."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [name, end, self ns]
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and mods[i][2] >= e:
            name = f"{mods[i][0]}/{short_name(name)}"
        else:
            name = short_name(name)
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out[done[0]] += done[2] / 1e9
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    for done in stack:
        out[done[0]] += done[2] / 1e9
    return out


def short_name(op: str) -> str:
    """`%fusion.68 = s32[2097152]{...} fusion(...)` -> `%fusion.68 =
    s32[2097152]`: the operation and its result's type and shape."""
    head, _, rest = op.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head} = {shape}" if rest else head


def opcode(op: str) -> str:
    """`%psum.7 = s32[1048577]{0:T(1024)} all-reduce(%x), ...` ->
    `all-reduce`: the HLO opcode after the result's shape, which may be a
    tuple."""
    _head, sep, rest = op.partition(" = ")
    if not sep:
        return ""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if not depth:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    return rest.strip().split("(", 1)[0]


def reduce(path: str, window_s: float, step_counts=()) -> dict:
    pd = _load(path)
    devices, host = read_planes(pd)
    busy, step_s, coll_s = [], [], []
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    host = sorted(host, key=lambda h: h[1])
    starts = [h[1] for h in host]
    for dev in devices:
        evs = dev.get(OPS_LINE, [])
        spans = union((s, e) for _n, s, e in evs)
        busy.append(sum(e - s for s, e in spans) / 1e9)
        coll_s.append(sum(e - s for name, s, e in evs
                          if opcode(name) in COLLECTIVES) / 1e9)
        mods = dev.get(MODULES_LINE, [])
        for name, t in self_times(evs, mods).items():
            ops[name] += t
        steps = step_modules(mods, step_counts)
        step_s.append(sum(e - s for n, s, e in mods if n in steps) / 1e9)
        for (_s0, e0), (s1, _e1) in zip(spans, spans[1:]):
            gaps[_cover(host, starts, e0, s1)] += (s1 - e0) / 1e9
    n = max(len(devices), 1)
    return {
        "busy_s": sum(busy) / n,
        "window_s": window_s,
        "step_s": sum(step_s) / n if all(step_s) else None,
        "collective_s": sum(coll_s) / n,
        "device_ops": [[k, v / n] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


def _cover(host, starts, s: float, e: float) -> str:
    """The host span that overlaps [s, e] most (spans do not nest)."""
    best, name = 0.0, "host: other"
    i = bisect.bisect_right(starts, e) - 1
    while i >= 0 and host[i][2] > s:
        hn, hs, he = host[i]
        ov = min(he, e) - max(hs, s)
        if ov > best:
            best, name = ov, hn
        i -= 1
    return name


def trace_file(trace_dir: str) -> str:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[0]
