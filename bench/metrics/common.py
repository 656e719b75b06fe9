"""Arithmetic shared by the metric readers.

A reader is `bench/metrics/<metric>.py`, found by the metric's name, with
`read(run) -> float | None`; None means it found nothing to read, and the
harness leaves the metric out. `run` holds the window's requests and pumps
(`bench.drive`), its length, the set-up time, and with `--trace 1` the
reduced device trace (`bench.trace_reduce`, with the phase numbers of
`bench.trace_phases.metrics`).

Percentiles are exact, over every request of the window, by linear
interpolation between order statistics (numpy's default). A request that
never got its answer counts as missing the limit: its latency is taken as
the whole run so far, longer than any answered request's.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def percentile(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if values else None


def latencies(run) -> List[float]:
    return [r.latency if r.done is not None else run.elapsed
            for r in run.requests]


def latency_p(run, q: float) -> Optional[float]:
    return percentile(latencies(run), q)


def qps(run) -> Optional[float]:
    """Answered requests over the seconds from the window's start to the
    last answer: continuous, where a count within the window moves in
    steps of one answer."""
    done = [r.done for r in run.requests if r.done is not None]
    return len(done) / (max(done) - run.start) if done else None


def pump_ms(run) -> Optional[float]:
    """Mean host time of a `pump()` call that stepped a pool."""
    spans = [p.end - p.start for p in run.pumps if p.steps]
    return 1e3 * float(np.mean(spans)) if spans else None


def iters_per_query(run) -> Optional[float]:
    its = [r.iterations for r in run.requests
           if r.done is not None and not r.from_cache]
    return float(np.mean(its)) if its else None


def device_idle_share(run) -> Optional[float]:
    tr = run.trace
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def step_device_ms(run) -> Optional[float]:
    """Device time of the pools' step executables per step."""
    tr = run.trace
    if not tr or not tr["step_s"] or not run.steps:
        return None
    return 1e3 * tr["step_s"] / run.steps


def collective_ms(run) -> Optional[float]:
    """Device time of the cross-chip collectives per step; None where no
    collective ran (one chip)."""
    tr = run.trace
    if not tr or not tr.get("collective_s") or not run.steps:
        return None
    return 1e3 * tr["collective_s"] / run.steps


def traced(run, key: str) -> Optional[float]:
    """A number the trace's phase reduction gives (`bench.trace_phases.
    metrics`), None where it found nothing to read."""
    return run.trace.get(key) if run.trace else None


def setup_s(run) -> float:
    return run.setup_s
