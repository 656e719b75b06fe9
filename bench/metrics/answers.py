"""Numbers the server stamps on each engine answer's `Completion`:
`queued_s`, `resident_s`, `push_iters`, `pull_iters`. A program whose
completions lack them reads None, and the harness leaves the metric out."""

from __future__ import annotations

from typing import List, Optional

from bench.metrics.common import percentile


def values(run, field: str) -> Optional[List[float]]:
    """`field` of every engine-served answer of the run; None if some
    answer lacks it or there is none."""
    comps = [r.completion for r in run.requests
             if r.done is not None and not r.from_cache]
    if not comps or not all(hasattr(c, field) for c in comps):
        return None
    return [float(getattr(c, field)) for c in comps]


def median(run, field: str) -> Optional[float]:
    vals = values(run, field)
    return percentile(vals, 50) if vals else None


def pull_iter_share(run) -> Optional[float]:
    """Pull iterations over all iterations of the engine answers, percent."""
    push, pull = values(run, "push_iters"), values(run, "pull_iters")
    if push is None or pull is None or not sum(push) + sum(pull):
        return None
    return 100.0 * sum(pull) / (sum(push) + sum(pull))
