"""Device time of a pool's step executable per run that took the push
branch, averaged over the chips (BFS/SSSP)."""

from bench.metrics.common import traced


def read(run):
    return traced(run, "push_step_ms")
