"""Share of the push runs' device time spent under the `push/expand`
scope (the edge budget's owner lookup and gathers), percent (BFS/SSSP)."""

from bench.metrics.common import traced


def read(run):
    return traced(run, "expand_share")
