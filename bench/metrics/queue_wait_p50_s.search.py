"""Median seconds an engine-served BFS/SSSP answer waited in the queue:
`Completion.queued_s`, submit to lane admission on the server's clock."""

from bench.metrics.answers import median


def read(run):
    return median(run, "queued_s")
