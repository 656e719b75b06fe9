"""Median seconds an engine-served BFS/SSSP answer spent in its lane:
`Completion.resident_s`, admission to harvest on the server's clock."""

from bench.metrics.answers import median


def read(run):
    return median(run, "resident_s")
