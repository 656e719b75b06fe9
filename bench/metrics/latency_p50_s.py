"""Median latency, due time to answer on the host, over every request."""

from bench.metrics.common import latency_p


def read(run):
    return latency_p(run, 50)
