"""95th percentile latency over every request; an unanswered one counts as
missing the limit."""

from bench.metrics.common import latency_p


def read(run):
    return latency_p(run, 95)
