"""Mean engine iterations of an engine-served BFS/SSSP answer."""

from bench.metrics.common import iters_per_query as read  # noqa: F401
