"""Mean host time of a GraphServer.pump() that stepped a pool (BFS/SSSP)."""

from bench.metrics.common import pump_ms as read  # noqa: F401
