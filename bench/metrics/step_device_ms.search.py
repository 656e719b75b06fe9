"""Device time of the pools' step executables per step (BFS/SSSP)."""

from bench.metrics.common import step_device_ms as read  # noqa: F401
