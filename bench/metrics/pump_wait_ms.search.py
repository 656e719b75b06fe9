"""Host time per stepping `serve.pump` spent in `serve.sync`, waiting for
the step's `done`/iteration fetch (BFS/SSSP)."""

from bench.metrics.common import traced


def read(run):
    return traced(run, "pump_wait_ms")
