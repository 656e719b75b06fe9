"""Host time per stepping `serve.pump` outside `serve.sync`: admission,
dispatch and the answers' copies (BFS/SSSP)."""

from bench.metrics.common import traced


def read(run):
    return traced(run, "pump_self_ms")
