"""Device time per step of the cross-chip collectives (the replicated
pools' union-mask all-reduce), averaged over the chips (BFS/SSSP)."""

from bench.metrics.common import collective_ms as read  # noqa: F401
