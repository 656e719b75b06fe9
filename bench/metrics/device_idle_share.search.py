"""Share of the traced window with no op on the device (BFS/SSSP), percent."""

from bench.metrics.common import device_idle_share as read  # noqa: F401
