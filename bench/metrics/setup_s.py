"""Process start to the first request of the window."""

from bench.metrics.common import setup_s as read  # noqa: F401
