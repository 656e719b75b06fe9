"""Answers per second: every answered request of the window, the drain
after the close included, over the time from the window's start to the
last answer."""

from bench.metrics.common import qps as read  # noqa: F401
