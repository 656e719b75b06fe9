"""Share of the engine answers' iterations that ran in pull mode, percent:
sum of `Completion.pull_iters` over sum of push and pull iterations."""

from bench.metrics.answers import pull_iter_share as read  # noqa: F401
