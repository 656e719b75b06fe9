"""The jax entry points the repo calls through one name (jax 0.9).

`shard_map`, `AxisType` and `make_mesh` are jax's own, re-exported so call
sites import them from one place. `pvary` names the varying-manual-axes cast
that zero-init loop carries need, and `cost_analysis` the per-program cost
dict of a compiled executable.
"""

from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import AxisType

make_mesh = jax.make_mesh


def pvary(x, axis_name):
    """Mark `x` as varying over the manual mesh axes `axis_name`."""
    return jax.lax.pcast(x, axis_name, to="varying")


def cost_analysis(compiled) -> dict:
    """`Compiled.cost_analysis()`: a dict of cost counters (empty if the
    backend reports none)."""
    return compiled.cost_analysis() or {}


__all__ = ["shard_map", "AxisType", "make_mesh", "pvary", "cost_analysis"]
