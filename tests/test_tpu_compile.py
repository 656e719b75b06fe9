"""Compile rehearsals for one described TPU v5e chip (no chip attached).

The TPU compiler is installed with jax, so a program can be compiled for a
chip that is described rather than present: it refuses what the chip would
refuse (tiling, memory) without any chip time. These tests compile

  * the served batched step exactly as `AlgoPool` jits it, on a host-built
    RMAT-12 graph with 8 slots, and check it fits one chip's HBM;
  * the Pallas kernels at RMAT-20 slice widths. Their block shapes are
    lane-dense and pass the TPU lowering's tiling checks; each then stops
    at an operation Mosaic cannot lower yet (ROADMAP D9/S4). The expected
    message pins that frontier: a tiling regression raises a different
    error, and a kernel that starts to lower fails here so the roadmap
    gets updated.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and pytest-xdist workers
import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.graph import generators
from repro.kernels import ell_spmv, frontier_pack
from repro.launch.catalog import make_catalog
from repro.serving import default_config
from repro.serving.scheduler import AlgoPool
from repro.streaming import StreamingGraph

#: v5e HBM per chip as the TPU compiler reports it (16 GB nominal)
HBM_BYTES = 15.75e9
#: RMAT-20 (Graph500 initiator, edge factor 16): vertex count and the ELL
#: bucket shapes (rows, width) `pack_ell` builds for it at seed 0
RMAT20_N = 1 << 20
RMAT20_SLICES = [(31912, 4), (825568, 32), (184648, 256), (30096, 256)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # jax[tpu] ships the compiler: failing to describe the chip is an error
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def served_rmat12():
    """A streaming-overlay RMAT-12 graph, as `GraphServer(delta_cap=...)`
    serves it."""
    sg = StreamingGraph(generators.rmat(12, 16, seed=0), delta_cap=256)
    return sg.graph, sg.pack, sg.delta


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("algo", ["bfs", "ppr_delta"])
def test_served_step_compiles_for_one_chip(algo, one_chip, served_rmat12):
    g, pack, delta = served_rmat12
    pool = AlgoPool(algo, make_catalog()[algo], g, pack, default_config(g),
                    slots=8, delta=delta)
    args = _shapes((pool.state, pool.g, pool.pack, pool.delta), one_chip)
    compiled = pool._step.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def _add(x, w):
    return x + w


@pytest.mark.parametrize("overlay", [False, True], ids=["plain", "overlay"])
@pytest.mark.parametrize("rows,width", RMAT20_SLICES)
def test_ell_combine_tiles_then_stops_at_gather(rows, width, overlay,
                                                one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = [s((rows, width), jnp.int32), s((rows, width), jnp.float32),
            s((RMAT20_N + 1,), jnp.float32)]
    if overlay:
        args.append(s((rows, width), jnp.int8))

    def f(nbr, wgt, vals, dead=None):
        return ell_spmv.ell_combine(nbr, wgt, vals, dead, compute_fn=_add,
                                    combine="min", interpret=False)

    # the in-kernel neighbour gather reads a 1-D table with 2-D indices
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        jax.jit(f).lower(*args).compile()


def test_frontier_pack_tiles_then_stops_at_cumsum(one_chip):
    mask = jax.ShapeDtypeStruct((RMAT20_N,), jnp.bool_, sharding=one_chip)
    with pytest.raises(NotImplementedError, match="cumsum"):
        jax.jit(lambda m: frontier_pack.frontier_pack(
            m, block=1024, interpret=False)).lower(mask).compile()
