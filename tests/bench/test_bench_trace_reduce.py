"""The trace reduction (bench/trace_reduce.py) against a trace recorded on a
TPU v5e: three pumps of the `g500-s20.search` cell (BFS and SSSP pools of 8
lanes at Graph500 scale 20), taken by `bench/run.py --seconds 3 --trace 1
--keep-trace` (tests/bench/data/)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

TRACE = Path(__file__).parent / "data" / "g500_s20_search.xplane.pb"
#: what that run's harness counted: the traced window, and the steps of the
#: bfs and sssp pools in it
STEPS = [3, 3]
WINDOW_S = 3.517


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(str(TRACE), WINDOW_S, STEPS)


def test_busy_time_is_the_union_of_device_ops(reduced):
    # the run printed busy_s 3.47301051 for this trace
    assert reduced["busy_s"] == pytest.approx(3.47301051, rel=1e-6)
    assert reduced["busy_s"] <= WINDOW_S


def test_the_steps_are_found_by_their_launch_counts(reduced):
    pd = trace_reduce._load(str(TRACE))
    devices, _host = trace_reduce.read_planes(pd)
    mods = devices[0][trace_reduce.MODULES_LINE]
    chosen = trace_reduce.step_modules(mods, STEPS)
    assert len(chosen) == 2
    assert all(n.startswith("jit__lambda(") for n in chosen)
    assert 0 < reduced["step_s"] <= reduced["busy_s"]
    # the run printed step_device_ms.search 577.81777 over its 6 steps
    assert reduced["step_s"] / 6 == pytest.approx(0.57781777, rel=1e-6)
    # no executable runs as often as a step count that did not happen
    assert trace_reduce.step_modules(mods, [10 ** 6]) == set()


def test_breakdown_lists_ops_and_gaps(reduced):
    ops, gaps = reduced["device_ops"], reduced["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert sum(t for _n, t in ops) <= reduced["busy_s"] + 1e-9
    assert all(t > 0 for _n, t in ops + gaps)
    assert {n for n, _t in gaps} <= {"bench.pump", "bench.submit",
                                     "bench.wait", "host: other"}


def test_union_and_self_times():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    evs = [("%cond.1 = f32[8]{0} conditional(...)", 0.0, 10.0),
           ("%fusion.2 = f32[8]{0} fusion(...)", 2.0, 5.0)]
    mods = [("jit_step(1)", 0.0, 10.0)]
    got = trace_reduce.self_times(evs, mods)
    assert got == {"jit_step(1)/%cond.1 = f32[8]": 7e-9,
                   "jit_step(1)/%fusion.2 = f32[8]": 3e-9}
