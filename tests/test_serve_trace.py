"""The served step traced from inside (DESIGN.md §12 "Profiling"):

  * the pools' executables are named after the pool (`jit_bfs_step`,
    `jit_sssp_admit`), and the lowered steps carry every ACC phase scope
    (`push/compact`, `push/expand`, ..., `policy`) in their op names;
  * `GraphServer.pump` records its host spans (`serve.pump`, `serve.admit`,
    `serve.step`, `serve.sync`, `serve.fetch`) into a profiler trace with
    telemetry off;
  * every engine answer carries where its time went (`queued_s`,
    `resident_s`) and its push/pull split, from host stamps and counters
    the server keeps anyway.
"""

from __future__ import annotations

import glob
import time

import jax
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.graph import generators, pack_ell
from repro.serving import GraphServer, default_config

#: every scope path the batched step's phases run under
STEP_SCOPES = ("push/compact", "push/expand", "push/compute",
               "push/combine", "push/apply", "pull/slices",
               "pull/slices/combine", "pull/apply", "policy")
SERVE_SPANS = ("serve.pump", "serve.admit", "serve.step", "serve.sync",
               "serve.fetch")


@pytest.fixture(scope="module")
def graph():
    g = generators.rmat(8, 8, seed=3, weighted=True)
    return g, pack_ell(g.inc)


def _server(graph, slots=4):
    g, pack = graph
    return GraphServer(g, pack, {"bfs": alg.bfs(0), "sssp": alg.sssp(0)},
                       slots=slots, cfg=default_config(g))


@pytest.mark.parametrize("algo", ["bfs", "sssp"])
def test_steps_are_named_and_carry_every_phase_scope(graph, algo):
    srv = _server(graph)
    pool = srv.pools[algo]
    low = pool._step.lower(pool.state, pool.g, pool.pack, pool.delta)
    assert low.as_text().startswith(f"module @jit_{algo}_step")
    text = low.as_text(debug_info=True)
    assert f"jit({algo}_step)/" in text
    for scope in STEP_SCOPES:
        assert f"/{scope}/" in text, scope
    admit = pool._admit.lower(pool.state, np.int32(0), np.int32(0),
                              pool.g, pool.delta, pool.live_deg)
    assert admit.as_text().startswith(f"module @jit_{algo}_admit")


def test_a_cohort_leaf_name_is_a_valid_executable_name(graph):
    g, pack = graph
    srv = GraphServer(g, pack, {"bfs": alg.bfs(0)}, slots=4,
                      cfg=default_config(g), cohorts={"bfs": 2})
    leaf = srv.pool_groups["bfs"][1]
    assert leaf._step.__name__ == "bfs_c0_step"   # shared with leaf 0


def test_answers_carry_queue_resident_time_and_the_mode_split(graph):
    srv = _server(graph, slots=2)
    sent = {}
    sources = [0, 3, 17, 40, 99, 120, 200, 255]
    for s in sources:
        for algo in ("bfs", "sssp"):
            sent[srv.submit(algo, s)] = time.monotonic()
    got = {}
    while len(got) < len(sent):
        for c in srv.pump():
            got[c.rid] = (c, time.monotonic())
    queued = 0
    for rid, (c, done_t) in got.items():
        assert not c.from_cache
        assert c.queued_s >= 0 and c.resident_s > 0
        assert c.queued_s + c.resident_s <= done_t - sent[rid]
        assert c.push_iters + c.pull_iters == c.iterations
        queued += c.queued_s > 0
    # two lanes per pool for eight queries each: most of them waited
    assert queued >= len(sent) // 2
    # both modes run on this graph: the consensus switches mid-query
    assert all(c.push_iters > 0 and c.pull_iters > 0
               for c, _t in got.values())
    hit = srv.submit("bfs", sources[0])
    c = srv.completions[-1]
    assert c.rid == hit and c.from_cache
    assert (c.queued_s, c.resident_s, c.push_iters, c.pull_iters) == (
        0.0, 0.0, 0, 0)


def test_pump_spans_land_in_a_profiler_trace_with_telemetry_off(
        graph, tmp_path):
    from jax.profiler import ProfileData

    srv = _server(graph)
    assert not srv.obs.enabled
    for s in (1, 2, 3):
        srv.submit("bfs", s)
        srv.submit("sssp", s)
    srv.pump()                 # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.drain()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    seen.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(seen) == set(SERVE_SPANS)
    assert {st["pool"] for st in seen["serve.step"]} == {"bfs", "sssp"}
    assert all(st.get("pool") in ("bfs", "sssp")
               for st in seen["serve.sync"])
