"""The served step read from inside: the standard-library trace reader
(bench/xplane_ops.py), the phase and span reduction (bench/trace_phases.py)
and the readers of the numbers the server stamps on its answers
(bench/metrics/answers.py), on traces recorded on a TPU v5e
(tests/bench/data/), on CPU traces and on synthetic runs.

The scoped trace is a whole 51 s window of the `g500-s20.search` cell on a
v5e chip, taken by `bench/run.py --seed 3000000022 --seconds 51 --trace 1
--keep-trace` and gzipped: the first steps after the warm-up are all push,
so a window of a few seconds holds no pull step."""

import glob
import gzip
import importlib.util
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import drive, trace_phases, trace_reduce, xplane_ops  # noqa: E402
from bench.metrics import answers  # noqa: E402

DATA = Path(__file__).parent / "data"
#: the first recorded trace: a program with no scopes and no `serve.*`
#: spans
UNSCOPED = DATA / "g500_s20_search.xplane.pb"
SCOPED = DATA / "g500_s20_search_scoped.xplane.pb.gz"
#: what the scoped trace's run printed: its traced window, the steps of the
#: bfs and sssp pools in it, and three of its metrics
SCOPED_WINDOW_S = 51.589296754999964
SCOPED_STEPS = [57, 57]
SCOPED_PUMP_MS = 905.0257012631666
SCOPED_STEP_DEVICE_MS = 442.6851350175439
SCOPED_BUSY_S = 50.50825579


def _reader(metric):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def unscoped_planes():
    return xplane_ops.read(str(UNSCOPED))


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "scoped.xplane.pb"
    path.write_bytes(gzip.decompress(SCOPED.read_bytes()))
    return str(path), trace_phases.reduce(str(path))


def test_the_reader_agrees_with_profile_data(unscoped_planes):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(UNSCOPED))
    pd_planes = list(pd.planes)
    assert [p.name for p in unscoped_planes] == [p.name for p in pd_planes]
    n = 0
    for mine, theirs in zip(unscoped_planes, pd_planes):
        for line in theirs.lines:
            evs = list(line.events)
            got = mine.lines.get(line.name, [])
            assert len(got) == len(evs), (theirs.name, line.name)
            for ev, m in zip(evs, got):
                assert m.name == ev.name
                assert m.start_ns == ev.start_ns
                assert m.end_ns - m.start_ns == ev.duration_ns
                n += 1
    assert n > 3000


def test_the_reader_gives_each_device_op_its_tf_op(unscoped_planes):
    (ops,) = [p.lines[trace_reduce.OPS_LINE] for p in unscoped_planes
              if p.name.startswith(trace_reduce.DEVICE_PREFIX)]
    tf_ops = [e.stats["tf_op"] for e in ops if "tf_op" in e.stats]
    assert len(tf_ops) > len(ops) / 3
    # the push and pull branches of the consensus `cond`, and the bisection
    # of the push path's edge expansion
    assert any("cond/branch_0_fun/jit(searchsorted)" in t for t in tf_ops)
    assert any("cond/branch_1_fun" in t for t in tf_ops)


def test_scope_paths():
    assert trace_phases.scope_of(
        "jit(bfs_step)/cond/branch_0_fun/push/expand/jit(searchsorted)/"
        "while") == "push/expand"
    assert trace_phases.scope_of(
        "jit(sssp_step)/cond/branch_1_fun/pull/slices/combine/scatter-add"
        ";jit(sssp_step)/policy/eq") == "pull/slices/combine"
    assert trace_phases.scope_of("jit(<lambda>)/cond/branch_1_fun/min") == ""


def test_self_time_subtracts_nested_ops():
    ev = xplane_ops.Event
    outer, inner = ev("while", 0, 10, {}), ev("fusion", 2, 5, {})
    got = dict((e.name, t) for e, t in trace_phases.self_times(
        [inner, outer]))
    assert got == {"while": 7, "fusion": 3}


def test_a_gap_goes_to_the_innermost_span_covering_most_of_it():
    spans = [("bench.pump", 0, 100), ("serve.pump", 1, 99),
             ("serve.sync", 10, 50), ("serve.fetch", 50, 52)]
    starts = [s for _n, s, _e in spans]

    def owner(s, e):
        return trace_phases.gap_owner(spans, starts, 100, s, e)

    assert owner(20, 40) == "serve.sync"
    assert owner(47, 55) == "serve.pump"     # no inner span covers half
    assert owner(49, 52) == "serve.fetch"
    assert owner(200, 210) == "host: other"


def test_an_unscoped_trace_reads_no_phases(unscoped_planes):
    red = trace_phases.reduce(str(UNSCOPED))
    assert red["push_runs"] == red["pull_runs"] == 0
    assert red["stepping_pumps"] == 0 and red["serve_s"] == {}
    assert set(trace_phases.metrics(red).values()) == {None}
    # the same idle time as `trace_reduce`, under the same spans
    old = trace_reduce.reduce(str(UNSCOPED), 3.517, [3, 3])
    assert sum(t for _n, t in red["idle_gaps"]) == pytest.approx(
        sum(t for _n, t in old["idle_gaps"]), rel=1e-9)
    assert {n for n, _t in red["idle_gaps"]} <= {
        "bench.pump", "bench.submit", "bench.wait", "host: other"}


def test_the_scoped_trace_splits_the_step_by_mode_and_phase(scoped):
    _path, red = scoped
    assert (red["push_runs"], red["pull_runs"]) == (28, 86)
    assert red["stepping_pumps"] == 57
    got = trace_phases.metrics(red)
    assert got["push_step_ms"] == pytest.approx(655.6455733571429, rel=1e-9)
    assert got["pull_step_ms"] == pytest.approx(373.3491783488373, rel=1e-9)
    assert got["expand_share"] == pytest.approx(71.27419541411845, rel=1e-9)
    assert got["pump_wait_ms"] == pytest.approx(890.7823186666666, rel=1e-9)
    assert got["pump_self_ms"] == pytest.approx(14.233912842105394, rel=1e-6)
    # a push step costs more device time than a full pull
    assert got["push_step_ms"] > 1.7 * got["pull_step_ms"]
    assert set(red["scope_s"]) == {
        "(none)", "policy", "push", "push/compact", "push/expand",
        "push/compute", "push/combine", "push/apply", "pull", "pull/slices",
        "pull/slices/combine", "pull/apply"}


def test_the_scoped_trace_reconciles_with_the_run(scoped):
    path, red = scoped
    got = trace_phases.metrics(red)
    steps = sum(SCOPED_STEPS)
    step_s = red["push_step_s"] + red["pull_step_s"]
    # the mode split covers exactly the step executables the harness found
    assert 1e3 * step_s / steps == pytest.approx(SCOPED_STEP_DEVICE_MS,
                                                 rel=1e-9)
    old = trace_reduce.reduce(path, SCOPED_WINDOW_S, SCOPED_STEPS)
    assert old["busy_s"] == pytest.approx(SCOPED_BUSY_S, rel=1e-9)
    assert 1e3 * old["step_s"] / steps == pytest.approx(
        SCOPED_STEP_DEVICE_MS, rel=1e-9)
    assert all(n.startswith(("jit_bfs_step(", "jit_sssp_step("))
               for n, _t in old["device_ops"])
    # the pump's parts add up to the harness's own clock around pump()
    assert got["pump_wait_ms"] + got["pump_self_ms"] == pytest.approx(
        SCOPED_PUMP_MS, rel=0.05)
    # little step time falls outside every scope
    assert red["scope_s"]["(none)"] < 0.05 * step_s
    # the idle time `trace_reduce` puts down to `bench.pump` goes to the
    # server's spans
    idle = sum(t for _n, t in red["idle_gaps"])
    assert idle == pytest.approx(sum(t for _n, t in old["idle_gaps"]),
                                 rel=1e-9)
    assert sum(t for n, t in red["idle_gaps"]
               if n.startswith("serve.")) > 0.9 * idle


def test_metrics_from_a_reduction():
    red = {"push_runs": 4, "pull_runs": 2, "push_step_s": 2.8,
           "pull_step_s": 0.75,
           "scope_s": {"push/expand": 1.2, "push/compute": 0.9,
                       "pull/slices": 0.6, "policy": 0.01},
           "stepping_pumps": 3,
           "serve_s": {"serve.pump": 2.7, "serve.sync": 2.55,
                       "serve.step": 0.001}}
    got = trace_phases.metrics(red)
    assert got["push_step_ms"] == pytest.approx(700.0)
    assert got["pull_step_ms"] == pytest.approx(375.0)
    assert got["expand_share"] == pytest.approx(100 * 1.2 / 2.8)
    assert got["pump_wait_ms"] == pytest.approx(850.0)
    assert got["pump_self_ms"] == pytest.approx(50.0)


def test_a_cpu_trace_of_the_server_reads_its_pump_spans(tmp_path):
    import jax

    from repro.core import algorithms as alg
    from repro.graph import generators, pack_ell
    from repro.serving import GraphServer, default_config

    g = generators.rmat(8, 8, seed=1, weighted=True)
    srv = GraphServer(g, pack_ell(g.inc), {"bfs": alg.bfs(0),
                                           "sssp": alg.sssp(0)},
                      slots=4, cfg=default_config(g))
    for s in range(6):
        srv.submit("bfs", s)
        srv.submit("sssp", s)
    srv.pump()                                 # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        pumps = 0
        while srv._queued() or any(p.live() for p in srv.pools.values()):
            srv.pump()
            pumps += 1
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    red = trace_phases.reduce(path)
    assert red["stepping_pumps"] == pumps
    assert set(red["serve_s"]) == {"serve.pump", "serve.admit", "serve.step",
                                   "serve.sync", "serve.fetch"}
    got = trace_phases.metrics(red)
    assert got["pump_wait_ms"] > 0 and got["pump_self_ms"] > 0
    assert got["pump_wait_ms"] + got["pump_self_ms"] == pytest.approx(
        1e3 * red["serve_s"]["serve.pump"] / pumps)


def _answer(done, from_cache=False, **fields):
    req = drive.Request("bfs", 0, due=0.0, done=done, from_cache=from_cache)
    req.completion = types.SimpleNamespace(**fields)
    return req


def test_answer_readers_on_a_synthetic_run():
    reqs = [_answer(1.0, queued_s=q, resident_s=r, push_iters=p,
                    pull_iters=u)
            for q, r, p, u in ((0.5, 4.0, 3, 1), (2.0, 6.0, 5, 2),
                               (9.0, 8.0, 4, 0))]
    reqs.append(_answer(1.5, from_cache=True, queued_s=0.0, resident_s=0.0,
                        push_iters=0, pull_iters=0))       # not engine work
    reqs.append(drive.Request("bfs", 1, due=0.0))           # unanswered
    run = types.SimpleNamespace(requests=reqs)
    assert _reader("queue_wait_p50_s.search")(run) == 2.0
    assert _reader("resident_p50_s.search")(run) == 6.0
    assert _reader("pull_iter_share.search")(run) == pytest.approx(
        100 * 3 / 15)


def test_answer_readers_read_nothing_from_a_program_without_the_stamps():
    reqs = [_answer(1.0, iterations=5), _answer(2.0, iterations=7)]
    run = types.SimpleNamespace(requests=reqs)
    for metric in ("queue_wait_p50_s.search", "resident_p50_s.search",
                   "pull_iter_share.search"):
        assert _reader(metric)(run) is None
    assert answers.values(types.SimpleNamespace(requests=[]),
                          "queued_s") is None
