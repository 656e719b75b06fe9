"""The benchmark harness (bench/run.py, bench/drive.py, bench/metrics/) on
the CPU: latency from the due time, percentiles over every request, lookup
by name, and a whole run at a small size, sound and with a fault."""

import json
import shutil
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import drive, run, traffic  # noqa: E402
from bench.metrics import common  # noqa: E402


class FakeServer:
    """Answers every request on the pump after its submit; one pump may
    stall."""

    def __init__(self, stall_on=None, stall_s=0.0):
        self.completions = []
        self.pool_groups = {"bfs": [types.SimpleNamespace(
            steps=0)]}
        self.cache = types.SimpleNamespace(clear=lambda: None)
        self.pending = []
        self.pumps = 0
        self.stall_on, self.stall_s = stall_on, stall_s

    def submit(self, algo, source):
        rid = len(self.completions) + len(self.pending)
        self.pending.append((rid, algo, source))
        return rid

    def pump(self):
        self.pumps += 1
        if self.pumps == self.stall_on:
            time.sleep(self.stall_s)
        self.pool_groups["bfs"][0].steps += 1
        new = [types.SimpleNamespace(rid=r, algo=a, source=s, result=1,
                                     iterations=1, from_cache=False)
               for r, a, s in self.pending]
        self.pending = []
        self.completions.extend(new)
        return new


def _open_run(srv, n=40, seconds=0.4):
    arrivals = [traffic.Arrival(i * seconds / n, "bfs", i) for i in range(n)]
    drv = drive.Client(srv)
    start = drive.now()
    drive.open_loop(drv, arrivals, start, seconds, lambda: None,
                    start + 10)
    return types.SimpleNamespace(requests=drv.requests, elapsed=10.0)


def test_a_stalled_pump_raises_the_tail_measured_from_due_time():
    calm = common.latency_p(_open_run(FakeServer()), 95)
    stalled = common.latency_p(_open_run(FakeServer(stall_on=5,
                                                    stall_s=0.3)), 95)
    assert calm < 0.05
    # arrivals due during the stall wait it out, though each is submitted
    # only after the stall: timing from submit would miss it
    assert stalled > 0.1


def test_percentiles_are_over_all_requests():
    lat = [0.1] * 90 + [5.0] * 10
    reqs = [drive.Request("bfs", 0, due=0.0, done=x) for x in lat]
    view = types.SimpleNamespace(requests=reqs, elapsed=10.0)
    assert common.latency_p(view, 95) == np.percentile(lat, 95)
    # chunked medians of tens would hide the tail entirely
    assert common.latency_p(view, 95) == 5.0
    reqs.append(drive.Request("bfs", 0, due=0.0))   # never answered
    assert common.latency_p(view, 100) == 10.0


def _fixture(tmp_path: Path, scale: int = 8) -> Path:
    """A benchmark root with the real configuration (cut to `scale`),
    mixes, metrics and cell, and a new configuration, traffic mix, metric
    and cell added as files only."""
    (tmp_path / "bench").mkdir()
    shutil.copytree(ROOT / "bench" / "metrics", tmp_path / "bench" / "metrics")
    shutil.copytree(ROOT / "bench" / "traffic", tmp_path / "bench" / "traffic")
    (tmp_path / "bench" / "configs").mkdir()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"], bench["workloads"] = [], []
    for name, mix in (("g500-s20", "search-closed"),):
        cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
        cfg["scale"] = scale
        (tmp_path / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({
            "name": f"{name}.{mix.split('-')[0]}", "config": name,
            "traffic": mix, "chips": 1, "why": "test"})
    cfg = json.loads((ROOT / "bench/configs/g500-s20.json").read_text())
    cfg.update(scale=scale, programs={"bfs": {}}, checks={
        "bfs": {"bfs_wrong_vertices": 0}}, lanes=4)
    (tmp_path / "bench/configs/new-bfs.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps({
        "loop": "open", "arrival": "poisson", "rate_qps": 40.0,
        "programs": {"bfs": 1}, "sources": {"draw": "zipf",
                                            "exponent": 0.99},
        "check_per_program": 8}))
    (tmp_path / "bench/metrics/answered_new.py").write_text(
        "def read(run):\n    return float(len(run.requests))\n")
    bench["configs"].append({"name": "new-bfs", "source": "test",
                             "file": "bench/configs/new-bfs.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new-bfs.mix", "config": "new-bfs",
                               "traffic": "new-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answered_new", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "latency_p50_s",
                               "workloads": ["new-bfs.mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def _run(root, capsys, workload, seed=2**31 + 3, seconds=1.0, trace=0):
    args = types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace,
                                 keep_trace=None)
    rc = run.run(args, root=root, chip=False)
    out = capsys.readouterr()
    return rc, out


def test_no_tpu_exits_nonzero_with_no_result(tmp_path, capsys):
    args = types.SimpleNamespace(workload="g500-s20.search", seed=1,
                                 seconds=1.0, trace=0, keep_trace=None)
    rc = run.run(args, root=_fixture(tmp_path))
    out = capsys.readouterr()
    assert rc != 0
    assert '"correct"' not in out.out


def test_new_files_are_found_by_name(tmp_path, capsys):
    rc, out = _run(_fixture(tmp_path), capsys, "new-bfs.mix", trace=1)
    assert rc == 0
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["answered_new"]["value"] == res["attempted"]
    assert list(res)[-1] == "checks"
    assert "check bfs_wrong_vertices" in out.err


def test_a_sound_run_is_correct_and_an_altered_answer_is_not(
        tmp_path, capsys, monkeypatch):
    root = _fixture(tmp_path)
    rc, out = _run(root, capsys, "g500-s20.search")
    res = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True, out.err[-2000:]
    assert set(res["metrics"]) == {"latency_p50_s", "latency_p95_s", "qps",
                                   "setup_s"}

    from repro.serving import scheduler

    harvest = scheduler._LanePool.harvest

    def altered(self):
        # one answer altered where it is produced
        out = harvest(self)
        return [(lane, rid, res * 1.01 if i == 0 else res, it, ex)
                for i, (lane, rid, res, it, ex) in enumerate(out)]

    monkeypatch.setattr(scheduler._LanePool, "harvest", altered)
    rc, out = _run(root, capsys, "g500-s20.search")
    res = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is False


#: the open-loop mixes the generator reads besides the cell's own
OPEN_MIXES = {
    "zipf-open": {"loop": "open", "arrival": "poisson", "rate_qps": 1.8,
                  "programs": {"ppr_delta": 1},
                  "sources": {"draw": "zipf", "exponent": 0.99},
                  "check_per_program": 16},
    "uniform-open": {"loop": "open", "arrival": "poisson", "rate_qps": 1.8,
                     "programs": {"ppr_delta": 1},
                     "sources": {"draw": "distinct"},
                     "check_per_program": 16},
}


@pytest.mark.parametrize("mix", ["search-closed", "zipf-open",
                                 "uniform-open"])
def test_traffic_is_drawn_from_the_seed(mix):
    spec = OPEN_MIXES.get(mix) or json.loads(
        (ROOT / "bench/traffic" / f"{mix}.json").read_text())
    deg = np.ones(5000)
    a = traffic.plan(spec, 2**31 + 9, deg, 8, 51.0)
    b = traffic.plan(spec, 2**31 + 9, deg, 8, 51.0)
    assert a == b
    warm = {s for ss in a[traffic.WARM].values() for s in ss}
    assert len(warm) == 8 * len(spec["programs"])
    if spec["loop"] == "open":
        assert len(a["arrivals"]) == round(spec["rate_qps"] * 51.0)
        if spec["sources"]["draw"] == "distinct":
            assert len({x.source for x in a["arrivals"]} | warm) == \
                len(a["arrivals"]) + len(warm)


def test_structure_labelled_keys_are_the_same_queries_under_each_seed():
    from bench.gen import graph500

    spec = json.loads((ROOT / "bench/traffic/search-closed.json").read_text())
    init = (0.57, 0.19, 0.19, 0.05)
    plans = []
    for seed in (2**31 + 1, 2**31 + 2):
        e = graph500.generate(seed, 9, 16, init, 20)
        p = traffic.for_graph(spec, e, 20, 8, 51.0)
        inv = np.argsort(e.perm)           # run label -> structure label
        plans.append({k: {prog: inv[np.asarray(q)].tolist()
                          for prog, q in p[k].items()}
                      for k in (traffic.WARM, "queues")})
        deg = e.degrees()
        assert all(deg[s] > 0 for q in p["queues"].values() for s in q)
    assert plans[0] == plans[1]


def _unchanged_state(monkeypatch):
    from repro.serving import scheduler

    def step(self):
        if self.live():
            self.steps += 1            # the state comes back unchanged

    monkeypatch.setattr(scheduler.AlgoPool, "step", step)


def _half_the_lanes(monkeypatch):
    from repro.serving import scheduler

    harvest = scheduler._LanePool.harvest

    def half(self):
        # the odd lanes' answers are left out of what the pool hands back
        return [h for h in harvest(self) if h[0] % 2 == 0]

    monkeypatch.setattr(scheduler._LanePool, "harvest", half)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_lanes],
                         ids=["unchanged-state", "half-the-lanes"])
def test_a_broken_step_in_the_window_is_not_correct(tmp_path, capsys,
                                                    monkeypatch, fault):
    warm_up = drive.warm_up

    def then_break(*a, **kw):
        warm_up(*a, **kw)
        fault(monkeypatch)

    monkeypatch.setattr(drive, "warm_up", then_break)
    monkeypatch.setattr(run, "DRAIN_S", 3.0)
    rc, out = _run(_fixture(tmp_path), capsys, "g500-s20.search")
    res = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is False
    assert res["failed"] > 0
