"""A configuration that places its pools on a mesh (bench/run.py `mesh_of`,
`build_server`, `memory_peaks`) and the trace reduction over several device
planes (bench/trace_reduce.py, bench/trace_phases.py), on the CPU.

The whole-run tests start a child process with four host devices
(`--xla_force_host_platform_device_count=4`): the four-chip cell
`g500-s20.search-4x` cut to Graph500 scale 8 runs through its sharded pools,
is `correct`, answers as a one-device server of the same lanes does, and is
not `correct` with a fault planted in its timed path, the exchange between
the chips among them.
The multi-plane traces are the one-chip chip traces of `tests/bench/data/`
with the device plane written again under `/device:TPU:1..3`, and a short
trace of the four-chip cell recorded on a v5e host."""

import gzip
import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, run, trace_phases, trace_reduce, xplane_ops  # noqa: E402
from bench.metrics import common  # noqa: E402

DATA = Path(__file__).parent / "data"
CELL = "g500-s20.search-4x"
#: the same configuration with its pools on one device
TWIN = "g500-s20.search-4x-one"


def _fixture(tmp: Path, scale: int = 8, mesh=(4, 1), chips: int = 4) -> Path:
    """A benchmark root with the four-chip cell cut to `scale`, and a twin
    cell of the same configuration without `mesh`."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    for sub in ("metrics", "traffic"):
        (tmp / "bench" / sub).symlink_to(ROOT / "bench" / sub)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    cfg.update(scale=scale, mesh=list(mesh))
    one = {k: v for k, v in cfg.items() if k != "mesh"}
    bench["configs"], bench["workloads"] = [], []
    for name, c, n in ((CELL, cfg, chips), (TWIN, one, 1)):
        (tmp / f"bench/configs/{name}.json").write_text(json.dumps(c))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": name, "config": name,
                                   "traffic": cell["traffic"], "chips": n,
                                   "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def _args(workload, seed=2**31 + 5, seconds=1.0, trace=0):
    return types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace,
                                 keep_trace=None)


# -- the configuration's keys ------------------------------------------------


def test_the_four_chip_configuration_states_its_mesh():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert run.mesh_of(cfg, cell["chips"]) == 4
    assert cfg["lanes"] == 32
    one = json.loads((ROOT / "bench/configs/g500-s20.json").read_text())
    # the graph is the one-chip cell's, unchanged
    for k in ("scale", "edge_factor", "graph", "programs"):
        assert cfg[k] == one[k], k
    # the answers are held as the one-chip cell's, and the pools to one
    # push/pull decision per step over every chip's lanes
    assert cfg["checks"] == dict(
        one["checks"], consensus={"consensus_split_steps": 0})
    mix = json.loads(
        (ROOT / f"bench/traffic/{cell['traffic']}.json").read_text())
    # the one-chip cell's load per lane: as many queued as in lanes
    assert mix["outstanding"] == 2 * cfg["lanes"]


@pytest.mark.parametrize("cfg,err", [
    ({"lanes": 8}, None),
    ({"lanes": 32, "mesh": [4, 1]}, None),
    ({"lanes": 8, "mesh": [1, 4]}, "no cell shards edges"),
    ({"lanes": 8, "mesh": [2, 2]}, "no cell shards edges"),
    ({"lanes": 32, "mesh": [2, 1]}, "holds 2"),
    ({"lanes": 32, "mesh": [4, 2]}, "holds 8"),
    ({"lanes": 6, "mesh": [4, 1]}, "divide"),
], ids=["none", "replicated", "edge-sharded", "both-axes", "wrong-size",
        "too-large", "lanes"])
def test_mesh_of(cfg, err):
    if err is None:
        got = run.mesh_of(cfg, 4 if "mesh" in cfg else 1)
        assert got == (cfg["mesh"][0] if "mesh" in cfg else None)
    else:
        with pytest.raises(ValueError, match=err):
            run.mesh_of(cfg, 4)


def test_a_mesh_of_the_wrong_size_exits_2_before_any_set_up(
        tmp_path, capsys, monkeypatch):
    from bench.gen import graph500

    def never(*_a, **_kw):
        raise AssertionError("set-up ran")

    monkeypatch.setattr(graph500, "for_config", never)
    root = _fixture(tmp_path, mesh=(2, 1))
    rc = run.run(_args(CELL), root=root, chip=False)
    out = capsys.readouterr()
    assert rc == 2
    assert '"correct"' not in out.out
    assert "mesh 2x1 holds 2 chips, the cell asks for 4" in out.err


def test_build_server_passes_a_mesh_only_where_the_configuration_has_one(
        monkeypatch):
    import repro.serving as serving
    from bench.gen import graph500

    calls = []

    def server(*args, **kw):
        calls.append((args, kw))
        return types.SimpleNamespace(pool_groups={})

    monkeypatch.setattr(serving, "GraphServer", server)
    monkeypatch.setattr(serving, "make_serving_mesh",
                        lambda d, s: ("mesh", d, s))
    edges = graph500.generate(2**31 + 7, 6, 16, (0.57, 0.19, 0.19, 0.05), 20)
    cfg = json.loads((ROOT / "bench/configs/g500-s20.json").read_text())
    run.build_server(cfg, edges)
    run.build_server(cfg, edges, 4)
    run.build_server(cfg, edges, 2)
    (args0, kw0), (args1, kw1), (_a2, kw2) = calls
    # no mesh: the call the harness has always made
    assert sorted(kw0) == ["cache_capacity", "cfg", "delta_cap",
                           "refresh_lanes", "slots"]
    assert kw0["slots"] == cfg["lanes"]
    assert set(kw1) == set(kw0) | {"mesh", "placements"}
    assert kw1["mesh"] == ("mesh", 4, 1)
    assert kw1["placements"] == {p: serving.Placement("replicated", 4)
                                 for p in cfg["programs"]}
    assert kw2["mesh"] == ("mesh", 2, 1)
    assert kw2["placements"] == {p: serving.Placement("replicated", 2)
                                 for p in cfg["programs"]}
    assert {k: v for k, v in kw1.items()
            if k not in ("mesh", "placements", "cfg")} == {
        k: v for k, v in kw0.items() if k != "cfg"}
    assert [type(a).__name__ for a in args1] == [
        type(a).__name__ for a in args0]


def test_peak_memory_is_the_fullest_device():
    devs = [types.SimpleNamespace(memory_stats=lambda b=b: (
        None if b is None else {"peak_bytes_in_use": b, "bytes_in_use": 1}))
        for b in (5, 9, None, 7)]
    assert run.memory_peaks(devs) == [5, 9, 0, 7]
    srv = types.SimpleNamespace(mesh=types.SimpleNamespace(
        devices=types.SimpleNamespace(flat=iter(devs))))
    assert max(run.memory_peaks(run.server_devices(srv))) == 9


# -- a whole run on four host devices ------------------------------------------


def _child(script: str, timeout: int = 900) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # a device count set by an earlier test in this worker must not win
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=4"])
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT), str(ROOT / "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout, cwd=str(ROOT))
    assert r.returncode == 0, f"stdout:\n{r.stdout[-4000:]}\n" \
                              f"stderr:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.fixture(scope="module")
def four_device_runs(tmp_path_factory):
    """The cut four-chip cell and its one-device twin, each run once in one
    child process; every answered request's result is kept."""
    root = _fixture(tmp_path_factory.mktemp("mesh"))
    out = _child(f"""
        import contextlib, io, json, sys
        from pathlib import Path
        import numpy as np
        from bench import check, run

        root = Path({str(root)!r})
        kept, pools = {{}}, {{}}
        sample, build = check.sample, run.build_server

        def every(reqs, per, seed):
            got = [r for r in reqs if r.completion is not None
                   and r.completion.result is not None]
            kept[name] = {{f"{{r.program}}:{{r.source}}":
                          np.asarray(r.completion.result).tolist()
                          for r in got}}
            return sample(reqs, per, seed)

        def built(*a, **kw):
            srv, t0, t1 = build(*a, **kw)
            pools[name] = [
                [type(p).__name__, len(p.state.active.sharding.device_set)]
                for grp in srv.pool_groups.values() for p in grp]
            return srv, t0, t1

        check.sample, run.build_server = every, built
        lines = {{}}
        for name in ({CELL!r}, {TWIN!r}):
            args = type("A", (), dict(workload=name, seed=2**31 + 5,
                                      seconds=1.0, trace=0,
                                      keep_trace=None))
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = run.run(args, root=root, chip=False)
            lines[name] = [rc, buf.getvalue().strip().splitlines()[-1]]
        print(json.dumps({{"lines": lines, "kept": kept, "pools": pools}}))
    """)
    return json.loads(out.strip().splitlines()[-1])


def test_the_mesh_cell_runs_sharded_pools_and_is_correct(four_device_runs):
    rc, line = four_device_runs["lines"][CELL]
    res = json.loads(line)
    assert rc == 0 and res["correct"] is True, line
    assert res["failed"] == 0 and res["attempted"] > 0
    assert four_device_runs["pools"][CELL] == [["ShardedAlgoPool", 4]] * 2
    assert four_device_runs["pools"][TWIN] == [["AlgoPool", 1]] * 2
    assert res["device"]["count"] == 4
    peaks = res["device"]["memory_peak_bytes_per_device"]
    assert len(peaks) == 4
    assert res["device"]["memory_peak_bytes"] == max(peaks)
    assert set(res["metrics"]) == {"latency_p50_s", "latency_p95_s", "qps",
                                   "setup_s"}
    # every chip's lanes stepped under the one decision of their pool
    assert res["checks"]["consensus_split_steps"] == {"value": 0,
                                                      "limit": 0.0}


def test_the_mesh_cell_answers_as_one_device_does(four_device_runs):
    rc, line = four_device_runs["lines"][TWIN]
    assert rc == 0 and json.loads(line)["correct"] is True, line
    sharded = four_device_runs["kept"][CELL]
    one = four_device_runs["kept"][TWIN]
    common_keys = sorted(set(sharded) & set(one))
    assert len(common_keys) >= 64
    assert {k.split(":")[0] for k in common_keys} == {"bfs", "sssp"}
    for k in common_keys:
        assert sharded[k] == one[k], k      # bit for bit


#: faults planted in the four-chip cell's timed path, after the warm-up
#: (the exchange before the server is built: the warm-up compiles the step)
FAULTS = {
    "unchanged-state": """
        def step(self):
            if self.live():
                self.steps += 1            # the state comes back unchanged
        placement.ShardedAlgoPool.step = step
    """,
    "half-the-lanes": """
        harvest = scheduler._LanePool.harvest
        scheduler._LanePool.harvest = lambda self: [
            h for h in harvest(self) if h[0] % 2 == 0]
    """,
    "altered-answer": """
        harvest = scheduler._LanePool.harvest
        scheduler._LanePool.harvest = lambda self: [
            (lane, rid, res * 1.01 if i == 0 else res, it, ex)
            for i, (lane, rid, res, it, ex) in enumerate(harvest(self))]
    """,
    "no-exchange": """
        def local(deg, cfg, mask, axis):
            union = jnp.any(mask, axis=-1)           # this chip's lanes only
            fe = jnp.sum(jnp.where(union[:-1], deg, 0)).astype(jnp.int32)
            return fe, jnp.sum(union[:-1]) > cfg.frontier_cap
        sharded._global_union_volume = local
    """,
}


@pytest.fixture(scope="module")
def four_device_faults(tmp_path_factory):
    """The cut four-chip cell run once with each fault, in one child."""
    root = _fixture(tmp_path_factory.mktemp("faults"))
    install = "def install(fault):\n" + "\n".join(
        f"    if fault == {k!r}:\n"
        + textwrap.indent(textwrap.dedent(v).strip(), " " * 8)
        for k, v in FAULTS.items())
    script = textwrap.dedent(f"""
        import contextlib, io, json
        from pathlib import Path
        import jax.numpy as jnp
        from bench import drive, run
        from repro.serving import placement, scheduler, sharded

        warm_up = drive.warm_up
        run.DRAIN_S = 5.0
        """) + install + textwrap.dedent(f"""

        out = {{}}
        for fault in {list(FAULTS)!r}:
            saved = (placement.ShardedAlgoPool.step,
                     scheduler._LanePool.harvest,
                     sharded._global_union_volume)
            if fault == "no-exchange":
                install(fault)
                drive.warm_up = warm_up
            else:
                def then(*a, fault=fault, **kw):
                    warm_up(*a, **kw)
                    install(fault)
                drive.warm_up = then
            args = type("A", (), dict(workload={CELL!r}, seed=2**31 + 11,
                                      seconds=1.0, trace=0,
                                      keep_trace=None))
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = run.run(args, root=Path({str(root)!r}), chip=False)
            out[fault] = [rc, buf.getvalue().strip().splitlines()[-1]]
            (placement.ShardedAlgoPool.step, scheduler._LanePool.harvest,
             sharded._global_union_volume) = saved
        print(json.dumps(out))
    """)
    return json.loads(_child(script).strip().splitlines()[-1])


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_broken_sharded_step_is_not_correct(four_device_faults, fault):
    rc, line = four_device_faults[fault]
    res = json.loads(line)
    assert rc == 0 and res["correct"] is False, line


def test_the_exchange_left_out_splits_the_pools_decision(
        four_device_faults):
    """Each chip computes its own lanes' answers from its own copy of the
    graph; the psum of the union masks only feeds the push/pull decision.
    Left out, every answer stays exact, and each chip decides for its own
    lanes: the steps at which the chips' lanes ran under different modes
    fail the run."""
    rc, line = four_device_faults["no-exchange"]
    checks = json.loads(line)["checks"]
    assert checks["bfs_wrong_vertices"]["value"] == 0
    assert checks["sssp_rel_err"]["value"] <= checks["sssp_rel_err"]["limit"]
    assert checks["consensus_split_steps"]["value"] > 0


# -- the pools' one decision per step --------------------------------------------


def _pool(admit, base, its, rows, width=8):
    trace = [list(r) + [-1] * (width - len(r)) for r in rows]
    return types.SimpleNamespace(
        state=types.SimpleNamespace(mode_trace=trace, it=its),
        lane_admit_step=admit, lane_it_base=base)


@pytest.mark.parametrize("pools,want", [
    # two lanes sharing steps 3-5 under one mode each step
    ([_pool([2, 1], [0, 0], [3, 4], [[0, 1, 1], [0, 0, 1, 1]])], 0),
    # the same lanes, the second pulling at step 4 while the first pushes
    ([_pool([2, 1], [0, 0], [3, 4], [[0, 0, 1], [0, 0, 1, 1]])], 1),
    # a resumed lane: its first two iterations ran before its admission
    ([_pool([2, 3], [0, 2], [3, 4], [[0, 1, 1], [1, 1, 1, 1]])], 0),
    ([_pool([2, 3], [0, 2], [3, 4], [[0, 1, 1], [1, 1, 0, 0]])], 2),
    # a lane never admitted ran no iteration
    ([_pool([0, 0], [0, 0], [0, 2], [[], [1, 0]])], 0),
    # the last column holds whichever iteration came last: left out
    ([_pool([0, 0], [0, 0], [4, 4], [[0, 0, 0, 0], [0, 0, 0, 1]],
            width=4)], 0),
    # pools are counted apart: the same step of two pools may differ
    ([_pool([0], [0], [2], [[0, 0]]), _pool([0], [0], [2], [[1, 1]])], 0),
    ([_pool([0, 0], [0, 0], [2, 2], [[0, 1], [1, 1]]),
      _pool([0, 0], [0, 0], [2, 2], [[1, 0], [1, 1]])], 2),
], ids=["agree", "split", "resumed", "resumed-split", "idle-lane",
        "last-column", "two-pools", "two-pools-split"])
def test_split_steps(pools, want):
    assert check.split_steps(pools) == want


# -- traces with four device planes --------------------------------------------


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(num: int, wt: int, v) -> bytes:
    key = _varint(num << 3 | wt)
    if wt == 0:
        return key + _varint(v)
    if wt == 2:
        return key + _varint(len(v)) + bytes(v)
    return key + bytes(v)


def four_planes(src: bytes) -> bytes:
    """The trace `src` with its one device plane written again as
    `/device:TPU:1`, `:2` and `:3` (new plane ids): four chips that ran
    the same operations at the same times."""
    out = bytearray(src)
    planes = [v for num, _wt, v in xplane_ops._fields(src) if num == 1]
    (dev,) = [p for p in planes
              if xplane_ops._plane(p).name == "/device:TPU:0"]
    top = max(v for p in planes for num, wt, v in xplane_ops._fields(p)
              if num == 1 and wt == 0)
    for i in (1, 2, 3):
        body = b"".join(
            _field(1, 0, top + i) if num == 1 else
            _field(2, 2, f"/device:TPU:{i}".encode()) if num == 2 else
            _field(num, wt, v)
            for num, wt, v in xplane_ops._fields(dev))
        out += _field(1, 2, body)
    return bytes(out)


@pytest.fixture(scope="module")
def unscoped_x4(tmp_path_factory):
    path = tmp_path_factory.mktemp("x4") / "unscoped_x4.xplane.pb"
    path.write_bytes(four_planes(
        (DATA / "g500_s20_search.xplane.pb").read_bytes()))
    return str(path)


def test_four_planes_are_read_as_four_devices(unscoped_x4):
    names = [p.name for p in xplane_ops.read(unscoped_x4)]
    assert sorted(n for n in names if n.startswith("/device:TPU:")) == [
        f"/device:TPU:{i}" for i in range(4)]
    devices, _host = trace_reduce.read_planes(trace_reduce._load(unscoped_x4))
    assert len(devices) == 4


def test_each_pools_step_is_found_on_every_plane(unscoped_x4):
    # the unscoped trace's window and steps (test_bench_trace_reduce.py)
    steps, window = [3, 3], 3.517
    devices, _host = trace_reduce.read_planes(trace_reduce._load(unscoped_x4))
    chosen = [trace_reduce.step_modules(d[trace_reduce.MODULES_LINE], steps)
              for d in devices]
    assert all(len(c) == 2 for c in chosen)
    assert all(c == chosen[0] for c in chosen)
    one = trace_reduce.reduce(str(DATA / "g500_s20_search.xplane.pb"),
                              window, steps)
    four = trace_reduce.reduce(unscoped_x4, window, steps)
    # per-device averages: four like chips read as one
    for k in ("busy_s", "step_s"):
        assert four[k] == pytest.approx(one[k], rel=1e-12), k
    assert four["collective_s"] == one["collective_s"] == 0
    # the breakdown too: four chips' seconds are each chip's
    for k in ("device_ops", "idle_gaps"):
        assert [n for n, _t in four[k]] == [n for n, _t in one[k]], k
        assert [t for _n, t in four[k]] == pytest.approx(
            [t for _n, t in one[k]], rel=1e-12), k


def test_the_phase_reduction_averages_over_device_planes(tmp_path):
    src = gzip.decompress(
        (DATA / "g500_s20_search_scoped.xplane.pb.gz").read_bytes())
    one_path, four_path = tmp_path / "one.xplane.pb", tmp_path / "x4.pb"
    one_path.write_bytes(src)
    four_path.write_bytes(four_planes(src))
    one = trace_phases.reduce(str(one_path))
    four = trace_phases.reduce(str(four_path))
    assert (four["push_runs"], four["pull_runs"]) == (28, 86)
    for k in ("push_step_s", "pull_step_s", "stepping_pumps"):
        assert four[k] == pytest.approx(one[k], rel=1e-12), k
    assert four["serve_s"] == one["serve_s"]
    assert four["scope_s"] == pytest.approx(one["scope_s"], rel=1e-12)
    got, want = trace_phases.metrics(four), trace_phases.metrics(one)
    assert got == pytest.approx(want, rel=1e-12)
    assert all(v is not None for v in got.values())


#: a four-chip trace recorded on a v5e host: `g500-s20.search-4x` taken by
#: `bench/run.py --seed 3150000001 --seconds 3 --trace 1 --keep-trace`
#: and gzipped; what that run printed
X4 = DATA / "g500_s20_search_4x.xplane.pb.gz"
X4_WINDOW_S = 3.236769111000001
X4_STEPS = [4, 4]
X4_PRINTED = {"busy_s": 2.991744119, "step_device_ms.search": 359.35466625,
              "collective_ms.search": 13.2005638125,
              "pump_wait_ms": 720.9806372500001,
              "pump_self_ms": 87.95173150000002,
              "push_step_ms": 345.1141089375001,
              "pull_step_ms": 373.5952235625,
              "expand_share": 43.558038191427194}


@pytest.fixture(scope="module")
def chip_x4(tmp_path_factory):
    path = tmp_path_factory.mktemp("chip_x4") / "x4.xplane.pb"
    path.write_bytes(gzip.decompress(X4.read_bytes()))
    return str(path)


def test_a_four_chip_trace_finds_the_pools_steps_on_every_plane(chip_x4):
    devices, _host = trace_reduce.read_planes(trace_reduce._load(chip_x4))
    assert len(devices) == 4
    chosen = [trace_reduce.step_modules(d[trace_reduce.MODULES_LINE],
                                        X4_STEPS) for d in devices]
    # a sharded pool's step is the shard_map of `step`, named `jit_step`
    # whichever pool it serves; each chip runs both pools' steps
    assert all(len(c) == 2 for c in chosen)
    assert all(c == chosen[0] for c in chosen)
    assert all(n.startswith("jit_step(") for n in chosen[0])
    red = trace_reduce.reduce(chip_x4, X4_WINDOW_S, X4_STEPS)
    assert red["busy_s"] == pytest.approx(X4_PRINTED["busy_s"], rel=1e-9)
    run_ = types.SimpleNamespace(trace=red, steps=sum(X4_STEPS))
    assert common.step_device_ms(run_) == pytest.approx(
        X4_PRINTED["step_device_ms.search"], rel=1e-9)
    assert common.collective_ms(run_) == pytest.approx(
        X4_PRINTED["collective_ms.search"], rel=1e-9)
    got = trace_phases.metrics(trace_phases.reduce(chip_x4))
    for k, v in got.items():
        assert v == pytest.approx(X4_PRINTED[k], rel=1e-9), k


def test_the_union_psum_runs_once_per_step_on_every_chip(chip_x4):
    """The v5e names the replicated step's union-mask psum
    `%psum.<i> = s32[1048577]{...} all-reduce(...)`; the collectives
    outside the steps are the sharded admission's and harvest's."""
    import bisect

    planes = [p for p in xplane_ops.read(chip_x4)
              if p.name.startswith(trace_reduce.DEVICE_PREFIX)]
    for plane in planes:
        mods = sorted(plane.lines[trace_reduce.MODULES_LINE],
                      key=lambda m: m.start_ns)
        starts = [m.start_ns for m in mods]
        in_step = []
        for ev in plane.lines[trace_reduce.OPS_LINE]:
            if trace_reduce.opcode(ev.name) not in trace_reduce.COLLECTIVES:
                continue
            mod = mods[bisect.bisect_right(starts, ev.start_ns) - 1]
            if mod.name.startswith("jit_step("):
                in_step.append(ev.name)
        assert len(in_step) == sum(X4_STEPS), plane.name
        assert all(n.startswith("%psum.") and " all-reduce(" in n
                   and n.split(" = ")[1].startswith("s32[1048577]")
                   for n in in_step)


# -- collectives -----------------------------------------------------------------


@pytest.mark.parametrize("op,code", [
    ("%psum.7 = s32[1048577]{0:T(1024)S(1)} all-reduce(%convert.34), "
     "channel_id=1, replica_groups={{0,1,2,3}}", "all-reduce"),
    ("%all-reduce-start.1 = (s32[1048577]{0:T(1024)}, s32[1048577]{0}) "
     "all-reduce-start(%x)", "all-reduce-start"),
    ("%copy-start.2 = (pred[1048577,8]{0,1:T(8,128)(4,1)S(1)}, "
     "u32[]{:S(2)}) copy-start(pred[1048577,8]{0,1:T(8,128)(4,1)} %a)",
     "copy-start"),
    ("%fusion.68 = s32[2097152]{0:T(1024)} fusion(%p), kind=kLoop",
     "fusion"),
    ("%all-gather.3 = f32[4,8]{1,0} all-gather(f32[1,8]{1,0} %y)",
     "all-gather"),
    ("while.1", ""),
])
def test_opcode(op, code):
    assert trace_reduce.opcode(op) == code
    assert (code in trace_reduce.COLLECTIVES) == code.startswith("all-")


def test_collective_time_per_step():
    red = {"collective_s": 0.012, "step_s": 1.0}
    run_ = types.SimpleNamespace(trace=red, steps=6)
    assert common.collective_ms(run_) == pytest.approx(2.0)
    # one chip: no collective ran, nothing to read
    assert common.collective_ms(types.SimpleNamespace(
        trace={"collective_s": 0.0}, steps=6)) is None
    assert common.collective_ms(types.SimpleNamespace(
        trace=None, steps=6)) is None
