"""`chip_smoke.py` and the compile-cache helper, exercised on the CPU.

The script refuses every platform but the TPU, so these tests call its
phase functions directly at a small scale: the served path, the update
batch and the independent references it checks against stay working
between chip runs. The four-chip phase runs on four virtual CPU devices in
a child process (the device count is fixed when jax starts).
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.graph import generators
from repro.launch import compile_cache
from repro.launch.catalog import make_catalog
from repro.serving.scheduler import Completion
from repro.streaming import StreamingGraph

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "needs a TPU" in err and "'cpu'" in err
    assert not any(line.startswith("{") for line in out.splitlines())


def test_one_chip_phases_verify_every_completion(smoke, capsys):
    smoke.smoke_one_chip(scale=10, seed=0)
    out = capsys.readouterr().out
    n = 3 * smoke.QUERIES
    assert f"verify: {n}/{n}" in out
    assert "update v1: +64 -64 directed edges" in out


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    code = ("import chip_smoke; "
            "chip_smoke.smoke_four_chips(scale=9, seed=0, chips=4)")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "answers agree" in res.stdout
    # each placement spreads over all four devices
    assert "dev0[:,0:2] dev1[:,2:4] dev2[:,4:6] dev3[:,6:8]" in res.stdout
    assert "dev3[3:4,:]" in res.stdout


def _reference_completions(smoke, g, edges, algo, sources):
    adj, walk = smoke.graph_matrices(edges, g.n_nodes)
    if algo == "ppr_delta":
        rows = smoke.ppr_power(walk, sources, 0.85)
    else:
        rows = smoke.shortest_paths(adj, sources, algo)
    return [Completion(rid=i, algo=algo, source=s,
                       result=np.where(np.isinf(r), 3e38, r).astype(
                           np.float32),
                       iterations=1, from_cache=False)
            for i, (s, r) in enumerate(zip(sources, rows))]


@pytest.mark.parametrize("algo", ["bfs", "sssp", "ppr_delta"])
def test_verify_flags_a_corrupted_answer(smoke, algo):
    g = generators.rmat(8, 8, seed=1)
    edges = smoke.host_edges(g)
    comps = _reference_completions(smoke, g, edges, algo, [0, 3, 17])
    ppr = make_catalog()["ppr_delta"]
    assert smoke.verify(comps, {0: edges}, g.n_nodes, ppr) == 3
    bad = comps[1].result.copy()
    if algo == "ppr_delta":
        # an overestimate at the hub, inside tol·deg: a cold rank may only
        # fall short of the exact PPR
        deg = np.bincount(edges[0], minlength=g.n_nodes)
        hub = int(np.argmax(deg))
        bad[hub] += 0.5 * ppr.param("tol") * deg[hub]
    else:
        bad[int(np.argmax(bad < 1e30))] += 0.5
    comps[1] = Completion(rid=1, algo=algo, source=3, result=bad,
                          iterations=1, from_cache=False)
    assert smoke.verify(comps, {0: edges}, g.n_nodes, ppr) == 2


@pytest.mark.parametrize("overshoot,matched", [(0.5, 3), (2.0, 2)])
def test_verify_bounds_a_resumed_ppr_on_both_sides(smoke, overshoot,
                                                   matched):
    """A cached PPR answer served after an update may overshoot by up to
    tol·deg (residuals resumed across deletions can be negative)."""
    g = generators.rmat(8, 8, seed=1)
    edges = smoke.host_edges(g)
    comps = _reference_completions(smoke, g, edges, "ppr_delta", [0, 3, 17])
    ppr = make_catalog()["ppr_delta"]
    deg = np.bincount(edges[0], minlength=g.n_nodes)
    hub = int(np.argmax(deg))
    bad = comps[1].result.copy()
    bad[hub] += overshoot * ppr.param("tol") * deg[hub]
    comps[1] = Completion(rid=1, algo="ppr_delta", source=3, result=bad,
                          iterations=1, from_cache=True, graph_version=1)
    assert smoke.verify(comps, {0: edges, 1: edges}, g.n_nodes,
                        ppr) == matched


def test_update_batch_reference_matches_streaming_graph(smoke):
    g = generators.rmat(9, 8, seed=2)
    n = g.n_nodes
    edges = smoke.host_edges(g)
    ins, dels = smoke.update_batch(np.random.default_rng(0), edges, n, 16)
    sg = StreamingGraph(g, delta_cap=256)
    rep = sg.apply(ins, dels)
    assert (rep.n_inserted, rep.n_deleted, rep.n_ignored) == (32, 32, 0)
    live = ~sg._dead_out
    xs, xd = sg._ins_coo()
    got = set(zip(np.concatenate([sg._base_src_host()[live], xs]).tolist(),
                  np.concatenate([sg._out_ci[live], xd]).tolist()))
    src, dst, _ = smoke.apply_batch(edges, ins, dels, n)
    assert got == set(zip(src.tolist(), dst.tolist()))
    assert len(got) == src.size            # no parallel edges


def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.cache_dir() == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
