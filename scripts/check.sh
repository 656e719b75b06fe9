#!/usr/bin/env bash
# Repo check: tier-1 tests + serving/streaming smokes + bench-record lint.
#
#   scripts/check.sh          # or: make check
#
# Tier-1 (ROADMAP.md): the full pytest suite, fail-fast.
# Serving smoke: a few queries through the batched graph server on a small
# generated graph — catches scheduler/engine wiring regressions in seconds.
# Streaming smoke: queries with edge-update batches interleaved, every
# completion verified against a from-scratch run on its graph version.
# Bench schema: BENCH_*.json records must stay well-formed (pass flags are
# bools, numbers finite — scripts/bench_schema.py).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: pytest =="
# --durations keeps the property suites (test_ppr_delta & co) honest about
# their runtime budget
python -m pytest -x -q --durations=10

echo "== acclint: ACC contracts / collective schedules / determinism =="
# static gate (DESIGN.md §16): jaxpr analyzer over every catalog program x
# engine entry point (§9 deadlock rule, §12 transfer-free, §8 static
# shapes), AST conventions + program metadata over src/repro/, and the
# combiner-algebra probes. Non-baselined findings fail the check
# (suppressions: ACCLINT_BASELINE.json); the seeded per-rule violations
# must keep firing (--fixtures exits non-zero by design).
python -m repro.launch.acclint
if python -m repro.launch.acclint --fixtures >/dev/null 2>&1; then
    echo "acclint --fixtures exited zero: seeded violations no longer fire" >&2
    exit 1
fi

echo "== ruff: generic lint floor (pyflakes + isort) =="
# gated: the container may not ship ruff — skip with a notice, never fail
# on absence (the repo carries the [tool.ruff] config either way)
if command -v ruff >/dev/null 2>&1; then
    ruff check .
elif python -c "import ruff" >/dev/null 2>&1; then
    python -m ruff check .
else
    echo "[check] ruff not installed — skipping generic lint floor"
fi

echo "== serving smoke =="
python -m repro.launch.serve_graph --requests 8 --slots 4

echo "== streaming smoke =="
python -m repro.launch.stream_graph --requests 9 --slots 3 --scale 8 \
    --update-every 4 --verify

echo "== sharded serving smoke (forced 8-device host mesh) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m repro.launch.serve_graph --requests 8 --slots 8 --scale 8 \
    --mesh 8x1
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m repro.launch.serve_graph --requests 6 --slots 4 --scale 8 \
    --mesh 2x4 --placement edge_sharded

echo "== sharded round-2 smoke: compacted edge scan + touched-delta shipping =="
# streaming updates through an edge-partitioned server on the forced
# 8-device mesh: exercises the frontier-compacted per-shard expansion,
# CSR-free admission and per-shard delta slice shipping, with every
# completion verified against a from-scratch run on its graph version
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m repro.launch.stream_graph --requests 9 --slots 3 --scale 8 \
    --update-every 4 --mesh 1x8 --placement edge_sharded \
    --algos bfs,sssp,ppr_delta --verify
# single-edge-shard pools must take the zero-copy delta path (allocation-
# count assertion: no full overlay reslice per update batch)
python - <<'PY'
import numpy as np
from repro.core import algorithms as alg
from repro.graph import generators, partition
from repro.serving import GraphServer, default_config, make_serving_mesh

g = generators.rmat(8, 4, seed=1, directed=True)
mesh = make_serving_mesh(1, 1)
srv = GraphServer(g, None, {"bfs": alg.bfs(0)}, slots=2,
                  cfg=default_config(g), delta_cap=16, mesh=mesh,
                  placements={"bfs": ("edge_sharded", 1)})
before = dict(partition.SHARD_DELTA_STATS)
for k in range(3):
    srv.submit("bfs", k)
    srv.drain()
    srv.apply_updates(inserts=[(k, k + 9)])
after = dict(partition.SHARD_DELTA_STATS)
assert after["full_reslice"] == before["full_reslice"], (
    "single-shard pool paid a full overlay reslice", before, after)
assert after["short_circuit"] > before["short_circuit"]
ship = srv.update_log[-1]["shipped"]["bfs"]
assert ship["edge_shards_shipped"] == 0, ship     # insert-only: base resident
print("[check] single-shard delta short-circuit + touched shipping OK")
PY

echo "== catalog smoke: whole-catalog batched + edge-sharded streamed =="
# the ACC catalog beyond the traversal trio, dispatched purely on program
# metadata (DESIGN.md §15): source-free wcc/kcore/mis/pagerank_delta
# through the batched server...
python -m repro.launch.serve_graph --requests 8 --slots 4 --scale 8 \
    --algos wcc,kcore,mis,pagerank_delta
# ...and wcc+kcore through an edge-partitioned forced 8-device mesh with
# streamed insert+delete batches, every completion verified against a
# from-scratch run on its graph version (monotone re-seed + the k-core
# deletion cascade through sharded pools)
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m repro.launch.stream_graph --requests 9 --slots 3 --scale 8 \
    --update-every 4 --mesh 1x8 --placement edge_sharded \
    --algos wcc,kcore --verify

echo "== ppr residual smoke (solo + batched + sharded 8-device mesh) =="
python - <<'PY'
# solo vs batched ppr_delta agreement + residual invariant on a small graph
import numpy as np, jax.numpy as jnp
from repro.core import algorithms as alg, engine as E
from repro.graph import generators, pack_ell
from repro.serving import default_config, query_result, run_batch

g = generators.rmat(8, 4, seed=1, directed=True)
pack = pack_ell(g.inc)
cfg = default_config(g, max_iters=256)
sources = [0, 17, 101, g.n_nodes - 1]
mb, _ = run_batch(alg.ppr_delta(0), g, pack, cfg, sources)
assert (np.abs(np.asarray(mb["resid"]))
        <= 1e-5 * np.asarray(mb["deg"]) + 1e-9).all()
for lane, s in enumerate(sources):
    ms, _ = E.run(alg.ppr_delta(s), g, pack, cfg, source=jnp.int32(s))
    a = np.asarray(query_result(mb, "rank", lane))
    assert np.abs(a - np.asarray(ms["rank"][:-1])).max() < 1e-6, s
print("[check] ppr_delta solo+batched smoke OK")
PY
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m repro.launch.serve_graph --requests 6 --slots 8 --scale 8 \
    --mesh 8x1 --algos ppr_delta

echo "== observability smoke: --trace spans + schema validation =="
# serve with request tracing on a small RMAT and validate every emitted
# span (lifecycle ordering, durations, per-iteration push/pull modes +
# frontier volumes) against the trace schema (DESIGN.md §12)
python -m repro.launch.serve_graph --requests 8 --slots 4 --scale 8 \
    --trace /tmp/repro_trace_check.jsonl
python scripts/trace_schema.py /tmp/repro_trace_check.jsonl

echo "== flight-record smoke: armed ring -> JSONL -> schema + report =="
# arm the §14 flight recorder on a deadline-pressured replay, dump the
# event ring, validate the dump (monotonic t, increasing seq, known kinds)
# and render the post-mortem report from the two artifacts
python -m repro.launch.slo_replay --scale 8 --rate 40 --duration 2 \
    --slots 4 --assert-goodput \
    --trace /tmp/repro_trace_flight_check.jsonl \
    --flight-record /tmp/repro_flight_check.jsonl
python scripts/trace_schema.py --flight /tmp/repro_flight_check.jsonl
python -m repro.launch.obs_report \
    --trace /tmp/repro_trace_flight_check.jsonl \
    --flight /tmp/repro_flight_check.jsonl > /dev/null

echo "== slo smoke: bursty open-loop replay + deadline policy (4-dev mesh) =="
# seeded MMPP arrivals with per-query deadlines replayed open-loop against
# a sharded server on the forced host mesh; --assert-goodput fails the
# check unless goodput > 0 with zero crashed lanes
XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python -m repro.launch.slo_replay --scale 8 --rate 40 --duration 3 \
    --slots 4 --mesh 4x1 --update-every 1 --assert-goodput
# traced replay through consensus cohorts: every span (including dropped /
# degraded / preempted outcomes and the slo flag block) must validate
python -m repro.launch.slo_replay --scale 8 --rate 40 --duration 2 \
    --slots 4 --cohorts 2 --assert-goodput \
    --trace /tmp/repro_trace_slo_check.jsonl
python scripts/trace_schema.py /tmp/repro_trace_slo_check.jsonl

echo "== bench schema (BENCH_*.json incl. BENCH_slo.json) =="
python scripts/bench_schema.py

echo "== check OK =="
