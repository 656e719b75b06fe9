.PHONY: check test lint-acc smoke smoke-streaming smoke-sharded smoke-sharded2 smoke-ppr smoke-catalog smoke-obs smoke-slo smoke-flight bench-serving bench-streaming bench-sharded bench-sharded2 bench-ppr bench-catalog bench-slo bench-schema flake-hunt

# tier-1 tests + serving/streaming smokes + bench-record lint (scripts/check.sh)
check:
	bash scripts/check.sh

test:
	PYTHONPATH=src python -m pytest -x -q

# static analysis gate (DESIGN.md §16): acclint over the whole catalog +
# src/repro/ + registered combiners, then the ruff generic-lint floor
# (skipped with a notice when the container doesn't ship ruff)
lint-acc:
	PYTHONPATH=src python -m repro.launch.acclint
	@if command -v ruff >/dev/null 2>&1; then ruff check .; \
	elif python -c "import ruff" >/dev/null 2>&1; then python -m ruff check .; \
	else echo "[lint-acc] ruff not installed — skipping generic lint floor"; fi

smoke:
	PYTHONPATH=src python -m repro.launch.serve_graph --requests 8 --slots 4

# verified streaming smoke: queries + edge-update batches interleaved
smoke-streaming:
	PYTHONPATH=src python -m repro.launch.stream_graph --requests 9 --slots 3 \
		--scale 8 --update-every 4 --verify

# sharded serving smoke on a forced 8-device host mesh
smoke-sharded:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
		python -m repro.launch.serve_graph --requests 8 --slots 8 \
		--scale 8 --mesh 8x1

# sharded round-2 smoke: streaming updates through an edge-partitioned
# server (compacted expansion + CSR-free admission + touched-delta
# shipping) on a forced 8-device mesh, completions verified
smoke-sharded2:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
		python -m repro.launch.stream_graph --requests 9 --slots 3 \
		--scale 8 --update-every 4 --mesh 1x8 --placement edge_sharded \
		--algos bfs,sssp,ppr_delta --verify

# residual-push PPR smoke through sharded pools on a forced 8-device mesh
smoke-ppr:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
		python -m repro.launch.serve_graph --requests 6 --slots 8 \
		--scale 8 --mesh 8x1 --algos ppr_delta

# whole-catalog smoke (DESIGN.md §15): wcc/kcore/mis/pagerank_delta through
# the batched server, then wcc+kcore through an edge-partitioned forced
# 8-device mesh with streamed insert+delete batches, completions verified
smoke-catalog:
	PYTHONPATH=src python -m repro.launch.serve_graph --requests 8 \
		--slots 4 --scale 8 --algos wcc,kcore,mis,pagerank_delta
	XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
		python -m repro.launch.stream_graph --requests 9 --slots 3 \
		--scale 8 --update-every 4 --mesh 1x8 --placement edge_sharded \
		--algos wcc,kcore --verify

# observability smoke: serve with --trace on a small RMAT, then validate
# the emitted per-request spans against the trace schema (DESIGN.md §12)
smoke-obs:
	PYTHONPATH=src python -m repro.launch.serve_graph --requests 8 \
		--slots 4 --scale 8 --trace /tmp/repro_trace_smoke.jsonl
	python scripts/trace_schema.py /tmp/repro_trace_smoke.jsonl

# SLO smoke: seeded bursty (MMPP) open-loop replay with per-query deadlines
# through a sharded server on a forced 4-device host mesh; asserts goodput
# > 0 with zero crashed lanes, then replays with --trace and validates the
# emitted spans (drop/degrade/preempt flags included) against the schema
smoke-slo:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
		python -m repro.launch.slo_replay --scale 8 --rate 40 \
		--duration 3 --slots 4 --mesh 4x1 --update-every 1 \
		--assert-goodput
	PYTHONPATH=src python -m repro.launch.slo_replay --scale 8 --rate 40 \
		--duration 2 --slots 4 --cohorts 2 --assert-goodput \
		--trace /tmp/repro_trace_slo_smoke.jsonl
	python scripts/trace_schema.py /tmp/repro_trace_slo_smoke.jsonl

# thread-sweep flake hunter for the parallel-edge residual property test
flake-hunt:
	bash scripts/flake_hunt.sh

# full serving throughput benchmark (writes BENCH_serving.json; ~2 min on CPU)
bench-serving:
	PYTHONPATH=src python benchmarks/serving_bench.py

# residual-push PPR benchmark: ppr_delta vs dense/masked pull + streaming
# resume-vs-rerun (writes BENCH_ppr.json)
bench-ppr:
	PYTHONPATH=src python benchmarks/serving_bench.py --ppr

# sharded q/s-vs-shard-count benchmark (writes BENCH_sharded.json)
bench-sharded:
	PYTHONPATH=src python benchmarks/sharded_bench.py

# round-2 column: compacted-vs-dense light iterations + touched-delta
# update shipping (appends "compacted" to BENCH_sharded.json)
bench-sharded2:
	PYTHONPATH=src python benchmarks/sharded_bench.py --compacted

# streaming incremental-vs-full benchmark (writes BENCH_streaming.json)
bench-streaming:
	PYTHONPATH=src python benchmarks/streaming_bench.py

# catalog streaming benchmark: declared-regime refresh (monotone / cascade /
# reelect / residual) vs full recompute per update kind (writes
# BENCH_catalog.json)
bench-catalog:
	PYTHONPATH=src python benchmarks/catalog_bench.py

# open-loop SLO benchmark: arrival-process x policy grid + cohort-isolation
# experiment (writes BENCH_slo.json; the isolation cell builds a scale-15
# graph — several minutes on CPU)
bench-slo:
	PYTHONPATH=src python benchmarks/slo_bench.py

# lint the BENCH_*.json records (also part of `make check`)
bench-schema:
	python scripts/bench_schema.py

# flight-recorder smoke: armed event ring through an SLO replay, dumped to
# JSONL, validated (--flight schema) and rendered (obs_report)
smoke-flight:
	PYTHONPATH=src python -m repro.launch.slo_replay --scale 8 --rate 40 \
		--duration 2 --slots 4 --assert-goodput \
		--trace /tmp/repro_trace_flight_smoke.jsonl \
		--flight-record /tmp/repro_flight_smoke.jsonl
	python scripts/trace_schema.py --flight /tmp/repro_flight_smoke.jsonl
	PYTHONPATH=src python -m repro.launch.obs_report \
		--trace /tmp/repro_trace_flight_smoke.jsonl \
		--flight /tmp/repro_flight_smoke.jsonl
