# Developer entry points. The tier-1 gate is exactly what CI runs.
PYTHONPATH := src

# Kernel backend for the bench targets. These targets are the CPU proxy, so
# they name the XLA references explicitly; on a TPU run e.g.
# `make bench KERNEL_BACKEND=auto` to time the Mosaic kernels.
KERNEL_BACKEND ?= xla
BENCH := REPRO_KERNEL_BACKEND=$(KERNEL_BACKEND) PYTHONPATH=src python -m

.PHONY: test test-dist smoke lint lint-mdrq budget-cert budget-check \
        bench-throughput bench-count bench-specs \
        bench-specs-smoke bench-smoke bench-ingest bench-ingest-smoke \
        bench-pipeline bench-pipeline-smoke bench-dist bench

# Tier-1 verify: the full test suite, fail-fast.
test:
	PYTHONPATH=src python -m pytest -x -q

# Distributed suite on a forced 8-device CPU platform: the in-process
# equivalence/counter tests run against a real multi-device mesh here
# (under plain `make test` they run single-device).
test-dist:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
	python -m pytest -q tests/test_distributed_batched.py tests/test_distributed.py

# Fast interpret-mode smoke of the fused multi-query kernels (oracle-checked).
smoke:
	PYTHONPATH=src python -m pytest -q tests/test_multi_scan.py tests/test_kernels.py

# Batched-execution throughput sweep (CPU: XLA proxy; TPU: Mosaic kernels).
bench-throughput:
	$(BENCH) benchmarks.run --only throughput

# Lint gate: ruff (config in pyproject.toml) + mdrqlint. CI runs exactly this.
lint: lint-mdrq budget-check
	ruff check .

# mdrqlint: whole-program AST invariant checks (launch/host-sync accounting
# with cross-module taint, dtype sentinels, lock + registry discipline,
# Pallas kernel contracts) — DESIGN.md §12. Stdlib-only.
lint-mdrq:
	PYTHONPATH=src python -m repro.analysis src tests benchmarks examples

# Regenerate the static launch/sync budget certificate (BUDGET.json) from
# the project call graph. Run after any serving-path change and commit the
# diff — CI diffs the checked-in file via budget-check.
budget-cert:
	PYTHONPATH=src python -m repro.analysis --budget BUDGET.json
	git diff --stat BUDGET.json

# Fail if BUDGET.json no longer matches a fresh derivation (stdlib-only, so
# it rides the cheap lint job).
budget-check:
	PYTHONPATH=src python -m repro.analysis --budget-check BUDGET.json

# Count-only result mode sweep (device-side reduction, no host nonzero).
bench-count:
	$(BENCH) benchmarks.run --only throughput-count

# Reduced result shapes (top-k / aggregates) vs ids at the largest batch.
bench-specs:
	$(BENCH) benchmarks.run --only throughput-specs

# CI-sized reducer smoke: one TopK row + one Agg row at tiny sizes so a
# reducer perf regression surfaces in CI logs.
bench-specs-smoke:
	$(BENCH) benchmarks.bench_throughput --spec topk --smoke
	$(BENCH) benchmarks.bench_throughput --spec agg --smoke

# CI smoke artifact: per-batch-size qps + latency percentiles as JSON.
# CI runs this into /tmp and diffs against the checked-in BENCH_smoke.json
# (benchmarks.check_bench, +-30% qps guard band, warn-only).
BENCH_SMOKE_OUT ?= BENCH_smoke.json
bench-smoke:
	$(BENCH) benchmarks.bench_throughput --smoke \
	--json $(BENCH_SMOKE_OUT)

# Pipelined serving: sync-vs-pipelined head-to-head + offered-load sweep
# (saturation knee, p99 under load, shed fraction) -> BENCH_pipeline.json.
bench-pipeline:
	$(BENCH) benchmarks.bench_throughput --offered-load

# CI-sized pipeline smoke: same sweep at tiny n. CI runs this into /tmp and
# diffs against the checked-in BENCH_pipeline.json (benchmarks.check_bench,
# +-30% guard band, warn-only).
BENCH_PIPELINE_OUT ?= BENCH_pipeline.json
bench-pipeline-smoke:
	$(BENCH) benchmarks.bench_throughput --offered-load \
	--smoke --json $(BENCH_PIPELINE_OUT)

# Serve-while-ingest sweep: qps vs delta fraction + post-compaction recovery.
bench-ingest:
	$(BENCH) benchmarks.run --only throughput-ingest

# CI-sized ingest smoke: same sweep at tiny n so a write-path serving
# regression (delta scan tax, compaction stall) surfaces in CI logs.
bench-ingest-smoke:
	$(BENCH) benchmarks.bench_throughput --ingest --smoke

# Cross-device batched scan sweep on the 8-device CPU proxy.
bench-dist:
	$(BENCH) benchmarks.bench_throughput --devices

# Full benchmark matrix (quick sizes). The multi-device sections (fig4's
# sharded row, fig11) run over the 8-device CPU proxy.
bench:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 $(BENCH) benchmarks.run
