# Developer entry points (role parity with the reference's per-component
# Makefiles: test / build / docker-build).

PY ?= python

test:
	$(PY) -m pytest tests/ -q

# fast tier-1 slice (skips @slow): the test half of `make ci`
test-fast:
	$(PY) -m pytest tests/ -q -m 'not slow'

# the whole gate in one command: every static contract, then the fast
# tier-1 tests (docs/static-analysis.md, CONTRIBUTING.md)
ci: check test-fast

bench:
	$(PY) bench.py

bench-suite:
	$(PY) -m benchmarks.suite

bench-pipeline:
	$(PY) -m benchmarks.pipeline_bench

# mixed-fleet suite (ISSUE 4 + ISSUE 14): the 16,384-service / 15%-joint
# fleet, the canary-heavy fleet (50% baseline-carrying docs — columnar
# canary bucket vs the object-path baseline, >= 3x and >= 12.5k w/s/chip
# asserted in-run, statuses byte-identical across arms), the
# strategy x regime scenario-matrix F1 sweep (floors asserted in-run),
# and pusher fan-in shapes over the real ingest receiver
bench-mixed:
	$(PY) -m benchmarks.mixed_bench

# watch-plane scale: 10k DeploymentMonitors on InMemoryKube
bench-plane:
	$(PY) -m benchmarks.plane_bench

# push-based ingest plane (ISSUE 5): warm RingSource vs
# PrometheusSource-over-localhost on a 4k-doc fleet
bench-ingest:
	$(PY) -m benchmarks.ingest_bench

# worker-mesh scale-out (ISSUE 6): 1 vs 4 REAL worker processes
# sharding a 64k-service fleet over one HTTP store, with in-run
# exactly-once + kill/rebalance assertions
bench-scaleout:
	$(PY) -m benchmarks.scaleout_bench

# cold-start benchmark (ISSUE 10): ring-resident cold fits vs the
# pull-path baseline at the 16k daily-season shape, 10%-churn tick,
# short-history newcomer admission + background refinement — with
# in-run asserts: zero HTTP when the ring covers, byte-identical
# statuses vs pull, band parity, and (at full shape) the round-12
# bars (cold <= 120 s, churn <= 8 s, first verdict <= 10 s)
bench-cold:
	$(PY) -m benchmarks.cold_bench

# durable-restart crash harness (ISSUE 7): SIGKILL a worker mid-tick,
# restart it against the same FOREMAST_SNAPSHOT_DIR state, and assert
# in-run: next tick >= 90% fast-path, ZERO fallback fetches, no lost
# or duplicated verdicts (single-worker and 3-worker-mesh variants)
bench-restart:
	$(PY) -m benchmarks.restart_bench

# chaos soak (ISSUE 9): 3-worker mesh + receivers + fault-injected
# store/Prometheus under a scheduled FaultPlan (store brownout, prom
# blackhole, pusher flood, skewed clocks, worker crash) with in-run
# asserts: zero lost/duplicated verdicts, breakers re-close, recovery
# <= 2 ticks per fault, lock witness clean, memory bounded
bench-chaos:
	$(PY) -m benchmarks.chaos_bench

# reactive plane (ISSUE 12): event-driven detection latency — deploy
# PATCH -> first verdict through the fake kube server's real watch
# stream (<= 1 s bar), anomaly POST -> completed_unhealth through the
# real ingest receiver at the 16k fleet (p99 <= 2 s bar),
# micro-vs-full tick-path status parity asserted in-run
bench-latency:
	$(PY) -m benchmarks.latency_bench

# elastic mesh (ISSUE 11): 2 -> 4 -> 2 workers under continuous load
# with in-run asserts: zero lost/duplicated verdicts, planned handoff
# inside 2 ticks with ZERO cold refits + ZERO fallback fetches, and a
# blackholed-transfer phase degrading to cold refit (never a wedge)
bench-elastic:
	$(PY) -m benchmarks.elastic_bench

# multi-tenant QoS plane (ISSUE 20): noisy-neighbor fleet (one whale
# tenant at 10x share flooding the real receiver) vs a solo-tenant
# control, with in-run asserts: quiet tenants' p99 verdict latency and
# F1 unchanged, every 429 + Retry-After lands on the whale, evictions
# charged to their causer, zero-vs-one-tenant byte parity on the
# sliced warm path, per-tenant ledger visible in /debug/state
bench-noisy:
	$(PY) -m benchmarks.noisy_bench

native:
	$(MAKE) -C native

deploy-render:
	$(PY) -m foremast_tpu.deploy deploy

# Unified static analysis (docs/static-analysis.md): the per-module
# rules (jit-hygiene, async-blocking, lock-discipline, env-contract,
# metrics-contract), the whole-program rules (lock-order,
# thread-escape, blocking-under-lock, device-flow, recompile-hazard,
# sharding-contract, status-machine), the generated-artifact gates
# (env table, metric families, lock graph, status graph) and the
# metric naming lint, gated against analysis_baseline.json.
check:
	$(PY) -m foremast_tpu.analysis

# legacy alias — the metrics lint now runs inside `make check`
metrics-lint: check

# regenerate the env-knob table in docs/operations.md from
# foremast_tpu/config.py's ENV_KNOBS registry
env-docs:
	$(PY) -m foremast_tpu.analysis --update-env-docs

# regenerate the metric-family index in docs/observability.md from
# observe/metrics_lint.py's registry (rule: metrics-contract)
metrics-docs:
	$(PY) -m foremast_tpu.analysis --update-metrics-docs

# recompute + commit the static lock-acquisition graph
# (analysis_lockgraph.json; rule: lock-order — `make check` fails when
# the committed artifact drifts from the computed graph)
lockgraph:
	$(PY) -m foremast_tpu.analysis --write-lockgraph

# recompute + commit the doc status transition graph
# (analysis_statusgraph.json; rule: status-machine — `make check` fails
# when the committed artifact drifts from the computed graph)
statusgraph:
	$(PY) -m foremast_tpu.analysis --write-statusgraph

docker-build:
	docker build -t foremast/foremast-tpu:0.1.0 .

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -prune -exec rm -rf {} +

.PHONY: test test-fast ci bench bench-suite bench-pipeline bench-mixed bench-plane bench-ingest bench-scaleout bench-cold bench-restart bench-chaos bench-elastic bench-noisy native deploy-render check metrics-lint env-docs metrics-docs lockgraph statusgraph docker-build clean
