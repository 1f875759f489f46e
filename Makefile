# Build/test fan-out (capability parity: reference top-level Makefile:1-9).
.PHONY: all test e2e e2e-kind chip-smoke bench-http bench-gas bench-gang bench-configs bench-serving bench-rebalance bench-chaos bench-decisions bench-forecast bench-shard test-serving test-obs test-rebalance test-faults test-decisions test-gang test-forecast test-ha test-slo test-shard test-record test-control test-admission test-explain test-solveobs test-fuzz fuzz-smoke test-wirec trace-lint pascheck obs-smoke lint image clean dryrun

all: test

test:
	python -m pytest tests/ -q

e2e:
	python -m pytest tests/test_e2e.py -q

# real-cluster e2e (requires kind/helm/kubectl/docker; CI runs this);
# teardown always runs — a failure anywhere in setup OR the scenarios
# must not leak the kind cluster
e2e-kind:
	( bash .github/scripts/e2e_setup_cluster.sh && \
		python .github/e2e/run_e2e.py ); rc=$$?; \
		bash .github/scripts/e2e_teardown_cluster.sh; exit $$rc

# the first command on a machine with a chip: one process holds the TPU,
# serves TAS (10k nodes) and GAS (2k x 8) in threads, drives its own
# sockets, compares every answer with the host-only reference, and exits
# non-zero unless the device path really ran (chip_smoke.py).  The compile
# cache lives where JAX_COMPILATION_CACHE_DIR says, else in ./.jax_cache
chip-smoke:
	python chip_smoke.py

# north-star serving A/B: threaded and async front-ends, host vs device
bench-http:
	python -m benchmarks.http_load

# GAS wire A/B
bench-gas:
	python -m benchmarks.gas_load

# serving front-end head-to-head: threaded vs async c=1 -> c=8 scaling
# curve (docs/serving.md)
bench-serving:
	python -m benchmarks.http_load --scaling

# hermetic serving-subsystem suite (wire parity, coalescing,
# backpressure, the c=8 <= 3x c=1 bar) — CI runs this as its own step
test-serving:
	python -m pytest tests/test_serving.py -q

# closed-loop rebalancer suite (docs/rebalance.md): hysteresis, dry-run
# plan parity, actuation guards, active-vs-label-only convergence
test-rebalance:
	python -m pytest tests/test_rebalance.py -q

# rebalance convergence A/B: synthetic churn, active vs label-only
bench-rebalance:
	python -m benchmarks.rebalance_load

# fault-tolerance & chaos suite (docs/robustness.md): retry/backoff
# schedules, circuit transitions, degraded modes, and the end-to-end
# outage -> degrade -> recover -> resume invariant (zero evictions on
# stale data) — deterministic: fault plans + fake clocks, no real sleeps
test-faults:
	python -m pytest tests/test_faults.py -q

# chaos A/B: availability + p99 through the live front-end under a
# scripted 10% metrics-API error rate vs a clean baseline
bench-chaos:
	python -m benchmarks.chaos_load

# decision-provenance suite (docs/observability.md "Decision
# provenance"): reason-code parity host<->device, concrete FailedNodes
# reasons, ring bounds, /debug/decisions filtering, bind feedback
test-decisions:
	python -m pytest tests/test_decisions.py -q

# decision-log on-vs-off serving p99 A/B + placement-quality scrape
bench-decisions:
	python -m benchmarks.http_load --decisions

# gang & topology-aware scheduling suite (docs/gang.md): topology-kernel
# device<->host parity, reservation lifecycle + TTL, the all-or-nothing
# invariant over real sockets on both front-ends, gang-atomic eviction
test-gang:
	python -m pytest tests/test_gang.py tests/test_binpack_edges.py -q

# gang A/B: competing gangs (gang-on admits both, gang-off
# deadlocks half-placed) + 10k-node reservation throughput
bench-gang:
	python -m benchmarks.gang_load

# predictive-telemetry suite (docs/forecast.md): kernel device<->host
# byte-exact parity, history-ring semantics, forecast-vs-snapshot ranking
# parity through the real verbs on both front-ends, trend-aware
# hysteresis, degraded bounded extrapolation, /debug/forecast
test-forecast:
	python -m pytest tests/test_forecast.py -q

# forecast A/B: trending violated-at-bind + transient-spike
# eviction suppression + forecaster on-vs-off p99 (skip the 10k-node
# overhead tier with the scenario functions directly)
bench-forecast:
	python -m benchmarks.forecast_load

# HA control-plane suite (docs/robustness.md "HA & leader election"):
# lease conflict semantics, elector lifecycle + fencing, the multi-
# replica exactly-one-actuator invariant, crash-safe gang recovery
test-ha:
	python -m pytest tests/test_lease.py tests/test_ha.py -q

# SLO engine + digital-twin suite (docs/observability.md "SLOs & error
# budgets"): burn-rate window math on fake clocks, bucket quantile
# interpolation, /debug/slo + off-path pins, and the scenario matrix
# incl. the metric-storm page -> recover acceptance over real sockets
test-slo:
	python -m pytest tests/test_slo.py tests/test_twin.py -q -m 'not slow'

# partition plane suite (docs/sharding.md): partition math +
# rendezvous determinism, journaled/fenced ownership incl. heartbeat
# renewal and lost write races, digest build/fencing/staleness, the
# scatter/gather plane, /debug/shard wire codes on both front-ends,
# off-path byte-identity, and the partitioned HA harness
test-shard:
	python -m pytest tests/test_shard.py -q -m 'not slow'

# sharded scale-out A/B: 4 partition-owner subprocesses vs one
# full-world replica — aggregate Filter rps and the measured ~1/P
# per-replica refresh cut (benchmarks/shard_load.py); exits nonzero
# unless both halves of the bet hold
bench-shard:
	python -m benchmarks.shard_load

# flight recorder + trace replay + what-if suite (docs/observability.md
# "Flight recorder & what-if"): anonymization sweep over real sockets,
# /debug/record + /debug/whatif codes, off-path byte-identity, the
# record->export->parse->replay round trip, and the hermetic overhead pin
test-record:
	python -m pytest tests/test_record.py -q -m 'not slow'

# budget feedback control suite (docs/observability.md "Budget feedback
# control"): knob ladders/clamps/rate limit, hysteresis + trend pre-arm,
# --sloControl fail-fast, /debug/control codes on both front-ends,
# off-path byte-identity, and the static-vs-self-tuning head-to-heads
test-control:
	python -m pytest tests/test_control.py -q -m 'not slow'

# priority-aware admission plane suite (docs/admission.md): class
# ladder + bounded queue semantics, backfill/fairness, gang-atomic
# preemption with fenced-refusal containment, flag fail-fast,
# /debug/admission + off-path byte-identity, torus parity, and the
# acceptance scenarios over real sockets on both front-ends
test-admission:
	python -m pytest tests/test_admission.py -q -m 'not slow'

# causal event spine + /debug/explain suite (docs/observability.md
# "Explain plane"): journal bounds/ordering under writer torture,
# one-hop correlation walks, the /debug/explain wire contract on both
# front-ends, TraceBuffer top-K under concurrent completions
test-explain:
	python -m pytest tests/test_explain.py -q

# solve observatory suite (docs/observability.md "Solve observatory"):
# per-stage attribution sums to the measured total, churn edge cases
# (first pass, delete, byte-identical refresh), /debug/solve codes on
# both front-ends, off-path byte-identity, and the recompile-watch twin
# gate
test-solveobs:
	python -m pytest tests/test_solveobs.py -q -m 'not slow'

# adversarial scenario search suite (docs/robustness.md "Adversarial
# scenario search"): seeded-LCG determinism + the pinned draw values,
# genome generation/mutation/validation, byte-identical candidate
# replay, coverage-novelty corpus, delta-debug minimization, planted
# bugs, the oracle no-false-positive matrix, and the committed
# minimized scenarios under tests/scenarios/
test-fuzz:
	python -m pytest tests/test_fuzz.py tests/test_oracles.py -q

# coverage-guided fuzzing smoke (benchmarks/fuzz_load.py): the four CI
# gates inside one wall-clock budget — reproducibility (same seed =>
# byte-identical candidate sequence), planted-bug detection (the
# stale-digest splice must be found AND minimized to <= 20 ticks /
# <= 8 events), no false positives on the healthy tree, and the
# candidate-throughput floor; exits nonzero on any gate failure.  Any
# find on the healthy tree is a real bug and is printed, never swallowed
fuzz-smoke:
	env JAX_PLATFORMS=cpu python -m benchmarks.fuzz_load

# native wire-path sanitizer gate (docs/architecture.md "The wire
# path"): compile _wirec with -fsanitize=address,undefined and run the
# wire-path suites — scanner strictness, universe interning/refcounts,
# the differential fuzzer — against the instrumented artifact via the
# PAS_TPU_WIREC_SO loader hook.  libstdc++ rides LD_PRELOAD next to
# libasan so XLA's C++ exceptions resolve real___cxa_throw before the
# interceptor asserts on it; leak detection stays off (CPython itself
# "leaks" interned state at exit) — ASan still reports heap overflows,
# use-after-free, and double-free, UBSan everything undefined.
WIREC_SAN_SO := $(abspath build/_wirec_sanitized.so)
test-wirec:
	mkdir -p build
	$(CC) -O1 -g -fsanitize=address,undefined -fno-sanitize-recover=all \
		-Wall -Wextra -Wshadow -Wvla -Werror \
		-shared -fPIC \
		-I$$(python -c 'import sysconfig; print(sysconfig.get_paths()["include"])') \
		platform_aware_scheduling_tpu/native/wirec.c -o $(WIREC_SAN_SO)
	env LD_PRELOAD="$$($(CC) -print-file-name=libasan.so) $$($(CC) -print-file-name=libstdc++.so)" \
		ASAN_OPTIONS=detect_leaks=0:halt_on_error=1 \
		PAS_TPU_WIREC_SO=$(WIREC_SAN_SO) \
		python -m pytest tests/test_wirec.py tests/test_wire_universe.py \
		tests/test_wire_fuzz.py -q

# metric-name convention gate (docs/observability.md): every emitted
# metric is declared in trace.METRICS, pas_-prefixed snake_case, no
# duplicates, and live /metrics output parses as valid exposition
trace-lint:
	python -m pytest tests/test_trace_lint.py -q

# project-native static analysis (docs/analysis.md): clock discipline,
# hot-path blocking, lock scope/ordering, metric declaration cross-check;
# exits nonzero on any finding not pragma'd or baselined
pascheck:
	python -m platform_aware_scheduling_tpu.analysis

# control-plane & device observability suite: /healthz + /readyz
# condition toggling on both front-ends, workqueue/informer
# instrumentation, device watermarks / cost analysis / profile capture
test-obs:
	python -m pytest tests/test_health.py tests/test_kube_instrumentation.py \
		tests/test_devicewatch.py -q

# one-command deployment sanity check: boot both front-ends and curl
# /healthz, /readyz, /metrics, /debug/traces (docs/observability.md)
obs-smoke:
	python -m benchmarks.obs_smoke

# BASELINE configs #2/#3/#4/#5 + solver surface + mesh checks
bench-configs:
	python -m benchmarks.configs

# multi-chip parity checks on a virtual 8-device CPU mesh; the same checks
# run on real chips as chip_smoke.py's mesh phase (>= 4 devices) or
# `python __graft_entry__.py --chips`
dryrun:
	python __graft_entry__.py 8

lint:
	python -m compileall -q platform_aware_scheduling_tpu tests benchmarks chip_smoke.py __graft_entry__.py

image:
	docker build -f deploy/images/Dockerfile.tas -t pas-tpu-tas .
	docker build -f deploy/images/Dockerfile.gas -t pas-tpu-gas .

clean:
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
	rm -rf build dist *.egg-info
