"""Fault-tolerant control plane (docs/robustness.md): retry/backoff
schedules, circuit transitions, degraded modes, and the end-to-end chaos
invariant — all deterministic: fault plans + fake clocks, zero real
sleeps, zero wall-clock randomness."""

import json
import threading

import pytest

from benchmarks.chaos_load import ChaosScenario
from platform_aware_scheduling_tpu.kube.client import (
    ConflictError,
    KubeError,
    NotFoundError,
)
from platform_aware_scheduling_tpu.kube.retry import (
    STATE_CLOSED,
    STATE_HALF_OPEN,
    STATE_OPEN,
    CircuitBreaker,
    CircuitBreakerRegistry,
    CircuitOpenError,
    FaultTolerantClient,
    RetryPolicy,
    backoff_delay,
)
from platform_aware_scheduling_tpu.tas.degraded import (
    ACTION_FAIL_CLOSED,
    ACTION_FAIL_OPEN,
    ACTION_LAST_KNOWN_GOOD,
    ACTION_NEUTRAL,
    ACTION_NORMAL,
    DegradedModeController,
)
from platform_aware_scheduling_tpu.testing.builders import make_node, make_pod
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.testing.faults import (
    FakeClock,
    FakeMetricsClient,
    FaultPlan,
    FaultyClient,
)
from platform_aware_scheduling_tpu.utils.tracing import CounterSet


# ---------------------------------------------------------------------------
# retry policy: deterministic schedules
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def test_backoff_deterministic_and_capped(self):
        policy = RetryPolicy(base_delay_s=0.1, max_delay_s=1.0, seed=3)
        a = [policy.backoff(n, verb="list_nodes") for n in range(1, 8)]
        b = [policy.backoff(n, verb="list_nodes") for n in range(1, 8)]
        assert a == b, "same seed+verb+attempt must give the same delay"
        # jittered exponential: within [0.5, 1.0) of the raw schedule
        for n, delay in enumerate(a, 1):
            raw = min(1.0, 0.1 * 2 ** (n - 1))
            assert raw * 0.5 <= delay < raw
        # distinct verbs get distinct (but still deterministic) schedules
        assert a != [policy.backoff(n, verb="get_pod") for n in range(1, 8)]

    def test_retry_after_is_a_floor(self):
        policy = RetryPolicy(base_delay_s=0.1, max_delay_s=1.0)
        assert policy.backoff(1, verb="v", retry_after_s=7.5) == 7.5
        # a tiny Retry-After never shrinks the computed backoff
        computed = policy.backoff(5, verb="v")
        assert policy.backoff(5, verb="v", retry_after_s=0.001) == computed

    def test_backoff_delay_seed_independent_of_process(self):
        # pinned values: stable_hash + LCG are process-independent, so
        # these exact numbers hold on every run and every machine
        assert backoff_delay(1, 1.0, 10.0, seed=0) == backoff_delay(
            1, 1.0, 10.0, seed=0
        )
        assert backoff_delay(1, 1.0, 10.0, seed=0) != backoff_delay(
            1, 1.0, 10.0, seed=1
        )


class TestRetryingReads:
    def _client(self, plan, clock, **kw):
        fake = FakeKubeClient()
        fake.add_node(make_node("n1"))
        fake.fault_plan = plan
        fake.fault_clock = clock
        sleeps = []

        def sleep(s):
            sleeps.append(s)
            clock.advance(s)

        ft = FaultTolerantClient(
            fake,
            policy=kw.pop("policy", RetryPolicy(
                max_attempts=4, base_delay_s=0.1, max_delay_s=1.0,
                deadline_s=30.0,
            )),
            breakers=CircuitBreakerRegistry(
                failure_threshold=kw.pop("threshold", 100),
                reset_timeout_s=5.0,
                clock=clock.now,
            ),
            clock=clock.now,
            sleep=sleep,
            counters=kw.pop("counters", CounterSet()),
        )
        return fake, ft, sleeps

    def test_read_retries_through_transient_errors(self):
        clock = FakeClock()
        plan = FaultPlan().fail("list_nodes", 3, status=503)
        counters = CounterSet()
        fake, ft, sleeps = self._client(plan, clock, counters=counters)
        nodes = ft.list_nodes()
        assert [n.name for n in nodes] == ["n1"]
        assert plan.call_count("list_nodes") == 4  # 3 failures + success
        assert len(sleeps) == 3  # one backoff per retry, nonzero
        assert all(s > 0 for s in sleeps)
        assert sleeps == sorted(sleeps)  # monotone under the cap
        assert counters.get(
            "pas_kube_retry_total",
            labels={"verb": "list_nodes", "reason": "server_error"},
        ) == 3

    def test_exhausted_retries_give_up_with_counter(self):
        clock = FakeClock()
        plan = FaultPlan().outage("list_nodes", status=503)
        counters = CounterSet()
        fake, ft, sleeps = self._client(plan, clock, counters=counters)
        with pytest.raises(KubeError):
            ft.list_nodes()
        assert plan.call_count("list_nodes") == 4  # max_attempts, bounded
        assert counters.get(
            "pas_kube_giveup_total", labels={"verb": "list_nodes"}
        ) == 1

    def test_empty_metric_answer_is_deterministic_not_a_circuit_failure(self):
        """A healthy metrics API answering 'no metric found' must not be
        retried and must not count against the metrics circuit — a
        missing metric opening the circuit would force degraded mode on
        a perfectly healthy cluster."""
        from platform_aware_scheduling_tpu.tas.metrics import MetricsError

        clock = FakeClock()
        metrics = FakeMetricsClient()  # empty store: every fetch 'not found'
        breakers = CircuitBreakerRegistry(
            failure_threshold=2, reset_timeout_s=5.0, clock=clock.now
        )
        ft = FaultTolerantClient(
            metrics, breakers=breakers, clock=clock.now, sleep=clock.sleep,
            counters=CounterSet(),
        )
        for _ in range(6):
            with pytest.raises(MetricsError):
                ft.get_node_metric("ghost")
        assert breakers.states().get("metrics", STATE_CLOSED) == STATE_CLOSED
        # but a WRAPPED transport failure (MetricsError from KubeError)
        # still classifies as retryable through its __cause__
        from platform_aware_scheduling_tpu.kube.retry import retry_reason

        try:
            try:
                raise KubeError("boom", status=503)
            except KubeError as inner:
                raise MetricsError("unable to fetch metrics") from inner
        except MetricsError as outer:
            assert retry_reason(outer) == "server_error"
        assert retry_reason(MetricsError("no metric ghost found")) is None

    def test_not_found_is_never_retried(self):
        clock = FakeClock()
        fake, ft, sleeps = self._client(FaultPlan(), clock)
        with pytest.raises(NotFoundError):
            ft.get_node("missing")
        assert sleeps == []

    def test_retry_after_header_honored(self):
        clock = FakeClock()
        plan = FaultPlan().fail(
            "list_nodes", 1,
            exc_factory=lambda: KubeError(
                "throttled", status=429, retry_after=9.0
            ),
        )
        fake, ft, sleeps = self._client(plan, clock)
        ft.list_nodes()
        assert sleeps == [9.0]

    def test_deadline_stops_retrying_early(self):
        clock = FakeClock()
        plan = FaultPlan().outage("list_nodes", status=503)
        fake, ft, sleeps = self._client(
            plan, clock,
            policy=RetryPolicy(
                max_attempts=10, base_delay_s=2.0, max_delay_s=2.0,
                deadline_s=3.0,
            ),
        )
        with pytest.raises(KubeError):
            ft.list_nodes()
        # the first backoff (~1-2 s) fits the 3 s deadline, the next
        # would overshoot -> bounded attempts, no 10-try storm
        assert plan.call_count("list_nodes") <= 3


class TestWritesNeverBlindRetry:
    def test_write_failure_single_attempt(self):
        clock = FakeClock()
        fake = FakeKubeClient()
        fake.add_node(make_node("n1"))
        plan = FaultPlan().fail("patch_node", 1, status=503)
        fake.fault_plan = plan
        fake.fault_clock = clock
        ft = FaultTolerantClient(
            fake, clock=clock.now, sleep=clock.sleep,
            counters=CounterSet(),
        )
        with pytest.raises(KubeError):
            ft.patch_node("n1", [{"op": "add", "path": "/metadata/labels/x",
                                  "value": "y"}])
        assert plan.call_count("patch_node") == 1  # ambiguous: NO retry
        # the next call goes straight through (plan exhausted)
        ft.patch_node("n1", [{"op": "add", "path": "/metadata/labels/x",
                              "value": "y"}])
        assert plan.call_count("patch_node") == 2

    def test_conflict_passes_through_unwrapped(self):
        fake = FakeKubeClient()
        fake.add_pod(make_pod("p1"))
        fake.update_pod_conflicts_remaining = 1
        ft = FaultTolerantClient(fake, counters=CounterSet())
        with pytest.raises(ConflictError):
            ft.update_pod(fake.get_pod("default", "p1"))


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def test_open_half_open_close_cycle(self):
        clock = FakeClock()
        counters = CounterSet()
        cb = CircuitBreaker(
            "kube", failure_threshold=3, reset_timeout_s=10.0,
            clock=clock.now, counters=counters,
        )
        assert cb.state == STATE_CLOSED
        for _ in range(3):
            assert cb.allow()
            cb.record_failure()
        assert cb.state == STATE_OPEN
        assert not cb.allow()  # fail-fast while open
        clock.advance(10.0)
        assert cb.state == STATE_HALF_OPEN
        assert cb.allow()       # the single probe
        assert not cb.allow()   # second caller refused while probing
        cb.record_success()
        assert cb.state == STATE_CLOSED
        # gauge + transition counters moved
        assert counters.get(
            "pas_circuit_state", kind="gauge", labels={"group": "kube"}
        ) == 0
        assert counters.get(
            "pas_circuit_transitions_total",
            labels={"group": "kube", "to": STATE_OPEN},
        ) == 1
        assert counters.get(
            "pas_circuit_transitions_total",
            labels={"group": "kube", "to": STATE_CLOSED},
        ) == 1

    def test_failed_probe_reopens_and_rearms_timer(self):
        clock = FakeClock()
        cb = CircuitBreaker(
            "kube", failure_threshold=1, reset_timeout_s=10.0,
            clock=clock.now, counters=CounterSet(),
        )
        cb.record_failure()
        assert cb.state == STATE_OPEN
        clock.advance(10.0)
        assert cb.allow()
        cb.record_failure()  # probe failed
        assert cb.state == STATE_OPEN
        clock.advance(5.0)
        assert not cb.allow()  # timer re-armed: 5 s < 10 s
        clock.advance(5.0)
        assert cb.allow()

    def test_success_resets_consecutive_count(self):
        clock = FakeClock()
        cb = CircuitBreaker(
            "kube", failure_threshold=3, clock=clock.now,
            counters=CounterSet(),
        )
        cb.record_failure()
        cb.record_failure()
        cb.record_success()  # N must be CONSECUTIVE
        cb.record_failure()
        cb.record_failure()
        assert cb.state == STATE_CLOSED

    def test_open_circuit_fails_fast_without_touching_inner(self):
        clock = FakeClock()
        fake = FakeKubeClient()
        fake.add_node(make_node("n1"))
        plan = FaultPlan().outage("list_nodes")
        fake.fault_plan = plan
        fake.fault_clock = clock
        ft = FaultTolerantClient(
            fake,
            policy=RetryPolicy(max_attempts=2, base_delay_s=0.01,
                               max_delay_s=0.01),
            breakers=CircuitBreakerRegistry(
                failure_threshold=2, reset_timeout_s=60.0, clock=clock.now
            ),
            clock=clock.now, sleep=clock.sleep, counters=CounterSet(),
        )
        with pytest.raises(KubeError):
            ft.list_nodes()  # 2 attempts -> circuit opens
        calls_after_open = plan.call_count("list_nodes")
        for _ in range(5):
            with pytest.raises(CircuitOpenError):
                ft.list_nodes()
        assert plan.call_count("list_nodes") == calls_after_open

    def test_write_refused_while_open(self):
        clock = FakeClock()
        fake = FakeKubeClient()
        fake.add_pod(make_pod("p1", node_name="n1", phase="Running"))
        breakers = CircuitBreakerRegistry(
            failure_threshold=1, reset_timeout_s=60.0, clock=clock.now
        )
        breakers.breaker("kube").record_failure()  # open it
        ft = FaultTolerantClient(
            fake, breakers=breakers, clock=clock.now, sleep=clock.sleep,
            counters=CounterSet(),
        )
        with pytest.raises(CircuitOpenError):
            ft.evict_pod("default", "p1")
        assert fake.evictions == []


# ---------------------------------------------------------------------------
# fault plan determinism
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_error_rate_is_seed_deterministic(self):
        def fire_pattern(seed):
            plan = FaultPlan(seed=seed).error_rate("v", 0.3)
            return [plan.next("v") is not None for _ in range(50)]

        assert fire_pattern(1) == fire_pattern(1)
        assert fire_pattern(1) != fire_pattern(2)
        rate = sum(fire_pattern(1)) / 50
        assert 0.1 < rate < 0.5  # roughly the asked-for rate

    def test_flap_schedule(self):
        plan = FaultPlan().flap("v", ok=2, fail=1, cycles=2)
        outcomes = [plan.next("v") is None for _ in range(6)]
        assert outcomes == [True, True, False, True, True, False]

    def test_latency_advances_fault_clock_only(self):
        clock = FakeClock(start=100.0)
        plan = FaultPlan().latency("v", 1, 2.5)
        plan.apply("v", clock)
        assert clock.now() == 102.5

    def test_faulty_client_wrapper_intercepts_by_name(self):
        fake = FakeKubeClient()
        fake.add_node(make_node("n1"))
        plan = FaultPlan().fail("list_nodes", 1)
        wrapped = FaultyClient(fake, plan)
        with pytest.raises(KubeError):
            wrapped.list_nodes()
        assert len(wrapped.list_nodes()) == 1


# ---------------------------------------------------------------------------
# degraded modes
# ---------------------------------------------------------------------------


def _stale_cache(clock, period=1.0, metric="m", age=100.0):
    from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
    from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
    from platform_aware_scheduling_tpu.utils.quantity import Quantity

    cache = AutoUpdatingCache(counters=CounterSet(), clock=clock.now)
    cache._refresh_period = period
    cache.write_metric(metric, {"n1": NodeMetric(value=Quantity("1"))})
    cache.write_metric(metric)  # register
    cache.update_all_metrics(FakeMetricsClient({
        metric: {"n1": NodeMetric(value=Quantity("1"))}
    }))
    clock.advance(age)
    return cache


class TestDegradedModeController:
    def test_fresh_cache_is_normal(self):
        clock = FakeClock()
        cache = _stale_cache(clock, age=0.5)
        ctl = DegradedModeController(cache, counters=CounterSet())
        assert ctl.filter_decision()[0] == ACTION_NORMAL
        assert ctl.prioritize_decision()[0] == ACTION_NORMAL
        assert ctl.evictions_allowed()[0]

    def test_last_known_good_window_then_neutral(self):
        clock = FakeClock()
        # period 1 -> freshness bound 3 s, LKG bound 9 s.  Age 5: stale
        # but within LKG
        cache = _stale_cache(clock, age=5.0)
        ctl = DegradedModeController(cache, counters=CounterSet())
        assert ctl.filter_decision()[0] == ACTION_LAST_KNOWN_GOOD
        assert ctl.prioritize_decision()[0] == ACTION_LAST_KNOWN_GOOD
        assert not ctl.evictions_allowed()[0]  # suspended EVEN within LKG
        clock.advance(10.0)  # age 15: past the LKG bound
        assert ctl.filter_decision()[0] == ACTION_FAIL_OPEN
        assert ctl.prioritize_decision()[0] == ACTION_NEUTRAL

    def test_fail_open_vs_fail_closed_flag(self):
        clock = FakeClock()
        cache = _stale_cache(clock, age=100.0)
        open_ctl = DegradedModeController(
            cache, mode="fail-open", counters=CounterSet()
        )
        closed_ctl = DegradedModeController(
            cache, mode="fail-closed", counters=CounterSet()
        )
        assert open_ctl.filter_decision()[0] == ACTION_FAIL_OPEN
        assert closed_ctl.filter_decision()[0] == ACTION_FAIL_CLOSED

    def test_kube_circuit_open_suspends_evictions_only(self):
        clock = FakeClock()
        cache = _stale_cache(clock, age=0.1)  # telemetry fresh
        breakers = CircuitBreakerRegistry(
            failure_threshold=1, clock=clock.now
        )
        breakers.breaker("kube").record_failure()
        ctl = DegradedModeController(
            cache, breakers=breakers, counters=CounterSet()
        )
        assert ctl.filter_decision()[0] == ACTION_NORMAL  # telemetry fine
        allowed, reason = ctl.evictions_allowed()
        assert not allowed and "kube" in reason

    def test_degraded_gauges_published(self):
        clock = FakeClock()
        cache = _stale_cache(clock, age=100.0)
        counters = CounterSet()
        ctl = DegradedModeController(cache, counters=counters)
        ctl.evictions_allowed()
        assert counters.get(
            "pas_degraded", kind="gauge", labels={"subsystem": "telemetry"}
        ) == 1
        assert counters.get(
            "pas_degraded", kind="gauge", labels={"subsystem": "evictions"}
        ) == 1
        assert counters.get(
            "pas_degraded", kind="gauge", labels={"subsystem": "kube_api"}
        ) == 0


class TestDegradedFilterWire:
    """fail-open passes every candidate; fail-closed fails every
    candidate — through the real Filter verb, both wire modes."""

    def _scenario(self, mode):
        s = ChaosScenario(degraded_mode=mode, hysteresis_cycles=100)
        for _ in range(2):
            s.tick()  # healthy: telemetry lands
        s.plan.outage("get_node_metric")
        for _ in range(12):
            s.tick()  # well past freshness AND the LKG window
        return s

    def _filter(self, s, nodes_mode):
        from platform_aware_scheduling_tpu.extender.server import HTTPRequest

        names = [f"node-{i}" for i in range(s.num_nodes)]
        pod = {"metadata": {"name": "p", "namespace": "default",
                            "labels": {"telemetry-policy": "chaos-pol"}}}
        if nodes_mode == "nodenames":
            obj = {"Pod": pod, "NodeNames": names}
        else:
            obj = {"Pod": pod,
                   "Nodes": {"items": [{"metadata": {"name": n}}
                                       for n in names]}}
        request = HTTPRequest(
            "POST", "/scheduler/filter",
            {"Content-Type": "application/json"},
            json.dumps(obj).encode(),
        )
        response = s.extender.filter(request)
        assert response.status == 200
        return json.loads(response.body), names

    @pytest.mark.parametrize("nodes_mode", ["nodes", "nodenames"])
    def test_fail_open_passes_all(self, nodes_mode):
        s = self._scenario("fail-open")
        result, names = self._filter(s, nodes_mode)
        assert not result.get("FailedNodes")
        got = result.get("NodeNames") or []
        assert [n for n in got if n] == names

    @pytest.mark.parametrize("nodes_mode", ["nodes", "nodenames"])
    def test_fail_closed_fails_all(self, nodes_mode):
        s = self._scenario("fail-closed")
        result, names = self._filter(s, nodes_mode)
        assert set(result.get("FailedNodes") or {}) == set(names)
        assert [n for n in (result.get("NodeNames") or []) if n] == []

    def test_prioritize_neutral_when_past_lkg(self):
        from platform_aware_scheduling_tpu.extender.server import HTTPRequest

        s = self._scenario("last-known-good")
        names = [f"node-{i}" for i in range(s.num_nodes)]
        obj = {"Pod": {"metadata": {"name": "p", "namespace": "default",
                                    "labels": {"telemetry-policy":
                                               "chaos-pol"}}},
               "NodeNames": names}
        response = s.extender.prioritize(HTTPRequest(
            "POST", "/scheduler/prioritize",
            {"Content-Type": "application/json"}, json.dumps(obj).encode(),
        ))
        assert response.status == 200
        scores = json.loads(response.body)
        assert {e["Host"] for e in scores} == set(names)
        assert len({e["Score"] for e in scores}) == 1  # neutral: all equal


# ---------------------------------------------------------------------------
# the chaos invariant, end to end
# ---------------------------------------------------------------------------


class TestChaosInvariant:
    def test_outage_degrade_recover_resume(self):
        """ISSUE 5 acceptance: under a scripted 100% metrics outage the
        assembled service keeps serving (degraded, /readyz lists the
        reason), performs ZERO evictions, issues a bounded number of
        retries, and returns to ready within a bounded number of cycles
        after the fault clears."""
        s = ChaosScenario(hysteresis_cycles=3)
        # one healthy tick: telemetry lands, node-0 violates (streak 1 of
        # 3 -> no evictions yet)
        record = s.tick()
        assert record.get("violating_nodes") == ["node-0"]
        assert s.evictions() == 0
        assert s.ready()[0]

        # -- outage: metrics API 100% down ------------------------------
        s.plan.outage("get_node_metric", status=503)
        calls_before = s.plan.call_count("get_node_metric")
        for _ in range(10):
            s.tick()
        # zero evictions despite the standing violation in the stale data
        assert s.evictions() == 0
        # the service reports WHY on /readyz
        ready, conditions = s.ready()
        assert not ready
        by_name = {c["name"]: c for c in conditions}
        assert not by_name["telemetry_fresh"]["ok"]
        assert not by_name["degraded_mode"]["ok"]
        assert "degraded" in by_name["degraded_mode"]["reason"]
        # bounded retries: the circuit caps the storm well below
        # ticks x max_attempts
        calls_during = s.plan.call_count("get_node_metric") - calls_before
        assert calls_during <= 10 * s.retry_policy.max_attempts
        assert calls_during < 15, f"retry storm: {calls_during} calls"
        assert s.breakers.states()["metrics"] != STATE_CLOSED
        # the rebalancer shows the suspension on its status JSON
        status = s.rebalancer.status()
        assert status["evictions_suspended"]
        assert status["degraded"]["evictions"]["allowed"] is False
        assert status["last_plan"].get("suspended")

        # -- recover ----------------------------------------------------
        s.plan.clear("get_node_metric")
        recovered_at = None
        for cycle in range(6):
            s.tick()
            if s.ready()[0]:
                recovered_at = cycle
                break
        assert recovered_at is not None, "never returned to ready"
        assert s.breakers.states()["metrics"] == STATE_CLOSED

        # -- resume: the standing violation now drives real evictions ---
        for _ in range(4):
            s.tick()
        assert s.evictions() > 0, "evictions must resume after recovery"

    def test_dry_run_stays_dry_through_chaos(self):
        s = ChaosScenario(rebalance_mode="dry-run", hysteresis_cycles=1)
        for _ in range(3):
            s.tick()
        s.plan.outage("get_node_metric")
        for _ in range(5):
            s.tick()
        s.plan.clear("get_node_metric")
        for _ in range(5):
            s.tick()
        assert s.evictions() == 0

    def test_kube_outage_also_suspends_evictions(self):
        """The OTHER half of the invariant: fresh telemetry but an open
        kube circuit must suspend evictions too."""
        s = ChaosScenario(hysteresis_cycles=1)
        s.breakers.breaker("kube")._failures = 0
        # trip the kube circuit directly (threshold 3)
        for _ in range(3):
            s.breakers.breaker("kube").record_failure()
        assert s.breakers.states()["kube"] == STATE_OPEN
        for _ in range(4):
            s.tick()
        assert s.evictions() == 0
        allowed, reason = s.degraded.evictions_allowed()
        assert not allowed and "kube" in reason

    def test_suspended_cycles_probe_the_kube_circuit_back_closed(self):
        """Liveness: the suspension gate removes every other kube-group
        call, so the suspended cycle itself must drive the half-open
        probe — otherwise an open kube circuit never closes and
        enforcement stays suspended forever after the API recovers."""
        s = ChaosScenario(hysteresis_cycles=1)
        for _ in range(3):
            s.breakers.breaker("kube").record_failure()
        assert s.breakers.states()["kube"] == STATE_OPEN
        # reset_timeout_s=5.0, period 1.0: by the 6th tick the breaker
        # is probe-eligible; the suspended cycle's list_nodes probe (the
        # fake kube is healthy) must close it and enforcement resume
        for _ in range(8):
            s.tick()
        assert s.breakers.states()["kube"] == STATE_CLOSED
        assert s.degraded.evictions_allowed()[0]
        for _ in range(3):
            s.tick()
        assert s.evictions() > 0, "enforcement must resume after recovery"


class TestChaosFrontEnds:
    """Recovery to ready through real /readyz on BOTH front-ends."""

    def _drive(self, start_server):
        from wirehelpers import get_request

        s = ChaosScenario(hysteresis_cycles=100)
        s.tick()
        server = start_server(s.extender)
        try:
            status, _, body = get_request(server.port, "/readyz")
            assert status == 200, body
            # outage long enough to blow the freshness bound
            s.plan.outage("get_node_metric")
            for _ in range(8):
                s.tick()
            status, _, body = get_request(server.port, "/readyz")
            assert status == 503
            payload = json.loads(body)
            failing = {c["name"]: c["reason"] for c in payload["conditions"]
                       if not c["ok"]}
            assert "telemetry_fresh" in failing
            assert "degraded_mode" in failing
            # the service KEEPS SERVING the scheduling verbs meanwhile
            from wirehelpers import post_bytes, raw_request

            names = [f"node-{i}" for i in range(s.num_nodes)]
            obj = {"Pod": {"metadata": {"name": "p", "namespace": "default",
                                        "labels": {"telemetry-policy":
                                                   "chaos-pol"}}},
                   "NodeNames": names}
            vstatus, _, vbody = raw_request(
                server.port,
                post_bytes("/scheduler/prioritize",
                           json.dumps(obj).encode()),
            )
            assert vstatus == 200
            assert json.loads(vbody), "degraded prioritize must answer"
            # recover: ready again within bounded cycles
            s.plan.clear("get_node_metric")
            for _ in range(6):
                s.tick()
                status, _, _ = get_request(server.port, "/readyz")
                if status == 200:
                    break
            assert status == 200
        finally:
            server.shutdown()

    def test_threaded_front_end(self):
        from wirehelpers import start_threaded

        self._drive(start_threaded)

    def test_async_front_end(self):
        from wirehelpers import start_async

        self._drive(start_async)


# ---------------------------------------------------------------------------
# service assembly wiring
# ---------------------------------------------------------------------------


class TestAssemblyWiring:
    def test_assemble_attaches_degraded_controller_everywhere(self):
        from platform_aware_scheduling_tpu.cmd.tas import assemble
        from platform_aware_scheduling_tpu.tas.metrics import (
            DummyMetricsClient,
        )

        fake = FakeKubeClient()
        breakers = CircuitBreakerRegistry(counters=CounterSet())
        pieces = assemble(
            fake,
            DummyMetricsClient({}),
            sync_period_s=3600.0,
            breakers=breakers,
            degraded_mode="fail-closed",
            rebalance_mode="dry-run",
        )
        cache, mirror, extender, controller, enforcer, stop = pieces
        try:
            assert extender.degraded is not None
            assert extender.degraded.mode == "fail-closed"
            assert enforcer.degraded is extender.degraded
            assert extender.rebalancer.degraded is extender.degraded
            assert extender.degraded.breakers is breakers
            names = [name for name, _ in extender.readiness_conditions()]
            assert "degraded_mode" in names
        finally:
            stop.set()

    def test_mains_accept_robustness_flags(self):
        from platform_aware_scheduling_tpu.cmd import gas, tas

        shared = [
            "--retryMaxAttempts", "7",
            "--retryBaseDelay", "50ms",
            "--circuitFailureThreshold", "9",
            "--circuitResetTimeout", "1m",
        ]
        args = tas.build_arg_parser().parse_args(
            shared + ["--degradedMode", "fail-open"]
        )
        assert args.retryMaxAttempts == 7
        assert args.degradedMode == "fail-open"
        gas_args = gas.build_arg_parser().parse_args(shared)
        assert gas_args.retryMaxAttempts == 7
        # GAS builds no DegradedModeController: the flag must not exist
        # there (a silently-ignored flag is an operator trap)
        assert not hasattr(gas_args, "degradedMode")
        with pytest.raises(SystemExit):
            gas.build_arg_parser().parse_args(
                shared + ["--degradedMode", "fail-open"]
            )
        from platform_aware_scheduling_tpu.cmd.common import (
            build_fault_tolerance,
        )

        policy, breakers = build_fault_tolerance(args)
        assert policy.max_attempts == 7
        assert policy.base_delay_s == pytest.approx(0.05)
        assert breakers.failure_threshold == 9
        assert breakers.reset_timeout_s == 60.0

    def test_flag_surface_matches_the_docs(self):
        """The mains' option surface, held from both sides: a tuning
        value that nothing ever set is a constant beside its user, not a
        flag (ROADMAP C2), and every flag the docs name still parses."""
        import pathlib
        import re

        from platform_aware_scheduling_tpu.cmd import gas, tas, whatif

        def accepted(parser):
            return {
                option
                for action in parser._actions
                for option in action.option_strings
            }

        mains = accepted(tas.build_arg_parser()) | accepted(
            gas.build_arg_parser()
        )
        constants = (
            "retryMaxDelay retryDeadline decisionLogSize eventsSize "
            "gangReservationTTL gangMeshRefresh admissionFairnessStreak "
            "admissionStarveConsults preemptionRetry preemptionRate "
            "preemptionBurst preemptionCooldown shardTopK shardStaleBound "
            "shardMemberTTL forecastBandBound recordSize batchWindow "
            "batchMax queueDepth rebalanceHysteresis rebalanceMaxMoves "
            "rebalanceCooldown rebalanceRate rebalanceBurst "
            "rebalanceMinAvailable"
        ).split()
        assert len(constants) == 26
        assert [n for n in constants if "--" + n in mains] == []

        # another program's flags the docs also name: pascheck's
        # (docs/analysis.md)
        elsewhere = {"--root", "--write-baseline"}
        known = mains | accepted(whatif.build_arg_parser()) | elsewhere
        root = pathlib.Path(__file__).resolve().parents[1]
        named = {}
        for page in sorted((root / "docs").glob("*.md")) + [root / "README.md"]:
            text = page.read_text(encoding="utf-8")
            for flag in re.findall(r"`(--[A-Za-z][\w-]*)", text):
                named.setdefault(flag, page.name)
        assert len(named) > 30, "the scan found no flag tables"
        assert {f: p for f, p in named.items() if f not in known} == {}


# ---------------------------------------------------------------------------
# satellites: GAS conflict-retry backoff
# ---------------------------------------------------------------------------


class TestGASAnnotateBackoff:
    def test_conflict_retries_back_off_on_fake_clock(self):
        """The annotate conflict-retry loop must SLEEP between attempts
        (the reference hammered with zero delay) — attempt timestamps on
        a fake clock pin the deterministic backoff schedule."""
        from platform_aware_scheduling_tpu.gas.cache import Cache
        from platform_aware_scheduling_tpu.gas.scheduler import GASExtender

        clock = FakeClock()
        stamps = []
        kube = FakeKubeClient()
        kube.add_node(make_node(
            "n1",
            labels={"gpu.intel.com/cards": "card0"},
            allocatable={"gpu.intel.com/i915": "4",
                         "gpu.intel.com/millicores": "4000"},
        ))
        pod = make_pod("p", container_requests=[
            {"gpu.intel.com/i915": "1", "gpu.intel.com/millicores": "100"}])
        kube.add_pod(pod)
        original_update = kube.update_pod

        def stamping_update(p):
            stamps.append(clock.now())
            return original_update(p)

        kube.update_pod = stamping_update
        kube.update_pod_conflicts_remaining = 3
        cache = Cache(kube, start=False)
        ext = GASExtender(
            kube, cache=cache, use_device=False, sleep=clock.sleep,
        )
        cache.start()
        try:
            from platform_aware_scheduling_tpu.extender.server import (
                HTTPRequest,
            )

            body = json.dumps({
                "PodName": "p", "PodNamespace": "default",
                "PodUID": pod.uid, "Node": "n1",
            }).encode()
            response = ext.bind(HTTPRequest(
                "POST", "/scheduler/bind",
                {"Content-Type": "application/json"}, body,
            ))
            assert json.loads(response.body) == {"Error": ""}
        finally:
            cache.stop()
        assert len(stamps) == 4  # 3 conflicts + success
        gaps = [b - a for a, b in zip(stamps, stamps[1:])]
        assert all(g > 0 for g in gaps), f"zero-sleep retry storm: {gaps}"
        expected = [
            ext.retry_policy.backoff(n, verb="update_pod")
            for n in (1, 2, 3)
        ]
        assert gaps == pytest.approx(expected)


# ---------------------------------------------------------------------------
# the FaultPlan contract itself: resolution order + seeded determinism
# ---------------------------------------------------------------------------


class TestFaultPlanResolutionOrder:
    """Pins the docstring contract (testing/faults.py FaultPlan): per
    call, an outage wins; else the next scripted entry (verb before the
    ``"*"`` wildcard) is consumed; else the seeded error rate decides;
    exhausted scripts mean healthy.  The fuzzer's fault events lean on
    this order — an outage must mask, not consume, whatever else is
    scheduled for the verb."""

    def test_outage_wins_and_preserves_the_script(self):
        clock = FakeClock()
        plan = FaultPlan().latency("v", 2, 5.0).outage("v", status=503)
        t0 = clock.now()
        for _ in range(3):
            with pytest.raises(KubeError):
                plan.apply("v", clock)
        # the outage answered every call: the latency script was NOT
        # consumed and the fault clock never advanced
        assert clock.now() == t0
        with plan._lock:
            assert len(plan._scripts["v"]) == 2
        assert plan.call_count("v") == 3

    def test_script_beats_rate_then_rate_takes_over(self):
        clock = FakeClock()
        plan = FaultPlan(seed=5).latency("v", 2, 5.0).error_rate("v", 1.0)
        t0 = clock.now()
        plan.apply("v", clock)  # scripted latency: slow, not failing
        plan.apply("v", clock)
        assert clock.now() == t0 + 10.0
        with pytest.raises(KubeError):
            plan.apply("v", clock)  # script exhausted: the rate fires

    def test_verb_script_before_wildcard_then_healthy(self):
        clock = FakeClock()
        plan = FaultPlan().fail("*", 1).latency("v", 1, 1.0)
        t0 = clock.now()
        plan.apply("v", clock)  # the verb's own script first
        assert clock.now() == t0 + 1.0
        with pytest.raises(KubeError):
            plan.apply("v", clock)  # then the wildcard entry
        plan.apply("v", clock)  # everything exhausted: healthy
        assert plan.call_count("v") == 3


class TestErrorRateDeterminism:
    """error_rate is a pure function of (seed, verb, call index) —
    the property the fuzz engine's byte-identical-replay pin rides."""

    def _fire_indexes(self, seed, n=400, rate=0.3):
        plan = FaultPlan(seed=seed).error_rate("v", rate)
        return [i for i in range(n) if plan.next("v") is not None]

    def test_pure_function_of_seed_verb_and_index(self):
        a = self._fire_indexes(11)
        assert a == self._fire_indexes(11)
        assert a != self._fire_indexes(12)
        assert 0 < len(a) < 400  # a real rate, not all-or-nothing
        # distinct verbs draw distinct (deterministic) streams
        plan = FaultPlan(seed=11).error_rate("w", 0.3)
        b = [i for i in range(400) if plan.next("w") is not None]
        assert a != b

    def test_concurrent_callers_see_the_same_outcome_multiset(self):
        """Call-index allocation is atomic under the plan's lock, so
        whichever THREAD draws index n sees outcome f(seed, verb, n):
        the total count of fired faults is interleaving-independent
        and equal to the sequential run's."""
        expected = len(self._fire_indexes(11))
        for _round in range(2):  # two genuinely different interleavings
            plan = FaultPlan(seed=11).error_rate("v", 0.3)
            fired = []

            def worker():
                count = 0
                for _ in range(50):
                    if plan.next("v") is not None:
                        count += 1
                fired.append(count)

            threads = [
                threading.Thread(target=worker) for _ in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert plan.call_count("v") == 400
            assert sum(fired) == expected
