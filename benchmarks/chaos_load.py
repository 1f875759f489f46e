"""Chaos harness + bench: the assembled TAS service under scripted API
faults (docs/robustness.md).

Two things live here:

  * :class:`ChaosScenario` — the deterministic outage → degrade →
    recover → resume driver shared with tests/test_faults.py: a FULLY
    assembled TAS stack (AutoUpdatingCache + TensorStateMirror +
    MetricsExtender + MetricEnforcer/deschedule + active Rebalancer +
    DegradedModeController) over FakeKubeClient and FakeMetricsClient,
    every clock a FakeClock, every fault a FaultPlan script.  ``tick()``
    is one sync period: advance the clock, run a telemetry refresh pass
    through the fault-tolerant client, run a deschedule enforcement pass
    (which drives the rebalancer).  Nothing sleeps; nothing is random.

  * ``run()`` — the bench: p99 + availability through a LIVE threaded
    front-end while the telemetry refresh loop runs against a metrics
    client with a scripted, seeded 10% error rate, vs the same service
    on a clean client.  Feeds the ``chaos`` section of bench.py's line
    and the BENCH_DETAIL artifact: the robustness claim in numbers —
    fault-tolerant retries + degraded modes keep the serving path's
    latency and availability flat through a flaky control plane.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, Optional

from platform_aware_scheduling_tpu.kube.retry import (
    CircuitBreakerRegistry,
    FaultTolerantClient,
    RetryPolicy,
)
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.rebalance import Rebalancer
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.degraded import (
    MODE_LAST_KNOWN_GOOD,
    DegradedModeController,
)
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import (
    TASPolicy,
    TASPolicyRule,
)
from platform_aware_scheduling_tpu.tas.strategies import core, deschedule
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.testing.builders import (
    make_node,
    make_pod,
    make_policy,
    rule,
)
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.testing.faults import (
    FakeClock,
    FakeMetricsClient,
    FaultPlan,
)

POLICY_NAME = "chaos-pol"
METRIC = "node_load"
THRESHOLD = 450
POD_LOAD = 100


class ChaosScenario:
    """One assembled TAS service on fakes, stepped sync period by sync
    period under a FaultPlan — deterministic end to end."""

    def __init__(
        self,
        num_nodes: int = 6,
        hot_pods: int = 6,
        period_s: float = 1.0,
        degraded_mode: str = MODE_LAST_KNOWN_GOOD,
        rebalance_mode: str = "active",
        hysteresis_cycles: int = 1,
        seed: int = 7,
        retry_policy: Optional[RetryPolicy] = None,
        failure_threshold: int = 3,
        reset_timeout_s: float = 5.0,
    ):
        self.clock = FakeClock()
        self.plan = FaultPlan(seed=seed)
        self.period_s = period_s

        # -- cluster: one hot node (violating while healthy), the rest idle
        self.fake = FakeKubeClient()
        self.fake.fault_plan = self.plan
        self.fake.fault_clock = self.clock
        self.num_nodes = num_nodes
        for i in range(num_nodes):
            self.fake.add_node(
                make_node(f"node-{i}", allocatable={"pods": "8"})
            )
        for i in range(hot_pods):
            self.fake.add_pod(
                make_pod(
                    f"pod-{i}",
                    labels={
                        "telemetry-policy": POLICY_NAME,
                        "pas-workload-group": f"g-{i}",
                    },
                    node_name="node-0",
                    phase="Running",
                )
            )

        # -- telemetry: fault-tolerant client over the fake metrics API
        self.metrics = FakeMetricsClient(plan=self.plan, clock=self.clock)
        self.breakers = CircuitBreakerRegistry(
            failure_threshold=failure_threshold,
            reset_timeout_s=reset_timeout_s,
            clock=self.clock.now,
        )
        self.retry_policy = retry_policy or RetryPolicy(
            max_attempts=3, base_delay_s=0.05, max_delay_s=0.5,
            deadline_s=10.0,
        )
        self.ft_metrics = FaultTolerantClient(
            self.metrics,
            policy=self.retry_policy,
            breakers=self.breakers,
            clock=self.clock.now,
            sleep=self.clock.sleep,
        )
        self.ft_kube = FaultTolerantClient(
            self.fake,
            policy=self.retry_policy,
            breakers=self.breakers,
            clock=self.clock.now,
            sleep=self.clock.sleep,
        )

        # -- the assembled TAS stack, clocks injected throughout
        self.cache = AutoUpdatingCache(clock=self.clock.now)
        self.cache._refresh_period = period_s  # stepped manually by tick()
        self.mirror = TensorStateMirror()
        self.mirror.attach(self.cache)
        self.cache.write_policy(
            "default",
            POLICY_NAME,
            TASPolicy.from_obj(
                make_policy(
                    POLICY_NAME,
                    strategies={
                        "deschedule": [rule(METRIC, "GreaterThan", THRESHOLD)],
                        "dontschedule": [
                            rule(METRIC, "GreaterThan", THRESHOLD)
                        ],
                        "scheduleonmetric": [rule(METRIC, "LessThan", 0)],
                    },
                )
            ),
        )
        self.cache.write_metric(METRIC, None)
        self.extender = MetricsExtender(
            self.cache, mirror=self.mirror, node_cache_capable=True
        )
        self.enforcer = core.MetricEnforcer(self.ft_kube, mirror=self.mirror)
        self.strategy = deschedule.Strategy(
            policy_name=POLICY_NAME,
            rules=[TASPolicyRule(METRIC, "GreaterThan", THRESHOLD)],
        )
        self.enforcer.register_strategy_type(self.strategy)
        self.enforcer.add_strategy(self.strategy, "deschedule")
        self.degraded = DegradedModeController(
            self.cache, breakers=self.breakers, mode=degraded_mode
        )
        self.extender.degraded = self.degraded
        self.enforcer.degraded = self.degraded
        self.rebalancer = Rebalancer(
            self.ft_kube,
            self.mirror,
            mode=rebalance_mode,
            hysteresis_cycles=hysteresis_cycles,
            max_moves=4,
            rate_per_s=1000.0,
            burst=100,
            cooldown_s=0.0,
            min_available=0,
            clock=self.clock.now,
        )
        self.rebalancer.degraded = self.degraded
        self.rebalancer.attach(self.enforcer)
        self.extender.rebalancer = self.rebalancer
        self.ticks = 0

    # -- simulation ------------------------------------------------------------

    def publish_loads(self) -> None:
        """Refresh the fake metrics API from actual pod placement.  Reads
        the fake's store directly — this models the EXTERNAL telemetry
        pipeline, which must not consume the service's fault budget."""
        counts: Dict[str, int] = {}
        with self.fake._lock:
            raws = list(self.fake._pods.values())
            for raw in raws:
                if (raw.get("status") or {}).get("phase") in (
                    "Succeeded", "Failed",
                ):
                    continue
                node = (raw.get("spec") or {}).get("nodeName", "")
                counts[node] = counts.get(node, 0) + 1
        self.metrics.set_all(
            METRIC,
            {
                f"node-{i}": counts.get(f"node-{i}", 0) * POD_LOAD
                for i in range(self.num_nodes)
            },
        )

    def tick(self) -> Dict:
        """One sync period: clock advances, telemetry refresh pass runs
        through the fault-tolerant client (errors land as growing metric
        age, never a crash), then one deschedule enforcement pass drives
        the rebalancer.  Returns the rebalancer's cycle record."""
        self.ticks += 1
        self.clock.advance(self.period_s)
        self.publish_loads()
        self.cache.update_all_metrics(self.ft_metrics)
        try:
            self.strategy.enforce(self.enforcer, self.cache)
        except Exception:
            pass  # a failed label pass is part of the chaos under test
        return self.rebalancer.status().get("last_plan") or {}

    def evictions(self) -> int:
        return len(self.fake.evictions)

    def ready(self):
        """(ready, conditions) from a probe over the extender — what
        /readyz would answer on either front-end."""
        from platform_aware_scheduling_tpu.utils.health import probe_for

        return probe_for(self.extender).evaluate()


# ---------------------------------------------------------------------------
# leader kill: the multi-replica failover scenario (testing/ha.py)
# ---------------------------------------------------------------------------


def _probe_prioritize(stack) -> bool:
    """One in-process Prioritize against a replica's extender — the
    availability signal during failover (a follower must keep serving
    the verbs while nobody holds the lease)."""
    from platform_aware_scheduling_tpu.extender.server import HTTPRequest
    from platform_aware_scheduling_tpu.testing.ha import POLICY_NAME as HA_POL

    body = json.dumps(
        {
            "Pod": {
                "metadata": {
                    "name": "probe-pod",
                    "namespace": "default",
                    "labels": {"telemetry-policy": HA_POL},
                }
            },
            "NodeNames": [f"node-{i}" for i in range(stack.harness.num_nodes)],
        }
    ).encode()
    response = stack.extender.prioritize(
        HTTPRequest(
            method="POST",
            path="/scheduler/prioritize",
            headers={"Content-Type": "application/json"},
            body=body,
        )
    )
    return response.status == 200


def leader_kill(
    replicas: int = 3, kill_tick: int = 1, max_ticks: int = 24
) -> Dict:
    """Scripted leader kill at tick K (docs/robustness.md "HA & leader
    election"): a standby must take the lease within the lease duration,
    every live replica must keep answering Prioritize throughout the
    leaderless gap, and the fleet's total evictions must equal the
    single-replica baseline with zero duplicates.  The scenario itself
    is the shared ``testing.ha.leader_kill``; this wrapper adds the
    Prioritize availability probe."""
    from platform_aware_scheduling_tpu.testing import ha

    return ha.leader_kill(
        replicas=replicas,
        kill_tick=kill_tick,
        max_ticks=max_ticks,
        probe=_probe_prioritize,
    )


# ---------------------------------------------------------------------------
# the bench: live front-end under a seeded 10% API-error rate
# ---------------------------------------------------------------------------


def _drive_side(error_rate: float, num_nodes: int, requests: int) -> Dict:
    from benchmarks import http_load
    from platform_aware_scheduling_tpu.extender.server import Server

    ext, names = http_load.build_extender(num_nodes, device=True)
    # a refresh loop against a (possibly faulty) metrics client keeps the
    # cache hot while the HTTP side is driven; the fault-tolerant client
    # retries/breaks exactly as in production
    plan = FaultPlan(seed=11)
    metrics = FakeMetricsClient(plan=plan)
    if error_rate > 0:
        plan.error_rate("get_node_metric", error_rate, status=503)
    values = {n: (i * 37) % 1_000_000 for i, n in enumerate(names)}
    metrics.set_all("load_metric", values)
    # register the metric for refresh (build_extender only seeds data;
    # a data-bearing write does not increment the refresh refcount)
    ext.cache.write_metric("load_metric")
    breakers = CircuitBreakerRegistry(failure_threshold=5, reset_timeout_s=1.0)
    ft = FaultTolerantClient(
        metrics,
        policy=RetryPolicy(max_attempts=3, base_delay_s=0.002,
                           max_delay_s=0.01, deadline_s=5.0),
        breakers=breakers,
    )
    ext.degraded = DegradedModeController(
        ext.cache, breakers=breakers, mode=MODE_LAST_KNOWN_GOOD
    )
    stop = ext.cache.start_periodic_update(0.02, ft)
    server = Server(ext, metrics_provider=ext.metrics_text)
    server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
    server.wait_ready()
    try:
        bodies = http_load.make_bodies(names, "nodenames", count=8)
        served = 0
        result: Dict = {}
        try:
            result = http_load.drive(
                server.port, bodies, requests=requests, concurrency=4
            )
            served = int(result.get("count", 0))
        except RuntimeError as exc:
            result = {"error": str(exc)}
        refreshes = plan.call_count("get_node_metric")
        return {
            "error_rate": error_rate,
            "availability": round(served / max(1, requests), 4),
            "p50_ms": result.get("p50_ms"),
            "p99_ms": result.get("p99_ms"),
            "requests_per_s": result.get("requests_per_s"),
            "metric_fetches": refreshes,
            "circuits": dict(breakers.states()),
        }
    finally:
        stop.set()
        server.shutdown()


def run(num_nodes: int = 256, requests: int = 400) -> Dict:
    """The ``chaos`` bench section: clean baseline vs scripted 10%
    metrics-API error rate through the same live service, plus the
    multi-replica leader-kill failover scenario."""
    out: Dict = {"num_nodes": num_nodes, "requests": requests}
    out["clean"] = _drive_side(0.0, num_nodes, requests)
    out["faulty"] = _drive_side(0.10, num_nodes, requests)
    clean_p99 = out["clean"].get("p99_ms") or 0.0
    faulty_p99 = out["faulty"].get("p99_ms") or 0.0
    out["p99_ratio_faulty_vs_clean"] = (
        round(faulty_p99 / clean_p99, 3) if clean_p99 else None
    )
    out["leader_kill"] = leader_kill()
    return out


def main() -> None:
    from benchmarks import children

    identity = children.hold_chip("benchmarks.chaos_load")
    result = run()
    result["platform"] = identity["platform"]
    lk = result["leader_kill"]
    print(
        f"chaos: availability clean={result['clean']['availability']} "
        f"faulty={result['faulty']['availability']} at 10% API errors; "
        f"p99 {result['clean']['p99_ms']} ms -> "
        f"{result['faulty']['p99_ms']} ms "
        f"(x{result['p99_ratio_faulty_vs_clean']}); leader kill: "
        f"failover {lk['failover_ticks']} ticks, availability "
        f"{lk['availability']}, evictions {lk['evictions']}=="
        f"{lk['evictions_baseline']} baseline, "
        f"{lk['duplicate_evictions']} duplicates",
        file=sys.stderr,
    )
    print(json.dumps(result))


if __name__ == "__main__":
    main()
