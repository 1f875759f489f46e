"""``make bench-forecast``: forecast-vs-snapshot placement quality A/B
(docs/forecast.md).

Three measurements, all hermetic and driven through the REAL verbs:

  * **trending** — a synthetic cluster where the currently-best-looking
    node is trending straight at its dontschedule threshold.  A
    simulated kube-scheduler round decides placements (Filter ->
    Prioritize), the cluster advances one refresh step (the riser
    crosses), a late Filter re-check records the now-violating state,
    and Bind lands on the node chosen earlier — exactly the
    decide-on-stale-snapshot race a real binding loses.  Snapshot
    ranking picks the riser (lowest value NOW) and pays
    ``pas_decision_violated_at_bind_total``; forecast ranking sees the
    predicted-at-bind value and places on a flat node instead.

  * **spike** — a node above its deschedule threshold but trending back
    down (a transient spike mid-resolution), through the real
    enforcement -> drift -> rebalance loop.  Snapshot hysteresis
    escalates after K cycles and evicts; the forecast trend hold keeps
    the streak below K (``pas_forecast_suppressed_evictions_total``)
    and the spike resolves with zero churn.

  * **overhead** — the 10k-node http_load A/B with the forecaster on vs
    off (same service harness as the decision-log A/B): the acceptance
    bar is that the off path is unchanged and the on path stays within
    a few percent (fits run off the request path).

``make bench-forecast`` runs ``run()`` and prints its result.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List

from benchmarks import children
from benchmarks.http_load import _PATHS, _best_of, _spawn_service, drive, make_bodies
from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.forecast import Forecaster
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.rebalance import Rebalancer
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
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
from platform_aware_scheduling_tpu.utils import decisions, trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity

POLICY = "forecast-pol"
METRIC = "load"
#: dontschedule / deschedule threshold (GreaterThan)
THRESHOLD = 2000
#: flat nodes sit just under the threshold; the riser climbs RISER_SLOPE
#: per refresh step and is still the lowest value (and clean) at decision
#: time — but crosses the threshold one step later, while the fit's
#: predicted-at-bind value already exceeds the flat nodes'
FLAT_VALUE = 1950
RISER_SLOPE = 300
#: history length before the scheduling burst (riser: 100 .. 1900)
DECISION_STEP = 6


def _policy_obj():
    return {
        "metadata": {"name": POLICY, "namespace": "default"},
        "spec": {
            "strategies": {
                # prefer the LEAST loaded node — the ranking that walks
                # straight into a rising series on snapshots
                "scheduleonmetric": {
                    "rules": [
                        {"metricname": METRIC, "operator": "LessThan",
                         "target": 0}
                    ]
                },
                "dontschedule": {
                    "rules": [
                        {"metricname": METRIC, "operator": "GreaterThan",
                         "target": THRESHOLD}
                    ]
                },
                "deschedule": {
                    "rules": [
                        {"metricname": METRIC, "operator": "GreaterThan",
                         "target": THRESHOLD}
                    ]
                },
            }
        },
    }


def _values_at(names: List[str], step: int) -> Dict[str, NodeMetric]:
    """The synthetic cluster at refresh step ``step``: node 0 ("riser")
    climbs RISER_SLOPE/step from 100 — still the lowest value at the
    decision step, above THRESHOLD one step later; every other node sits
    flat at FLAT_VALUE."""
    out = {}
    for i, name in enumerate(names):
        value = 100 + step * RISER_SLOPE if i == 0 else FLAT_VALUE
        out[name] = NodeMetric(value=Quantity(value))
    return out


def _post(extender, verb: str, obj: Dict):
    request = HTTPRequest(
        method="POST",
        path=f"/scheduler/{verb}",
        headers={"Content-Type": "application/json"},
        body=json.dumps(obj).encode(),
    )
    return getattr(extender, verb)(request)


def trending_ab(num_nodes: int = 8, pods: int = 6) -> Dict:
    """Placement-quality A/B on the trending scenario; returns per-mode
    violated-at-bind counts (the pas_decision_violated_at_bind_total
    movement) and the node each mode chose."""
    out: Dict = {"num_nodes": num_nodes, "pods": pods}
    for label, forecast in (("snapshot", False), ("forecast", True)):
        names = [f"node-{i}" for i in range(num_nodes)]
        cache = AutoUpdatingCache()
        mirror = TensorStateMirror()
        mirror.attach(cache)
        cache.write_policy(
            "default", POLICY, TASPolicy.from_obj(_policy_obj())
        )
        forecaster = None
        if forecast:
            forecaster = Forecaster(cache, mirror, window=8, period_s=1.0)
        # the refresh history before the scheduling burst: the riser ends
        # at 1900 — the LOWEST current value, clean — climbing 300/step
        for step in range(DECISION_STEP + 1):
            cache.write_metric(METRIC, _values_at(names, step))
        if forecaster is not None:
            forecaster.refresh()
        extender = MetricsExtender(
            cache, mirror=mirror, node_cache_capable=True
        )
        extender.forecaster = forecaster
        decisions.DECISIONS.configure(enabled=True, capacity=256)
        before = trace.COUNTERS.get(
            "pas_decision_violated_at_bind_total", kind="counter"
        )
        chosen: Dict[str, str] = {}
        pod_objs = []
        for p in range(pods):
            pod = {
                "metadata": {
                    "name": f"pod-{p}",
                    "namespace": "default",
                    "labels": {"telemetry-policy": POLICY},
                }
            }
            pod_objs.append(pod)
            response = _post(
                extender, "filter", {"Pod": pod, "NodeNames": names}
            )
            passing = json.loads(response.body).get("NodeNames") or []
            response = _post(
                extender, "prioritize", {"Pod": pod, "NodeNames": passing}
            )
            ranked = json.loads(response.body) or []
            best = max(ranked, key=lambda e: e["Score"])["Host"]
            chosen[pod["metadata"]["name"]] = best
        # the cluster advances one refresh step while the binding is in
        # flight: the riser crosses the threshold (2200 > 2000)
        cache.write_metric(METRIC, _values_at(names, DECISION_STEP + 1))
        if forecaster is not None:
            forecaster.refresh()
        for pod in pod_objs:
            # the late Filter re-check records the now-violating state...
            _post(extender, "filter", {"Pod": pod, "NodeNames": names})
            # ...and the bind lands where the STALE decision pointed
            _post(
                extender,
                "bind",
                {
                    "PodName": pod["metadata"]["name"],
                    "PodNamespace": "default",
                    "PodUID": "uid",
                    "Node": chosen[pod["metadata"]["name"]],
                },
            )
        violated = trace.COUNTERS.get(
            "pas_decision_violated_at_bind_total", kind="counter"
        ) - before
        out[label] = {
            "violated_at_bind": int(violated),
            "chose_riser": sum(
                1 for node in chosen.values() if node == "node-0"
            ),
            "chosen": sorted(set(chosen.values())),
        }
    decisions.DECISIONS.configure(enabled=True, capacity=512)
    return out


#: the spike series: above THRESHOLD (2000) for 4 cycles but strictly
#: declining (a transient mid-resolution), then back under
SPIKE_SERIES = (2600, 2450, 2300, 2150, 900, 900)


def spike_ab(num_nodes: int = 4, cycles: int = 6) -> Dict:
    """Eviction-churn A/B on the transient-spike scenario through the
    real enforcement -> drift -> rebalance loop (hysteresis K=2)."""
    out: Dict = {"num_nodes": num_nodes, "cycles": cycles}
    for label, forecast in (("snapshot", False), ("forecast", True)):
        fake = FakeKubeClient()
        names = [f"node-{i}" for i in range(num_nodes)]
        for name in names:
            fake.add_node(make_node(name, allocatable={"pods": "10"}))
        for p in range(3):
            fake.add_pod(
                make_pod(
                    f"pod-{p}",
                    labels={
                        "telemetry-policy": POLICY,
                        "pas-workload-group": f"group-{p}",
                    },
                    node_name="node-0",
                    phase="Running",
                )
            )
        cache = AutoUpdatingCache()
        mirror = TensorStateMirror()
        mirror.attach(cache)
        cache.write_policy(
            "default",
            POLICY,
            TASPolicy.from_obj(
                make_policy(
                    POLICY,
                    strategies={
                        "deschedule": [
                            rule(METRIC, "GreaterThan", THRESHOLD)
                        ],
                        "dontschedule": [
                            rule(METRIC, "GreaterThan", THRESHOLD)
                        ],
                        "scheduleonmetric": [rule(METRIC, "LessThan", 0)],
                    },
                )
            ),
        )
        cache.write_metric(METRIC, None)
        enforcer = core.MetricEnforcer(fake, mirror=mirror)
        strategy = deschedule.Strategy(
            policy_name=POLICY,
            rules=[TASPolicyRule(METRIC, "GreaterThan", THRESHOLD)],
        )
        enforcer.register_strategy_type(strategy)
        enforcer.add_strategy(strategy, "deschedule")
        rebalancer = Rebalancer(
            fake,
            mirror,
            mode="active",
            hysteresis_cycles=2,
            rate_per_s=1000.0,
            burst=100,
            cooldown_s=0.0,
            min_available=0,
        )
        rebalancer.attach(enforcer)
        forecaster = None
        if forecast:
            forecaster = Forecaster(cache, mirror, window=8, period_s=1.0)
            rebalancer.forecaster = forecaster
        before = trace.COUNTERS.get(
            "pas_forecast_suppressed_evictions_total", kind="counter"
        )
        for cycle in range(cycles):
            spike = SPIKE_SERIES[min(cycle, len(SPIKE_SERIES) - 1)]
            cache.write_metric(
                METRIC,
                {
                    name: NodeMetric(
                        value=Quantity(spike if i == 0 else 100)
                    )
                    for i, name in enumerate(names)
                },
            )
            if forecaster is not None:
                forecaster.refresh()
            strategy.enforce(enforcer, cache)
        suppressed = trace.COUNTERS.get(
            "pas_forecast_suppressed_evictions_total", kind="counter"
        ) - before
        out[label] = {
            "evictions": len(fake.evictions),
            "suppressed": int(suppressed),
            "final_violations": len(
                (rebalancer.status()["last_plan"] or {}).get(
                    "violating_nodes", []
                )
            ),
        }
    return out


def overhead(
    num_nodes: int = 10_000,
    requests: int = 240,
    warmup: int = 5,
    repeats: int = 2,
    platform: str = "tpu",
) -> Dict:
    """Forecast on-vs-off serving p99 at cluster scale (the acceptance
    bar: the off path is the pre-forecast path, and fits off the request
    path keep the on path within a few percent)."""
    names_bodies = make_bodies(
        [f"node-{i:05d}" for i in range(num_nodes)], "nodenames"
    )
    out: Dict = {"num_nodes": num_nodes}
    for label, forecast in (("on", True), ("off", False)):
        proc, port, out["platform"] = _spawn_service(
            num_nodes, device=True, forecast=forecast, platform=platform
        )
        try:
            side: Dict = {}
            for verb in ("prioritize", "filter"):
                best = None
                for _rep in range(max(repeats, 1)):
                    drive(
                        port, names_bodies[:5], warmup, concurrency=1,
                        path=_PATHS[verb],
                    )
                    measured = drive(
                        port, names_bodies, requests, concurrency=1,
                        path=_PATHS[verb],
                    )
                    best = (
                        measured if best is None else _best_of(best, measured)
                    )
                side[verb] = best
            out[label] = side
        finally:
            proc.terminate()
            proc.wait(timeout=10)
    for verb in ("prioritize", "filter"):
        on_p99 = out["on"][verb]["p99_ms"]
        off_p99 = out["off"][verb]["p99_ms"]
        out[f"overhead_pct_{verb}_p99"] = round(
            (on_p99 / off_p99 - 1.0) * 100.0, 1
        )
    return out


def scenarios() -> Dict:
    """The two placement-quality A/Bs; both compute on the device in this
    process."""
    return {"trending": trending_ab(), "spike": spike_ab()}


def run(num_nodes: int = 10_000, with_overhead: bool = True) -> Dict:
    """Scenarios + the on-vs-off overhead.  The overhead A/B launches
    device services, so this process stays off JAX and the scenarios run
    as a child of their own first (benchmarks/children.py)."""
    out = children.run_child(["-m", "benchmarks.forecast_load", "--scenarios"])
    if with_overhead:
        out["overhead"] = overhead(num_nodes=num_nodes)
    return out


def main() -> None:
    if sys.argv[1:] == ["--scenarios"]:
        identity = children.hold_chip("forecast scenarios")
        result = scenarios()
        result["platform"] = identity["platform"]
        print(json.dumps(result))
        return
    result = run()
    children.assert_launcher("benchmarks.forecast_load")
    trending, spike = result["trending"], result["spike"]
    print(
        f"forecast: trending violated-at-bind snapshot="
        f"{trending['snapshot']['violated_at_bind']} vs forecast="
        f"{trending['forecast']['violated_at_bind']}; spike evictions "
        f"snapshot={spike['snapshot']['evictions']} vs forecast="
        f"{spike['forecast']['evictions']} "
        f"(suppressed={spike['forecast']['suppressed']})",
        file=sys.stderr,
    )
    if "overhead" in result:
        print(
            f"forecast overhead: prioritize "
            f"{result['overhead']['overhead_pct_prioritize_p99']}% / "
            f"filter {result['overhead']['overhead_pct_filter_p99']}% "
            f"(on vs off p99)",
            file=sys.stderr,
        )
    print(json.dumps(result))


if __name__ == "__main__":
    main()
