"""Rebalance convergence bench: synthetic churn on N nodes, rebalancer
active vs label-only baseline (docs/rebalance.md).

The scenario models the loop the reference never closes: pods crammed
onto a few hot nodes push a load metric past the deschedule threshold;
the label-only baseline (the reference's behavior — mark the node, wait
for an external descheduler that isn't there) never converges, while the
active rebalancer drives violations to zero within the churn budget.

The harness is hermetic (FakeKubeClient + AutoUpdatingCache + mirror)
and doubles as the test fixture for tests/test_rebalance.py: the
"scheduler honoring the plan" is simulated by re-binding each evicted
pod onto its planned target node, and per-node load is simply
``pods_on_node * pod_load`` recomputed every cycle.

Measured per mode: cycles-to-zero-violations, evictions executed, and
plan latency (mean + p99 across planning cycles, first-cycle compile
included in the max).  ``make bench-rebalance`` runs ``run()`` and
prints its result.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, List, Optional

from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.rebalance import Rebalancer
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import (
    TASPolicy,
    TASPolicyRule,
)
from platform_aware_scheduling_tpu.tas.strategies import core, deschedule
from platform_aware_scheduling_tpu.testing.builders import (
    make_node,
    make_pod,
    make_policy,
    rule,
)
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.utils.quantity import Quantity
from platform_aware_scheduling_tpu.utils.tracing import quantile

POLICY_NAME = "rebalance-pol"
METRIC = "node_load"
POD_LOAD = 100
#: per-node pod allocatable; load stays under threshold at <= CAP pods
NODE_CAP = 4
#: GreaterThan threshold: violated at NODE_CAP + 1 pods or more
THRESHOLD = NODE_CAP * POD_LOAD + POD_LOAD // 2


class ChurnHarness:
    """One synthetic cluster + one rebalancer, stepped cycle by cycle."""

    def __init__(
        self,
        num_nodes: int = 16,
        hot_nodes: int = 3,
        pods_per_hot_node: int = 8,
        mode: str = "active",
        hysteresis_cycles: int = 2,
        max_moves: int = 5,
        solver: str = "greedy",
        rate_per_s: float = 1000.0,
        burst: int = 100,
        cooldown_s: float = 0.0,
        min_available: int = 1,
        clock=time.monotonic,
        groups: int = 3,
    ):
        self.fake = FakeKubeClient()
        self.num_nodes = num_nodes
        for i in range(num_nodes):
            self.fake.add_node(
                make_node(f"node-{i}", allocatable={"pods": str(NODE_CAP)})
            )
        self.pod_labels: Dict[str, Dict[str, str]] = {}
        for i in range(hot_nodes * pods_per_hot_node):
            labels = {
                "telemetry-policy": POLICY_NAME,
                "pas-workload-group": f"group-{i % groups}",
            }
            name = f"pod-{i}"
            self.pod_labels[name] = labels
            self.fake.add_pod(
                make_pod(
                    name,
                    labels=labels,
                    node_name=f"node-{i % hot_nodes}",
                    phase="Running",
                )
            )
        self.cache = AutoUpdatingCache()
        self.mirror = TensorStateMirror()
        self.mirror.attach(self.cache)
        self.cache.write_policy(
            "default",
            POLICY_NAME,
            TASPolicy.from_obj(
                make_policy(
                    POLICY_NAME,
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
        self.cache.write_metric(METRIC, None)
        self.enforcer = core.MetricEnforcer(self.fake, mirror=self.mirror)
        self.strategy = deschedule.Strategy(
            policy_name=POLICY_NAME,
            rules=[TASPolicyRule(METRIC, "GreaterThan", THRESHOLD)],
        )
        self.enforcer.register_strategy_type(self.strategy)
        self.enforcer.add_strategy(self.strategy, "deschedule")
        self.rebalancer = Rebalancer(
            self.fake,
            self.mirror,
            mode=mode,
            hysteresis_cycles=hysteresis_cycles,
            max_moves=max_moves,
            solver=solver,
            rate_per_s=rate_per_s,
            burst=burst,
            cooldown_s=cooldown_s,
            min_available=min_available,
            clock=clock,
        )
        self.rebalancer.attach(self.enforcer)
        self._seen_evictions = 0
        self.records: List[Dict] = []

    # -- simulation ------------------------------------------------------------

    def loads(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for pod in self.fake.list_pods():
            if pod.phase not in ("Succeeded", "Failed"):
                node = pod.spec_node_name
                counts[node] = counts.get(node, 0) + 1
        return {
            f"node-{i}": counts.get(f"node-{i}", 0) * POD_LOAD
            for i in range(self.num_nodes)
        }

    def step(self) -> Dict:
        """One full cycle: publish telemetry, enforce (which drives the
        rebalancer), then re-bind evicted pods onto their planned targets
        (the stand-in for controller re-create + scheduler placement)."""
        self.cache.write_metric(
            METRIC,
            {
                node: NodeMetric(value=Quantity(str(value)))
                for node, value in self.loads().items()
            },
        )
        self.strategy.enforce(self.enforcer, self.cache)
        record = self.rebalancer.status()["last_plan"] or {}
        targets = {
            move["pod_key"]: move["to_node"] for move in record.get("moves", [])
        }
        for eviction in self.fake.evictions[self._seen_evictions :]:
            key = f"{eviction['namespace']}&{eviction['pod']}"
            self.fake.add_pod(
                make_pod(
                    eviction["pod"],
                    namespace=eviction["namespace"],
                    labels=self.pod_labels.get(
                        eviction["pod"], {"telemetry-policy": POLICY_NAME}
                    ),
                    node_name=targets.get(key, eviction["node"]),
                    phase="Running",
                )
            )
        self._seen_evictions = len(self.fake.evictions)
        self.records.append(record)
        return record

    def run_until_converged(self, max_cycles: int = 30) -> Optional[int]:
        """Step until a cycle observes zero violations; returns that
        cycle index (0-based) or None."""
        for cycle in range(max_cycles):
            record = self.step()
            if not record.get("violating_nodes"):
                return cycle
        return None

    def summary(self) -> Dict:
        plan_ms = [
            r["plan_ms"] for r in self.records if r.get("plan_ms", 0) > 0
        ]
        return {
            "cycles": len(self.records),
            "evictions": len(self.fake.evictions),
            "moves_planned": sum(len(r.get("moves", [])) for r in self.records),
            "plans": len(plan_ms),
            "plan_ms_mean": round(sum(plan_ms) / len(plan_ms), 3)
            if plan_ms
            else None,
            "plan_ms_p99": round(quantile(sorted(plan_ms), 0.99), 3)
            if plan_ms
            else None,
            "residual_violations": len(
                (self.records[-1] if self.records else {}).get(
                    "violating_nodes", []
                )
            ),
        }


def run(
    num_nodes: int = 64,
    hot_nodes: int = 4,
    pods_per_hot_node: int = 10,
    hysteresis_cycles: int = 2,
    max_moves: int = 8,
    max_cycles: int = 30,
    solver: str = "greedy",
) -> Dict:
    """The bench entry: identical churn, rebalancer active vs label-only
    (mode=off — labels are applied, nothing is ever evicted, exactly the
    reference's in-tree behavior)."""
    out: Dict = {
        "num_nodes": num_nodes,
        "hot_nodes": hot_nodes,
        "pods": hot_nodes * pods_per_hot_node,
        "hysteresis_cycles": hysteresis_cycles,
        "max_moves": max_moves,
        "solver": solver,
    }
    for label, mode in (("active", "active"), ("label_only", "off")):
        harness = ChurnHarness(
            num_nodes=num_nodes,
            hot_nodes=hot_nodes,
            pods_per_hot_node=pods_per_hot_node,
            mode=mode,
            hysteresis_cycles=hysteresis_cycles,
            max_moves=max_moves,
            solver=solver,
        )
        converged_at = harness.run_until_converged(max_cycles)
        side = harness.summary()
        side["cycles_to_zero"] = converged_at
        side["converged"] = converged_at is not None
        out[label] = side
    return out


def main() -> None:
    from benchmarks import children

    identity = children.hold_chip("benchmarks.rebalance_load")
    result = run()
    result["platform"] = identity["platform"]
    active, label_only = result["active"], result["label_only"]
    print(
        f"rebalance: active converged in {active['cycles_to_zero']} cycles "
        f"({active['evictions']} evictions, plan mean "
        f"{active['plan_ms_mean']} ms); label-only converged="
        f"{label_only['converged']} with {label_only['residual_violations']} "
        f"violating nodes after {label_only['cycles']} cycles",
        file=sys.stderr,
    )
    print(json.dumps(result))


if __name__ == "__main__":
    main()
