"""Strategy contract, rule math, and the periodic enforcer.

Reference: telemetry-aware-scheduling/pkg/strategies/core/.

``evaluate_rule`` and ``ordered_list`` (operator.go:13-42) are the entire
mathematical core of TAS.  These host versions are the exact-semantics
control; the batched device versions live in ``ops/rules.py`` and
``ops/scoring.py`` and are cross-checked against these in tests.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Protocol, runtime_checkable

from platform_aware_scheduling_tpu.tas.metrics import NodeMetricsInfo
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicyRule
from platform_aware_scheduling_tpu.utils import klog
from platform_aware_scheduling_tpu.utils.quantity import Quantity

OPERATOR_LESS_THAN = "LessThan"
OPERATOR_GREATER_THAN = "GreaterThan"
OPERATOR_EQUALS = "Equals"


def evaluate_rule(value: Quantity, rule: TASPolicyRule) -> bool:
    """True when ``value <op> target`` holds (operator.go:13-26).  An unknown
    operator raises KeyError, matching the reference's nil-map panic."""
    operators = {
        OPERATOR_LESS_THAN: lambda v, t: v.cmp_int64(t) == -1,
        OPERATOR_GREATER_THAN: lambda v, t: v.cmp_int64(t) == 1,
        OPERATOR_EQUALS: lambda v, t: v.cmp_int64(t) == 0,
    }
    return operators[rule.operator](value, rule.target)


@dataclass
class NodeSortableMetric:
    node_name: str
    metric_value: Quantity


def ordered_list(
    metrics_info: NodeMetricsInfo, operator: str
) -> List[NodeSortableMetric]:
    """Order nodes by metric value: GreaterThan -> descending, LessThan ->
    ascending, anything else -> input order (operator.go:30-42)."""
    mtrcs = [
        NodeSortableMetric(name, info.value) for name, info in metrics_info.items()
    ]
    if operator == OPERATOR_GREATER_THAN:
        mtrcs.sort(key=lambda m: m.metric_value.value, reverse=True)
    elif operator == OPERATOR_LESS_THAN:
        mtrcs.sort(key=lambda m: m.metric_value.value)
    return mtrcs


@runtime_checkable
class StrategyInterface(Protocol):
    """Expected behavior of a strategy (core/types.go:12-18)."""

    def violated(self, cache) -> Dict[str, None]: ...

    def strategy_type(self) -> str: ...

    def equals(self, other: "StrategyInterface") -> bool: ...

    def get_policy_name(self) -> str: ...

    def set_policy_name(self, name: str) -> None: ...


@runtime_checkable
class Enforceable(Protocol):
    """Strategies that act on the cluster each sync period
    (core/types.go:20-24)."""

    def enforce(self, enforcer: "MetricEnforcer", cache) -> int: ...

    def cleanup(self, enforcer: "MetricEnforcer", policy_name: str) -> None: ...


def rules_equal(a, b) -> bool:
    """Shared ``Equals`` body of all three strategies (e.g.
    dontschedule/strategy.go:57-76): same policy name, non-empty rule list,
    identical (metricname, operator, target) per index."""
    if a.get_policy_name() != b.get_policy_name():
        return False
    ra, rb = a.rules, b.rules
    if not ra or len(ra) != len(rb):
        return False
    return all(
        x.metricname == y.metricname
        and x.operator == y.operator
        and x.target == y.target
        for x, y in zip(ra, rb)
    )


class MetricEnforcer:
    """Registers strategies by type and periodically enforces them
    (core/enforcer.go:15-131)."""

    def __init__(self, kube_client=None, mirror=None):
        self.registered_strategies: Dict[str, Dict[int, StrategyInterface]] = {}
        self.kube_client = kube_client
        # optional TensorStateMirror: strategies with a device-path
        # ``violated_device`` use it during enforcement
        self.mirror = mirror
        # per-cycle violation subscribers: callables
        # ``(strategy_type, {node: [policy names]})`` invoked by strategies
        # at the end of every enforcement pass (including empty ones) —
        # the rebalance loop's drift detector feeds off this
        self.violation_observers: List = []
        # optional tas.degraded.DegradedModeController: while it reports
        # evictions suspended (stale telemetry / open kube circuit), the
        # deschedule strategy skips its label pass — no new eviction
        # pressure (in-tree or external) is created from data we cannot
        # trust (docs/robustness.md, hard invariant)
        self.degraded = None
        # optional kube.lease.LeaseElector: with --leaderElect, the
        # deschedule label pass is a singleton loop — followers evaluate
        # and publish violations (their caches stay warm for failover)
        # but never write labels (docs/robustness.md "HA & leader
        # election")
        self.leadership = None
        self._lock = threading.RLock()

    def publish_violations(
        self, strategy_type: str, violations: Dict[str, List[str]]
    ) -> None:
        """Fan a finished enforcement cycle's violation map out to the
        registered observers; a failing observer must never break the
        enforcement loop."""
        for observer in list(self.violation_observers):
            try:
                observer(strategy_type, violations)
            except Exception as exc:  # noqa: BLE001 — observer errors are theirs
                klog.error("violation observer failed: %r", exc)

    def register_strategy_type(self, strategy: StrategyInterface) -> None:
        with self._lock:
            self.registered_strategies[strategy.strategy_type()] = {}

    def unregister_strategy_type(self, strategy: StrategyInterface) -> None:
        with self._lock:
            self.registered_strategies.pop(strategy.strategy_type(), None)

    def is_registered(self, strategy_type: str) -> bool:
        with self._lock:
            return strategy_type in self.registered_strategies

    def registered_strategy_types(self) -> List[str]:
        with self._lock:
            return list(self.registered_strategies)

    def add_strategy(self, strategy: StrategyInterface, strategy_type: str) -> None:
        """Dedup by ``equals``; only Enforceable strategies under a registered
        type are stored (enforcer.go:85-103)."""
        with self._lock:
            registry = self.registered_strategies.get(strategy_type)
            if registry is not None:
                for existing in registry.values():
                    if existing.equals(strategy):
                        klog.v(2).info_s(
                            f"Duplicate strategy found. Not adding "
                            f"{existing.get_policy_name()}: {existing.strategy_type()} to registry",
                            component="controller",
                        )
                        return
            klog.v(2).info_s(
                f"Adding strategies: {strategy.strategy_type()} {strategy.get_policy_name()}",
                component="controller",
            )
            if registry is not None and isinstance(strategy, Enforceable):
                registry[id(strategy)] = strategy

    def remove_strategy(self, strategy: StrategyInterface, strategy_type: str) -> None:
        """Remove matching strategies, then run the strategy's cleanup
        (enforcer.go:65-82)."""
        with self._lock:
            registry = self.registered_strategies.get(strategy_type, {})
            for key, existing in list(registry.items()):
                if existing.equals(strategy):
                    del registry[key]
                    klog.v(2).info_s(
                        f"Removed {existing.get_policy_name()}: {strategy_type} "
                        "from strategy register",
                        component="controller",
                    )
        if isinstance(strategy, Enforceable):
            try:
                strategy.cleanup(self, strategy.get_policy_name())
            except Exception as exc:
                klog.v(2).info_s(
                    f"Failed to remove strategy: {exc}", component="controller"
                )

    def enforce_strategy(self, strategy_type: str, cache) -> None:
        with self._lock:
            strategies = list(
                self.registered_strategies.get(strategy_type, {}).values()
            )
        for strategy in strategies:
            if isinstance(strategy, Enforceable):
                try:
                    strategy.enforce(self, cache)
                except Exception as exc:
                    klog.error("Strategy was not enforceable. %s", exc)

    def enforce_registered_strategies(
        self,
        cache,
        period_seconds: float,
        stop: Optional[threading.Event] = None,
    ) -> None:
        """Periodic enforcement loop (enforcer.go:106-113): waits a tick,
        then enforces every registered type."""
        stop = stop or threading.Event()
        while not stop.wait(period_seconds):
            for strategy_type in self.registered_strategy_types():
                self.enforce_strategy(strategy_type, cache)

    def start_enforcing(
        self,
        cache,
        period_seconds: float,
        stop: Optional[threading.Event] = None,
    ) -> threading.Event:
        stop = stop or threading.Event()
        thread = threading.Thread(
            target=self.enforce_registered_strategies,
            args=(cache, period_seconds, stop),
            name="pas-enforce",
            daemon=True,
        )
        thread.start()
        return stop
