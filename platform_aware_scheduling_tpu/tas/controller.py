"""TASPolicy controller: CRD informer -> cache writes + enforcer registry.

Reference: telemetry-aware-scheduling/pkg/controller/{controller,types}.go.
The informer watches ``taspolicies`` (controller.go:38-57); onAdd caches the
policy, registers each strategy with the enforcer, and registers each rule's
metric (refcounted) in the cache (controller.go:61-91); onUpdate removes the
old strategies/metrics then re-adds the new (111-149); onDelete unregisters
strategies, derefs metrics, drops the policy (152-176).  ``cast_strategy``
maps a strategy-type name to its concrete class (94-108).
"""

from __future__ import annotations

import threading
from typing import Optional

from platform_aware_scheduling_tpu.kube.informer import (
    DeletedFinalStateUnknown,
    Informer,
    ListWatch,
)
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import (
    TASPolicy,
    TASPolicyStrategy,
)
from platform_aware_scheduling_tpu.tas.strategies import (
    core,
    deschedule,
    dontschedule,
    scheduleonmetric,
)
from platform_aware_scheduling_tpu.utils import klog

_STRATEGY_CLASSES = {
    scheduleonmetric.STRATEGY_TYPE: scheduleonmetric.Strategy,
    deschedule.STRATEGY_TYPE: deschedule.Strategy,
    dontschedule.STRATEGY_TYPE: dontschedule.Strategy,
}


class InvalidStrategyError(ValueError):
    pass


def cast_strategy(strategy_type: str, strat: TASPolicyStrategy):
    """Strategy-type name -> concrete strategy instance
    (reference controller.go:94-108)."""
    cls = _STRATEGY_CLASSES.get(strategy_type)
    if cls is None:
        raise InvalidStrategyError(
            "strategy could not be added - invalid strategy type"
        )
    return cls.from_policy_strategy(strat)


class TelemetryPolicyController:
    """Watches the TASPolicy CRD and keeps cache + enforcer in sync
    (reference pkg/controller/types.go:11-15)."""

    def __init__(
        self,
        kube_client,
        cache: AutoUpdatingCache,
        enforcer: core.MetricEnforcer,
        namespace: Optional[str] = None,
    ):
        self.kube_client = kube_client
        self.cache = cache
        self.enforcer = enforcer
        self.namespace = namespace
        #: the CRD informer once :meth:`run` starts it — the mains feed
        #: its has_synced into /readyz (utils/health.informer_synced)
        self.informer: Optional[Informer] = None

    # -- lifecycle (controller.go:23-57) --------------------------------------

    def run(self, stop: Optional[threading.Event] = None) -> Informer:
        """Start the CRD informer; returns it (caller may wait for sync).
        Panics in handlers are contained per-event, like the reference's
        recover wrapper (controller.go:25-29)."""

        def list_policies():
            obj = self.kube_client.list_taspolicies(self.namespace)
            items = obj.get("items") or []
            rv = (obj.get("metadata") or {}).get("resourceVersion", "")
            return [TASPolicy.from_obj(item) for item in items], rv

        def watch_policies(resource_version):
            for event_type, raw in self.kube_client.watch_taspolicies(
                self.namespace, resource_version=resource_version
            ):
                yield event_type, TASPolicy.from_obj(raw)

        def key(policy: TASPolicy) -> str:
            return f"{policy.namespace}/{policy.name}"

        informer = Informer(
            ListWatch(list_policies, watch_policies, key),
            on_add=self._guarded(self.on_add),
            on_update=self._guarded(self.on_update),
            on_delete=self._guarded(self.on_delete),
            name="taspolicy",
        )
        self.informer = informer
        informer.start()
        if stop is not None:
            threading.Thread(
                target=lambda: (stop.wait(), informer.stop()),
                name="pas-stop-taspolicy",
                daemon=True,
            ).start()
        return informer

    def _guarded(self, fn):
        def wrapped(*args):
            try:
                fn(*args)
            except Exception as exc:
                klog.error("Recovered from policy event panic: %s", exc)

        return wrapped

    # -- handlers -------------------------------------------------------------

    def on_add(self, policy: TASPolicy) -> None:
        """Cache the policy, register strategies + metrics
        (controller.go:61-91)."""
        if not isinstance(policy, TASPolicy):
            klog.v(4).info_s(
                "cannot add policy: not recognized as a telemetry policy",
                component="controller",
            )
            return
        pol = policy.deep_copy()
        self.cache.write_policy(pol.namespace, pol.name, pol)
        for name, strat in pol.strategies.items():
            klog.v(4).info_s(
                f"registering {name} from {pol.name}", component="controller"
            )
            try:
                instance = cast_strategy(name, strat)
            except InvalidStrategyError as exc:
                klog.v(2).info_s(str(exc), component="controller")
                return
            instance.set_policy_name(pol.name)
            self.enforcer.add_strategy(instance, name)
            for rule in strat.rules:
                self.cache.write_metric(rule.metricname, None)
                klog.v(2).info_s(f"Added {rule.metricname}", component="controller")
        klog.v(2).info_s(f"Added policy, {pol.name}", component="controller")

    def on_update(self, old: TASPolicy, new: TASPolicy) -> None:
        """Swap cached policy; per strategy type remove old registration +
        metric refcounts, then add the new (controller.go:111-149)."""
        pol = new.deep_copy()
        self.cache.write_policy(pol.namespace, pol.name, pol)
        klog.v(2).info_s(f"Policy: {pol.name} updated", component="controller")
        for name, strat in pol.strategies.items():
            old_strat = old.strategies.get(name, TASPolicyStrategy())
            try:
                old_instance = cast_strategy(name, old_strat)
            except InvalidStrategyError as exc:
                klog.v(2).info_s(str(exc), component="controller")
                return
            old_instance.set_policy_name(old.name)
            self.enforcer.remove_strategy(old_instance, old_instance.strategy_type())
            for rule in old_strat.rules:
                self.cache.delete_metric(rule.metricname)
            try:
                instance = cast_strategy(name, strat)
            except InvalidStrategyError as exc:
                klog.v(2).info_s(str(exc), component="controller")
                return
            instance.set_policy_name(pol.name)
            self.enforcer.add_strategy(instance, name)
            for rule in strat.rules:
                self.cache.write_metric(rule.metricname, None)

    def on_delete(self, policy: TASPolicy) -> None:
        """Unregister strategies, deref metrics, drop the policy
        (controller.go:152-176)."""
        if isinstance(policy, DeletedFinalStateUnknown):
            policy = policy.obj
        pol = policy.deep_copy()
        for name, strat in pol.strategies.items():
            try:
                instance = cast_strategy(name, strat)
            except InvalidStrategyError as exc:
                klog.v(2).info_s(str(exc), component="controller")
                return
            instance.set_policy_name(pol.name)
            self.enforcer.remove_strategy(instance, instance.strategy_type())
            for rule in strat.rules:
                self.cache.delete_metric(rule.metricname)
        self.cache.delete_policy(pol.namespace, pol.name)
        klog.v(2).info_s(f"Policy: {pol.name} deleted", component="controller")
