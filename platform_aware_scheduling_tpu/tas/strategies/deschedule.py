"""deschedule strategy: violation detection + node labeling enforcement.

Reference: telemetry-aware-scheduling/pkg/strategies/deschedule/
{strategy,enforce}.go.  Violating nodes get the label
``<policyName>=violating`` via JSON patch; non-violating nodes that still
carry the label get it removed and re-added as "null" (the reference's
acknowledged oddity at enforce.go:118-132, kept for behavior parity since
external deschedulers match on these labels).  Actual pod eviction is
delegated to an external descheduler (survey §1 L6).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import (
    TASPolicyRule,
    TASPolicyStrategy,
)
from platform_aware_scheduling_tpu.tas.strategies import core
from platform_aware_scheduling_tpu.utils import klog, trace

STRATEGY_TYPE = "deschedule"


class _BareNode:
    """A name-only stand-in for a node known to carry none of the
    registered policy labels (it missed every label-exists selector):
    the label pass needs only its name to add ``=violating``."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def get_labels(self) -> Dict[str, str]:
        return {}


@dataclass
class Strategy:
    policy_name: str = ""
    rules: List[TASPolicyRule] = field(default_factory=list)

    @classmethod
    def from_policy_strategy(cls, strat: TASPolicyStrategy) -> "Strategy":
        return cls(policy_name=strat.policy_name, rules=list(strat.rules))

    # -- violation detection (strategy.go:31-55) -----------------------------

    def violated(self, cache) -> Dict[str, None]:
        trace.COUNTERS.inc(
            "pas_strategy_evaluations_total", labels={"strategy": STRATEGY_TYPE}
        )
        violating: Dict[str, None] = {}
        for rule in self.rules:
            try:
                node_metrics = cache.read_metric(rule.metricname)
            except Exception as exc:
                klog.v(2).info_s(str(exc), component="controller")
                continue
            for node_name, node_metric in node_metrics.items():
                if core.evaluate_rule(node_metric.value, rule):
                    klog.v(2).info_s(
                        f"{node_name} violating {self.policy_name}: "
                        f"{rule.metricname} {rule.operator} {rule.target}",
                        component="controller",
                    )
                    violating[node_name] = None
        if violating:
            trace.COUNTERS.inc(
                "pas_strategy_violations_total",
                len(violating),
                labels={"strategy": STRATEGY_TYPE},
            )
        return violating

    def violated_device(self, mirror) -> "Dict[str, None] | None":
        """Batched violation detection through the tensor mirror; None means
        'use the host path' (policy unknown, host-only values, or the
        compiled rules don't match this instance)."""
        try:
            import numpy as np

            from platform_aware_scheduling_tpu.ops.rules import (
                OP_IDS,
                violated_nodes,
            )

            compiled, view = mirror.policy_with_view_by_name(self.policy_name)
            if compiled is None or compiled.deschedule is None:
                return None
            rs = compiled.deschedule
            if rs.host_only or not rs.active.any():
                return None
            if any(mirror.metric_host_only(m) for m in rs.metric_names):
                return None
            # the enforcer's strategy instance and the mirror's compiled
            # policy come from the same CRD event but through different
            # paths — verify they describe the same rules before trusting
            # the device result
            mine = tuple(
                (r.metricname, OP_IDS.get(r.operator, -1), r.target * 1000)
                for r in self.rules
            )
            theirs = tuple(
                (name, int(rs.op_ids[i]), int(rs.targets[i]))
                for i, name in enumerate(rs.metric_names)
            )
            if mine != theirs:
                return None
            rules = compiled.device_rules("deschedule")
            mask = np.asarray(violated_nodes(view.values, view.present, rules))
            names = view.node_names
            violating = {
                names[i]: None for i in np.nonzero(mask)[0] if i < len(names)
            }
            # same counters the host path keeps — the evaluation happened,
            # just on the device (None returns fall through to the host
            # path, which counts itself)
            trace.COUNTERS.inc(
                "pas_strategy_evaluations_total",
                labels={"strategy": STRATEGY_TYPE},
            )
            if violating:
                trace.COUNTERS.inc(
                    "pas_strategy_violations_total",
                    len(violating),
                    labels={"strategy": STRATEGY_TYPE},
                )
            return violating
        except Exception as exc:
            trace.COUNTERS.inc(
                "pas_device_path_errors_total", labels={"site": "deschedule"}
            )
            klog.error("device deschedule failed, host fallback: %s", exc)
            return None

    # -- enforcement (enforce.go) --------------------------------------------

    def enforce(self, enforcer: core.MetricEnforcer, cache) -> int:
        """Compute per-policy violations, list the nodes whose labels
        can change, patch labels (enforce.go:57-71; see
        :meth:`_nodes_needing_labels` for the deliberate divergence from
        the reference's list-every-node loop).

        Hard invariant (docs/robustness.md): while the degraded-mode
        controller reports evictions suspended — telemetry stale or the
        kube circuit open — the LABEL pass is skipped.  Violations
        computed from untrustworthy data must not become ``=violating``
        labels (the eviction trigger external deschedulers act on).  The
        stale violation map is still published so the rebalancer can
        record the suspension on /debug/rebalance — its own gate
        guarantees it neither plans, actuates, nor advances drift
        streaks from it.

        HA (docs/robustness.md "HA & leader election"): with leader
        election wired, the label pass is a singleton loop.  A follower
        still evaluates violations and publishes them — its drift
        detector and /debug surfaces stay warm for failover — but never
        writes ``=violating`` labels, so N replicas create exactly one
        stream of eviction pressure."""
        leadership = getattr(enforcer, "leadership", None)
        if leadership is not None and not leadership.is_leader():
            enforcer.publish_violations(
                STRATEGY_TYPE,
                self._node_status_for_strategy(enforcer, cache),
            )
            return 0
        degraded = getattr(enforcer, "degraded", None)
        if degraded is not None:
            allowed, reason = degraded.evictions_allowed()
            if not allowed:
                klog.v(2).info_s(
                    f"deschedule enforcement suspended: {reason}",
                    component="controller",
                )
                # liveness: with the label pass skipped, NOTHING else in
                # this process may be calling the kube group — and a
                # breaker can only leave half-open through a probe CALL.
                # This read is that probe: refused instantly while the
                # circuit is open, it becomes the half-open probe once
                # the reset timeout elapses, closing the circuit (and
                # ending the suspension) as soon as the API server is
                # really back
                try:
                    enforcer.kube_client.list_nodes()
                except Exception as probe_exc:
                    klog.v(4).info_s(
                        f"suspended-cycle kube probe: {probe_exc}",
                        component="controller",
                    )
                enforcer.publish_violations(
                    STRATEGY_TYPE,
                    self._node_status_for_strategy(enforcer, cache),
                )
                return 0
        violations = self._node_status_for_strategy(enforcer, cache)
        try:
            nodes = self._nodes_needing_labels(enforcer, violations)
        except Exception as exc:
            klog.v(2).info_s(f"cannot list nodes: {exc}", component="controller")
            raise
        try:
            total = self._update_node_labels(enforcer, violations, nodes)
        finally:
            # close-the-loop feed: every enforcement cycle publishes its
            # full node -> [violated policies] map — including the empty
            # one (hysteresis streaks reset on clean cycles) and even when
            # label patching fails (the violations are already final; a
            # patch-failure window must not freeze the drift detector's
            # consecutive-cycle accounting)
            enforcer.publish_violations(STRATEGY_TYPE, violations)
        trace.COUNTERS.inc(
            "pas_strategy_enforcements_total", labels={"strategy": STRATEGY_TYPE}
        )
        return total

    def cleanup(self, enforcer: core.MetricEnforcer, policy_name: str) -> None:
        """Remove the violation label from labeled nodes when the policy is
        deleted (enforce.go:28-52)."""
        try:
            nodes = enforcer.kube_client.list_nodes(
                label_selector=f"{policy_name}=violating"
            )
        except Exception as exc:
            klog.v(2).info_s(f"cannot list nodes: {exc}", component="controller")
            raise
        for node in nodes:
            payload = []
            if policy_name in node.get_labels():
                payload.append(
                    {"op": "remove", "path": "/metadata/labels/" + policy_name}
                )
            try:
                self._patch_node(node.name, enforcer, payload)
            except Exception as exc:
                klog.v(2).info_s(str(exc), component="controller")
        klog.v(2).info_s(
            f"Remove the node label on policy {policy_name} deletion",
            component="controller",
        )

    def _patch_node(
        self, node_name: str, enforcer: core.MetricEnforcer, payload: List[Dict]
    ) -> None:
        enforcer.kube_client.patch_node(node_name, payload)

    def _nodes_needing_labels(
        self, enforcer: core.MetricEnforcer, violations: Dict[str, List[str]]
    ):
        """Only the nodes whose label state can change this cycle: any
        node carrying a registered policy's label (the remove/re-add-
        "null" dance, enforce.go:118-132) plus the violating nodes
        themselves.  The reference lists EVERY node each cycle; at 100k
        nodes that is a full-cluster copy per enforcement pass to build
        payloads that are empty on all but a handful.  A label-exists
        selector asks the API server for exactly the candidate set, and
        the final label state is identical — a node matching neither
        list got an empty payload (a no-op patch) before."""
        candidates: Dict[str, object] = {}
        for policy_name in self._all_policies(enforcer):
            for node in enforcer.kube_client.list_nodes(
                label_selector=policy_name
            ):
                candidates[node.name] = node
        for name in violations:
            if name not in candidates:
                candidates[name] = _BareNode(name)
        return list(candidates.values())

    def _all_policies(self, enforcer: core.MetricEnforcer) -> Dict[str, None]:
        return {
            strat.get_policy_name(): None
            for strat in enforcer.registered_strategies.get(
                STRATEGY_TYPE, {}
            ).values()
        }

    def _node_status_for_strategy(
        self, enforcer: core.MetricEnforcer, cache
    ) -> Dict[str, List[str]]:
        """node -> [policy names violated] over every registered deschedule
        strategy (enforce.go:154-164)."""
        violations: Dict[str, List[str]] = {}
        mirror = getattr(enforcer, "mirror", None)
        for strat in list(
            enforcer.registered_strategies.get(STRATEGY_TYPE, {}).values()
        ):
            klog.v(2).info_s(
                "Evaluating " + strat.get_policy_name(), component="controller"
            )
            nodes = None
            if mirror is not None and hasattr(strat, "violated_device"):
                nodes = strat.violated_device(mirror)
            if nodes is None:
                nodes = strat.violated(cache)
            for node in nodes:
                violations.setdefault(node, []).append(strat.get_policy_name())
        return violations

    def _update_node_labels(
        self,
        enforcer: core.MetricEnforcer,
        violations: Dict[str, List[str]],
        all_nodes,
    ) -> int:
        """Patch the candidate nodes: violating policies -> add
        ``=violating``; registered-but-not-violating policies whose
        label is present -> remove + re-add as "null"
        (enforce.go:99-151).  Empty payloads are skipped — a no-op
        patch costs an API round trip and changes nothing."""
        total_violations = 0
        label_errs = ""
        for node in all_nodes:
            payload: List[Dict] = []
            non_violated = self._all_policies(enforcer)
            violated_policies = ""
            for policy_name in violations.get(node.name, []):
                non_violated.pop(policy_name, None)
                payload.append(
                    {
                        "op": "add",
                        "path": "/metadata/labels/" + policy_name,
                        "value": "violating",
                    }
                )
                violated_policies += policy_name + ", "
            for policy_name in non_violated:
                if policy_name in node.get_labels():
                    payload.append(
                        {"op": "remove", "path": "/metadata/labels/" + policy_name}
                    )
                    payload.append(
                        {
                            "op": "add",
                            "path": "/metadata/labels/" + policy_name,
                            "value": "null",
                        }
                    )
            # the count is the node's ACTUAL violations; the old placement
            # inside the non-violated loop returned the number of
            # non-violating registered policies per node instead
            total_violations += len(violations.get(node.name, []))
            if not payload:
                # an empty JSON patch changes nothing: spare the API
                # server the round trip entirely
                continue
            try:
                self._patch_node(node.name, enforcer, payload)
            except Exception as exc:
                if not label_errs:
                    label_errs = "could not label: "
                klog.v(4).info_s(str(exc), component="controller")
                label_errs += f"{node.name}: [ {violated_policies} ]; "
            if violated_policies:
                klog.v(2).info_s(
                    f"Node {node.name} violating {violated_policies}",
                    component="controller",
                )
        if label_errs:
            raise RuntimeError(label_errs)
        return total_violations

    # -- identity ------------------------------------------------------------

    def strategy_type(self) -> str:
        return STRATEGY_TYPE

    def equals(self, other) -> bool:
        return isinstance(other, Strategy) and core.rules_equal(self, other)

    def get_policy_name(self) -> str:
        return self.policy_name

    def set_policy_name(self, name: str) -> None:
        self.policy_name = name
