"""In-memory fake Kubernetes API server.

Implements the same method surface as ``kube.client.KubeClient`` over
dictionaries, with live watch streams, JSON-patch support, optimistic
conflict injection, and a custom-metrics backend — the functional equivalent
of client-go's ``fake.NewSimpleClientset`` plus the cmfake the reference's
tests use (reference pkg/metrics/client_test.go:28-55,
pkg/gpuscheduler/node_resource_cache_test.go:23-44).
"""

# pascheck: allow-file[locks] -- the fake IS the store: deep-copying every object under its lock is its consistency contract (callers must never alias internal state), and test-sized objects make the O(N) cost irrelevant

from __future__ import annotations

import copy
import json
import queue
import threading
import time
from datetime import datetime, timezone
from typing import Any, Dict, Iterator, List, Optional, Tuple

from platform_aware_scheduling_tpu.kube.client import (
    ConflictError,
    KubeError,
    NotFoundError,
)
from platform_aware_scheduling_tpu.kube.objects import Node, Pod
from platform_aware_scheduling_tpu.utils import labels as shared_labels


def mesh_coord_labels(row: int, col: int) -> Dict[str, str]:
    """The node labels carrying one mesh coordinate (the production
    cluster's ``pas-tpu-coord``), synthesized for hermetic gang tests
    and benchmarks — no real cluster labels needed (docs/gang.md)."""
    return {
        shared_labels.TPU_COORD_LABEL: shared_labels.format_coord(row, col)
    }


def _unescape_pointer(token: str) -> str:
    return token.replace("~1", "/").replace("~0", "~")


def apply_json_patch(obj: Dict[str, Any], patch: List[Dict[str, Any]]) -> None:
    """Minimal RFC-6902 apply: add/remove/replace on nested dict paths."""
    for op in patch:
        tokens = [_unescape_pointer(t) for t in op["path"].lstrip("/").split("/")]
        target = obj
        for token in tokens[:-1]:
            if token not in target or target[token] is None:
                target[token] = {}
            target = target[token]
        leaf = tokens[-1]
        kind = op["op"]
        if kind in ("add", "replace"):
            target[leaf] = op.get("value")
        elif kind == "remove":
            if leaf not in target:
                raise KubeError(f"json patch remove: path not found: {op['path']}")
            del target[leaf]
        else:
            raise KubeError(f"unsupported json patch op: {kind}")


class _WatchHub:
    """Fan-out of watch events to subscriber queues."""

    def __init__(self):
        self._subscribers: List[queue.Queue] = []
        self._lock = threading.Lock()

    def subscribe(self) -> queue.Queue:
        q: queue.Queue = queue.Queue()
        with self._lock:
            self._subscribers.append(q)
        return q

    def unsubscribe(self, q: queue.Queue) -> None:
        with self._lock:
            if q in self._subscribers:
                self._subscribers.remove(q)

    def publish(self, event_type: str, obj: Dict[str, Any]) -> None:
        with self._lock:
            subs = list(self._subscribers)
        for q in subs:
            q.put((event_type, copy.deepcopy(obj)))


class FakeKubeClient:
    """Drop-in test double for ``kube.client.KubeClient``."""

    def __init__(self):
        self._lock = threading.RLock()
        self._rv = 0
        self._nodes: Dict[str, Dict[str, Any]] = {}
        self._pods: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._policies: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._metrics: Dict[str, Dict[str, Dict[str, Any]]] = {}  # metric -> node -> item
        # coordination.k8s.io Lease + ConfigMap stores (HA control plane,
        # docs/robustness.md "HA & leader election"): both enforce
        # optimistic concurrency — an update carrying a stale
        # resourceVersion answers 409, exactly the conflict the real API
        # server raises, so leader-election races resolve the same way
        # against the fake as against kube
        self._leases: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._configmaps: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._hubs = {"nodes": _WatchHub(), "pods": _WatchHub(), "taspolicies": _WatchHub()}
        self.bindings: List[Dict[str, Any]] = []
        self.node_patches: List[Tuple[str, List[Dict[str, Any]]]] = []
        self.evictions: List[Dict[str, Any]] = []
        # PDB-style eviction guard: (namespace, name) keys whose eviction
        # the fake refuses with 409 (the API server's disruption-budget
        # rejection), recorded but never applied
        self.evict_denials: set = set()
        # fault injection
        self.update_pod_conflicts_remaining = 0
        self.fail_next_bind: Optional[Exception] = None
        self.fail_metric_fetch: Optional[Exception] = None
        self.fail_next_evict: Optional[Exception] = None
        # scripted deterministic faults (testing/faults.py): when a
        # FaultPlan is attached, every verb consults it by name before
        # touching the store; latencies advance fault_clock, never the
        # wall clock
        self.fault_plan = None
        self.fault_clock = None

    def _fault(self, verb: str) -> None:
        if self.fault_plan is not None:
            self.fault_plan.apply(verb, self.fault_clock)

    def _next_rv(self) -> str:
        self._rv += 1
        return str(self._rv)

    # -- seeding helpers -----------------------------------------------------

    def add_node(self, node) -> None:
        raw = node.raw if isinstance(node, Node) else node
        with self._lock:
            raw.setdefault("metadata", {})["resourceVersion"] = self._next_rv()
            self._nodes[raw["metadata"]["name"]] = copy.deepcopy(raw)
        self._hubs["nodes"].publish("ADDED", raw)

    def add_pod(self, pod) -> None:
        raw = pod.raw if isinstance(pod, Pod) else pod
        meta = raw.setdefault("metadata", {})
        meta.setdefault("namespace", "default")
        with self._lock:
            meta["resourceVersion"] = self._next_rv()
            self._pods[(meta["namespace"], meta["name"])] = copy.deepcopy(raw)
        self._hubs["pods"].publish("ADDED", raw)

    def delete_pod(self, namespace: str, name: str) -> None:
        with self._lock:
            raw = self._pods.pop((namespace, name), None)
        if raw is not None:
            self._hubs["pods"].publish("DELETED", raw)

    def delete_node(self, name: str) -> None:
        with self._lock:
            raw = self._nodes.pop(name, None)
        if raw is not None:
            self._hubs["nodes"].publish("DELETED", raw)

    def add_mesh(
        self,
        rows: int,
        cols: int,
        prefix: str = "mesh",
        extra_labels: Optional[Dict[str, str]] = None,
    ) -> List[str]:
        """Seed an ``rows x cols`` TPU node mesh: one node per cell
        carrying its ``pas-tpu-coord`` label (row-major names
        ``{prefix}-{row}-{col}``).  Returns the node names in row-major
        order — the hermetic substrate of tests/test_gang.py and
        benchmarks/gang_load.py."""
        names: List[str] = []
        for row in range(rows):
            for col in range(cols):
                name = f"{prefix}-{row}-{col}"
                labels = dict(mesh_coord_labels(row, col))
                if extra_labels:
                    labels.update(extra_labels)
                self.add_node(
                    {
                        "metadata": {"name": name, "labels": labels},
                        "status": {"allocatable": {}},
                    }
                )
                names.append(name)
        return names

    # -- nodes ---------------------------------------------------------------

    def list_nodes(self, label_selector: Optional[str] = None) -> List[Node]:
        self._fault("list_nodes")
        # selector pushdown, like the real API server: match on the raw
        # labels FIRST and deepcopy only the hits — a label-filtered
        # list over 100k nodes copies a handful, not the cluster.
        # ``k=v`` matches equality; a bare ``k`` is the exists matcher.
        want: Dict[str, Optional[str]] = {}
        if label_selector:
            for part in label_selector.split(","):
                part = part.strip()
                if not part:
                    continue
                if "=" in part:
                    key, value = part.split("=", 1)
                    want[key] = value
                else:
                    want[part] = None
        with self._lock:
            if not want:
                return [
                    Node(copy.deepcopy(raw)) for raw in self._nodes.values()
                ]
            matched = []
            if len(want) == 1:
                # single-term selector (the enforcement path's exists
                # query) gets a branch-free scan: one dict dig per node
                (key, value), = want.items()
                for raw in self._nodes.values():
                    meta = raw.get("metadata")
                    labels = meta.get("labels") if meta is not None else None
                    if not labels:
                        continue
                    if value is None:
                        if key in labels:
                            matched.append(Node(copy.deepcopy(raw)))
                    elif labels.get(key) == value:
                        matched.append(Node(copy.deepcopy(raw)))
                return matched
            for raw in self._nodes.values():
                labels = (raw.get("metadata") or {}).get("labels") or {}
                if all(
                    (key in labels if value is None
                     else labels.get(key) == value)
                    for key, value in want.items()
                ):
                    matched.append(Node(copy.deepcopy(raw)))
            return matched

    def get_node(self, name: str) -> Node:
        self._fault("get_node")
        with self._lock:
            if name not in self._nodes:
                raise NotFoundError(f"node {name} not found", status=404)
            return Node(copy.deepcopy(self._nodes[name]))

    def patch_node(self, name: str, json_patch: List[Dict[str, Any]]) -> Node:
        self._fault("patch_node")
        with self._lock:
            if name not in self._nodes:
                raise NotFoundError(f"node {name} not found", status=404)
            raw = self._nodes[name]
            apply_json_patch(raw, json_patch)
            raw["metadata"]["resourceVersion"] = self._next_rv()
            self.node_patches.append((name, copy.deepcopy(json_patch)))
            snapshot = copy.deepcopy(raw)
        self._hubs["nodes"].publish("MODIFIED", snapshot)
        return Node(snapshot)

    # -- pods ----------------------------------------------------------------

    def list_pods(self, namespace: Optional[str] = None) -> List[Pod]:
        self._fault("list_pods")
        with self._lock:
            return [
                Pod(copy.deepcopy(raw))
                for (ns, _), raw in self._pods.items()
                if namespace is None or ns == namespace
            ]

    def get_pod(self, namespace: str, name: str) -> Pod:
        self._fault("get_pod")
        with self._lock:
            raw = self._pods.get((namespace, name))
            if raw is None:
                raise NotFoundError(f"pod {namespace}/{name} not found", status=404)
            return Pod(copy.deepcopy(raw))

    def update_pod(self, pod: Pod) -> Pod:
        self._fault("update_pod")
        with self._lock:
            key = (pod.namespace, pod.name)
            if key not in self._pods:
                raise NotFoundError(f"pod {pod.namespace}/{pod.name} not found", status=404)
            if self.update_pod_conflicts_remaining > 0:
                self.update_pod_conflicts_remaining -= 1
                raise ConflictError(
                    "Operation cannot be fulfilled: please apply your changes to "
                    "the latest version and try again",
                    status=409,
                )
            raw = copy.deepcopy(pod.raw)
            raw.setdefault("metadata", {})["resourceVersion"] = self._next_rv()
            self._pods[key] = raw
            snapshot = copy.deepcopy(raw)
        self._hubs["pods"].publish("MODIFIED", snapshot)
        return Pod(snapshot)

    def bind_pod(self, namespace: str, pod_name: str, pod_uid: str, node: str) -> None:
        if self.fail_next_bind is not None:
            exc, self.fail_next_bind = self.fail_next_bind, None
            raise exc
        with self._lock:
            key = (namespace, pod_name)
            if key not in self._pods:
                raise NotFoundError(f"pod {namespace}/{pod_name} not found", status=404)
            self._pods[key].setdefault("spec", {})["nodeName"] = node
            self.bindings.append(
                {"namespace": namespace, "pod": pod_name, "uid": pod_uid, "node": node}
            )
            snapshot = copy.deepcopy(self._pods[key])
        self._hubs["pods"].publish("MODIFIED", snapshot)

    def evict_pod(
        self,
        namespace: str,
        pod_name: str,
        grace_period_seconds: Optional[int] = None,
    ) -> None:
        """pods/eviction subresource: a denied key answers 409 (the
        PDB-style guard); success records the eviction and deletes the
        pod (DELETED published to pod watchers)."""
        if self.fail_next_evict is not None:
            exc, self.fail_next_evict = self.fail_next_evict, None
            raise exc
        # plan-driven faults like every other verb: a scenario can break
        # the eviction API for a window (twin control head-to-heads drive
        # the eviction-safety burn through this)
        self._fault("evict_pod")
        key = (namespace, pod_name)
        with self._lock:
            if key not in self._pods:
                raise NotFoundError(
                    f"pod {namespace}/{pod_name} not found", status=404
                )
            if key in self.evict_denials:
                raise ConflictError(
                    "Cannot evict pod as it would violate the pod's "
                    "disruption budget.",
                    status=409,
                )
            raw = self._pods.pop(key)
            self.evictions.append(
                {
                    "namespace": namespace,
                    "pod": pod_name,
                    "node": (raw.get("spec") or {}).get("nodeName", ""),
                    "grace_period_seconds": grace_period_seconds,
                }
            )
        self._hubs["pods"].publish("DELETED", raw)

    # -- TASPolicy CRD -------------------------------------------------------

    def list_taspolicies(self, namespace: Optional[str] = None) -> Dict[str, Any]:
        self._fault("list_taspolicies")
        with self._lock:
            items = [
                copy.deepcopy(raw)
                for (ns, _), raw in self._policies.items()
                if namespace is None or ns == namespace
            ]
            return {
                "apiVersion": "telemetry.intel.com/v1alpha1",
                "kind": "TASPolicyList",
                "metadata": {"resourceVersion": str(self._rv)},
                "items": items,
            }

    def get_taspolicy(self, namespace: str, name: str) -> Dict[str, Any]:
        with self._lock:
            raw = self._policies.get((namespace, name))
            if raw is None:
                raise NotFoundError(f"taspolicy {namespace}/{name} not found", status=404)
            return copy.deepcopy(raw)

    def create_taspolicy(self, policy: Dict[str, Any]) -> Dict[str, Any]:
        meta = policy.setdefault("metadata", {})
        meta.setdefault("namespace", "default")
        with self._lock:
            meta["resourceVersion"] = self._next_rv()
            self._policies[(meta["namespace"], meta["name"])] = copy.deepcopy(policy)
        self._hubs["taspolicies"].publish("ADDED", policy)
        return copy.deepcopy(policy)

    def update_taspolicy(self, policy: Dict[str, Any]) -> Dict[str, Any]:
        meta = policy.setdefault("metadata", {})
        meta.setdefault("namespace", "default")
        key = (meta["namespace"], meta["name"])
        with self._lock:
            if key not in self._policies:
                raise NotFoundError(f"taspolicy {key} not found", status=404)
            meta["resourceVersion"] = self._next_rv()
            self._policies[key] = copy.deepcopy(policy)
        self._hubs["taspolicies"].publish("MODIFIED", policy)
        return copy.deepcopy(policy)

    def delete_taspolicy(self, namespace: str, name: str) -> None:
        with self._lock:
            raw = self._policies.pop((namespace, name), None)
        if raw is None:
            raise NotFoundError(f"taspolicy {namespace}/{name} not found", status=404)
        self._hubs["taspolicies"].publish("DELETED", raw)

    # -- coordination.k8s.io leases + configmaps ------------------------------
    #
    # Optimistic-concurrency object stores shared by leader election
    # (kube/lease.py) and the gang journal (gang/journal.py).  The
    # semantics under test: create of an existing object and update with
    # a stale resourceVersion both answer 409, so exactly one of N
    # concurrent acquirers can win any given transition.

    def _oc_get(self, store, kind: str, namespace: str, name: str):
        with self._lock:
            raw = store.get((namespace, name))
            if raw is None:
                raise NotFoundError(
                    f"{kind} {namespace}/{name} not found", status=404
                )
            return copy.deepcopy(raw)

    def _oc_create(self, store, kind: str, obj: Dict[str, Any]):
        meta = obj.setdefault("metadata", {})
        meta.setdefault("namespace", "default")
        key = (meta["namespace"], meta["name"])
        with self._lock:
            if key in store:
                raise ConflictError(
                    f"{kind} {key[0]}/{key[1]} already exists", status=409
                )
            meta["resourceVersion"] = self._next_rv()
            store[key] = copy.deepcopy(obj)
        return copy.deepcopy(obj)

    def _oc_update(self, store, kind: str, obj: Dict[str, Any]):
        meta = obj.setdefault("metadata", {})
        meta.setdefault("namespace", "default")
        key = (meta["namespace"], meta["name"])
        with self._lock:
            stored = store.get(key)
            if stored is None:
                raise NotFoundError(
                    f"{kind} {key[0]}/{key[1]} not found", status=404
                )
            if (
                meta.get("resourceVersion")
                != stored["metadata"]["resourceVersion"]
            ):
                raise ConflictError(
                    "Operation cannot be fulfilled: please apply your "
                    "changes to the latest version and try again",
                    status=409,
                )
            meta["resourceVersion"] = self._next_rv()
            store[key] = copy.deepcopy(obj)
        return copy.deepcopy(obj)

    def get_lease(self, namespace: str, name: str) -> Dict[str, Any]:
        self._fault("get_lease")
        return self._oc_get(self._leases, "lease", namespace, name)

    def create_lease(self, lease: Dict[str, Any]) -> Dict[str, Any]:
        self._fault("create_lease")
        return self._oc_create(self._leases, "lease", lease)

    def update_lease(self, lease: Dict[str, Any]) -> Dict[str, Any]:
        self._fault("update_lease")
        return self._oc_update(self._leases, "lease", lease)

    def get_configmap(self, namespace: str, name: str) -> Dict[str, Any]:
        self._fault("get_configmap")
        return self._oc_get(self._configmaps, "configmap", namespace, name)

    def create_configmap(self, configmap: Dict[str, Any]) -> Dict[str, Any]:
        self._fault("create_configmap")
        return self._oc_create(self._configmaps, "configmap", configmap)

    def update_configmap(self, configmap: Dict[str, Any]) -> Dict[str, Any]:
        self._fault("update_configmap")
        return self._oc_update(self._configmaps, "configmap", configmap)

    # -- watches -------------------------------------------------------------

    def _watch(self, hub_name: str, stop_sentinel_timeout: float = 0.1):
        hub = self._hubs[hub_name]
        q = hub.subscribe()

        def iterator() -> Iterator[Tuple[str, Dict[str, Any]]]:
            try:
                while True:
                    try:
                        yield q.get(timeout=stop_sentinel_timeout)
                    except queue.Empty:
                        continue
            finally:
                hub.unsubscribe(q)

        return iterator()

    def watch_nodes(self, **kw):
        return self._watch("nodes")

    def watch_pods(self, **kw):
        return self._watch("pods")

    def watch_taspolicies(self, namespace: Optional[str] = None, **kw):
        return self._watch("taspolicies")

    # -- custom metrics ------------------------------------------------------

    def set_node_metric(
        self,
        metric_name: str,
        node_name: str,
        value: str,
        window_seconds: Optional[int] = None,
        timestamp: Optional[str] = None,
    ) -> None:
        item = {
            "describedObject": {"kind": "Node", "name": node_name, "apiVersion": "/v1"},
            "metric": {"name": metric_name},
            "timestamp": timestamp
            or datetime.now(timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),  # pascheck: allow[clock] -- mimics the API server's server-side default; tests pass an explicit timestamp when they care
            "value": value,
        }
        if window_seconds is not None:
            item["windowSeconds"] = window_seconds
        with self._lock:
            self._metrics.setdefault(metric_name, {})[node_name] = item

    def replace_node_metric(
        self, metric_name: str, values: Dict[str, str], timestamp: str
    ) -> None:
        """Swap a metric's whole per-node map in ONE step: a concurrent
        ``get_node_custom_metric`` sees the old map or the new one, never
        a half-written refresh (chip_smoke.py rewrites 10k nodes per
        metric while the service's own refresh loop is polling)."""
        items = {
            node_name: {
                "describedObject": {"kind": "Node", "name": node_name, "apiVersion": "/v1"},
                "metric": {"name": metric_name},
                "timestamp": timestamp,
                "value": value,
            }
            for node_name, value in values.items()
        }
        with self._lock:
            self._metrics[metric_name] = items

    def clear_node_metric(self, metric_name: str, node_name: Optional[str] = None) -> None:
        with self._lock:
            if node_name is None:
                self._metrics.pop(metric_name, None)
            else:
                self._metrics.get(metric_name, {}).pop(node_name, None)

    def get_node_custom_metric(self, metric_name: str) -> Dict[str, Any]:
        if self.fail_metric_fetch is not None:
            raise self.fail_metric_fetch
        with self._lock:
            items = list(copy.deepcopy(list(self._metrics.get(metric_name, {}).values())))
        return {
            "apiVersion": "custom.metrics.k8s.io/v1beta2",
            "kind": "MetricValueList",
            "metadata": {},
            "items": items,
        }
