"""The kube and custom-metrics APIs as the harness plays them: an in-memory
API server behind the method surface of the program's ``kube.client``, with
live watch streams.  It is the benchmark's own (the part of the program's
``testing/fake_kube.py`` that the served path calls was copied, so that an
edit to the program's test double moves no metric); of the program it takes
only the client interface's own types, ``Node``, ``Pod`` and the errors.

Like an API server, it never shares an object with a caller: what goes in
and what comes out is copied, as a client's decoding would copy it.

``PlayedTas`` answers every fetch of a metric with that metric's next
telemetry round and logs it; ``PlayedGas`` logs each pod's card annotation
as Bind sent it, the node it was bound to, and tells the churn about every
binding.
"""

from __future__ import annotations

import collections
import copy
import queue
import threading
import time

from platform_aware_scheduling_tpu.kube.client import KubeError, NotFoundError
from platform_aware_scheduling_tpu.kube.objects import Node, Pod

from generator import metric_round, node_names

STAMP = "2026-01-01T00:00:00Z"


class WatchHub:
    """Fan-out of watch events to subscriber queues."""

    def __init__(self):
        self.subscribers = []
        self.lock = threading.Lock()

    def publish(self, event_type: str, obj: dict) -> None:
        with self.lock:
            subscribers = list(self.subscribers)
        for q in subscribers:
            q.put((event_type, copy.deepcopy(obj)))

    def watch(self):
        q = queue.Queue()
        with self.lock:
            self.subscribers.append(q)
        try:
            while True:
                try:
                    yield q.get(timeout=0.1)
                except queue.Empty:
                    continue
        finally:
            with self.lock:
                self.subscribers.remove(q)


def apply_json_patch(obj: dict, patch: list) -> None:
    """RFC 6902 add / replace / remove on nested dict paths."""
    for op in patch:
        tokens = [t.replace("~1", "/").replace("~0", "~")
                  for t in op["path"].lstrip("/").split("/")]
        target = obj
        for token in tokens[:-1]:
            if target.get(token) is None:
                target[token] = {}
            target = target[token]
        if op["op"] in ("add", "replace"):
            target[tokens[-1]] = op.get("value")
        elif op["op"] == "remove":
            if tokens[-1] not in target:
                raise KubeError(f"json patch remove: path not found: {op['path']}")
            del target[tokens[-1]]
        else:
            raise KubeError(f"unsupported json patch op: {op['op']}")


class PlayedKube:
    """Nodes, pods and TASPolicies behind ``kube.client``'s methods."""

    def __init__(self):
        self.lock = threading.RLock()
        self.version = 0
        self.nodes, self.pods, self.policies = {}, {}, {}
        self.hubs = {kind: WatchHub() for kind in ("nodes", "pods", "taspolicies")}

    def _stamp(self, raw: dict) -> dict:
        self.version += 1
        raw.setdefault("metadata", {})["resourceVersion"] = str(self.version)
        return raw

    # -- what the harness does to the cluster --------------------------------

    def add_node(self, raw: dict) -> None:
        with self.lock:
            self.nodes[raw["metadata"]["name"]] = copy.deepcopy(self._stamp(raw))
        self.hubs["nodes"].publish("ADDED", raw)

    def add_pod(self, raw: dict) -> None:
        meta = raw["metadata"]
        with self.lock:
            self.pods[(meta["namespace"], meta["name"])] = copy.deepcopy(
                self._stamp(raw))
        self.hubs["pods"].publish("ADDED", raw)

    def delete_pod(self, namespace: str, name: str) -> None:
        with self.lock:
            raw = self.pods.pop((namespace, name), None)
        if raw is not None:
            self.hubs["pods"].publish("DELETED", raw)

    def create_taspolicy(self, policy: dict) -> None:
        meta = policy["metadata"]
        with self.lock:
            self.policies[(meta["namespace"], meta["name"])] = copy.deepcopy(
                self._stamp(policy))
        self.hubs["taspolicies"].publish("ADDED", policy)

    # -- what the program asks of the API ---------------------------------------

    def list_nodes(self, label_selector: str = None) -> list:
        """Every node, or those a selector of ``key=value`` and bare ``key``
        (exists) terms matches; only what is returned is copied."""
        want = {}
        for part in (label_selector or "").split(","):
            if part.strip():
                key, _, value = part.strip().partition("=")
                want[key] = value if "=" in part else None

        def matches(raw: dict) -> bool:
            labels = raw["metadata"].get("labels") or {}
            return all(key in labels if value is None else labels.get(key) == value
                       for key, value in want.items())

        with self.lock:
            return [Node(copy.deepcopy(raw)) for raw in self.nodes.values()
                    if matches(raw)]

    def get_node(self, name: str) -> Node:
        with self.lock:
            if name not in self.nodes:
                raise NotFoundError(f"node {name} not found", status=404)
            return Node(copy.deepcopy(self.nodes[name]))

    def patch_node(self, name: str, json_patch: list) -> Node:
        with self.lock:
            if name not in self.nodes:
                raise NotFoundError(f"node {name} not found", status=404)
            apply_json_patch(self.nodes[name], json_patch)
            snapshot = copy.deepcopy(self._stamp(self.nodes[name]))
        self.hubs["nodes"].publish("MODIFIED", snapshot)
        return Node(snapshot)

    def list_pods(self, namespace: str = None) -> list:
        with self.lock:
            return [Pod(copy.deepcopy(raw)) for (ns, _), raw in self.pods.items()
                    if namespace is None or ns == namespace]

    def get_pod(self, namespace: str, name: str) -> Pod:
        with self.lock:
            raw = self.pods.get((namespace, name))
            if raw is None:
                raise NotFoundError(f"pod {namespace}/{name} not found", status=404)
            return Pod(copy.deepcopy(raw))

    def update_pod(self, pod: Pod) -> Pod:
        with self.lock:
            key = (pod.namespace, pod.name)
            if key not in self.pods:
                raise NotFoundError(f"pod {key[0]}/{key[1]} not found", status=404)
            self.pods[key] = self._stamp(copy.deepcopy(pod.raw))
            snapshot = copy.deepcopy(self.pods[key])
        self.hubs["pods"].publish("MODIFIED", snapshot)
        return Pod(snapshot)

    def bind_pod(self, namespace: str, pod_name: str, pod_uid: str, node: str) -> None:
        with self.lock:
            key = (namespace, pod_name)
            if key not in self.pods:
                raise NotFoundError(f"pod {namespace}/{pod_name} not found", status=404)
            self.pods[key].setdefault("spec", {})["nodeName"] = node
            snapshot = copy.deepcopy(self.pods[key])
        self.hubs["pods"].publish("MODIFIED", snapshot)

    def list_taspolicies(self, namespace: str = None) -> dict:
        with self.lock:
            items = [copy.deepcopy(raw) for (ns, _), raw in self.policies.items()
                     if namespace is None or ns == namespace]
            return {"apiVersion": "telemetry.intel.com/v1alpha1",
                    "kind": "TASPolicyList",
                    "metadata": {"resourceVersion": str(self.version)},
                    "items": items}

    def get_taspolicy(self, namespace: str, name: str) -> dict:
        with self.lock:
            raw = self.policies.get((namespace, name))
            if raw is None:
                raise NotFoundError(f"taspolicy {namespace}/{name} not found", status=404)
            return copy.deepcopy(raw)

    def watch_nodes(self, **_kw):
        return self.hubs["nodes"].watch()

    def watch_pods(self, **_kw):
        return self.hubs["pods"].watch()

    def watch_taspolicies(self, namespace: str = None, **_kw):
        return self.hubs["taspolicies"].watch()

    def get_node_custom_metric(self, metric_name: str) -> dict:
        return {"apiVersion": "custom.metrics.k8s.io/v1beta2",
                "kind": "MetricValueList", "metadata": {}, "items": []}


class PlayedTas(PlayedKube):
    """The custom-metrics API is alive: every fetch of a metric is answered
    with that metric's current round, and the next fetch gets the next one —
    so every sync pass does full refresh, publish and warm work.
    ``fetches`` logs (time answered, metric, round)."""

    def __init__(self, config: dict, seed: int):
        super().__init__()
        self.config, self.seed = config, seed
        self.names = node_names(config["node_prefix"], config["nodes"])
        self.metrics = list(config["metrics"])
        self.fetches = []
        self.next_round = {metric: 0 for metric in self.metrics}
        self.round_lock = threading.Lock()

    def get_node_custom_metric(self, metric_name: str) -> dict:
        with self.round_lock:
            round_index = self.next_round[metric_name]
            self.next_round[metric_name] = round_index + 1
        column = metric_round(
            self.seed, round_index, self.metrics.index(metric_name),
            self.config["nodes"], self.config["value_step"])
        items = [
            {"describedObject": {"kind": "Node", "name": name, "apiVersion": "/v1"},
             "metric": {"name": metric_name}, "timestamp": STAMP,
             "value": str(value)}
            for name, value in zip(self.names, column.tolist())
        ]
        self.fetches.append((time.monotonic(), metric_name, round_index))
        return {"apiVersion": "custom.metrics.k8s.io/v1beta2",
                "kind": "MetricValueList", "metadata": {}, "items": items}


class PlayedGas(PlayedKube):
    """Logs what the comparison needs of the API's writes: each pod's card
    annotation as Bind sent it and the node it was bound to; ``bound`` hands
    every binding to the churn."""

    def __init__(self):
        super().__init__()
        self.annotations = {}
        self.bound_to = {}
        self.bound = collections.deque()
        self.bound_event = threading.Event()

    def update_pod(self, pod: Pod) -> Pod:
        out = super().update_pod(pod)
        note = pod.annotations.get("gas-container-cards")
        if note is not None:
            self.annotations[pod.name] = note
        return out

    def bind_pod(self, namespace: str, pod_name: str, pod_uid: str, node: str) -> None:
        super().bind_pod(namespace, pod_name, pod_uid, node)
        self.bound_to[pod_name] = node
        self.bound.append(pod_name)
        self.bound_event.set()
