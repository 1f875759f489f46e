"""The seeded world of the ``batch-10k`` deployment (kube ``scheduler_perf``
SchedulingBasic 5000Nodes_10000Pods in front of TAS with ``--batchPlanner``):
what its driver, its assembler and its plain reference all derive from the
configuration and the seed — the nodes' allocatable, the pods' requests and
policies, the pods bound before the window, kube-scheduler's own Fit, and
where the played kube API takes bindings.  NumPy and the standard library;
never JAX, never the program.
"""

from __future__ import annotations

import numpy as np

from generator import rng

STREAM_INIT_NODES, STREAM_POD_POLICIES = 31, 32
BINARY = {"Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30, "Ti": 1 << 40}
FIT_RESOURCES = ("cpu", "memory")


def milli(quantity: str) -> int:
    """A Kubernetes resource quantity in milli-units: ``4``, ``100m``,
    ``500Mi``, ``32Gi`` — the forms the source's templates use."""
    text = str(quantity)
    if text.endswith("m"):
        return int(text[:-1])
    for suffix, factor in BINARY.items():
        if text.endswith(suffix):
            return int(text[: -len(suffix)]) * factor * 1000
    return int(text) * 1000


def fit_per_node(config: dict) -> int:
    """How many of the configuration's pods kube-scheduler's NodeResourcesFit
    lets onto one of its nodes: the least, over ``pods``, ``cpu`` and
    ``memory``, of allocatable over request, floored."""
    alloc, asked = config["node_allocatable"], config["pod_requests"]
    return min([int(alloc["pods"])] + [
        milli(alloc[r]) // milli(asked[r]) for r in FIT_RESOURCES])


def init_pod_nodes(config: dict, seed: int) -> np.ndarray:
    """Node index of each pod bound before the warm-up: uniform over nodes."""
    return rng(seed, STREAM_INIT_NODES).integers(
        0, config["nodes"], size=config["init_pods"])


def pod_policies(config: dict, seed: int) -> np.ndarray:
    """Policy index of each measured pod: the configuration's policies at
    equal weights."""
    return rng(seed, STREAM_POD_POLICIES).integers(
        0, len(config["policies"]), size=config["measure_pods"])


def node_raw(config: dict, name: str) -> dict:
    """A node of the source's ``node-default.yaml``: its allocatable."""
    resources = dict(config["node_allocatable"])
    return {"metadata": {"name": name, "labels": {}},
            "status": {"allocatable": resources, "capacity": resources,
                       "phase": "Running"}}


def pod_raw(config: dict, name: str, policy: str = "", node: str = "") -> dict:
    """A pod of the source's ``pod-default.yaml`` (pause, the configuration's
    requests); ``policy`` is its ``telemetry-policy`` label, ``node`` where it
    is bound already."""
    labels = {"app": "bench"}
    if policy:
        labels["telemetry-policy"] = policy
    raw = {
        "metadata": {"name": name, "namespace": "default",
                     "uid": f"uid-{name}", "labels": labels},
        "spec": {"schedulerName": "default-scheduler", "containers": [{
            "name": "pause", "image": "registry.k8s.io/pause:3.9",
            "resources": {"requests": dict(config["pod_requests"])},
        }]},
        "status": {"phase": "Running" if node else "Pending"},
    }
    if node:
        raw["spec"]["nodeName"] = node
    return raw


def init_pod_name(index: int) -> str:
    return f"init-{index:05d}"


def bind_address(parent_pid: int) -> str:
    """Where the played kube API takes ``pods/binding`` writes: an abstract
    Unix socket named after the process that holds the chip, which the
    generator — its child — knows as its parent."""
    return f"\0perfbench-kube-api-{parent_pid}"


def binding_path(pod: str) -> str:
    return f"/api/v1/namespaces/default/pods/{pod}/binding"
