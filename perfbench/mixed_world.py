"""The seeded world of the ``alibaba-colo-4k`` deployment (a co-located
cluster after Alibaba's ``cluster-trace-v2018``: services and batch side by
side, in front of TAS with ``--batchPlanner``): what its driver, its
assembler and its plain reference all derive from the configuration and the
seed — the pod classes and each pod's own requests, the pods bound before
the window, and kube-scheduler's NodeResourcesFit for ONE pod (free pods,
cpu and memory each cover that pod's requests).  What the deployment shares
with ``batch-10k`` (nodes, policies, init-pod placement, where bindings go)
is ``batch_world``'s, imported.  NumPy and the standard library; never JAX,
never the program.
"""

from __future__ import annotations

import numpy as np

import batch_world
from batch_world import pod_raw as pod_of_requests
from generator import rng

STREAM_POD_CLASSES, STREAM_INIT_CLASSES = 33, 34
#: what Fit counts, in this order everywhere: pod slots, cpu, memory —
#: milli-units (one pod takes 1000 of ``pods``), in int64
RESOURCES = ("pods", "cpu", "memory")


def class_names(config: dict) -> list:
    return [c["name"] for c in config["pod_classes"]]


def demands(config: dict) -> np.ndarray:
    """int64 [classes, 3]: what a pod of each class asks of RESOURCES."""
    return np.array(
        [[1000] + [batch_world.milli(c["requests"][r]) for r in RESOURCES[1:]]
         for c in config["pod_classes"]], dtype=np.int64)


def allocatable(config: dict) -> np.ndarray:
    """int64 [3]: a node's allocatable of RESOURCES."""
    alloc = config["node_allocatable"]
    return np.array([int(alloc["pods"]) * 1000]
                    + [batch_world.milli(alloc[r]) for r in RESOURCES[1:]],
                    dtype=np.int64)


def pod_classes(config: dict, seed: int) -> np.ndarray:
    """Class index of each measured pod, in creation order: drawn from the
    seed by the classes' shares, independently of the pod's policy."""
    shares = np.array([c["share"] for c in config["pod_classes"]], dtype=float)
    return rng(seed, STREAM_POD_CLASSES).choice(
        len(shares), size=config["measure_pods"], p=shares / shares.sum())


def init_pod_classes(config: dict, seed: int) -> np.ndarray:
    """Class index of each pod bound before the warm-up: the configuration's
    ``init_pod_classes`` by their weights."""
    names = class_names(config)
    among = np.array([names.index(c["class"]) for c in config["init_pod_classes"]])
    weights = np.array([c["weight"] for c in config["init_pod_classes"]], dtype=float)
    return among[rng(seed, STREAM_INIT_CLASSES).choice(
        len(among), size=config["init_pods"], p=weights / weights.sum())]


def warm_class(config: dict, index: int) -> int:
    """The warm-up's pods take every class in turn."""
    return index % len(config["pod_classes"])


def class_of(config: dict, seed: int, name: str, tables: dict) -> int:
    """The class of the pod called ``name`` (``init-``, ``warm-`` or
    ``bench-`` and its number); ``tables`` caches the seed's draws."""
    kind, number = name.split("-")
    if kind == "warm":
        return warm_class(config, int(number))
    if kind not in tables:
        tables[kind] = (init_pod_classes if kind == "init" else pod_classes)(
            config, seed)
    return int(tables[kind][int(number)])


def initial_held(config: dict, seed: int) -> np.ndarray:
    """int64 [nodes, 3]: what the pods bound before the warm-up hold."""
    held = np.zeros((config["nodes"], len(RESOURCES)), dtype=np.int64)
    np.add.at(held, batch_world.init_pod_nodes(config, seed),
              demands(config)[init_pod_classes(config, seed)])
    return held


def pod_raw(config: dict, name: str, klass: int, policy: str = "",
            node: str = "") -> dict:
    """``batch_world.pod_raw`` with the requests of the pod's own class."""
    return pod_of_requests(
        {"pod_requests": config["pod_classes"][klass]["requests"]},
        name, policy, node)
