"""``make bench-gang``: gang scheduling A/B on a shared TPU mesh.

Two scenarios (docs/gang.md):

  * **deadlock A/B** — two competing gangs (each 8 pods needing a
    contiguous 2x4 slice) on one 4x4 mesh that fits both.  A simulated
    kube-scheduler admits pods one at a time through the REAL verbs
    (Filter -> Prioritize -> Bind), strictly interleaving the gangs.
    With ``--gang=on`` the first member of each gang atomically reserves
    a whole slice, so both gangs fully bind on disjoint slices — zero
    deadlock.  With ``--gang=off`` the stock metric ranking scatters the
    two gangs across each other's rows: every pod binds somewhere, but
    NEITHER gang's node set forms a valid 2x4 slice — the half-placed
    deadlock the reference cannot express (ROADMAP item 3).

  * **admission throughput at 10k nodes** — one 4x4 gang on a 100x100
    mesh: wall time of the reservation solve (the topology-feasibility
    kernel over 10k cells) and the per-member Filter admissions/s after
    it.

The harness is hermetic: FakeKubeClient.add_mesh synthesizes the
``pas-tpu-coord`` labels, the telemetry cache is seeded directly, and
the verbs are invoked in-process (this bench measures scheduling
semantics + solve cost, not HTTP framing — benchmarks/http_load.py owns
the wire).
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Tuple

from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.gang import GangTracker
from platform_aware_scheduling_tpu.ops import topology
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.utils import labels as shared_labels
from platform_aware_scheduling_tpu.utils.quantity import Quantity

POLICY = "gang-pol"


def _policy_obj():
    return {
        "metadata": {"name": POLICY, "namespace": "default"},
        "spec": {
            "strategies": {
                "scheduleonmetric": {
                    "rules": [
                        {"metricname": "mesh_metric",
                         "operator": "GreaterThan", "target": 0}
                    ]
                },
                "dontschedule": {
                    "rules": [
                        {"metricname": "mesh_metric",
                         "operator": "GreaterThan", "target": 10**9}
                    ]
                },
            }
        },
    }


def build_mesh_service(
    rows: int, cols: int, gang: bool, ttl_s: float = 30.0
) -> Tuple[MetricsExtender, FakeKubeClient, List[str]]:
    """(extender, fake kube, node names) over an ``rows x cols`` mesh
    with clean telemetry; ``gang`` wires the tracker (--gang=on)."""
    kube = FakeKubeClient()
    names = kube.add_mesh(rows, cols)
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    cache.write_policy(
        "default", POLICY, TASPolicy.from_obj(_policy_obj())
    )
    # metric values DESCENDING in row-major order: the stock ranking
    # walks the mesh cell by cell, so interleaved gangs grab alternating
    # cells — the half-placed scatter gang-off cannot avoid
    cache.write_metric(
        "mesh_metric",
        {
            name: NodeMetric(value=Quantity(len(names) - i))
            for i, name in enumerate(names)
        },
    )
    extender = MetricsExtender(cache, mirror=mirror, node_cache_capable=True)
    if gang:
        extender.gangs = GangTracker(
            nodes_provider=kube.list_nodes, ttl_s=ttl_s
        )
    return extender, kube, names


def _gang_pod_obj(name: str, group: str, size: int, topo: str) -> Dict:
    return {
        "metadata": {
            "name": name,
            "namespace": "default",
            "labels": {
                "telemetry-policy": POLICY,
                shared_labels.GROUP_LABEL: group,
                shared_labels.GANG_SIZE_LABEL: str(size),
                shared_labels.GANG_TOPOLOGY_LABEL: topo,
            },
        }
    }


def _post(extender: MetricsExtender, verb: str, obj: Dict):
    body = json.dumps(obj).encode()
    request = HTTPRequest(
        method="POST",
        path=f"/scheduler/{verb}",
        headers={"Content-Type": "application/json"},
        body=body,
    )
    return getattr(extender, verb)(request)


def _filter_passing(extender, pod_obj, candidates: List[str]) -> List[str]:
    response = _post(
        extender, "filter", {"Pod": pod_obj, "NodeNames": candidates}
    )
    if response.status != 200:
        return []
    obj = json.loads(response.body)
    return list(obj.get("NodeNames") or [])


def _prioritize_top(extender, pod_obj, candidates: List[str]) -> Optional[str]:
    response = _post(
        extender, "prioritize", {"Pod": pod_obj, "NodeNames": candidates}
    )
    ranked = json.loads(response.body or b"[]") or []
    if not ranked:
        return candidates[0] if candidates else None
    best = max(ranked, key=lambda e: e["Score"])
    return best["Host"]


def _bind(extender, pod_obj, node: str) -> None:
    _post(
        extender,
        "bind",
        {
            "PodName": pod_obj["metadata"]["name"],
            "PodNamespace": "default",
            "PodUID": "uid",
            "Node": node,
        },
    )


def _forms_slice(
    nodes: List, bound: List[str], rows: int, cols: int
) -> bool:
    """Does ``bound`` form a contiguous ``rows x cols`` sub-mesh?  The
    deadlock verdict, checked with the host topology mirror."""
    mesh = topology.MeshView(nodes)
    mask = mesh.free_mask(bound)
    if int(mask.sum()) != rows * cols:
        return False
    for h, w in {(rows, cols), (cols, rows)}:
        feas = topology.topology_feasibility_host(mask, h, w)
        if feas.anchor_ok.any():
            return True
    return False


def run_deadlock_ab(max_rounds: int = 12) -> Dict:
    """The acceptance scenario: gang-on admits both gangs on disjoint
    slices; gang-off scatters them (neither forms a slice)."""
    out: Dict = {"mesh": "4x4", "gang_size": 8, "topology": "2x4"}
    for mode, gang_on in (("gang_on", True), ("gang_off", False)):
        extender, kube, names = build_mesh_service(4, 4, gang=gang_on)
        pods = []
        for i in range(8):  # strict interleave: a0 b0 a1 b1 ...
            pods.append(_gang_pod_obj(f"a-{i}", "gang-a", 8, "2x4"))
            pods.append(_gang_pod_obj(f"b-{i}", "gang-b", 8, "2x4"))
        available = list(names)
        bound: Dict[str, List[str]] = {"gang-a": [], "gang-b": []}
        pending = list(pods)
        rounds = 0
        while pending and rounds < max_rounds:
            rounds += 1
            progressed = []
            for pod_obj in pending:
                passing = _filter_passing(extender, pod_obj, available)
                if not passing:
                    continue
                node = _prioritize_top(extender, pod_obj, passing)
                if node is None:
                    continue
                _bind(extender, pod_obj, node)
                available.remove(node)
                group = pod_obj["metadata"]["labels"][
                    shared_labels.GROUP_LABEL
                ]
                bound[group].append(node)
                progressed.append(pod_obj)
            if not progressed:
                break
            pending = [p for p in pending if p not in progressed]
        cluster_nodes = kube.list_nodes()
        slices_ok = {
            group: _forms_slice(cluster_nodes, nodes_bound, 2, 4)
            for group, nodes_bound in bound.items()
        }
        admitted = sum(
            1
            for group in bound
            if len(bound[group]) == 8 and slices_ok[group]
        )
        out[mode] = {
            "rounds": rounds,
            "bound_pods": sum(len(v) for v in bound.values()),
            "unplaced_pods": len(pending),
            "gangs_admitted_as_valid_slice": admitted,
            "deadlock": admitted < 2,
        }
    return out


def run_throughput(rows: int = 100, cols: int = 100) -> Dict:
    """Reservation-solve latency + member-admission rate at 10k nodes."""
    extender, _kube, names = build_mesh_service(rows, cols, gang=True)
    size = 16
    pods = [
        _gang_pod_obj(f"t-{i}", "gang-t", size, "4x4") for i in range(size)
    ]
    # warm the kernel's compile for this mesh shape so reserve_ms
    # reports the steady-state solve, not the first-trace XLA compile
    import numpy as np

    topology.topology_feasibility_device(np.zeros((rows, cols), bool), 4, 4)
    t0 = time.perf_counter()
    first_passing = _filter_passing(extender, pods[0], names)
    reserve_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    for pod_obj in pods[1:]:
        _filter_passing(extender, pod_obj, names)
    member_s = time.perf_counter() - t1
    return {
        "num_nodes": rows * cols,
        "reserve_ms": round(reserve_s * 1000, 3),
        "member_filter_ms_mean": round(member_s * 1000 / (size - 1), 3),
        "admissions_per_s": round((size - 1) / member_s, 1)
        if member_s > 0
        else None,
        "slice_nodes": len(first_passing),
    }


def run() -> Dict:
    result = run_deadlock_ab()
    result["throughput"] = run_throughput()
    return result


def main() -> int:
    from benchmarks import children

    identity = children.hold_chip("benchmarks.gang_load")
    result = run()
    result["platform"] = identity["platform"]
    print(json.dumps(result, indent=2))
    on, off = result["gang_on"], result["gang_off"]
    ok = not on["deadlock"] and off["deadlock"]
    print(
        f"gang_load: gang-on admitted "
        f"{on['gangs_admitted_as_valid_slice']}/2 gangs (deadlock="
        f"{on['deadlock']}), gang-off admitted "
        f"{off['gangs_admitted_as_valid_slice']}/2 (deadlock="
        f"{off['deadlock']}); reserve at 10k nodes "
        f"{result['throughput']['reserve_ms']} ms"
    )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
