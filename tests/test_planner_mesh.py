"""The batch planner's solve over a mesh (``--batchPlannerDevices=n``), on
four of the conftest's eight CPU devices: the plan it publishes is the
one-device planner's and the plain reference's (``perfbench/plan_reference.py``:
NumPy, nothing of the program), pod for pod; with one device nothing of the
mesh path is built, counted or compiled; and what cannot span the devices is
refused at start-up."""

import json
import os
import sys

import jax
import numpy as np
import pytest

from platform_aware_scheduling_tpu.cmd import tas as tas_main
from platform_aware_scheduling_tpu.kube.objects import object_key
from platform_aware_scheduling_tpu.models import batch_scheduler
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.parallel.sharded import (
    greedy_assign_collective_count,
)
from platform_aware_scheduling_tpu.tas import planner as planner_module
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.planner import BatchPlanner, MeshRefused
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.testing.builders import make_pod
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.utils import trace
from test_planner_batch import (
    metric_info,
    pending,
    prioritize_request,
    rule,
    write_policy,
)

PERFBENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench")
DEVICES = 4
MESH_FAMILIES = ("pas_planner_place_seconds_total", "pas_planner_mesh_devices",
                 "pas_planner_mesh_solves_total")

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < DEVICES, reason="needs four of the virtual CPU devices"
)


@pytest.fixture(scope="module")
def plan_reference():
    """The benchmark's plain reference of the plan; it finds its own
    neighbours (``reference``, ``generator``) beside itself."""
    sys.path.insert(0, PERFBENCH)
    try:
        import plan_reference as module
    finally:
        sys.path.remove(PERFBENCH)
    return module


# -- the worlds -------------------------------------------------------------------

METRICS = ("load", "mem", "net", "temp")


def policies_of(top: int) -> list:
    """batch-10k's three policies, in the reference's form: thresholds so
    that about a tenth of the nodes violate each."""
    return [
        {"name": "greater", "strategies": {
            "scheduleonmetric": [("load", "GreaterThan", 0)],
            "dontschedule": [("mem", "GreaterThan", int(top * 0.9))]}},
        {"name": "less", "strategies": {
            "scheduleonmetric": [("net", "LessThan", 0)],
            "dontschedule": [("temp", "LessThan", int(top * 0.1))]}},
        {"name": "multi", "strategies": {
            "scheduleonmetric": [("mem", "GreaterThan", 0)],
            "dontschedule": [("load", "GreaterThan", int(top * 0.95)),
                             ("net", "LessThan", int(top * 0.05))]}},
    ]


def world(nodes: int, pods: int, case: str, seed: int = 5) -> dict:
    """Columns, policies, each pod's policy and the nodes' room.

    ``exhausted``: unique values (a permutation, as the cells make them) and
    fewer slots than pods.  ``ties``: a handful of distinct values, so that
    whole runs of nodes tie and the first index has to win.  ``forbidden``:
    the best node of every policy by its own scheduleonmetric rule violates
    that policy's dontschedule, and of no other's."""
    rng = np.random.default_rng(seed)
    if case == "ties":
        columns = {m: rng.integers(0, 6, nodes).astype(np.int64) * 100
                   for m in METRICS}
        top = 500
    else:
        columns = {m: rng.permutation(nodes).astype(np.int64) * 97 + 1
                   for m in METRICS}
        top = (nodes - 1) * 97
    policies = policies_of(top)
    if case == "forbidden":
        best = int(columns["load"].argmax())
        columns["mem"][best] = top * 2  # 'greater' forbids its own best node
        best = int(columns["net"].argmin())
        columns["temp"][best] = 0  # so does 'less'
    slots = 1 if case == "exhausted" else 3
    bound = rng.integers(0, nodes, nodes // 5)
    room = np.maximum(slots - np.bincount(bound, minlength=nodes), 0)
    return {
        "names": [f"n{i:04d}" for i in range(nodes)], "columns": columns,
        "policies": policies, "which": rng.integers(0, 3, pods), "slots": slots,
        "bound": bound, "room": room,
    }


def planners_over(w: dict, devices=(1, DEVICES)) -> list:
    """One cache and mirror, and a planner for each number of devices, all
    fed the same nodes, bound pods and pending pods."""
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    for policy in w["policies"]:
        strategies = policy["strategies"]
        write_policy(cache, policy["name"], rule(*strategies["scheduleonmetric"][0]),
                     [rule(*r) for r in strategies["dontschedule"]])
    for metric in METRICS:
        cache.write_metric(metric, metric_info(
            **dict(zip(w["names"], w["columns"][metric].tolist()))))
    planners = [BatchPlanner(cache, mirror, node_capacity=w["slots"], devices=d)
                for d in devices]
    for planner in planners:
        for i, node in enumerate(w["bound"].tolist()):
            planner.pod_observed(make_pod(f"held{i}", node_name=w["names"][node]))
        for i, which in enumerate(w["which"].tolist()):
            planner.pod_added(pending(f"p{i:05d}", w["policies"][which]["name"]))
    return planners


def key(i: int) -> str:
    return object_key(make_pod(f"p{i:05d}"))


def reference_plan(plan_reference, w: dict) -> dict:
    """{pod key: node name} by the benchmark's plain reference."""
    ranked = [plan_reference.ranked_nodes(p, w["columns"].__getitem__)
              for p in w["policies"]]
    plan = plan_reference.Plan(
        np.arange(len(w["which"])), w["which"], ranked, w["room"].copy())
    out = {}
    for i in range(len(w["which"])):
        node = plan.node_of(i)
        if node is not None:
            out[key(i)] = w["names"][node]
    return out


# 320 nodes and 2,400 pods are batch-10k's and tas-40k's rehearsal size
# (512 lanes, 128 a device); 50 nodes make 64 lanes, 16 a device: under the
# assigner's block of 32, and no multiple of it
SIZES = {"rehearsal": (320, 2400, 4096), "narrow": (50, 90, 8)}
CASES = [(size, case) for size in SIZES for case in ("exhausted", "ties", "forbidden")]
# ... and pending counts on each side of a padded size (16 with a floor of 8)
CASES += [("narrow", f"pending-{count}") for count in (15, 16, 17, 33)]


@pytest.mark.parametrize("size,case", CASES)
def test_the_mesh_plan_is_the_one_device_plan_and_the_references(
        size, case, monkeypatch, plan_reference):
    nodes, pods, floor = SIZES[size]
    if case.startswith("pending-"):
        pods, case = int(case.split("-")[1]), "exhausted"
    monkeypatch.setattr(planner_module, "PAD_FLOOR", floor)
    w = world(nodes, pods, case)
    one, mesh = planners_over(w)
    assert one.mesh is None and mesh.mesh.devices.size == DEVICES
    assert mesh.mirror.device_view().node_capacity == {"rehearsal": 512, "narrow": 64}[size]
    planned = one.replan()
    assert mesh.replan() == planned
    want = reference_plan(plan_reference, w)
    assert one._published[0] == want
    assert mesh._published[0] == want
    assert one._published[1] == mesh._published[1] == mesh.mirror.version
    if case == "exhausted":
        assert 0 < planned < pods or pods <= w["room"].sum()
    if case == "forbidden":
        best = w["names"][int(w["columns"]["load"].argmax())]
        firsts = [want[key(i)] for i in np.flatnonzero(w["which"] == 0)[:3]]
        assert firsts and best not in firsts
    # a pod of the mesh plan is served as any plan's: the same bytes
    pod = pending("p00000", w["policies"][int(w["which"][0])]["name"])
    assert mesh.planned_node(pod) == one.planned_node(pod) == want.get(object_key(pod))


@pytest.mark.parametrize("size", list(SIZES))
def test_an_all_padding_batch_assigns_nothing_on_the_mesh(size, monkeypatch):
    """What ``_warm_smaller`` solves: no pending row, every lane padding."""
    nodes, pods, floor = SIZES[size]
    monkeypatch.setattr(planner_module, "PAD_FLOOR", floor)
    w = world(nodes, pods, "exhausted")
    (mesh,) = planners_over(w, devices=(DEVICES,))
    state, batch, _keys, view, _timer = mesh._snapshot()
    state, batch = mesh._place(state, batch)
    rows, n_cap = batch.candidates.shape
    empty = batch._replace(candidates=mesh._mask(rows, n_cap, 0, nodes))
    assigned = np.asarray(mesh._mesh_step(*mesh._place(state, empty)))
    assert assigned.shape == (rows,) and (assigned == -1).all()
    # the candidate mask is born split over the mesh: a quarter of the lanes
    # on each device, never whole on one
    shards = batch.candidates.addressable_shards
    assert len(shards) == DEVICES
    assert {s.data.shape for s in shards} == {(rows, n_cap // DEVICES)}
    assert state.metric_present.sharding.spec == batch.candidates.sharding.spec
    assert greedy_assign_collective_count(rows) == -(-rows // 32)


def counters_now() -> dict:
    return {name: trace.COUNTERS.get(name) for name in MESH_FAMILIES}


def test_one_device_builds_counts_and_compiles_nothing_of_the_mesh(monkeypatch):
    """``--batchPlannerDevices=1`` (the default) is the tree's planner: no
    mesh, the module's own candidate mask, no ``pas_planner_mesh_*`` family
    or ``place`` second counted, no mesh program compiled, and the answer on
    the wire byte for byte the mesh planner's."""
    from platform_aware_scheduling_tpu.tas.metrics import DummyMetricsClient

    monkeypatch.setattr(planner_module, "PAD_FLOOR", 4)  # a shape of its own
    before = counters_now()
    compiled = batch_scheduler._mesh_scheduling_step.cache_size()
    store = {"m": metric_info(n1=100, n2=50, n3=10)}
    cache, mirror, extender, _c, _e, stop = tas_main.assemble(
        FakeKubeClient(), DummyMetricsClient(store), 3600.0,
        enable_batch_planner=True, planner_devices=1)
    stop.set()
    planner = extender.planner
    assert planner.mesh is None
    assert planner._mask is planner_module._candidate_mask
    planner.node_capacity = 1
    write_policy(cache, "plan-pol", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", 900)])
    cache.write_metric("m")
    pods = [pending(f"p{i}", "plan-pol") for i in range(3)]
    for pod in pods:
        planner.pod_added(pod)
    cache.update_all_metrics(DummyMetricsClient(store))  # a pass, and its replan
    assert planner._published[0] == {
        object_key(pod): node for pod, node in zip(pods, ("n1", "n2", "n3"))}
    answer = extender.prioritize(prioritize_request(pods[1], ("n1", "n2", "n3")))
    assert counters_now() == before
    assert batch_scheduler._mesh_scheduling_step.cache_size() == compiled

    # the same pass through a planner over four devices: the mesh families
    # move, and the wire does not
    meshed = BatchPlanner(cache, mirror, node_capacity=1, devices=DEVICES)
    for pod in pods:
        meshed.pod_added(pod)
    assert meshed.replan() == 3
    assert meshed._published[0] == planner._published[0]
    again = MetricsExtender(cache, mirror=mirror, planner=meshed).prioritize(
        prioritize_request(pods[1], ("n1", "n2", "n3")))
    assert again.body == answer.body
    assert json.loads(again.body)[0] == {"Host": "n2", "Score": 10}
    after = counters_now()
    assert after["pas_planner_mesh_devices"] == DEVICES
    assert after["pas_planner_mesh_solves_total"] == before[
        "pas_planner_mesh_solves_total"] + 1
    assert after["pas_planner_place_seconds_total"] > before[
        "pas_planner_place_seconds_total"]
    assert batch_scheduler._mesh_scheduling_step.cache_size() > compiled


def test_a_drain_on_the_mesh_compiles_no_more_than_the_padded_sizes(monkeypatch):
    """As on one device: the first replan compiles its size and every
    smaller one, placed as a replan places them, and the drain none."""
    monkeypatch.setattr(planner_module, "PAD_FLOOR", 8)
    w = world(100, 70, "ties")  # 128 lanes: shapes no other test has compiled
    (mesh,) = planners_over(w, devices=(DEVICES,))
    step = batch_scheduler._mesh_scheduling_step
    before = step.cache_size()
    mesh.replan()
    assert 1 <= step.cache_size() - before <= 5  # 128, 64, 32, 16, 8
    after_first = step.cache_size()
    for left in (65, 64, 33, 17, 9, 8, 1):
        for i in range(70 - left):
            mesh.pod_bound(pending(f"p{i:05d}", "greater"))
        mesh.replan()
        assert mesh.pending_count() == left
    assert step.cache_size() == after_first


# -- start-up refusals --------------------------------------------------------------


@pytest.mark.parametrize("devices,solver,says", [
    (len(jax.devices()) + 1, "greedy", "JAX has"),
    (DEVICES, "sinkhorn", "sinkhorn"),
    (3, "greedy", "does not divide"),
    (0, "greedy", "not a number of devices"),
])
def test_a_planner_that_cannot_span_its_devices_is_refused(devices, solver, says):
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    with pytest.raises(MeshRefused, match=says):
        BatchPlanner(cache, mirror, solver=solver, devices=devices)


@pytest.mark.parametrize("argv,says", [
    (["--batchPlanner", f"--batchPlannerDevices={len(jax.devices()) + 1}"],
     "JAX has"),
    (["--batchPlanner", "--batchSolver", "sinkhorn", "--batchPlannerDevices=4"],
     "sinkhorn"),
    (["--batchPlannerDevices=4"], "needs --batchPlanner"),
])
def test_the_main_exits_2_with_usage(argv, says, monkeypatch, capsys):
    from platform_aware_scheduling_tpu.cmd import common

    monkeypatch.setattr(tas_main, "get_kube_client", lambda _path: FakeKubeClient())
    monkeypatch.setattr(common, "prepare_device_runtime", lambda: None)
    with pytest.raises(SystemExit) as exit_info:
        tas_main.main(["--unsafe", "--port", "0", "--syncPeriod", "1h", *argv])
    assert exit_info.value.code == 2
    err = capsys.readouterr().err
    assert "usage:" in err and "--batchPlannerDevices" in err and says in err
