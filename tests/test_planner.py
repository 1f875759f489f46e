"""BatchPlanner: pending-set maintenance, batch solve, plan serving, and
the prioritize steering path."""

import json
import time

import pytest

from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
from platform_aware_scheduling_tpu.tas.planner import BatchPlanner
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.testing.builders import (
    make_policy,
    make_pod,
    rule,
)
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.utils.quantity import Quantity


def metric_info(**kv):
    return {n: NodeMetric(value=Quantity(str(v))) for n, v in kv.items()}


def build(node_capacity=1):
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    planner = BatchPlanner(cache, mirror, node_capacity=node_capacity)
    cache.write_policy(
        "default",
        "plan-pol",
        TASPolicy.from_obj(
            make_policy(
                "plan-pol",
                strategies={
                    "scheduleonmetric": [rule("m", "GreaterThan", 0)],
                    "dontschedule": [rule("m", "GreaterThan", 900)],
                },
            )
        ),
    )
    cache.write_metric("m", metric_info(n1=100, n2=50, n3=10))
    return cache, mirror, planner


def pending_pod(name):
    return make_pod(name, labels={"telemetry-policy": "plan-pol"})


class TestReplan:
    def test_capacity_one_spreads_pods(self):
        _, _, planner = build(node_capacity=1)
        for i in range(3):
            planner.pod_added(pending_pod(f"p{i}"))
        assert planner.replan() == 3
        nodes = {
            planner.planned_node(pending_pod(f"p{i}")) for i in range(3)
        }
        # greedy-in-order: p0 gets n1 (100), p1 n2 (50), p2 n3 (10)
        assert planner.planned_node(pending_pod("p0")) == "n1"
        assert planner.planned_node(pending_pod("p1")) == "n2"
        assert planner.planned_node(pending_pod("p2")) == "n3"
        assert nodes == {"n1", "n2", "n3"}

    def test_dontschedule_respected(self):
        cache, _, planner = build(node_capacity=5)
        cache.write_metric("m", metric_info(n1=1000, n2=50, n3=10))
        planner.pod_added(pending_pod("p0"))
        planner.replan()
        # n1 violates (1000 > 900): best eligible is n2
        assert planner.planned_node(pending_pod("p0")) == "n2"

    def test_bound_pod_leaves_plan(self):
        _, _, planner = build()
        planner.pod_added(pending_pod("p0"))
        planner.replan()
        assert planner.planned_node(pending_pod("p0")) == "n1"
        planner.pod_bound(pending_pod("p0"))
        assert planner.planned_node(pending_pod("p0")) is None

    def test_stale_plan_invalidated_by_state_change(self):
        cache, mirror, planner = build()
        planner.pod_added(pending_pod("p0"))
        planner.replan()
        assert planner.planned_node(pending_pod("p0")) == "n1"
        cache.write_metric("m", metric_info(n1=1, n2=50, n3=10))
        assert planner.planned_node(pending_pod("p0")) is None
        planner.replan()
        assert planner.planned_node(pending_pod("p0")) == "n2"

    def test_unlabelled_or_bound_pods_ignored(self):
        _, _, planner = build()
        planner.pod_added(make_pod("nolabel"))
        planner.pod_added(make_pod("bound", labels={"telemetry-policy": "x"},
                                   node_name="n1"))
        assert planner.pending_count() == 0


class TestPrioritizeSteering:
    def _request(self, pod_name):
        return HTTPRequest(
            method="POST",
            path="/scheduler/prioritize",
            headers={"Content-Type": "application/json"},
            body=json.dumps({
                "Pod": pending_pod(pod_name).raw,
                "Nodes": {"items": [
                    {"metadata": {"name": n}} for n in ("n1", "n2", "n3")
                ]},
            }).encode(),
        )

    def test_planned_node_promoted(self):
        cache, mirror, planner = build(node_capacity=1)
        ext = MetricsExtender(cache, mirror=mirror, planner=planner)
        for i in range(2):
            planner.pod_added(pending_pod(f"p{i}"))
        planner.replan()
        # p1's batch node is n2 even though n1 scores higher individually
        out = json.loads(ext.prioritize(self._request("p1")).body)
        assert out[0] == {"Host": "n2", "Score": 10}
        assert [e["Score"] for e in out] == [10, 9, 8]
        # p0 keeps n1 on top; unplanned pods get the plain ordering
        out0 = json.loads(ext.prioritize(self._request("p0")).body)
        assert out0[0] == {"Host": "n1", "Score": 10}
        outx = json.loads(ext.prioritize(self._request("ghost")).body)
        assert outx[0] == {"Host": "n1", "Score": 10}

    def test_planner_off_is_reference_behavior(self):
        cache, mirror, _ = build()
        ext = MetricsExtender(cache, mirror=mirror, planner=None)
        out = json.loads(ext.prioritize(self._request("p1")).body)
        assert out[0] == {"Host": "n1", "Score": 10}


class TestWatchFeed:
    def test_informer_feeds_pending_set(self):
        cache, mirror, planner = build()
        kube = FakeKubeClient()
        informer = planner.watch(kube)
        try:
            kube.add_pod(pending_pod("w0"))
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and planner.pending_count() == 0:
                time.sleep(0.02)
            assert planner.pending_count() == 1
            bound = pending_pod("w0")
            bound.raw["spec"]["nodeName"] = "n1"
            bound.metadata["resourceVersion"] = "9"
            kube.update_pod(bound)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and planner.pending_count() > 0:
                time.sleep(0.02)
            assert planner.pending_count() == 0
        finally:
            informer.stop()


class TestCapacityFidelity:
    def test_full_node_stops_receiving_assignments(self):
        """A node with no remaining pod slots (allocatable − bound == 0) must
        not receive plan assignments, however well it scores."""
        _, _, planner = build(node_capacity=5)
        from platform_aware_scheduling_tpu.testing.builders import make_node

        planner.node_changed(make_node("n1", allocatable={"pods": "2"}))
        planner.pod_observed(make_pod("b0", node_name="n1"))
        planner.pod_observed(make_pod("b1", node_name="n1"))
        planner.pod_added(pending_pod("p0"))
        assert planner.replan() == 1
        assert planner.planned_node(pending_pod("p0")) == "n2"

    def test_terminated_pod_frees_its_slot(self):
        _, _, planner = build(node_capacity=5)
        from platform_aware_scheduling_tpu.testing.builders import make_node

        planner.node_changed(make_node("n1", allocatable={"pods": "1"}))
        bound = make_pod("b0", node_name="n1")
        planner.pod_observed(bound)
        planner.pod_added(pending_pod("p0"))
        planner.replan()
        assert planner.planned_node(pending_pod("p0")) == "n2"
        done = make_pod("b0", node_name="n1", phase="Succeeded")
        planner.pod_observed(done)
        planner.replan()
        assert planner.planned_node(pending_pod("p0")) == "n1"

    def test_unobserved_nodes_fall_back_to_default(self):
        """Nodes with no observed allocatable keep the kubelet-default
        fallback, so behavior without informers matches round 1."""
        _, _, planner = build(node_capacity=1)
        for i in range(3):
            planner.pod_added(pending_pod(f"p{i}"))
        assert planner.replan() == 3

    def test_node_informer_feeds_allocatable(self):
        from platform_aware_scheduling_tpu.testing.builders import make_node

        cache, mirror, planner = build(node_capacity=5)
        kube = FakeKubeClient()
        kube.add_node(make_node("n1", allocatable={"pods": "0"}))
        handle = planner.watch(kube)
        try:
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and "n1" not in planner._node_alloc:
                time.sleep(0.02)
            assert planner._node_alloc.get("n1")[0] == 0  # (pods, cpu, memory)
            planner.pod_added(pending_pod("p0"))
            planner.replan()
            assert planner.planned_node(pending_pod("p0")) == "n2"
        finally:
            handle.stop()


class TestSinkhornPlanner:
    def test_sinkhorn_solver_coordinates(self):
        cache = AutoUpdatingCache()
        mirror = TensorStateMirror()
        mirror.attach(cache)
        planner = BatchPlanner(cache, mirror, node_capacity=1,
                               solver="sinkhorn")
        cache.write_policy(
            "default", "plan-pol",
            TASPolicy.from_obj(make_policy("plan-pol", strategies={
                "scheduleonmetric": [rule("m", "GreaterThan", 0)]})),
        )
        cache.write_metric("m", metric_info(n1=100, n2=99))
        planner.pod_added(pending_pod("p0"))
        planner.pod_added(pending_pod("p1"))
        assert planner.replan() == 2
        placed = {planner.planned_node(pending_pod("p0")),
                  planner.planned_node(pending_pod("p1"))}
        assert placed == {"n1", "n2"}
