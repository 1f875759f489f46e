"""The batch planner as a deployment runs it (``--batchPlanner``): padded
solves, per-policy ``dontschedule``, room by kube-scheduler's
NodeResourcesFit, the replan hung on the refresh pass, and the counters.

Every plan is held to a plain NumPy loop written from the contract: pods in
creation order, each on the best node — by its own policy's
``scheduleonmetric`` rule — that reports the metric, does not violate the
pod's own policy's ``dontschedule``, and still has room; ties to the lowest
node index."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.models import batch_scheduler
from platform_aware_scheduling_tpu.models.batch_scheduler import (
    ClusterState,
    PendingPods,
    score_and_filter,
)
from platform_aware_scheduling_tpu.ops import i64
from platform_aware_scheduling_tpu.ops.assign import greedy_assign_kernel
from platform_aware_scheduling_tpu.ops.pallas_assign import greedy_assign_pallas
from platform_aware_scheduling_tpu.ops.rules import (
    OP_GREATER_THAN,
    OP_LESS_THAN,
    RuleSet,
)
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas import planner as planner_module
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import DummyMetricsClient, NodeMetric
from platform_aware_scheduling_tpu.tas.planner import BatchPlanner, padded_size
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.testing.builders import (
    make_node,
    make_policy,
    make_pod,
    rule,
)
from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity

# -- the solve against a plain loop -----------------------------------------------

M, N, P, D = 4, 37, 53, 3  # metrics, nodes, pods, policies


def plain_plan(values, present, rows, ops, policy, rules, capacity, known):
    """node index per pod (-1: none), by the loop the contract describes."""
    room = capacity.copy()
    out = []
    for row, op, d in zip(rows, ops, policy):
        best = -1
        for node in range(known):
            if not present[row, node] or room[node] <= 0:
                continue
            if any(present[r, node]
                   and (values[r, node] > t if o == OP_GREATER_THAN
                        else values[r, node] < t)
                   for r, o, t in rules[d]):
                continue
            key = values[row, node] if op == OP_GREATER_THAN else -values[row, node]
            if best < 0 or key > best_key:
                best, best_key = node, key
        if best >= 0:
            room[best] -= 1
        out.append(best)
    return np.array(out)


def world(seed):
    """Mixed policies, room that runs out, a metric some nodes do not
    report, values that tie, and a policy every node violates."""
    rng = np.random.default_rng(seed)
    values = rng.integers(0, 40, size=(M, N)).astype(np.int64)  # ties
    present = rng.random((M, N)) > 0.15
    rules = [[(1, OP_GREATER_THAN, 30)],
             [(2, OP_LESS_THAN, 8), (0, OP_GREATER_THAN, 35)],
             [(3, OP_GREATER_THAN, -1)]]  # every reporting node violates
    present[3] = True
    capacity = rng.integers(0, 3, size=N).astype(np.int32)  # 53 pods, ~37 slots
    policy = rng.integers(0, D, size=P).astype(np.int32)
    rows = np.array([0, 1, 2], dtype=np.int32)[policy]
    ops = np.array([OP_GREATER_THAN, OP_LESS_THAN, OP_GREATER_THAN],
                   dtype=np.int32)[policy]
    return values, present, rules, capacity, policy, rows, ops


def device_operands(values, present, rules, capacity, policy, rows, ops, size,
                    known):
    """(state, pods) with the pending set padded to ``size`` rows."""
    width = 8
    metric_row = np.zeros((8, width), np.int32)
    op_id = np.zeros((8, width), np.int32)
    target = np.zeros((8, width), np.int64)
    active = np.zeros((8, width), bool)
    for d, listed in enumerate(rules):
        for k, (r, o, t) in enumerate(listed):
            metric_row[d, k], op_id[d, k], target[d, k], active[d, k] = r, o, t, True
    hi, lo = i64.split_int64_np(values)
    t_hi, t_lo = i64.split_int64_np(target)
    state = ClusterState(
        metric_values=i64.I64(hi=jnp.asarray(hi), lo=jnp.asarray(lo)),
        metric_present=jnp.asarray(present),
        dontschedule=RuleSet(
            metric_row=jnp.asarray(metric_row), op_id=jnp.asarray(op_id),
            target=i64.I64(hi=jnp.asarray(t_hi), lo=jnp.asarray(t_lo)),
            active=jnp.asarray(active)),
        capacity=jnp.asarray(capacity),
    )

    def pad(column):
        out = np.zeros(size, np.int32)
        out[: len(column)] = column
        return jnp.asarray(out)

    pods = PendingPods(
        metric_row=pad(rows), op_id=pad(ops),
        candidates=planner_module._candidate_mask(size, N, len(rows), known),
        policy=pad(policy))
    return state, pods


@pytest.mark.parametrize("assigner", ["scan", "pallas-interpret"])
@pytest.mark.parametrize("size", [P, 64, 128])
@pytest.mark.parametrize("seed", [1, 2])
def test_padded_solve_is_the_unpadded_one_and_the_plain_loop(seed, size, assigner):
    values, present, rules, capacity, policy, rows, ops = world(seed)
    known = N - 3  # the mirror's capacity is past the nodes it knows
    want = plain_plan(values, present, rows, ops, policy, rules, capacity, known)
    assert (want < 0).any() and (want >= 0).any()  # room does run out
    assert (want[policy == 2] < 0).all()  # the all-violating policy places none
    state, pods = device_operands(
        values, present, rules, capacity, policy, rows, ops, size, known)
    if assigner == "scan":
        got = batch_scheduler.scheduling_step(state, pods, assigner="scan")
        assigned = np.asarray(got.assignment.node_for_pod)
        assert got.violating.shape == (8, N)
    else:
        _violating, score, eligible = score_and_filter(state, pods)
        assigned = np.asarray(greedy_assign_pallas(
            score, eligible, state.capacity, interpret=True).node_for_pod)
        again = np.asarray(
            greedy_assign_kernel(score, eligible, state.capacity).node_for_pod)
        assert np.array_equal(assigned, again)
    assert np.array_equal(assigned[:P], want)
    assert (assigned[P:] == -1).all()  # a padded row is never assigned


def test_one_rule_list_for_every_pod_is_the_case_of_one_policy():
    """bench.py, the mesh dry run and the benches solve one rule list over
    every pod: D = 1 and a zero ``policy``, no form of its own."""
    state, pods = batch_scheduler.example_inputs(num_nodes=32, num_pods=8)
    assert state.dontschedule.active.shape == (1, 2)
    assert not np.asarray(pods.policy).any()
    out = batch_scheduler.scheduling_step(state, pods, assigner="scan")
    assert out.violating.shape == (1, 32)
    assert out.assignment.node_for_pod.shape == (8,)


# -- the planner over a cache and a mirror ----------------------------------------


def metric_info(**kv):
    return {n: NodeMetric(value=Quantity(str(v))) for n, v in kv.items()}


def write_policy(cache, name, schedule, dont):
    cache.write_policy("default", name, TASPolicy.from_obj(make_policy(
        name, strategies={"scheduleonmetric": [schedule], "dontschedule": dont})))


def build(node_capacity=5):
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    planner = BatchPlanner(cache, mirror, node_capacity=node_capacity)
    return cache, mirror, planner


def pending(name, policy, **requests):
    return make_pod(name, labels={"telemetry-policy": policy},
                    container_requests=[requests] if requests else None)


def moved(name, before):
    return trace.COUNTERS.get(name) - before.get(name, 0.0)


COUNTERS = ("pas_planner_replans_total", "pas_planner_replan_seconds_total",
            "pas_planner_snapshot_seconds_total", "pas_planner_solve_seconds_total",
            "pas_planner_publish_seconds_total", "pas_planner_promoted_total",
            "pas_planner_reordered_total", "pas_planner_stale_total",
            "pas_planner_unplanned_total")


def counters_now():
    return {name: trace.COUNTERS.get(name) for name in COUNTERS}


def test_padded_sizes_are_powers_of_two_from_the_floor():
    assert [padded_size(p) for p in (0, 1, 1024, 1025, 4096, 10000)] == [
        1024, 1024, 1024, 2048, 4096, 16384]
    # 10,000 pods drain through five shapes
    assert len({padded_size(p) for p in range(1, 10001)}) == 5


def test_a_drain_compiles_no_more_than_the_padded_sizes(monkeypatch):
    monkeypatch.setattr(planner_module, "PAD_FLOOR", 8)
    cache, mirror, planner = build(node_capacity=1000)
    write_policy(cache, "drain-pol", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", 900)])
    cache.write_metric("m", metric_info(**{f"n{i}": 100 - i for i in range(6)}))
    pods = [pending(f"p{i:03d}", "drain-pol") for i in range(300)]
    for pod in pods:
        planner.pod_added(pod)
    step = batch_scheduler._scheduling_step
    before = step.cache_size()
    assert planner.replan() == 300
    sizes = [512, 256, 128, 64, 32, 16, 8]
    # the first replan compiled its own size and every smaller one
    assert 1 <= step.cache_size() - before <= len(sizes)
    after_first = step.cache_size()
    for left in (299, 257, 256, 130, 128, 65, 33, 17, 9, 8, 3, 1):
        for pod in pods[: 300 - left]:
            planner.pod_bound(pod)
        assert planner.replan() == left
        assert planner.planned_node(pods[-1]) == "n0"
    for pod in pods:
        planner.pod_bound(pod)
    assert planner.replan() == 0
    assert step.cache_size() == after_first  # the drain compiled nothing


def test_a_pod_is_held_to_its_own_policys_dontschedule_only():
    """n1 ranks first for both policies; only pol-a forbids it.  Under the
    union of all pending pods' rules pol-b's pod lost n1 as well."""
    cache, mirror, planner = build()
    write_policy(cache, "pol-a", rule("load", "GreaterThan", 0),
                 [rule("temp", "GreaterThan", 80)])
    write_policy(cache, "pol-b", rule("load", "GreaterThan", 0),
                 [rule("temp", "LessThan", 10)])
    cache.write_metric("load", metric_info(n1=100, n2=50, n3=10))
    cache.write_metric("temp", metric_info(n1=90, n2=50, n3=5))
    planner.pod_added(pending("a0", "pol-a"))
    planner.pod_added(pending("b0", "pol-b"))
    planner.pod_added(pending("b1", "pol-b"))
    assert planner.replan() == 3
    assert planner.planned_node(pending("a0", "pol-a")) == "n2"  # n1 forbidden
    assert planner.planned_node(pending("b0", "pol-b")) == "n1"  # not to pol-b
    assert planner.planned_node(pending("b1", "pol-b")) == "n1"


def test_room_is_what_the_schedulers_fit_leaves():
    """cpu: 4 holds forty 100m pods; ``pods: 110`` would take seventy more."""
    cache, mirror, planner = build()
    write_policy(cache, "fit-pol", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", 900)])
    cache.write_metric("m", metric_info(n1=100, n2=50))
    source = {"pods": "110", "cpu": "4", "memory": "32Gi"}
    for name in ("n1", "n2"):
        planner.node_changed(make_node(name, allocatable=source))
    for i in range(40):
        planner.pod_observed(make_pod(
            f"b{i}", node_name="n1",
            container_requests=[{"cpu": "100m", "memory": "500Mi"}]))
    asks = {"cpu": "100m", "memory": "500Mi"}
    for i in range(45):
        planner.pod_added(pending(f"p{i:02d}", "fit-pol", **asks))
    assert planner.replan() == 40  # n2 takes forty; nothing else has room
    assert {planner.planned_node(pending(f"p{i:02d}", "fit-pol"))
            for i in range(40)} == {"n2"}
    assert planner.planned_node(pending("p40", "fit-pol")) is None
    # memory binds the same way, and each pod is counted at its own
    # requests (tests/test_planner_demand.py): a pod of another size in the
    # pending set no longer sizes every slot by the largest
    planner.pod_observed(make_pod("b0", node_name="n1", phase="Succeeded"))
    planner.pod_added(pending("big", "fit-pol", cpu="100m", memory="20Gi"))
    planner.replan()
    # n1, the better node, has 0.1 cpu free again: p00 takes it (500Mi
    # fits 12.96Gi) where a slot sized by "big" would have found no room;
    # forty more fill n2; "big", created last, finds no cpu anywhere
    assert planner.planned_node(pending("big", "fit-pol")) is None
    assert planner.planned_node(pending("p00", "fit-pol")) == "n1"
    assert {planner.planned_node(pending(f"p{i:02d}", "fit-pol"))
            for i in range(1, 41)} == {"n2"}
    assert planner.planned_node(pending("p41", "fit-pol")) is None


def prioritize_request(pod, nodes):
    return HTTPRequest(
        method="POST", path="/scheduler/prioritize",
        headers={"Content-Type": "application/json"},
        body=json.dumps({"Pod": pod.raw, "Nodes": {"items": [
            {"metadata": {"name": n}} for n in nodes]}}).encode())


def test_the_replan_follows_the_refresh_pass_and_the_counters_move():
    from platform_aware_scheduling_tpu.cmd.tas import assemble
    from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient

    kube = FakeKubeClient()
    store = {"m": metric_info(n1=100, n2=50, n3=10)}
    cache, mirror, extender, _controller, _enforcer, stop = assemble(
        kube, DummyMetricsClient(store), 3600.0, enable_batch_planner=True)
    stop.set()  # one trigger, and no timer of the planner's own
    planner = extender.planner
    assert planner.replan in cache.on_refresh_pass
    assert not hasattr(planner, "start")
    planner.node_capacity = 1
    write_policy(cache, "plan-pol", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", 900)])
    cache.write_metric("m")  # registered, as the policy controller does
    pods = [pending(f"p{i}", "plan-pol") for i in range(3)]
    for pod in pods:
        planner.pod_added(pod)
    before = counters_now()
    cache.update_all_metrics(DummyMetricsClient(store))
    # the pass published, then planned: the next Prioritize carries it
    out = json.loads(extender.prioritize(
        prioritize_request(pods[1], ("n1", "n2", "n3"))).body)
    assert out[0] == {"Host": "n2", "Score": 10}
    assert [e["Score"] for e in out] == [10, 9, 8]
    assert moved("pas_planner_replans_total", before) == 1
    # n2 leads the answer, and the ordinal ranking had n1 first: reordered
    assert moved("pas_planner_promoted_total", before) == 1
    assert moved("pas_planner_reordered_total", before) == 1
    staged = [moved(f"pas_planner_{s}_seconds_total", before)
              for s in ("snapshot", "solve", "publish")]
    assert all(s > 0 for s in staged)
    assert sum(staged) <= moved("pas_planner_replan_seconds_total", before)
    assert trace.COUNTERS.get("pas_planner_pending_pods") == 3
    # the planned node is not among the candidates sent: counted, not promoted
    out = json.loads(extender.prioritize(
        prioritize_request(pods[1], ("n1", "n3"))).body)
    assert out[0]["Host"] == "n1"
    assert moved("pas_planner_unplanned_total", before) == 1
    # a pod the plan does not know
    extender.prioritize(prioritize_request(pending("ghost", "plan-pol"),
                                           ("n1", "n2")))
    assert moved("pas_planner_unplanned_total", before) == 2
    # new telemetry, no pass yet: the plan is stale and is not served
    cache.write_metric("m", metric_info(n1=1, n2=50, n3=10))
    out = json.loads(extender.prioritize(
        prioritize_request(pods[1], ("n1", "n2", "n3"))).body)
    assert out[0]["Host"] == "n2" and moved("pas_planner_stale_total", before) == 1
    assert moved("pas_planner_promoted_total", before) == 1
    # the next pass plans on what it published: p0 n2, p1 n3, p2 n1
    store["m"] = metric_info(n1=1, n2=50, n3=10)
    cache.update_all_metrics(DummyMetricsClient(store))
    out = json.loads(extender.prioritize(
        prioritize_request(pods[1], ("n1", "n2", "n3"))).body)
    assert out[0]["Host"] == "n3"
    assert moved("pas_planner_replans_total", before) == 2
    assert moved("pas_planner_promoted_total", before) == 2
    assert moved("pas_planner_reordered_total", before) == 2
    # p0's node is the ordinal ranking's first host: it leads, nothing moved
    out = json.loads(extender.prioritize(
        prioritize_request(pods[0], ("n1", "n2", "n3"))).body)
    assert out[0]["Host"] == "n2"
    assert moved("pas_planner_promoted_total", before) == 3
    assert moved("pas_planner_reordered_total", before) == 2


@pytest.mark.parametrize("path", ["native", "device", "host"])
def test_a_promotion_is_counted_where_it_is_made_on_every_path(path, monkeypatch):
    """led and reordered, apart: the native encoder says what the planned
    row did (cached answers say it again), ``prioritize_bytes`` and the
    host path's ``_apply_plan`` see it in the ranking they reorder."""
    from platform_aware_scheduling_tpu.tas import telemetryscheduler

    cache, mirror, planner = build(node_capacity=1)
    write_policy(cache, "plan-pol", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", 900)])
    cache.write_metric("m", metric_info(n1=100, n2=50, n3=10))
    if path == "device":
        monkeypatch.setattr(telemetryscheduler, "get_wirec", lambda: None)
    extender = MetricsExtender(
        cache, mirror=None if path == "host" else mirror, planner=planner)
    pods = [pending(f"p{i}", "plan-pol") for i in range(3)]
    for pod in pods:
        planner.pod_added(pod)
    assert planner.replan() == 3  # p0 n1, p1 n2, p2 n3
    before = counters_now()

    def first_host(pod, nodes):
        return json.loads(extender.prioritize(
            prioritize_request(pod, nodes)).body)[0]["Host"]

    def counted():
        return tuple(int(moved(f"pas_planner_{name}_total", before))
                     for name in ("promoted", "reordered", "unplanned"))

    assert first_host(pods[0], ("n1", "n2", "n3")) == "n1"
    assert counted() == (1, 0, 0)  # it led the ordinal ranking already
    assert first_host(pods[1], ("n1", "n2", "n3")) == "n2"
    assert counted() == (2, 1, 0)  # moved past n1
    assert first_host(pods[1], ("n1", "n2", "n3")) == "n2"
    assert counted() == (3, 2, 0)  # the same answer again, from a cache or not
    assert first_host(pods[1], ("n1", "n3")) == "n1"
    assert counted() == (3, 2, 1)  # its node was not among those sent


def test_with_the_planner_off_the_verbs_count_nothing_and_answer_as_before():
    cache, mirror, planner = build(node_capacity=1)
    write_policy(cache, "plan-pol", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", 900)])
    cache.write_metric("m", metric_info(n1=100, n2=50, n3=10))
    plain = MetricsExtender(cache, mirror=mirror, planner=None)
    steered = MetricsExtender(cache, mirror=mirror, planner=planner)
    pod = pending("p0", "plan-pol")
    before = counters_now()
    request = prioritize_request(pod, ("n1", "n2", "n3"))
    off = plain.prioritize(request).body
    assert all(moved(name, before) == 0 for name in COUNTERS)
    # no plan yet: the planner's answer is the plain one, byte for byte
    assert steered.prioritize(prioritize_request(pod, ("n1", "n2", "n3"))).body == off
    assert json.loads(off)[0] == {"Host": "n1", "Score": 10}


def test_the_informer_moves_the_bound_and_the_pending_set_as_one():
    from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
    import time

    cache, mirror, planner = build()
    kube = FakeKubeClient()

    def sets_after(moved) -> tuple:
        """(pending, bound) as one snapshot reads them, once ``moved``."""
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with planner._lock:
                now = len(planner._pending), dict(planner._bound_used)
            if moved(now):
                break
            time.sleep(0.02)
        return now

    handle = planner.watch(kube)
    try:
        kube.add_pod(pending("w0", "plan-pol", cpu="100m", memory="500Mi"))
        assert sets_after(lambda now: now[0] == 1) == (1, {})
        bound = pending("w0", "plan-pol", cpu="100m", memory="500Mi")
        bound.raw["spec"]["nodeName"] = "n1"
        bound.metadata["resourceVersion"] = "9"
        kube.update_pod(bound)
        # never (1, {n1: ...}) nor (0, {}): the two sets move under one lock
        assert sets_after(lambda now: now != (1, {})) == (
            0, {"n1": (1000, 100, 500 * 1024 * 1024 * 1000)})
    finally:
        handle.stop()


def test_a_snapshot_never_sees_a_pod_both_pending_and_bound():
    """The informer binds pods while replans take snapshots: under the one
    lock a pod is pending or bound, and the two sets add up."""
    import sys
    import threading
    import time

    from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient

    cache, mirror, planner = build(node_capacity=1000)
    write_policy(cache, "plan-pol", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", 900)])
    cache.write_metric("m", metric_info(n1=100, n2=50))
    kube = FakeKubeClient()
    total = 120
    for i in range(total):
        kube.add_pod(pending(f"s{i:03d}", "plan-pol", cpu="100m"))
    handle = planner.watch(kube)
    deadline = time.monotonic() + 10
    while planner.pending_count() < total and time.monotonic() < deadline:
        time.sleep(0.01)
    assert planner.pending_count() == total

    def bind_all():
        for i in range(total):
            bound = pending(f"s{i:03d}", "plan-pol", cpu="100m")
            bound.raw["spec"]["nodeName"] = "n1"
            bound.metadata["resourceVersion"] = str(1000 + i)
            kube.update_pod(bound)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    binder = threading.Thread(target=bind_all)
    try:
        binder.start()
        torn = 0
        while binder.is_alive() or planner.pending_count():
            if time.monotonic() > deadline + 20:
                break
            with planner._lock:
                waiting, bound = set(planner._pending), set(planner._bound_pods)
                held = planner._bound_used.get("n1", (0, 0, 0))
            torn += bool(waiting & bound) or len(waiting) + len(bound) != total
            torn += held[0] != 1000 * len(bound) or held[1] != 100 * len(bound)
            planner.replan()
        binder.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
        handle.stop()
    assert not binder.is_alive() and planner.pending_count() == 0
    assert torn == 0
