"""Pods of unlike requests through the batch planner: each books its own
cpu, memory and pod slot (kube-scheduler's NodeResourcesFit on exact
integers), where a count of "pods of the largest request" used to stand.

Every plan is held to a plain Python loop written from the contract: pods in
creation order, each on the best node by its policy's rule that reports the
metric, passes its ``dontschedule`` and has ``free[r] >= request[r]`` for
pods, cpu and memory in Python integers; the chosen node's free amounts lose
the pod's own requests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from platform_aware_scheduling_tpu.models import batch_scheduler
from platform_aware_scheduling_tpu.ops import i64
from platform_aware_scheduling_tpu.ops.assign import (
    LIMB_BITS,
    LIMB_MASK,
    greedy_assign_kernel,
)
from platform_aware_scheduling_tpu.ops.pallas_assign import greedy_assign_pallas
from platform_aware_scheduling_tpu.parallel.mesh import make_mesh
from platform_aware_scheduling_tpu.parallel.sharded import sharded_greedy_assign
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas import planner as planner_module
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.planner import BatchPlanner
from platform_aware_scheduling_tpu.testing.builders import make_node, make_pod, rule
from platform_aware_scheduling_tpu.utils import trace
from test_planner_batch import build, metric_info, moved, pending, write_policy

GI = 1 << 30
NEW_COUNTERS = ("pas_planner_demand_solves_total", "pas_planner_room_seconds_total",
                "pas_planner_conservative_room_total", "pas_planner_replans_total",
                "pas_planner_mesh_demand_solves_total",
                "pas_planner_mesh_demand_seconds_total")
DEVICES = 4  # of the conftest's eight CPU devices: tas-40k's mesh


def now():
    return {name: trace.COUNTERS.get(name) for name in NEW_COUNTERS}


# -- the assigners against a plain loop ---------------------------------------------


def plain_assign(score, eligible, room, demand):
    """(node per pod, room left) in Python integers."""
    room = [[int(v) for v in row] for row in room]
    out = []
    for i in range(score.shape[0]):
        best = -1
        for j in range(score.shape[1]):
            if not eligible[i, j] or any(
                    row[j] < int(demand[i, r]) for r, row in enumerate(room)):
                continue
            if best < 0 or score[i, j] > score[i, best]:
                best = j
        if best >= 0:
            for r, row in enumerate(room):
                row[best] -= int(demand[i, r])
        out.append(best)
    return np.array(out), np.array(room, dtype=np.int64)


def operands(seed, limbs, pods=41, nodes=50, resources=3):
    """Scores that tie, room that runs out resource by resource; with two
    limbs, quantities past 2**33 with no common factor."""
    rng = np.random.default_rng(seed)
    score = rng.integers(-5, 5, size=(pods, nodes)).astype(np.int64) * (1 << 33)
    eligible = rng.random((pods, nodes)) > 0.2
    scale = 1 if limbs == 1 else (1 << 33) + 7
    odd = limbs == 2
    room = rng.integers(0, 4, size=(resources, nodes)).astype(np.int64) * scale
    demand = rng.integers(0, 5, size=(pods, resources)).astype(np.int64) * scale
    if odd:
        room += rng.integers(0, 3, size=room.shape)
        demand += rng.integers(0, 3, size=demand.shape)
    return score, eligible, room, demand


def as_limbs(values, limbs, axis):
    if limbs == 1:
        return values.astype(np.int32)
    lo, hi = i64.split31_np(values)
    return np.concatenate([lo, hi], axis=axis)


@pytest.mark.parametrize("assigner", ["scan", "pallas-interpret"])
@pytest.mark.parametrize("limbs", [1, 2])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_an_assigner_books_each_pods_own_vector_as_the_plain_loop(
        seed, limbs, assigner):
    score, eligible, room, demand = operands(seed, limbs)
    want, want_left = plain_assign(score, eligible, room, demand)
    args = (i64.from_int64(score), jnp.asarray(eligible),
            jnp.asarray(as_limbs(room, limbs, 0)))
    asked = jnp.asarray(as_limbs(demand, limbs, 1))
    if assigner == "scan":
        got = greedy_assign_kernel(*args, asked, limbs=limbs)
    else:
        got = greedy_assign_pallas(
            *args, interpret=True, demand=asked, limbs=limbs)
    assert np.array_equal(np.asarray(got.node_for_pod), want)
    left = np.asarray(got.capacity_left).astype(np.int64)
    if limbs == 2:
        r = room.shape[0]
        assert left.min() >= 0 and left[:r].max() <= LIMB_MASK
        left = left[:r] + (left[r:] << LIMB_BITS)
    assert np.array_equal(left, want_left)
    assert (want < 0).any() and (want >= 0).any()  # room does run out


def test_a_count_is_a_demand_of_one_on_one_resource():
    """The count form and the demand form of the same room give one plan."""
    score, eligible, room, _demand = operands(3, 1, resources=1)
    ones = np.ones((score.shape[0], 1), dtype=np.int32)
    keys = i64.from_int64(score)
    count = greedy_assign_kernel(
        keys, jnp.asarray(eligible), jnp.asarray(room[0].astype(np.int32)))
    vector = greedy_assign_kernel(
        keys, jnp.asarray(eligible), jnp.asarray(room.astype(np.int32)),
        jnp.asarray(ones))
    assert np.array_equal(np.asarray(count.node_for_pod),
                          np.asarray(vector.node_for_pod))
    assert np.array_equal(np.asarray(count.capacity_left),
                          np.asarray(vector.capacity_left)[0])


def four_devices():
    if len(jax.devices()) < DEVICES:
        pytest.skip("needs four of the virtual CPU devices")
    return make_mesh(n_node_shards=DEVICES, devices=jax.devices()[:DEVICES])


def over_the_mesh(score, eligible, room, demand, limbs, block_size, lanes=64):
    """(node per pod, room left [limbs * R, nodes]) of the mesh solve, the
    node axis padded to ``lanes`` with nodes that have no room and take no
    pod, as the mirror pads it."""
    pods, nodes = eligible.shape
    wide = np.zeros((pods, lanes), dtype=np.int64)
    wide[:, :nodes] = score
    allowed = np.zeros((pods, lanes), dtype=bool)
    allowed[:, :nodes] = eligible
    rows = np.zeros((room.shape[0], lanes), dtype=np.int64)
    rows[:, :nodes] = room
    got, left = sharded_greedy_assign(
        four_devices(), i64.from_int64(wide), jnp.asarray(allowed),
        jnp.asarray(as_limbs(rows, limbs, 0)),
        demand=jnp.asarray(as_limbs(demand, limbs, 1)), limbs=limbs,
        block_size=block_size)
    left = np.asarray(left).astype(np.int64)
    if limbs == 2:
        r = room.shape[0]
        assert left.min() >= 0 and left[:r].max() <= LIMB_MASK
        left = left[:r] + (left[r:] << LIMB_BITS)
    assert not left[:, nodes:].any()
    return np.asarray(got), left[:, :nodes]


@pytest.mark.parametrize("block_size", [4, 32])
@pytest.mark.parametrize("limbs", [1, 2])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_mesh_books_each_pods_own_vector_as_the_plain_loop(
        seed, limbs, block_size):
    """41 pods (no multiple of either block) over 50 nodes in 64 lanes, 16
    a device: under a block of 32, and no multiple of it."""
    score, eligible, room, demand = operands(seed, limbs)
    want, want_left = plain_assign(score, eligible, room, demand)
    got, left = over_the_mesh(score, eligible, room, demand, limbs, block_size)
    assert np.array_equal(got, want)
    assert np.array_equal(left, want_left)
    assert (want < 0).any() and (want >= 0).any()  # room does run out


@pytest.mark.parametrize("limbs", [1, 2])
def test_a_pods_best_node_is_the_last_of_its_shards_top_b(limbs):
    """The top-B argument, at its edge.  One block of B = 4 pods; every pod
    ranks shard 0's nodes 0, 1, 2, ... first.  Pods 0-2 each take all the
    cpu of one of nodes 0-2 and leave its memory and pod slots; pod 3 asks
    for more cpu than any node of the other shards has, so its best node at
    its turn is node 3: shard 0's B-th candidate at block start, after B - 1
    bookings in the block made the first three infeasible for it and for it
    alone."""
    block, nodes = 4, 64  # 16 lanes a device
    scale = 1 if limbs == 1 else (1 << 33) + 7
    score = np.tile(np.arange(nodes, 0, -1, dtype=np.int64), (block, 1))
    eligible = np.ones((block, nodes), dtype=bool)
    room = np.zeros((3, nodes), dtype=np.int64)  # pods, cpu, memory
    room[0], room[2] = 10, 64
    room[1, :16], room[1, 16:] = 8, 2  # shard 0 has the cpu
    room *= scale
    demand = np.array([[1, 8, 1], [1, 8, 1], [1, 8, 1], [1, 4, 32]],
                      dtype=np.int64) * scale
    want, want_left = plain_assign(score, eligible, room, demand)
    assert want.tolist() == [0, 1, 2, 3]
    got, left = over_the_mesh(score, eligible, room, demand, limbs, block)
    assert got.tolist() == want.tolist()
    assert np.array_equal(left, want_left)
    # the same pods in blocks of one: the plain sequential solve
    alone, _ = over_the_mesh(score, eligible, room, demand, limbs, 1)
    assert alone.tolist() == want.tolist()


def test_a_count_on_the_mesh_is_a_demand_of_one():
    """Alike pods keep tas-40k's plan: the count form and a demand of one on
    one resource give the same plan and room on the mesh, and the
    one-device scan's."""
    score, eligible, room, _demand = operands(4, 1, pods=70, nodes=64,
                                              resources=1)
    keys, allowed = i64.from_int64(score), jnp.asarray(eligible)
    count = jnp.asarray(room[0].astype(np.int32))
    want = greedy_assign_kernel(keys, allowed, count)
    got, left = sharded_greedy_assign(four_devices(), keys, allowed, count)
    ones = np.ones((70, 1), dtype=np.int64)
    vector, vector_left = over_the_mesh(score, eligible, room, ones, 1, 32)
    assert np.array_equal(np.asarray(got), np.asarray(want.node_for_pod))
    assert np.array_equal(np.asarray(left), np.asarray(want.capacity_left))
    assert left.shape == (64,)
    assert np.array_equal(vector, np.asarray(got))
    assert np.array_equal(vector_left[0], np.asarray(left))


@pytest.mark.parametrize("assigner", ["scan", "pallas-interpret"])
def test_the_scheduling_step_takes_the_form_its_operands_hold(assigner):
    """``PendingPods.demand`` given: the room is ``[L, R, N]`` and the step
    books vectors; absent: the count, as before — no flag."""
    state, pods = batch_scheduler.example_inputs(
        num_nodes=40, num_pods=24, seed=5, resources=3)
    _violating, score, eligible = batch_scheduler.score_and_filter(state, pods)
    if assigner == "scan":
        got = batch_scheduler.scheduling_step(state, pods, assigner="scan").assignment
    else:
        got = greedy_assign_pallas(
            score, eligible, state.capacity[0], interpret=True,
            demand=pods.demand[:, 0])
    want, want_left = plain_assign(
        i64.to_int64_np(score), np.asarray(eligible),
        np.asarray(state.capacity)[0], np.asarray(pods.demand)[:, 0])
    assert np.array_equal(np.asarray(got.node_for_pod), want)
    assert np.array_equal(
        np.asarray(got.capacity_left).reshape(want_left.shape), want_left)
    counted, alike = batch_scheduler.example_inputs(num_nodes=40, num_pods=24, seed=5)
    assert alike.demand is None and counted.capacity.ndim == 1


# -- the planner against the plain reference ----------------------------------------

BINARY = {"Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30}


def milli(text) -> int:
    text = str(text)
    if text.endswith("m"):
        return int(text[:-1])
    for suffix, factor in BINARY.items():
        if text.endswith(suffix):
            return int(text[: -len(suffix)]) * factor * 1000
    return int(text) * 1000


def plain_plan(nodes, bound, pods, values, forbidden_over, fallback):
    """{pod: node or None}.  ``nodes``: name -> allocatable dict or None (never
    seen); ``bound``: (node, requests); ``pods``: (name, requests) in creation
    order; ``values``: node -> metric (GreaterThan ranks; a node over
    ``forbidden_over`` violates dontschedule)."""
    free = {}
    for name, alloc in nodes.items():
        if alloc is None:
            free[name] = [fallback * 1000, None, None]
        else:
            free[name] = [milli(alloc["pods"])] + [
                milli(alloc[r]) if r in alloc else None for r in ("cpu", "memory")]
    for node, requests in bound:
        took = [1000, milli(requests.get("cpu", 0)), milli(requests.get("memory", 0))]
        free[node] = [None if f is None else f - t
                      for f, t in zip(free[node], took)]
    order = list(nodes)
    plan = {}
    for name, requests in pods:
        want = [1000, milli(requests.get("cpu", 0)), milli(requests.get("memory", 0))]
        best = None
        for node in order:
            if node not in values or values[node] > forbidden_over:
                continue
            if any(f is not None and w > 0 and f < w
                   for f, w in zip(free[node], want)):
                continue
            if best is None or values[node] > values[best]:
                best = node
        plan[name] = best
        if best is not None:
            free[best] = [None if f is None else f - w
                          for f, w in zip(free[best], want)]
    return plan


#: request vectors with no common factor among them: 1500m, 1Gi + 1 byte
CLASSES = (
    {"cpu": "500m", "memory": "2Gi"}, {"cpu": "1", "memory": "4Gi"},
    {"cpu": "1500m", "memory": str(GI + 1)}, {"cpu": "4", "memory": "16Gi"},
    {"cpu": "2", "memory": "48Gi"}, {"cpu": "8"}, {"memory": "3Gi"}, {},
)
SHAPES = (
    {"pods": "110", "cpu": "96", "memory": "512Gi"},
    {"pods": "12", "cpu": "16", "memory": "64Gi"},
    {"pods": "110", "cpu": "8", "memory": "256Gi"},
    {"pods": "20", "memory": "32Gi"},  # reports no cpu: not limited by it
    {"pods": "10", "cpu": "24"},  # reports no memory
    None,  # allocatable never seen: the fallback's pod slots
)


def mixed_world(seed, classes=CLASSES, n_nodes=11, n_pods=300, n_bound=30):
    rng = np.random.default_rng(seed)
    nodes = {f"n{i:02d}": SHAPES[int(rng.integers(0, len(SHAPES)))]
             for i in range(n_nodes)}
    ranks = rng.permutation(n_nodes) * 10
    values = {name: int(ranks[i]) for i, name in enumerate(nodes)}
    values.pop("n03")  # a node that does not report the metric
    bound = [(f"n{int(rng.integers(0, n_nodes)):02d}",
              classes[int(rng.integers(0, len(classes)))])
             for _ in range(n_bound)]
    pods = [(f"p{i:03d}", classes[int(rng.integers(0, len(classes)))])
            for i in range(n_pods)]
    return nodes, values, bound, pods


def feed(planner, cache, nodes, values, bound, pods, forbidden_over):
    write_policy(cache, "mix-pol", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", forbidden_over)])
    cache.write_metric("m", metric_info(**values))
    for name, alloc in nodes.items():
        if alloc is not None:
            planner.node_changed(make_node(name, allocatable=alloc))
    for i, (node, requests) in enumerate(bound):
        planner.pod_observed(make_pod(
            f"b{i:03d}", node_name=node, container_requests=[requests]))
    for name, requests in pods:
        planner.pod_added(pending(name, "mix-pol", **requests))


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_the_plan_for_unlike_pods_is_the_plain_references(seed, monkeypatch):
    monkeypatch.setattr(planner_module, "PAD_FLOOR", 64)
    nodes, values, bound, pods = mixed_world(seed)
    cache, mirror, planner = build(node_capacity=4)
    # the mirror interns nodes in the metric's order: rank ties cannot arise
    # (values are distinct), so the plain loop's node order does not matter
    feed(planner, cache, nodes, values, bound, pods, forbidden_over=200)
    before = now()
    want = plain_plan(nodes, bound, pods, values, 200, fallback=4)
    planned = planner.replan()
    assert planned == sum(1 for node in want.values() if node is not None)
    got = {name: planner.planned_node(pending(name, "mix-pol")) for name, _ in pods}
    assert got == want
    assert len(set(want.values())) > 5 and None in want.values()
    # the mechanism engaged, was timed, and said how many classes it saw
    assert moved("pas_planner_demand_solves_total", before) == 1
    assert moved("pas_planner_room_seconds_total", before) > 0
    assert moved("pas_planner_conservative_room_total", before) == 0
    assert trace.COUNTERS.get("pas_planner_demand_classes") == len(
        {(milli(r.get("cpu", 0)), milli(r.get("memory", 0))) for _, r in pods})
    # memory in milli-bytes with a 1 Gi + 1 byte pod shares no factor: the
    # rows ride as two limbs, and whole-Gi pods alone as one
    _state, batch, *_ = planner._snapshot()
    assert batch.demand.shape[1:] == (2, 3)


def test_whole_unit_requests_ride_in_one_limb(monkeypatch):
    monkeypatch.setattr(planner_module, "PAD_FLOOR", 64)
    whole = tuple(c for c in CLASSES if c.get("memory") != str(GI + 1))
    nodes, values, bound, pods = mixed_world(21, classes=whole)
    cache, mirror, planner = build(node_capacity=4)
    feed(planner, cache, nodes, values, bound, pods, forbidden_over=200)
    state, batch, *_ = planner._snapshot()
    assert batch.demand.shape == (512, 1, 3)
    assert state.capacity.shape[:2] == (1, 3)
    room = np.asarray(state.capacity)
    # divided by each row's gcd: pod slots as pods, memory in Gi; a node
    # that reports no memory has the pending set's total, which is as good
    # as no limit
    total = sum(milli(r.get("memory", 0)) for _, r in pods) // (GI * 1000)
    assert room[0, 0].max() <= 110
    assert set(room[0, 2][room[0, 2] > 512].tolist()) == {total}
    planner.replan()
    want = plain_plan(nodes, bound, pods, values, 200, fallback=4)
    assert {name: planner.planned_node(pending(name, "mix-pol"))
            for name, _ in pods} == want


@pytest.mark.parametrize("requests", [
    {"cpu": "100m", "memory": "500Mi"}, {"cpu": "1500m", "memory": str(GI + 1)}, {},
])
def test_alike_pods_are_planned_as_before_by_a_count(requests, monkeypatch):
    """One request vector in the pending set: the count form, the program
    that ran before, and the plan the plain loop gives — whatever the bound
    pods asked for."""
    monkeypatch.setattr(planner_module, "PAD_FLOOR", 64)
    nodes, values, bound, _pods = mixed_world(31)
    pods = [(f"p{i:03d}", requests) for i in range(150)]
    cache, mirror, planner = build(node_capacity=4)
    feed(planner, cache, nodes, values, bound, pods, forbidden_over=200)
    before = now()
    state, batch, *_ = planner._snapshot()
    assert batch.demand is None
    assert state.capacity.shape == (mirror.device_view().node_capacity,)
    planner.replan()
    want = plain_plan(nodes, bound, pods, values, 200, fallback=4)
    assert {name: planner.planned_node(pending(name, "mix-pol"))
            for name, _ in pods} == want
    assert moved("pas_planner_demand_solves_total", before) == 0
    assert moved("pas_planner_conservative_room_total", before) == 0
    assert moved("pas_planner_room_seconds_total", before) > 0
    assert trace.COUNTERS.get("pas_planner_demand_classes") == 1


def test_a_drain_of_unlike_pods_compiles_no_more_than_the_padded_sizes(monkeypatch):
    monkeypatch.setattr(planner_module, "PAD_FLOOR", 8)
    cache, mirror, planner = build(node_capacity=1000)
    write_policy(cache, "drain-mix", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", 900)])
    cache.write_metric("m", metric_info(**{f"n{i}": 100 - i for i in range(6)}))
    pods = [pending(f"p{i:03d}", "drain-mix", cpu=f"{1 + i % 3}")
            for i in range(300)]
    for pod in pods:
        planner.pod_added(pod)
    step = batch_scheduler._scheduling_step
    before = step.cache_size()
    assert planner.replan() == 300
    assert 1 <= step.cache_size() - before <= 7
    after_first = step.cache_size()
    for left in (299, 257, 130, 65, 33, 17, 9, 8, 3):
        for pod in pods[: 300 - left]:
            planner.pod_bound(pod)
        assert planner.replan() == left
    assert step.cache_size() == after_first  # the drain compiled nothing


def test_a_drain_of_unlike_pods_on_the_mesh_compiles_no_more_than_the_padded_sizes(
        monkeypatch):
    """``_warm_smaller`` compiles the mesh's demand shapes below the first
    size, placed as a replan places them: the drain compiles nothing."""
    four_devices()
    monkeypatch.setattr(planner_module, "PAD_FLOOR", 8)
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    planner = BatchPlanner(cache, mirror, node_capacity=1000, devices=DEVICES)
    write_policy(cache, "drain-mix", rule("m", "GreaterThan", 0),
                 [rule("m", "GreaterThan", 900)])
    cache.write_metric("m", metric_info(**{f"n{i}": 100 - i for i in range(6)}))
    pods = [pending(f"p{i:03d}", "drain-mix", cpu=f"{1 + i % 3}")
            for i in range(100)]
    for pod in pods:
        planner.pod_added(pod)
    step = batch_scheduler._mesh_scheduling_step
    before = step.cache_size()
    assert planner.replan() == 100
    assert 1 <= step.cache_size() - before <= 5  # 128, 64, 32, 16, 8
    after_first = step.cache_size()
    for left in (65, 33, 17, 9, 8, 3):
        for pod in pods[: 100 - left]:
            planner.pod_bound(pod)
        assert planner.replan() == left
    assert step.cache_size() == after_first


# -- the mesh books each pod's own vector; sinkhorn takes a count only ------------------


@pytest.mark.parametrize("seed", [41, 11, 12, 13, 14])
def test_the_mesh_plans_unlike_pods_as_the_plain_reference(seed, monkeypatch):
    """``BatchPlanner(devices=4)`` over the worlds of
    ``test_the_plan_for_unlike_pods_is_the_plain_references`` (41: whole
    units, one limb; the others two): each pod books its own vector, the
    plan is ``plain_plan``'s, and no room is conservative."""
    four_devices()
    monkeypatch.setattr(planner_module, "PAD_FLOOR", 64)
    classes = CLASSES
    if seed == 41:
        classes = tuple(c for c in CLASSES if c.get("memory") != str(GI + 1))
    nodes, values, bound, pods = mixed_world(seed, classes=classes)
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    planner = BatchPlanner(cache, mirror, node_capacity=4, devices=DEVICES)
    feed(planner, cache, nodes, values, bound, pods, forbidden_over=200)
    before = now()
    want = plain_plan(nodes, bound, pods, values, 200, fallback=4)
    assert planner.replan() == sum(1 for node in want.values() if node is not None)
    got = {name: planner.planned_node(pending(name, "mix-pol")) for name, _ in pods}
    assert got == want
    assert len(set(want.values())) > 5 and None in want.values()
    assert moved("pas_planner_mesh_demand_solves_total", before) == 1
    assert moved("pas_planner_mesh_demand_seconds_total", before) > 0
    assert moved("pas_planner_demand_solves_total", before) == 1
    assert moved("pas_planner_conservative_room_total", before) == 0
    # the room rows split over the nodes, the demand on every device
    state, batch, *_ = planner._snapshot()
    state, batch = planner._place(state, batch)
    assert batch.demand.shape[1:] == ((1, 3) if seed == 41 else (2, 3))
    assert state.capacity.sharding.spec == (None, None, "nodes")
    assert batch.demand.sharding.is_fully_replicated
    # the one-device planner's plan is the same, and neither counts the mesh
    one = BatchPlanner(cache, mirror, node_capacity=4)
    feed(one, cache, nodes, values, bound, pods, forbidden_over=200)
    before = now()
    one.replan()
    assert one._published[0] == planner._published[0]
    assert moved("pas_planner_mesh_demand_solves_total", before) == 0


@pytest.mark.parametrize("form", ["sinkhorn"])
def test_a_count_only_form_counts_unlike_pods_as_the_largest_and_says_so(
        form, monkeypatch):
    """Sinkhorn, the one form left that takes a count only."""
    monkeypatch.setattr(planner_module, "PAD_FLOOR", 64)
    whole = tuple(c for c in CLASSES if c.get("memory") != str(GI + 1))
    nodes, values, bound, pods = mixed_world(41, classes=whole, n_pods=90)
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    planner = BatchPlanner(cache, mirror, node_capacity=4, solver="sinkhorn")
    feed(planner, cache, nodes, values, bound, pods, forbidden_over=200)
    before = now()
    planner.replan()
    assert moved("pas_planner_conservative_room_total", before) == 1
    assert moved("pas_planner_demand_solves_total", before) == 0
    got = {name: planner.planned_node(pending(name, "mix-pol")) for name, _ in pods}
    # never an overcommit: the true requests fit where the plan put them
    held = {}
    for (name, requests), node in zip(pods, got.values()):
        if node is not None:
            held.setdefault(node, []).append(requests)
    for node, taken in held.items():
        alloc = nodes[node]
        if alloc is None:
            continue
        mine = [r for where, r in bound if where == node] + taken
        for resource in ("cpu", "memory"):
            if resource in alloc:
                assert sum(milli(r.get(resource, 0)) for r in mine) <= milli(
                    alloc[resource]), (node, resource)
        assert len(mine) * 1000 <= milli(alloc["pods"])


def test_the_new_families_are_declared_with_the_stage():
    for name in NEW_COUNTERS + ("pas_planner_demand_classes",):
        assert name in trace.METRICS
    assert trace.METRICS["pas_planner_demand_classes"][0] == "gauge"


# -- the widened kernel, compiled for the chip at the cell's widths -----------------


@pytest.fixture(scope="module")
def v5e_2x2():
    """Four described v5e chips (nothing attached): the TPU's own compiler
    says here what it would say there — a misaligned block, an SMEM operand
    it will not take, too much VMEM, a program too large for a chip."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.mark.parametrize("limbs", [1, 2])
def test_the_demand_kernel_compiles_for_a_v5e_at_the_cells_widths(one_chip, limbs):
    """32,768 rows of 4,096 lanes, R = 3: ``alibaba-colo-4k``'s solve."""
    rows, lanes, held = 32768, 4096, 3 * limbs

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def solve(hi, lo, eligible, room, demand):
        return greedy_assign_pallas(
            i64.I64(hi=hi, lo=lo), eligible, room, demand=demand, limbs=limbs)

    compiled = jax.jit(solve).lower(
        spec((rows, lanes), jnp.int32), spec((rows, lanes), jnp.uint32),
        spec((rows, lanes), jnp.bool_), spec((held, lanes), jnp.int32),
        spec((rows, held), jnp.int32)).compile()
    # the [limbs * R, n] room and the block's score rows fit the 16 MB of VMEM
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def test_the_mesh_demand_solve_compiles_for_four_v5e_chips_at_the_cells_size(v5e_2x2):
    """``alibaba-colo-40k``'s solve: 32,768 rows over 65,536 lanes split on
    four chips, room ``[1, 3, N]`` split with them, demand on every chip.
    It fits a chip (5.37 GB: 0.54 of arguments, 4.83 of temporaries) where
    the whole ``[P, N]`` state is 32 GB, and the block loop's one gather a
    block is there."""
    from jax.sharding import NamedSharding, PartitionSpec

    from platform_aware_scheduling_tpu.ops.rules import RuleSet
    from platform_aware_scheduling_tpu.parallel.mesh import make_mesh

    rows, lanes, metrics, policies, rules = 32768, 65536, 4, 8, 8
    mesh = make_mesh(n_node_shards=DEVICES, devices=v5e_2x2.devices[:DEVICES])

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, PartitionSpec(*axes)))

    def split(shape, dtype):
        return spec(shape, dtype, *(None,) * (len(shape) - 1), "nodes")

    def copied(shape, dtype):
        return spec(shape, dtype)

    state = batch_scheduler.ClusterState(
        metric_values=i64.I64(hi=split((metrics, lanes), jnp.int32),
                              lo=split((metrics, lanes), jnp.uint32)),
        metric_present=split((metrics, lanes), jnp.bool_),
        dontschedule=RuleSet(
            metric_row=copied((policies, rules), jnp.int32),
            op_id=copied((policies, rules), jnp.int32),
            target=i64.I64(hi=copied((policies, rules), jnp.int32),
                           lo=copied((policies, rules), jnp.uint32)),
            active=copied((policies, rules), jnp.bool_)),
        capacity=split((1, 3, lanes), jnp.int32))
    pods = batch_scheduler.PendingPods(
        metric_row=copied((rows,), jnp.int32), op_id=copied((rows,), jnp.int32),
        candidates=split((rows, lanes), jnp.bool_),
        policy=copied((rows,), jnp.int32), demand=copied((rows, 1, 3), jnp.int32))
    compiled = batch_scheduler._mesh_scheduling_step.lower(
        state, pods, mesh=mesh).compile()
    used = compiled.memory_analysis()
    assert used.argument_size_in_bytes + used.temp_size_in_bytes < 8 << 30
    assert "all-gather" in compiled.as_text()


@pytest.mark.parametrize("shape", [(2, 2), (2, 4), (4, 8), (8, 8)])
def test_the_domain_slice_solve_compiles_for_a_v5e_at_the_fleets_size(
    one_chip, shape
):
    """``tpu-v5e-fleet-12k``'s reservation: 199 ICI domains of 8 x 8 hosts,
    padded to 256, one program an orientation; it returns four integers."""
    from platform_aware_scheduling_tpu.ops import topology

    free = jax.ShapeDtypeStruct((256, 8, 8), jnp.bool_, sharding=one_chip)
    compiled = topology._domains_best_anchor.lower(free, *shape).compile()
    used = compiled.memory_analysis()
    assert used.argument_size_in_bytes == 256 * 8 * 8
    assert used.temp_size_in_bytes < 1 << 20
