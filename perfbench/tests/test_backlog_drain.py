"""``batch-10k.backlog-drain``: the cell end to end on the CPU, its controls,
and the plan's plain reference against hand-worked cases."""

import json
import os

import numpy as np
import pytest

import batch_world
import contract
import generator
import plan_reference
import plugins
import reference
from conftest import PERFBENCH, rehearse

CELL = "batch-10k.backlog-drain"
EXIT_REHEARSAL = 4


def within_limits(line: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in line["compared"].values())


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_end_to_end(benchmark, trace):
    code, line, err = rehearse(CELL, trace, seconds=6.0)
    assert code == EXIT_REHEARSAL and line is not None, err[-3000:]
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert within_limits(line), line["compared"]
    for number in ("promotions_wrong", "promotions_missing", "room_exceeded",
                   "pods_unplaced", "pods_placed_twice", "dontschedule_violated",
                   "compiled_in_window", "retraced_in_window", "host_fallbacks"):
        assert line["compared"][number] == {"value": 0, "limit": 0}
    rooflines = {m["name"] for m in benchmark["per_layer"]
                 if m["name"].endswith("_roofline")}
    assert contract.check_line(json.dumps(line), benchmark, CELL, bool(trace),
                               optional=rooflines) == []
    counted = line["counted"]
    assert line["attempted"] > 200 and line["failed"] == 0
    assert counted["bindings"] == counted["prioritizes"] == line["attempted"]
    assert counted["kept"] > 20 and counted["nodes_filled"] > 10
    # the window held replans, and the answers given while a plan was
    # certainly current carried its node
    assert counted["plan_current"] > line["attempted"] / 2
    assert counted["plan_followed"] == counted["plan_current"]
    # ... and the program's own count of the answers it led with a plan's
    # node covers them; those the plan changed are the few the reference saw
    assert counted["plan_current"] <= counted["led"] <= line["attempted"]
    assert counted["promoted"] <= counted["reordered"] <= counted["led"]
    if trace:
        metrics = line["metrics"]
        assert "telemetry_lag_ms" not in metrics and "refresh_pass_ms" not in metrics
        # the share of answers the plan changed: next to none where the
        # plan's node is the ordinal ranking's first host (PERF.md §6, PR 29)
        assert metrics["plan_applied_pct"]["value"] == pytest.approx(
            100.0 * counted["reordered"] / line["attempted"])
        assert 0 < metrics["plan_solve_pct"]["value"] <= 100
        assert metrics["plan_replan_ms"]["value"] > 0
        for name in ("filter_p50_ms", "second_verb_p50_ms", "cycle_p50_ms",
                     "stalled_cycles_pct", "frontend_read_ms",
                     "frontend_write_ms", "device_idle_pct"):
            assert name in metrics
    else:
        assert set(line["metrics"]) == {"pods_per_s", "cycle_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault,numbers", [
    ("plan-shifted", ("promotions_wrong",)),
    ("plan-dropped", ("promotions_missing",)),
    # the top two hosts swapped: a kept answer is no ranking at all, any other
    # reads as the promotion of a node that is no plan's (one of the two)
    ("answer-altered", ("prioritize_mismatched", "promotions_wrong")),
    # a metric's older values: its rounds go backwards on the wire, or — struck
    # before the window, where an older round is still admissible — the plan
    # is not the one the rounds in force give
    ("stale-round", ("rounds_backwards", "promotions_missing", "promotions_wrong")),
])
def test_a_broken_timed_path_is_not_correct(fault, numbers):
    seconds = 9.0 if fault == "stale-round" else 6.0
    code, line, err = rehearse(CELL, 0, fault=fault, seconds=seconds)
    assert line is not None, err[-3000:]
    assert not within_limits(line)
    assert any(line["compared"][n]["value"] > 0 for n in numbers), line["compared"]


def test_a_withheld_plan_entry_changes_no_byte_and_is_missed_all_the_same():
    """Greedy in creation order gives a pod the best node with room, which is
    the first host of the ordinal ranking over the nodes kube-scheduler's Fit
    still offers: an answer is the same bytes with the plan's entry and
    without it.  So ``plan-dropped`` leaves its limit by the program's own
    count of the answers it led with a plan's node, held to the reference's
    count of those that had to be; and by the bytes where earlier pods left
    the plan (the hand-worked cases below, and under ``plan-shifted``)."""
    code, line, err = rehearse(CELL, 0, fault="plan-dropped", seconds=6.0)
    assert line is not None, err[-3000:]
    counted, compared = line["counted"], line["compared"]
    assert counted["promoted"] == 0  # no byte differs
    assert compared["promotions_missing"]["value"] == (
        counted["plan_current"] - counted["led"]) > 0
    assert all(n["value"] == 0 for name, n in compared.items()
               if name != "promotions_missing"), compared
    code, line, err = rehearse(CELL, 0, fault="plan-shifted", seconds=6.0)
    assert line["compared"]["promotions_missing"]["value"] > 0
    assert line["counted"]["promoted"] > line["compared"]["promotions_wrong"]["value"]


def test_the_files_of_the_deployment():
    with open(os.path.join(PERFBENCH, "configs", "batch-10k.json")) as handle:
        config = json.load(handle)
    assert config["reduced"] == [] and config["architecture"] is None
    assert {"workload", "node_allocatable", "pod_requests", "fit_per_node"} <= set(
        config["cited"])
    assert {"policy_labels", "top_scored_host", "percentage_of_nodes_to_score"} <= set(
        config["assumed"])
    assert (config["nodes"], config["init_pods"], config["measure_pods"]) == (
        5000, 1000, 10000)
    # 4 cpu / 100m = 40; 32Gi / 500Mi = 65; pods 110
    assert batch_world.fit_per_node(config) == 40
    assert batch_world.fit_per_node(generator.sized(config, True)) == 10
    with open(os.path.join(PERFBENCH, "traffic", "backlog-drain.json")) as handle:
        traffic = json.load(handle)
    assert generator.checked_traffic(dict(traffic)) == traffic
    for wrong in ({"percentage_of_nodes_to_score": "100"}, {"bind_threads": 2}):
        with pytest.raises(ValueError):
            generator.checked_traffic({**traffic, **wrong})
    # the reference imports nothing of the program, nor does the driver
    for name in ("plan_reference.py", "batch_world.py", "drivers/backlog.py"):
        with open(os.path.join(PERFBENCH, name)) as handle:
            source = handle.read()
        assert "platform_aware_scheduling_tpu" not in source and "jax" not in source
    assert plugins.load("reader_kinds", "counter_mean").read(
        {"seconds": ["s"], "count": ["c"]},
        {"counters": ({"s": 1.0, "c": 2}, {"s": 1.5, "c": 12})}) == 50.0
    assert plugins.load("reader_kinds", "counter_mean").read(
        {"seconds": ["s"], "count": ["c"]}, {"counters": ({}, {"s": 1.0})}) is None
    work = plugins.load("work_functions", "batch_plan").work(
        {"nodes": 5000, "policies": 3, "pending_mean": 6000.0})
    assert work["bytes"] == 3 * 5000 * 9 + 5000 * 4 + 6000 * 4


# -- the plan's reference against hand-worked cases -----------------------------------


def test_a_plan_is_one_pointer_a_policy():
    # two policies; policy 0 ranks nodes 2, 0, 1, 3; policy 1 ranks 2, 3
    ranked = [np.array([2, 0, 1, 3]), np.array([2, 3])]
    which = np.array([0, 1, 0, 0, 1, 1, 0])
    room = np.array([1, 5, 2, 1])
    plan = plan_reference.Plan(np.arange(7), which, ranked, room.copy())
    # pod 3 asked first: pods 0..3 are planned on the way, in creation order
    assert plan.node_of(3) == 1 and plan.done == 4
    assert [plan.node_of(i) for i in range(7)] == [2, 2, 0, 1, 3, None, 1]
    # a pod that is bound in this state has no node, and costs nothing
    bound = plan_reference.Plan(np.array([2, 3]), which, ranked, room.copy())
    assert bound.node_of(0) is None and bound.done == 0
    assert bound.node_of(3) == 2 and bound.node_of(2) == 2


HAND = {
    "nodes": 4, "node_prefix": "n", "metrics": ["m0", "m1"], "value_step": 10,
    "node_allocatable": {"pods": "110", "cpu": "200m", "memory": "32Gi"},
    "pod_requests": {"cpu": "100m", "memory": "500Mi"},
    "init_pods": 0, "measure_pods": 6,
    "policies": [{"name": "p", "strategies": {
        "scheduleonmetric": [
            {"metric": "m0", "operator": "GreaterThan", "top_share": 0.0}],
        "dontschedule": [
            {"metric": "m1", "operator": "GreaterThan", "top_share": 2.0}]}}],
}


def hand_record(index, sent, top, bound_on=None, order=None):
    record = {
        "index": index, "which": 0, "start": 0, "count": 4, "gone": 0,
        "t": [sent, sent + 0.1, sent + 0.2, sent + 0.3], "status": [200, 200],
        "second": "prioritize", "node": top, "error": "",
        "bind_t": [sent + 0.4, sent + 0.5], "bind_status": 201,
        "passed": np.arange(4, dtype=np.int32),
        "failed": np.array([], dtype=np.int32),
    }
    if order is not None:
        record["order"] = np.array(order, dtype=np.int32)
        record["scores"] = 10 - np.arange(4, dtype=np.int32)
    record["bound_on"] = top if bound_on is None else bound_on
    return record


@pytest.mark.parametrize("pod2,wrong,missing", [
    ("plan", 1, 0),     # pod 2 answered with the plan's node first
    ("ordinal", 1, 1),  # ... with the plain ranking, a current plan withheld
    ("other", 2, 0),    # ... with a node first that is no plan's
])
def test_promotions_are_held_to_the_plan_by_hand(pod2, wrong, missing):
    """Two pods fill a node.  One replan (t = 2..3) plans pods 0, 1 on the
    best node A and pods 2, 3 on the next, B.  Pod 1's answer promotes D —
    wrongly — and it is bound there, so A keeps room: for pod 2 the plain
    ranking still leads with A, and the plan says B."""
    seed = 5
    column = generator.metric_round(seed, 0, 0, 4, 10)
    ranking = [int(i) for i in np.argsort(-column)]
    a, b, c, d = ranking
    names = generator.node_names("n", 4)
    fetches = [(1.0, "m0", 0), (1.1, "m1", 0)]

    def promoted(node):
        return [node] + [x for x in ranking if x != node]

    records = [
        hand_record(0, 10, a, order=ranking),
        hand_record(1, 11, d, order=promoted(d)),
        {"plan": hand_record(2, 12, b, order=promoted(b)),
         "ordinal": hand_record(2, 12, a, order=ranking),
         "other": hand_record(2, 12, c)}[pod2],
    ]
    bindings = [(r["bind_t"][0] + 0.05, generator.bench_pod_name(r["index"]),
                 names[r["bound_on"]]) for r in records]
    observed = {generator.bench_pod_name(r["index"]): r["bind_t"][1] + 0.2
                for r in records}
    window = {"began": 9.0, "ended": 13.0, "records": records, "left": []}
    compared = plan_reference.compare(
        HAND, seed, window, fetches, [[2.0, 3.0]], bindings, observed, 0, led=3)
    numbers = compared["numbers"]
    assert numbers["promotions_wrong"] == wrong, compared["notes"]
    assert numbers["promotions_missing"] == missing, compared["notes"]
    assert numbers["prioritize_mismatched"] == numbers["filter_mismatched"] == 0
    assert numbers["room_exceeded"] == numbers["pods_unplaced"] == 0
    # bound twice, and on a node that is full: both counted
    bindings += [(14.0, generator.bench_pod_name(0), names[a]),
                 (14.1, generator.bench_pod_name(4), names[d]),
                 (14.2, generator.bench_pod_name(5), names[d])]
    numbers = plan_reference.compare(
        HAND, seed, window, fetches, [[2.0, 3.0]], bindings, observed,
        0, led=3)["numbers"]
    assert numbers["pods_placed_twice"] == 1 and numbers["room_exceeded"] == 1
    # the program's own count of the answers a plan's node led is held to
    # those that had to be and, by their bytes, were: pods 0 and 2 under
    # "plan", pod 0 alone otherwise (pod 2's is missed by its bytes already)
    owed = 2 if pod2 == "plan" else 1
    for led in (0, 1, 2):
        numbers = plan_reference.compare(
            HAND, seed, window, fetches, [[2.0, 3.0]], bindings, observed,
            0, led=led)["numbers"]
        assert numbers["promotions_missing"] == missing + max(owed - led, 0)


def test_a_stale_plan_owes_no_promotion_by_hand():
    """The same ordinal answer for pod 2, but a fetch of the next round was
    answered before it: the plan may have been dropped, and nothing is owed."""
    seed = 5
    column = generator.metric_round(seed, 0, 0, 4, 10)
    ranking = [int(i) for i in np.argsort(-column)]
    a, _b, _c, d = ranking
    names = generator.node_names("n", 4)
    records = [hand_record(0, 10, a), hand_record(1, 11, d),
               hand_record(2, 12, a, order=ranking)]
    bindings = [(r["bind_t"][0] + 0.05, generator.bench_pod_name(r["index"]),
                 names[r["node"]]) for r in records]
    observed = {generator.bench_pod_name(r["index"]): r["bind_t"][1] + 0.2
                for r in records}
    window = {"began": 9.0, "ended": 13.0, "records": records, "left": []}
    fetches = [(1.0, "m0", 0), (1.1, "m1", 0), (11.9, "m1", 1)]
    numbers = plan_reference.compare(
        HAND, seed, window, fetches, [[2.0, 3.0]], bindings, observed,
        0, led=1)["numbers"]  # pod 0's answer, given before that fetch
    assert numbers["promotions_missing"] == 0 and numbers["promotions_wrong"] == 1
