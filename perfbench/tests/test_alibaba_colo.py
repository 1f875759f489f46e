"""``alibaba-colo-4k.mixed-backlog-drain``: the deployment is added by new
files and new entries alone, the cell rehearses end to end on the CPU, its
three controls read ``correct: false``, a program from before the per-pod
room is refused at once, and the plain reference — each pod at its own
requests, in Python integers — is held to hand-worked cases, an overcommit
of each resource among them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import contract
import generator
import mixed_plan_reference
import mixed_world
import plugins
from conftest import PERFBENCH, ROOT, rehearse

CELL = "alibaba-colo-4k.mixed-backlog-drain"
PARENT = "a09e67d81504525253b42a643c762347ecc32ae7"  # PR 34, this PR's parent
EXIT_REHEARSAL = 4
NEW_METRICS = ("demand_replan_ms", "demand_solve_pct", "demand_room_pct",
               "demand_solves_pct", "demand_plan_current_pct",
               "demand_plan_roofline")
NEW_FILES = {
    "perfbench/configs/alibaba-colo-4k.json",
    "perfbench/traffic/mixed-backlog-drain.json",
    "perfbench/mixed_world.py",
    "perfbench/mixed_plan_reference.py",
    "perfbench/drivers/backlog-mixed.py",
    "perfbench/assemblers/tas-planner-mixed.py",
    "perfbench/work_functions/batch_plan_demand.py",
    "perfbench/tests/test_alibaba_colo.py",
    *(f"perfbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}
ROOM = ("room_exceeded_pods", "room_exceeded_cpu", "room_exceeded_memory")


def within_limits(line: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in line["compared"].values())


def git(*args) -> str:
    done = subprocess.run(["git", *args], cwd=ROOT, capture_output=True, text=True)
    if done.returncode:
        pytest.skip(f"no git history to compare with: {done.stderr.strip()[:200]}")
    return done.stdout


def load(*parts) -> dict:
    with open(os.path.join(PERFBENCH, *parts)) as handle:
        return json.load(handle)


# -- the files ----------------------------------------------------------------------


def test_the_deployment_is_new_files_and_new_entries_alone(benchmark):
    changed = [line.split("\t") for line in git(
        "diff", "--name-status", PARENT, "--", "perfbench").splitlines()]
    assert {path for status, path in changed if status != "A"} == set()
    assert NEW_FILES <= {path for _status, path in changed} | {
        p for p in NEW_FILES if os.path.isfile(os.path.join(ROOT, p))}
    before = json.loads(git("show", f"{PARENT}:BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert benchmark[key] == before[key]
    for key, added in (("configs", 1), ("workloads", 1), ("per_layer", 6)):
        assert benchmark[key][: len(before[key])] == before[key]
        assert len(benchmark[key]) >= len(before[key]) + added


def test_the_entries_and_the_files_of_the_deployment(benchmark):
    entry = next(c for c in benchmark["configs"] if c["name"] == "alibaba-colo-4k")
    cell = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert entry["reduced"] == []
    assert entry["file"] == "perfbench/configs/alibaba-colo-4k.json"
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "alibaba-colo-4k", "mixed-backlog-drain", 1)
    assert all(len(e["why"]) <= 200 for e in (entry, cell))
    assert len(entry["source"]) <= 200
    named = {m["name"]: m for m in benchmark["per_layer"]}
    for name in NEW_METRICS:
        assert named[name]["workloads"] == [CELL]
        assert named[name]["moves"] == "cycle_p95_ms"
        assert set(load("layer_metrics", f"{name}.json")) == {"reader"}
    assert named["demand_plan_roofline"]["source"] == "device_trace"
    assert named["demand_plan_roofline"]["layer"] == named["plan_roofline"]["layer"]
    assert set(contract.cell_metrics(benchmark, CELL, False)) == {
        "pods_per_s", "cycle_p95_ms", "setup_s"}
    traced = set(contract.cell_metrics(benchmark, CELL, True))
    assert set(NEW_METRICS) <= traced
    assert not {"plan_roofline", "plan_replan_ms", "mesh_replan_ms",
                "telemetry_lag_ms", "refresh_pass_ms"} & traced

    config, batch = load("configs", "alibaba-colo-4k.json"), load(
        "configs", "batch-10k.json")
    assert config["source"] == entry["source"]
    assert config["reduced"] == [] and config["architecture"] is None
    assert config["assembler"] == "tas-planner-mixed"
    assert (config["nodes"], config["init_pods"], config["measure_pods"]) == (
        4034, 8000, 26000)
    assert config["node_allocatable"] == {
        "pods": "110", "cpu": "96", "memory": "512Gi"}
    assert [(c["name"], c["share"], c["requests"]["cpu"], c["requests"]["memory"])
            for c in config["pod_classes"]] == [
        ("batch-half", 0.30, "500m", "2Gi"), ("batch-one", 0.42, "1", "4Gi"),
        ("svc-4", 0.15, "4", "16Gi"), ("svc-8", 0.08, "8", "32Gi"),
        ("mem-heavy", 0.05, "2", "48Gi")]
    assert config["init_pod_classes"] == [
        {"class": "svc-4", "weight": 2}, {"class": "svc-8", "weight": 1}]
    # TAS as batch-10k runs it; the third guarantee counts each pod's own
    for key in ("node_prefix", "batch_planner", "metrics", "value_step",
                "sync_period_s", "serving", "policies"):
        assert config[key] == batch[key], key
    assert [g for i, g in enumerate(config["guarantees"]) if i != 2] == [
        g for i, g in enumerate(batch["guarantees"]) if i != 2]
    assert "each pod counted at its own requests" in config["guarantees"][2]
    assert all("from memory of the source" in text for key, text in
               config["cited"].items() if key not in (
                   "node_allocatable.pods", "tas", "percentage_of_nodes_to_score"))
    assert {"node_allocatable.memory", "pod_classes.share", "pod_classes.memory",
            "init_pods", "measure_pods"} <= set(config["assumed"])
    # 26,000 pending pods are solved at 32,768 rows, 4,034 nodes in 4,096 lanes
    assert 16384 + 9600 < config["measure_pods"] <= 32768
    assert 2048 < config["nodes"] <= 4096
    small = generator.sized(config, True)
    assert (small["nodes"], small["measure_pods"]) == (320, 2400)
    # the traffic is backlog-drain's but for the driver
    traffic, drain = load("traffic", "mixed-backlog-drain.json"), load(
        "traffic", "backlog-drain.json")
    assert {**traffic, "name": "", "driver": ""} == {**drain, "name": "", "driver": ""}
    assert traffic["driver"] == "backlog-mixed"
    assert generator.checked_traffic(dict(traffic)) == traffic
    # the reference, the world and the driver import nothing of the program
    for name in ("mixed_plan_reference.py", "mixed_world.py",
                 "drivers/backlog-mixed.py", "work_functions/batch_plan_demand.py"):
        with open(os.path.join(PERFBENCH, name)) as handle:
            source = handle.read()
        assert "platform_aware_scheduling_tpu" not in source
        assert "import jax" not in source
    work = plugins.load("work_functions", "batch_plan_demand").work(
        {"nodes": 4034, "policies": 3, "pending_mean": 25000.0, "resources": 3,
         "room_bytes": 4})
    assert work["bytes"] == 3 * 4034 * 9 + 3 * 4034 * 4 + 25000 * 4 * 4


def test_the_world_of_the_seed():
    config = load("configs", "alibaba-colo-4k.json")
    demand, alloc = mixed_world.demands(config), mixed_world.allocatable(config)
    assert demand[:, 0].tolist() == [1000] * 5
    assert demand[:, 1].tolist() == [500, 1000, 4000, 8000, 2000]
    assert demand[4, 2] == 48 * (1 << 30) * 1000 and alloc[2] == 512 * (1 << 30) * 1000
    klass = mixed_world.pod_classes(config, 7)
    assert len(klass) == 26000
    shares = np.bincount(klass, minlength=5) / len(klass)
    assert np.abs(shares - [0.30, 0.42, 0.15, 0.08, 0.05]).max() < 0.01
    # mean demand 1.91 cores and 9.6Gi: cpu and memory bind at about one fill
    mean = demand[klass].mean(axis=0)
    assert 1850 < mean[1] < 1970 and 9.3 < mean[2] / ((1 << 30) * 1000) < 9.9
    held = mixed_world.initial_held(config, 7)
    assert held[:, 0].sum() == 8000 * 1000
    assert set(np.unique(mixed_world.init_pod_classes(config, 7))) == {2, 3}
    assert 0.10 < held[:, 1].sum() / (4034 * 96000) < 0.12  # 11% of the cores
    assert (held <= alloc[None, :]).all()
    tables = {}
    assert mixed_world.class_of(config, 7, "bench-00003", tables) == klass[3]
    assert mixed_world.class_of(config, 7, "warm-00006", tables) == 1
    raw = mixed_world.pod_raw(config, "bench-00003", 4, "bench-less")
    assert raw["spec"]["containers"][0]["resources"]["requests"] == {
        "cpu": "2", "memory": "48Gi"}


# -- the cell, rehearsed ------------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_end_to_end(benchmark, trace):
    code, line, err = rehearse(CELL, trace, seconds=6.0)
    assert code == EXIT_REHEARSAL and line is not None, err[-3000:]
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert within_limits(line), line["compared"]
    for number in ("promotions_wrong", "promotions_missing", *ROOM,
                   "pods_unplaced", "pods_placed_twice", "dontschedule_violated",
                   "candidates_not_fit", "window_without_replan",
                   "compiled_in_window", "retraced_in_window", "host_fallbacks",
                   "refresh_errors", "requests_failed"):
        assert line["compared"][number] == {"value": 0, "limit": 0}
    rooflines = {m["name"] for m in benchmark["per_layer"]
                 if m["name"].endswith("_roofline")}
    assert contract.check_line(json.dumps(line), benchmark, CELL, bool(trace),
                               optional=rooflines) == []
    counted = line["counted"]
    assert line["attempted"] > 200 and line["failed"] == 0
    assert counted["bindings"] == counted["prioritizes"] == line["attempted"]
    assert counted["plan_current"] > line["attempted"] / 2
    assert counted["plan_followed"] == counted["plan_current"] <= counted["led"]
    # nodes left the classes' candidates, and cpu AND memory each bound on a
    # twentieth of those or more
    assert counted["nodes_left_a_class"] > 50
    for resource in ("cpu", "memory"):
        assert counted[f"left_short_of_{resource}"] >= 0.05 * counted[
            "nodes_left_a_class"]
    assert "times a node left a class's candidates" in err
    if trace:
        metrics = line["metrics"]
        assert "telemetry_lag_ms" not in metrics and "plan_replan_ms" not in metrics
        assert metrics["demand_replan_ms"]["value"] > 0
        assert metrics["demand_solves_pct"]["value"] == 100.0
        assert 0 < metrics["demand_room_pct"]["value"] < 100
        assert (metrics["demand_solve_pct"]["value"]
                + metrics["demand_room_pct"]["value"]) <= 100
        assert metrics["demand_plan_current_pct"]["value"] == pytest.approx(
            100.0 * counted["led"] / line["attempted"])
        for name in ("filter_p50_ms", "second_verb_p50_ms", "cycle_p50_ms",
                     "stalled_cycles_pct", "frontend_read_ms",
                     "frontend_write_ms", "device_idle_pct"):
            assert name in metrics
        # the program the roofline's pattern reads ran in the traced part
        assert "jit__scheduling_step" in err
    else:
        assert set(line["metrics"]) == {"pods_per_s", "cycle_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault,number", [
    ("room-by-largest", "promotions_wrong"),
    ("plan-shifted", "promotions_wrong"),
    ("plan-dropped", "promotions_missing"),
])
def test_a_control_is_not_correct(fault, number):
    code, line, err = rehearse(CELL, 0, fault=fault, seconds=6.0)
    assert line is not None, err[-3000:]
    assert not within_limits(line)
    assert line["compared"][number]["value"] > 0, line["compared"]
    if fault == "room-by-largest":
        # the planner as it was: far above 0, and caught by the answers' bytes
        assert line["compared"]["promotions_wrong"]["value"] > 100
        assert line["counted"]["promoted"] > 100
        assert all(line["compared"][n]["value"] == 0 for n in ROOM)


def test_a_program_from_before_the_per_pod_room_is_refused_at_once():
    """The parent declares no ``pas_planner_demand_solves_total``: the
    assembler raises before it assembles anything (``run.py`` then exits 1
    with no chip work behind it)."""
    script = f"""
import json, sys, time
sys.path[:0] = [{ROOT!r}, {PERFBENCH!r}]
import plugins
from platform_aware_scheduling_tpu.cmd import tas
from platform_aware_scheduling_tpu.utils import trace

def parents(*args, **kwargs):
    raise AssertionError("assembled")

tas.assemble = parents
for name in [n for n in trace.METRICS if n.startswith("pas_planner_demand_")]:
    del trace.METRICS[name]
config = json.load(open({os.path.join(PERFBENCH, 'configs', 'alibaba-colo-4k.json')!r}))
began = time.monotonic()
try:
    plugins.load("assemblers", "tas-planner-mixed").assemble(
        config, {{"wire": "names"}}, 1, 6)
except RuntimeError as exc:
    print("REFUSED", round(time.monotonic() - began, 3), exc)
"""
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.stdout.startswith("REFUSED"), done.stderr[-2000:]
    assert "counted as the largest request pending" in done.stdout
    assert float(done.stdout.split()[1]) < 5.0


# -- the plain reference against hand-worked cases ------------------------------------


def test_a_plan_is_one_pointer_a_policy_and_class():
    # one policy ranking nodes 2, 0, 1; class 0 asks 1 cpu, class 1 asks 3
    ranked = [np.array([2, 0, 1])]
    which = np.zeros(6, dtype=np.int64)
    klass = np.array([1, 0, 0, 1, 0, 1])
    demand = [[1000, 1000, 1], [1000, 3000, 1]]

    def free():
        return [[110000] * 3, [4000] * 3, [10] * 3]

    plan = mixed_plan_reference.Plan(
        np.arange(6), which, klass, demand, ranked, free())
    # big on 2 (1 left), small on 2 (0 left), small on 0, big on 0 (0 left),
    # small on 1 (3 left), big on 1 — a node without room for one class had
    # room for the other, and every pod took its own amount
    assert plan.node_of(3) == 0 and plan.done == 4
    assert [plan.node_of(i) for i in range(6)] == [2, 2, 0, 0, 1, 1]
    assert plan.free[1] == [0, 0, 0] and plan.free[2] == [8, 8, 8]
    # the same pods each counted as the largest: one a node, and pod 1 is moved on
    by_largest = mixed_plan_reference.Plan(
        np.arange(6), which, np.ones(6, dtype=np.int64), demand, ranked, free())
    assert [by_largest.node_of(i) for i in range(6)] == [2, 0, 1, None, None, None]
    # a pod that is bound in this state has no node, and costs nothing
    bound = mixed_plan_reference.Plan(
        np.array([2, 3]), which, klass, demand, ranked, free())
    assert bound.node_of(0) is None and bound.done == 0
    assert bound.node_of(3) == 2 and bound.node_of(2) == 2


HAND = {
    "nodes": 4, "node_prefix": "n", "metrics": ["m0", "m1"], "value_step": 10,
    "node_allocatable": {"pods": "3", "cpu": "4", "memory": "16Gi"},
    "pod_classes": [
        {"name": "small", "share": 0.5, "requests": {"cpu": "1", "memory": "1Gi"}},
        {"name": "big", "share": 0.3, "requests": {"cpu": "2", "memory": "1Gi"}},
        {"name": "wide", "share": 0.2, "requests": {"cpu": "100m", "memory": "9Gi"}}],
    "init_pods": 0, "init_pod_classes": [{"class": "small", "weight": 1}],
    "measure_pods": 8,
    "policies": [{"name": "p", "strategies": {
        "scheduleonmetric": [
            {"metric": "m0", "operator": "GreaterThan", "top_share": 0.0}],
        "dontschedule": [
            {"metric": "m1", "operator": "GreaterThan", "top_share": 2.0}]}}],
}


def hand_record(index, klass, sent, top, order=None):
    record = {
        "index": index, "which": 0, "klass": klass, "start": 0, "count": 4,
        "gone": 0, "t": [sent, sent + 0.1, sent + 0.2, sent + 0.3],
        "status": [200, 200], "second": "prioritize", "node": top, "error": "",
        "bind_t": [sent + 0.4, sent + 0.5], "bind_status": 201,
        "passed": np.arange(4, dtype=np.int32),
        "failed": np.array([], dtype=np.int32),
    }
    if order is not None:
        record["order"] = np.array(order, dtype=np.int32)
        record["scores"] = 10 - np.arange(4, dtype=np.int32)
    return record


def hand_case(monkeypatch, classes, tops, orders):
    monkeypatch.setattr(mixed_world, "pod_classes",
                        lambda config, seed: np.array(classes + [0] * 8)[:8])
    names = generator.node_names("n", 4)
    records = [hand_record(i, classes[i], 10 + i, top, order)
               for i, (top, order) in enumerate(zip(tops, orders))]
    bindings = [(r["bind_t"][0] + 0.05, generator.bench_pod_name(r["index"]),
                 names[r["node"]]) for r in records]
    observed = {generator.bench_pod_name(r["index"]): r["bind_t"][1] + 0.2
                for r in records}
    window = {"began": 9.0, "ended": 10.0 + len(records), "records": records,
              "left": [[], [], []], "short": [[], [], []]}
    return window, bindings, observed


@pytest.mark.parametrize("pod2,wrong,missing", [
    ("ordinal", 0, 0),  # the exact plan's node is the ranking's first host
    ("largest", 1, 0),  # the node a room counted by the largest request gives
])
def test_a_plan_by_the_largest_request_is_caught_by_the_answers_bytes(
        pod2, wrong, missing, monkeypatch):
    """A big pod (2 cpu) and two small ones (1 cpu) on nodes of 4 cpu.  The
    exact plan puts all three on the best node A.  Counted by the largest
    request A "has room for two", and the plan moves pod 2 on to B — while
    kube's Fit still offers A, and the pod's own rule ranks it first."""
    seed = 5
    column = generator.metric_round(seed, 0, 0, 4, 10)
    ranking = [int(i) for i in np.argsort(-column)]
    a, b, _c, _d = ranking
    fetches = [(1.0, "m0", 0), (1.1, "m1", 0)]
    promoted = [b] + [x for x in ranking if x != b]
    window, bindings, observed = hand_case(
        monkeypatch, [1, 0, 0],
        [a, a, a if pod2 == "ordinal" else b],
        [ranking, ranking, ranking if pod2 == "ordinal" else promoted])
    compared = mixed_plan_reference.compare(
        HAND, seed, window, fetches, [[2.0, 3.0]], bindings, observed, 0, led=3)
    numbers, counted = compared["numbers"], compared["counted"]
    assert numbers["promotions_wrong"] == wrong, compared["notes"]
    assert numbers["promotions_missing"] == missing, compared["notes"]
    assert numbers["prioritize_mismatched"] == numbers["filter_mismatched"] == 0
    assert [numbers[n] for n in ROOM] == [0, 0, 0]
    assert numbers["pods_unplaced"] == 0
    if pod2 == "ordinal":
        assert counted["plan_current"] == counted["plan_followed"] == 3
        # the program's own count is held to those that had to be
        short = mixed_plan_reference.compare(
            HAND, seed, window, fetches, [[2.0, 3.0]], bindings, observed, 0,
            led=1)["numbers"]
        assert short["promotions_missing"] == 2


@pytest.mark.parametrize("resource,classes", [
    ("pods", [0, 0, 0, 0]),  # four pods where the node takes three
    ("cpu", [1, 1, 0]),      # 2 + 2 + 1 cpu on 4
    ("memory", [2, 2]),      # 9Gi + 9Gi on 16Gi
])
def test_an_overcommit_of_each_resource_is_counted_under_its_own_name(
        resource, classes, monkeypatch):
    seed = 5
    column = generator.metric_round(seed, 0, 0, 4, 10)
    a = int(np.argmax(column))
    window, bindings, observed = hand_case(
        monkeypatch, classes, [a] * len(classes), [None] * len(classes))
    numbers = mixed_plan_reference.compare(
        HAND, seed, window, [(1.0, "m0", 0), (1.1, "m1", 0)], [[2.0, 3.0]],
        bindings, observed, 0, led=len(classes))["numbers"]
    assert {n: numbers[n] for n in ROOM} == {
        n: int(n == f"room_exceeded_{resource}") for n in ROOM}
    # and the driver's candidates were not kube's Fit: the node was offered
    assert numbers["candidates_not_fit"] > 0
