"""``alibaba-colo-40k.mixed-backlog-drain``: the deployment is added by new
files and new entries alone, it is ``alibaba-colo-4k``'s mix at ``tas-40k``'s
scale, the cell rehearses end to end on four host CPU devices with every pod
booking its own vector on the mesh, its control ``room-by-largest`` reaches
the mesh and reads ``correct: false``, and a program whose mesh counts unlike
pods as the largest (or a JAX with too few devices) is refused at once."""

import json
import os
import subprocess
import sys

import pytest

import contract
import generator
import mixed_world
import plugins
from conftest import PERFBENCH, ROOT, rehearse

CELL = "alibaba-colo-40k.mixed-backlog-drain"
PARENT = "5ca00bf4f295a3f15fdb58bd962a69d831d63529"  # PR 38, this PR's parent
EXIT_REHEARSAL = 4
FOUR = "--xla_force_host_platform_device_count=4"
NEW_METRICS = ("mesh_demand_solves_pct", "mesh_demand_replan_ms",
               "mesh_demand_plan_roofline")
NEW_FILES = {
    "perfbench/configs/alibaba-colo-40k.json",
    "perfbench/assemblers/tas-planner-mesh-mixed.py",
    "perfbench/tests/test_alibaba_colo_40k.py",
    *(f"perfbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}
ROOM = ("room_exceeded_pods", "room_exceeded_cpu", "room_exceeded_memory")


@pytest.fixture
def four_devices(monkeypatch):
    """Four host devices stand in for the four chips (the configuration's
    ``rehearsal`` block says so)."""
    monkeypatch.setenv("XLA_FLAGS", FOUR)


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
    for key, added in (("configs", 1), ("workloads", 1), ("per_layer", 3)):
        assert benchmark[key][: len(before[key])] == before[key]
        assert len(benchmark[key]) >= len(before[key]) + added


def test_the_entries_and_the_files_of_the_deployment(benchmark):
    entry = next(c for c in benchmark["configs"] if c["name"] == "alibaba-colo-40k")
    cell = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert entry["reduced"] == []
    assert entry["file"] == "perfbench/configs/alibaba-colo-40k.json"
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "alibaba-colo-40k", "mixed-backlog-drain", 4)
    assert all(len(e["why"]) <= 200 for e in (entry, cell))
    assert len(entry["source"]) <= 200
    # the benchmark's second four-chip cell, of at most half of its cells
    four = [w["name"] for w in benchmark["workloads"] if w["chips"] == 4]
    assert four == ["tas-40k.backlog-drain", CELL]
    assert len(four) <= len(benchmark["workloads"]) // 2
    named = {m["name"]: m for m in benchmark["per_layer"]}
    for name in NEW_METRICS:
        assert named[name]["workloads"] == [CELL]
        assert named[name]["moves"] == "cycle_p95_ms"
        assert set(load("layer_metrics", f"{name}.json")) == {"reader"}
    assert named["mesh_demand_plan_roofline"]["source"] == "device_trace"
    assert named["mesh_demand_plan_roofline"]["layer"] == named[
        "mesh_plan_roofline"]["layer"]
    assert named["mesh_demand_replan_ms"]["layer"] == named["mesh_replan_ms"]["layer"]
    assert load("layer_metrics", "mesh_demand_plan_roofline.json")["reader"] == {
        "kind": "mesh_module_roofline", "pattern": "^jit__?mesh_scheduling_step$",
        "work": "batch_plan_demand"}
    assert set(contract.cell_metrics(benchmark, CELL, False)) == {
        "pods_per_s", "cycle_p95_ms", "setup_s"}
    traced = set(contract.cell_metrics(benchmark, CELL, True))
    assert set(NEW_METRICS) <= traced
    assert not {"mesh_replan_ms", "demand_replan_ms", "plan_roofline",
                "telemetry_lag_ms", "refresh_pass_ms"} & traced

    config, colo, t40 = (load("configs", f"{name}.json") for name in (
        "alibaba-colo-40k", "alibaba-colo-4k", "tas-40k"))
    assert config["source"] == entry["source"]
    assert config["reduced"] == [] and config["architecture"] is None
    assert (config["assembler"], config["planner_devices"]) == (
        "tas-planner-mesh-mixed", 4)
    assert (config["nodes"], config["init_pods"], config["measure_pods"]) == (
        40000, 80000, 30000)
    # alibaba-colo-4k's mix, shares, policies and guarantees, word for word
    for key in ("node_prefix", "node_allocatable", "pod_classes",
                "init_pod_classes", "batch_planner", "metrics", "value_step",
                "sync_period_s", "serving", "policies", "guarantees"):
        assert config[key] == colo[key], key
    # ... at its per-node occupancy: two service pods a node
    assert config["init_pods"] / config["nodes"] == pytest.approx(
        colo["init_pods"] / colo["nodes"], rel=0.01)
    assert config["cited"]["cluster_size"] == t40["cited"]["cluster_size"]
    assert {"nodes", "mix_at_scale", "init_pods", "measure_pods",
            "planner_devices", "pod_classes.share"} <= set(config["assumed"])
    # 30,000 pending pods at 32,768 rows, 40,000 nodes in 65,536 lanes
    assert 16384 < config["measure_pods"] <= 32768 < config["nodes"] <= 65536
    small = generator.sized(config, True)
    assert (small["nodes"], small["planner_devices"]) == (640, 4)
    assert small["nodes"] % (8 * small["planner_devices"]) == 0
    held = mixed_world.initial_held(config, 7)
    assert 0.10 < held[:, 1].sum() / (40000 * 96000) < 0.12  # 11% of the cores


# -- the cell, rehearsed ------------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_on_four_host_devices(benchmark, trace, four_devices):
    code, line, err = rehearse(CELL, trace, seconds=6.0)
    assert code == EXIT_REHEARSAL and line is not None, err[-3000:]
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
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
    assert counted["plan_current"] > 0
    assert counted["plan_followed"] == counted["plan_current"] <= counted["led"]
    for resource in ("cpu", "memory"):
        assert counted[f"left_short_of_{resource}"] > 0
    if trace:
        metrics = line["metrics"]
        assert metrics["mesh_demand_solves_pct"]["value"] == 100.0
        assert metrics["mesh_demand_replan_ms"]["value"] > 0
        assert "mesh_replan_ms" not in metrics and "demand_replan_ms" not in metrics
        assert "jit__mesh_scheduling_step" in err
    else:
        assert set(line["metrics"]) == {"pods_per_s", "cycle_p95_ms", "setup_s"}


def test_the_control_reaches_the_mesh_and_is_not_correct(four_devices):
    code, line, err = rehearse(CELL, 0, fault="room-by-largest", seconds=6.0)
    assert line is not None, err[-3000:]
    assert not within_limits(line)
    assert line["compared"]["promotions_wrong"]["value"] > 100, line["compared"]
    assert all(line["compared"][n]["value"] == 0 for n in ROOM)


# -- what cannot run the cell is refused at once --------------------------------------


def test_too_few_devices_are_refused_at_assembly(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one host device
    code, line, err = rehearse(CELL, 0, seconds=3.0)
    assert code == 1 and line is None
    assert "the planner's mesh needs 4 devices; JAX has 1" in err


def test_a_program_whose_mesh_counts_by_the_largest_is_refused_at_once():
    """The parent declares no ``pas_planner_mesh_demand_solves_total``: the
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
for name in [n for n in trace.METRICS if n.startswith("pas_planner_mesh_demand_")]:
    del trace.METRICS[name]
config = json.load(open({os.path.join(PERFBENCH, 'configs', 'alibaba-colo-40k.json')!r}))
began = time.monotonic()
try:
    plugins.load("assemblers", "tas-planner-mesh-mixed").assemble(
        config, {{"wire": "names"}}, 1, 6)
except RuntimeError as exc:
    print("REFUSED", round(time.monotonic() - began, 3), exc)
"""
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": FOUR})
    assert done.stdout.startswith("REFUSED"), done.stderr[-2000:]
    assert "counts every pod as the largest request pending" in done.stdout
    assert float(done.stdout.split()[1]) < 5.0


def test_the_class_is_both_parents_and_copies_neither():
    module = plugins.load("assemblers", "tas-planner-mesh-mixed")
    mesh = plugins.load("assemblers", "tas-planner-mesh")
    mixed = plugins.load("assemblers", "tas-planner-mixed")
    order = module.MeshMixedPlannerSystem.__mro__
    assert order[1:3] == (mesh.MeshPlannerSystem, mixed.MixedPlannerSystem)
    for name in ("compare", "plant_fault"):
        assert getattr(module.MeshMixedPlannerSystem, name) is getattr(
            mixed.MixedPlannerSystem, name)


def test_the_pods_bound_before_the_warm_up_are_not_counted_as_drained():
    """``pending_mean`` (the roofline's work) counts the bindings of pods that
    were pending, not the init pods the planner's informer fed in."""
    module = plugins.load("assemblers", "tas-planner-mesh-mixed")
    system = object.__new__(module.MeshMixedPlannerSystem)
    config = {"nodes": 40000, "init_pods": 80000, "policies": [{}, {}, {}]}
    system.pending_at_start = 30010
    system.window = (10.0, 50.0)
    system.replans = [[20.0, 20.5], [30.0, 30.5], [60.0, None]]
    system.observed = {f"init-{i:05d}": 1.0 for i in range(80000)}
    system.observed.update({f"bench-{i:05d}": 11.0 + i / 100 for i in range(1500)})
    sizes = system.logical_sizes(config, 40000)
    # 900 of the backlog bound by the first replan, 1,500 by the second
    assert sizes["pending_mean"] == (30010 - 900 + 30010 - 1500) / 2
    assert (sizes["nodes"], sizes["policies"], sizes["resources"]) == (40000, 3, 3)
    assert len(system.observed) == 81500
