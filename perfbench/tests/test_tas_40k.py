"""``tas-40k.backlog-drain``: the deployment is added by new files and new
entries alone, the cell rehearses end to end on four host CPU devices, its
controls read ``correct: false``, a program from before the mesh path (or a
JAX with too few devices) is refused at once, and the new reader kind holds
the work to all the chips the program spans."""

import json
import os
import subprocess
import sys

import pytest

import batch_world
import contract
import generator
import plugins
import readers
import work
from conftest import PERFBENCH, ROOT, rehearse

CELL = "tas-40k.backlog-drain"
PARENT = "ba93489e2769485848cae5c4f19b87ebe1328895"  # PR 32, this PR's parent
EXIT_REHEARSAL = 4
FOUR = "--xla_force_host_platform_device_count=4"
NEW_METRICS = ("mesh_replan_ms", "mesh_solve_pct", "mesh_place_pct",
               "mesh_plan_current_pct", "mesh_plan_roofline")
NEW_FILES = {
    "perfbench/configs/tas-40k.json",
    "perfbench/assemblers/tas-planner-mesh.py",
    "perfbench/reader_kinds/mesh_module_roofline.py",
    "perfbench/tests/test_tas_40k.py",
    *(f"perfbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}


@pytest.fixture
def four_devices(monkeypatch):
    """Four host devices stand in for the four chips (the configuration's
    ``rehearsal`` block says so); ``rehearse`` hands the child this
    process's environment."""
    monkeypatch.setenv("XLA_FLAGS", FOUR)


def within_limits(line: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in line["compared"].values())


def git(*args) -> str:
    done = subprocess.run(["git", *args], cwd=ROOT, capture_output=True, text=True)
    if done.returncode:
        pytest.skip(f"no git history to compare with: {done.stderr.strip()[:200]}")
    return done.stdout


# -- the files ----------------------------------------------------------------------


def test_the_deployment_is_new_files_and_new_entries_alone(benchmark):
    """Against the parent commit, where there is a history to ask: under
    ``perfbench/`` this PR only adds, and BENCHMARK.json keeps every entry it
    had, in place, with the new ones at the end of their lists."""
    changed = [line.split("\t") for line in git(
        "diff", "--name-status", PARENT, "--", "perfbench").splitlines()]
    assert {path for status, path in changed if status != "A"} == set()
    assert NEW_FILES <= {path for _status, path in changed} | {
        p for p in NEW_FILES if os.path.isfile(os.path.join(ROOT, p))}
    before = json.loads(git("show", f"{PARENT}:BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert benchmark[key] == before[key]
    for key, added in (("configs", 1), ("workloads", 1), ("per_layer", 5)):
        assert benchmark[key][: len(before[key])] == before[key]
        assert len(benchmark[key]) >= len(before[key]) + added


def test_the_entries_and_the_files_of_the_deployment(benchmark):
    entry = next(c for c in benchmark["configs"] if c["name"] == "tas-40k")
    cell = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert entry["reduced"] == [] and entry["file"] == "perfbench/configs/tas-40k.json"
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tas-40k", "backlog-drain", 4)
    # the benchmark's first four-chip cell, and its only one
    assert [w["name"] for w in benchmark["workloads"] if w["chips"] == 4] == [CELL]
    assert all(len(e["why"]) <= 200 for e in (entry, cell))
    assert len(entry["source"]) <= 200
    named = {m["name"]: m for m in benchmark["per_layer"]}
    for name in NEW_METRICS:
        assert named[name]["workloads"] == [CELL]
        assert named[name]["moves"] == "cycle_p95_ms"
        assert os.path.isfile(
            os.path.join(PERFBENCH, "layer_metrics", f"{name}.json"))
    assert named["mesh_plan_roofline"]["source"] == "device_trace"
    # what the cell reports: the end-to-end metrics batch-10k reports, and
    # traced the five new metrics beside those every cell owes
    assert set(contract.cell_metrics(benchmark, CELL, False)) == {
        "pods_per_s", "cycle_p95_ms", "setup_s"}
    traced = set(contract.cell_metrics(benchmark, CELL, True))
    assert set(NEW_METRICS) <= traced
    assert not {"plan_roofline", "telemetry_lag_ms", "refresh_pass_ms"} & traced

    with open(os.path.join(ROOT, entry["file"])) as handle:
        config = json.load(handle)
    with open(os.path.join(PERFBENCH, "configs", "batch-10k.json")) as handle:
        batch = json.load(handle)
    assert config["source"] == entry["source"]
    assert config["reduced"] == [] and config["architecture"] is None
    assert (config["assembler"], config["planner_devices"]) == ("tas-planner-mesh", 4)
    assert (config["nodes"], config["init_pods"], config["measure_pods"]) == (
        40000, 8000, 30000)
    # the rest as batch-10k's: shapes, metrics, policies, sync, guarantees
    for key in ("node_prefix", "node_allocatable", "pod_requests", "batch_planner",
                "metrics", "value_step", "sync_period_s", "serving", "policies",
                "guarantees"):
        assert config[key] == batch[key], key
    assert batch_world.fit_per_node(config) == 40
    assert {"cluster_size", "node_allocatable", "pod_requests"} <= set(config["cited"])
    assert {"nodes", "measure_pods", "init_pods", "planner_devices"} <= set(
        config["assumed"])
    small = generator.sized(config, True)
    assert (small["nodes"], small["measure_pods"], small["planner_devices"]) == (
        320, 2400, 4)
    # 30,000 pending pods are solved at 32,768 rows: one padded shape all window
    assert 16384 < config["measure_pods"] <= 32768 < config["nodes"] <= 65536


def test_the_reader_holds_the_work_to_every_chip_the_program_spans():
    sizes = {"nodes": 40000, "policies": 3, "pending_mean": 29000.0}
    # two solves traced on each of four chips: eight plane-runs, 0.5 s each
    ctx = {"trace": {"modules": {"jit__mesh_scheduling_step": [8, 4.0],
                                 "jit__scheduling_step": [1, 9.0]},
                     "devices": 4},
           "sizes": sizes, "device_kind": "TPU v5 lite"}
    spec = {"kind": "mesh_module_roofline",
            "pattern": "^jit__?mesh_scheduling_step$", "work": "batch_plan"}
    least, bound = work.roofline_seconds(
        work.function("batch_plan")(sizes), work.peaks("TPU v5 lite"))
    got = plugins.load("reader_kinds", "mesh_module_roofline").read(spec, ctx)
    assert bound == "bytes"
    assert got == pytest.approx(100.0 * (least / 4) / 0.5)
    # the built-in kind reads the same runs as a share of ONE chip's peak
    one_chip = readers.reader("module_roofline")({**spec, "kind": "module_roofline"}, ctx)
    assert got == pytest.approx(one_chip / 4)
    with open(os.path.join(PERFBENCH, "layer_metrics", "mesh_plan_roofline.json")) as handle:
        assert json.load(handle)["reader"] == spec
    # nothing to read — no trace, or a program that names no such module, as
    # the parent's: None, never 0 and never an error
    read = plugins.load("reader_kinds", "mesh_module_roofline").read
    assert read(spec, {**ctx, "trace": None}) is None
    assert read(spec, {**ctx, "trace": {"modules": {"jit__scheduling_step": [1, 9.0]},
                                        "devices": 1}}) is None


# -- the cell, rehearsed ------------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_on_four_host_devices(benchmark, trace, four_devices):
    code, line, err = rehearse(CELL, trace, seconds=6.0)
    assert code == EXIT_REHEARSAL and line is not None, err[-3000:]
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert within_limits(line), line["compared"]
    for number in ("promotions_wrong", "promotions_missing", "room_exceeded",
                   "pods_unplaced", "pods_placed_twice", "dontschedule_violated",
                   "window_without_replan", "compiled_in_window",
                   "retraced_in_window", "host_fallbacks", "refresh_errors"):
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
    if trace:
        metrics = line["metrics"]
        assert "telemetry_lag_ms" not in metrics and "plan_roofline" not in metrics
        assert metrics["mesh_replan_ms"]["value"] > 0
        assert 0 < metrics["mesh_solve_pct"]["value"] <= 100
        assert 0 < metrics["mesh_place_pct"]["value"] < 100
        assert metrics["mesh_solve_pct"]["value"] + metrics["mesh_place_pct"][
            "value"] <= 100
        assert metrics["mesh_plan_current_pct"]["value"] == pytest.approx(
            100.0 * counted["led"] / line["attempted"])
        for name in ("filter_p50_ms", "second_verb_p50_ms", "cycle_p50_ms",
                     "stalled_cycles_pct", "frontend_read_ms",
                     "frontend_write_ms", "device_idle_pct"):
            assert name in metrics
        # the mesh program ran inside the traced part, under the name the
        # roofline's pattern reads (its share needs a chip's peak)
        assert "jit__mesh_scheduling_step" in err
        assert "jit__scheduling_step\"" not in err
    else:
        assert set(line["metrics"]) == {"pods_per_s", "cycle_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault,number", [
    ("plan-shifted", "promotions_wrong"),
    ("plan-dropped", "promotions_missing"),
])
def test_a_planted_fault_is_not_correct(fault, number, four_devices):
    code, line, err = rehearse(CELL, 0, fault=fault, seconds=6.0)
    assert line is not None, err[-3000:]
    assert not within_limits(line)
    assert line["compared"][number]["value"] > 0, line["compared"]


# -- what cannot run the cell is refused at once --------------------------------------


def test_too_few_devices_are_refused_at_assembly(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one host device
    code, line, err = rehearse(CELL, 0, seconds=3.0)
    assert code == 1 and line is None
    assert "the planner's mesh needs 4 devices; JAX has 1" in err


def test_a_program_from_before_the_mesh_path_is_refused_at_once():
    """The parent's ``cmd.tas.assemble`` takes no ``planner_devices``: the
    assembler raises before it assembles anything (so ``run.py`` exits 1 with
    no chip work behind it)."""
    script = f"""
import json, sys, time
sys.path[:0] = [{ROOT!r}, {PERFBENCH!r}]
import plugins
from platform_aware_scheduling_tpu.cmd import tas

def parents(kube_client, metrics_client, sync_period_s, enable_device_path=True,
            enable_batch_planner=False, batch_solver="greedy",
            node_cache_capable=False):
    raise AssertionError("assembled")

tas.assemble = parents
config = json.load(open({os.path.join(PERFBENCH, 'configs', 'tas-40k.json')!r}))
began = time.monotonic()
try:
    plugins.load("assemblers", "tas-planner-mesh").assemble(
        config, {{"wire": "names"}}, 1, 2)
except RuntimeError as exc:
    print("REFUSED", round(time.monotonic() - began, 3), exc)
"""
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": FOUR})
    assert done.stdout.startswith("REFUSED"), done.stderr[-2000:]
    assert "takes no planner_devices" in done.stdout
    assert float(done.stdout.split()[1]) < 5.0
