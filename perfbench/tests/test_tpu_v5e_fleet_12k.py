"""``tpu-v5e-fleet-12k.gang-backlog-drain``: the deployment is added by new
files and new entries alone, it is 199 TPU v5e pods of 8 x 8 hosts with the
job mix of its configuration, the cell rehearses end to end at six domains
with every compared number 0, the three controls read ``correct: false`` by the
numbers they are for, and a program whose gang tracker knows no ICI domain
and hears no binding from the cluster is refused at once."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import contract
import gang_world
import generator
from conftest import PERFBENCH, ROOT, rehearse

CELL = "tpu-v5e-fleet-12k.gang-backlog-drain"
PARENT = "329de0a2748f55577f86cd1a767039b25b8aeb12"  # this PR's parent
EXIT_REHEARSAL = 4
NEW_METRICS = ("gang_reserve_ms", "gang_overlay_ms", "gang_admitted_pct",
               "topology_roofline")
NEW_FILES = {
    "perfbench/configs/tpu-v5e-fleet-12k.json",
    "perfbench/traffic/gang-backlog-drain.json",
    "perfbench/drivers/backlog-gang.py",
    "perfbench/assemblers/tas-gang.py",
    "perfbench/gang_world.py",
    "perfbench/gang_reference.py",
    "perfbench/work_functions/gang_topology.py",
    "perfbench/tests/test_tpu_v5e_fleet_12k.py",
    *(f"perfbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}
GANG_NUMBERS = ("slice_wrong", "slice_straddles_domain", "slice_not_rectangle",
                "members_off_slice", "reserved_host_taken", "gangs_half_placed",
                "gangs_not_admitted", "gangs_admitted_unbound",
                "slice_not_released")


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
    for key, added in (("configs", 1), ("workloads", 1), ("per_layer", 4)):
        assert benchmark[key][: len(before[key])] == before[key]
        assert len(benchmark[key]) == len(before[key]) + added


def test_the_entries_and_the_files_of_the_deployment(benchmark):
    entry = next(c for c in benchmark["configs"] if c["name"] == "tpu-v5e-fleet-12k")
    cell = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert entry["reduced"] == []
    assert entry["file"] == "perfbench/configs/tpu-v5e-fleet-12k.json"
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpu-v5e-fleet-12k", "gang-backlog-drain", 1)
    assert all(len(e["why"]) <= 200 for e in (entry, cell))
    assert len(entry["source"]) <= 200
    named = {m["name"]: m for m in benchmark["per_layer"]}
    for name in NEW_METRICS:
        assert named[name]["workloads"] == [CELL]
        assert set(load("layer_metrics", f"{name}.json")) == {"reader"}
    assert named["topology_roofline"]["source"] == "device_trace"
    assert load("layer_metrics", "topology_roofline.json")["reader"] == {
        "kind": "module_roofline", "pattern": "^jit__?domains_best_anchor$",
        "work": "gang_topology"}
    assert set(contract.cell_metrics(benchmark, CELL, False)) == {
        "pods_per_s", "cycle_p95_ms", "setup_s"}
    traced = set(contract.cell_metrics(benchmark, CELL, True))
    assert set(NEW_METRICS) | {"device_idle_pct", "frontend_handle_ms",
                               "cycle_p50_ms", "stalled_cycles_pct"} <= traced
    assert not {"telemetry_lag_ms", "refresh_pass_ms", "plan_roofline"} & traced

    config, batch = load("configs", "tpu-v5e-fleet-12k.json"), load(
        "configs", "batch-10k.json")
    assert config["source"] == entry["source"]
    assert config["reduced"] == [] and config["architecture"] is None
    assert config["assembler"] == "tas-gang"
    assert (config["domains"], config["domain_rows"], config["domain_cols"]) == (
        199, 8, 8)
    assert config["nodes"] == 199 * 64 == 12736 < 15000
    assert config["domains"] * 256 == 50944  # the blog's chips
    for key in ("metrics", "value_step", "sync_period_s", "serving"):
        assert config[key] == batch[key], key
    assert [p["name"] for p in config["policies"]] == [
        p["name"] for p in batch["policies"]]
    shapes = gang_world.shapes(config)
    shares = gang_world.shares(config)
    assert shapes == [(1, 1), (2, 2), (2, 4), (4, 4), (4, 8), (8, 8)]
    hosts = sum(s * h * w for s, (h, w) in zip(shares, shapes))
    assert hosts == pytest.approx(7.72)
    assert sum(s * h * w for s, (h, w) in zip(shares, shapes) if h * w > 1) / hosts \
        == pytest.approx(0.95, abs=0.01)
    assert {"job_mix", "start_state", "thresholds", "requeue", "churn",
            "warm_policy"} <= set(config["assumed"])
    small = generator.sized(config, True)
    assert (small["domains"], small["nodes"]) == (6, 384)


def test_the_fleet_at_the_start_is_half_busy_and_churned():
    config = load("configs", "tpu-v5e-fleet-12k.json")
    small = generator.sized(config, True)
    running = gang_world.history(small, 7)
    free = gang_world.free_at_start(small, running)
    assert 0.45 < free.mean() <= 0.55
    taken = np.concatenate([job.hosts for job in running])
    assert len(taken) == len(set(taken.tolist()))
    per_domain = small["domain_rows"] * small["domain_cols"]
    for job in running:  # every running slice lies inside one domain
        assert len(set((job.hosts // per_domain).tolist())) == 1
    jobs = gang_world.backlog(small, 7)
    assert len(jobs) == small["measure_jobs"]
    assert [p for job in jobs for p in job.pods] == [
        generator.bench_pod_name(i) for i in range(sum(j.size for j in jobs))]


# -- the cell, rehearsed ------------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_with_every_compared_number_zero(benchmark, trace):
    code, line, err = rehearse(CELL, trace, seconds=6.0)
    assert code == EXIT_REHEARSAL and line is not None, err[-3000:]
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert within_limits(line), line["compared"]
    for number in (*GANG_NUMBERS, "filter_mismatched", "prioritize_mismatched",
                   "rounds_backwards", "pods_unplaced", "pods_placed_twice",
                   "dontschedule_violated", "candidates_mismatched",
                   "window_without_pass", "compiled_in_window",
                   "retraced_in_window", "host_fallbacks", "device_path_errors",
                   "requests_failed"):
        assert line["compared"][number] == {"value": 0, "limit": 0}
    rooflines = {m["name"] for m in benchmark["per_layer"]
                 if m["name"].endswith("_roofline")}
    assert contract.check_line(json.dumps(line), benchmark, CELL, bool(trace),
                               optional=rooflines) == []
    counted = line["counted"]
    assert line["failed"] == 0
    assert counted["reservations"] > 20 and counted["gangs_deleted"] > 10
    assert counted["admitted"] == counted["gangs_completed"] > 10
    if trace:
        metrics = line["metrics"]
        assert metrics["gang_admitted_pct"]["value"] > 85
        assert metrics["gang_reserve_ms"]["value"] > 0
        assert metrics["gang_overlay_ms"]["value"] > 0
        assert "jit__domains_best_anchor" in err
    else:
        assert set(line["metrics"]) == {"pods_per_s", "cycle_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault, number", [
    ("domain-blind", "slice_straddles_domain"),
    ("bind-unheard", "gangs_not_admitted"),
    ("release-unheard", "slice_not_released"),
])
def test_the_controls_are_not_correct(fault, number):
    code, line, err = rehearse(CELL, 0, fault=fault, seconds=6.0)
    assert line is not None, err[-3000:]
    assert not within_limits(line)
    assert line["compared"][number]["value"] > 0, line["compared"]


# -- what cannot run the cell is refused at once --------------------------------------


def test_a_program_without_domains_or_the_pod_feed_is_refused_at_once():
    """The parent has no ``TPU_DOMAIN_LABEL`` and no ``GangTracker.watch``:
    the assembler raises before it assembles anything."""
    script = f"""
import json, sys, time
sys.path[:0] = [{ROOT!r}, {PERFBENCH!r}]
import plugins
from platform_aware_scheduling_tpu.cmd import tas
from platform_aware_scheduling_tpu.gang import GangTracker
from platform_aware_scheduling_tpu.utils import labels

def parents(*args, **kwargs):
    raise AssertionError("assembled")

tas.assemble = parents
del labels.TPU_DOMAIN_LABEL
del GangTracker.watch
config = json.load(open({os.path.join(PERFBENCH, 'configs', 'tpu-v5e-fleet-12k.json')!r}))
began = time.monotonic()
try:
    plugins.load("assemblers", "tas-gang").assemble(config, {{"wire": "names"}}, 1, 6)
except RuntimeError as exc:
    print("REFUSED", round(time.monotonic() - began, 3), exc)
"""
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.stdout.startswith("REFUSED"), done.stderr[-2000:]
    assert "reads no ICI domain label" in done.stdout
    assert float(done.stdout.split()[1]) < 5.0
