"""Every cell end to end on the CPU (child generator, served verbs,
comparison, trace reduction, validator), the faults that must turn
``correct`` false, a throwaway cell added by files alone, and the plain
reference against hand-worked cases."""

import json
import os
import shutil

import numpy as np
import pytest

import contract
import generator
import reference
from conftest import PERFBENCH, ROOT, rehearse

CELLS = ["gas-pai-1800.filter-bind", "tas-shipped-5k.nodes-wire",
         "tas-shipped-5k.names-wire"]
EXIT_REHEARSAL = 4


def within_limits(line: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in line["compared"].values())


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_end_to_end(benchmark, workload, trace):
    code, line, err = rehearse(workload, trace)
    assert code == EXIT_REHEARSAL and line is not None, err[-3000:]
    # a rehearsal never passes for a chip run ...
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    # ... but every number compared is within its limit, and the line is the
    # contract's (a roofline has no peak on a CPU and is left out)
    assert within_limits(line), line["compared"]
    rooflines = {m["name"] for m in benchmark["per_layer"]
                 if m["name"].endswith("_roofline")}
    assert contract.check_line(json.dumps(line), benchmark, workload,
                               bool(trace), optional=rooflines) == []
    assert line["attempted"] > 20 and line["failed"] == 0
    counted = line["counted"]
    if workload.startswith("tas-"):
        assert counted["lag_samples"] >= 4 and counted["prioritizes"] > 5
        assert "perfbench: compared (value, limit):" in err.splitlines()[-1]
    else:
        assert counted["binds"] > 20
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["breakdown"]["device_ops"] and line["breakdown"]["idle_gaps"]
        assert line["metrics"]["device_idle_pct"]["value"] < 100


FAULTS = [
    ("gas-pai-1800.filter-bind", "answer-altered", "filter_mismatched"),
    ("gas-pai-1800.filter-bind", "unbooked-bind", "filter_mismatched"),
    ("tas-shipped-5k.names-wire", "answer-altered", "prioritize_mismatched"),
    ("tas-shipped-5k.names-wire", "stale-round", "rounds_backwards"),
    ("tas-shipped-5k.nodes-wire", "answer-altered", "prioritize_mismatched"),
]


@pytest.mark.parametrize("workload,fault,number", FAULTS)
def test_a_broken_timed_path_is_not_correct(workload, fault, number):
    # stale-round strikes every third 2 s pass: give it a chance or two
    seconds = 9.0 if fault == "stale-round" else 5.0
    code, line, err = rehearse(workload, 0, fault=fault, seconds=seconds)
    assert line is not None, err[-3000:]
    assert not within_limits(line)
    assert line["compared"][number]["value"] > 0, line["compared"]


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A configuration, a traffic mix and a per-layer metric of an existing
    reader kind, each as a new file plus a new BENCHMARK.json entry."""
    root = tmp_path / "checkout"
    shutil.copytree(PERFBENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    os.symlink(os.path.join(ROOT, "platform_aware_scheduling_tpu"),
               root / "platform_aware_scheduling_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
        benchmark = json.load(handle)
    before = {p: (root / "perfbench" / p).read_bytes()
              for p in ("run.py", "generator.py", "readers.py", "reference.py")}

    config = json.loads((root / "perfbench/configs/gas-pai-1800.json").read_text())
    config.update(name="gas-small", nodes=256, rehearsal={"nodes": 96},
                  node_shapes=[{"share": 0.5, "cards": 4}, {"share": 0.5, "cards": 2}])
    config["pod_templates"] = config["pod_templates"][:7]
    (root / "perfbench/configs/gas-small.json").write_text(json.dumps(config))
    traffic = json.loads((root / "perfbench/traffic/filter-bind.json").read_text())
    traffic.update(name="filter-bind-wide")
    traffic["candidates"]["examined_extra_max"] = 3
    (root / "perfbench/traffic/filter-bind-wide.json").write_text(json.dumps(traffic))
    (root / "perfbench/layer_metrics/bind_p50_ms.json").write_text(json.dumps(
        {"reader": {"kind": "client_verb_p50", "verb": "second"}}))

    cell = "gas-small.filter-bind-wide"
    benchmark["configs"].append({
        "name": "gas-small", "source": "a throwaway deployment for this test",
        "file": "perfbench/configs/gas-small.json", "reduced": [], "why": "test"})
    benchmark["workloads"].append({
        "name": cell, "config": "gas-small", "traffic": "filter-bind-wide",
        "chips": 1, "why": "test"})
    benchmark["per_layer"].append({
        "name": "bind_p50_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "served verbs, client side",
        "moves": "cycle_p95_ms", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))

    code, line, err = rehearse(cell, 1, root=str(root))
    assert code == EXIT_REHEARSAL and line is not None, err[-3000:]
    assert within_limits(line) and line["metrics"]["bind_p50_ms"]["value"] > 0
    # the metrics that name no cells came with the cell; those of other cells did not
    assert "stalled_cycles_pct" in line["metrics"]
    assert "gas_device_filter_pct" not in line["metrics"]
    assert line["counted"]["binds"] > 20
    for path, content in before.items():
        assert (root / "perfbench" / path).read_bytes() == content


def test_without_the_program_there_is_no_result(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(PERFBENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    code, line, err = rehearse(CELLS[0], 0, root=str(root))
    assert code not in (0, EXIT_REHEARSAL) and line is None


def test_without_a_tpu_there_is_no_result():
    """No chip, no number: the real command on a CPU-only machine."""
    import subprocess
    import sys

    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 2 and "{" not in done.stdout
    assert "needs 1 TPU chip(s)" in done.stderr


# -- the plain reference against hand-worked cases ---------------------------------


def test_candidate_rule_is_kube_schedulers():
    # numFeasibleNodesToFind: 50 - N/125 percent, at least 5% and 100 nodes
    assert generator.candidates_to_find(5000) == 500
    assert generator.candidates_to_find(1800) == 648
    assert generator.candidates_to_find(99) == 99
    assert generator.candidates_to_find(200) == 100
    assert generator.candidates_to_find(15000) == 750
    starts = generator.window_starts(7, 5000, 500, 20)
    steps = np.diff(starts[:1000]) % 5000
    assert steps.min() >= 500 and steps.max() <= 520 and len(set(starts[:1000])) > 900


def test_a_traffic_file_cannot_set_what_no_code_reads():
    with open(os.path.join(PERFBENCH, "traffic", "names-wire.json")) as handle:
        traffic = json.load(handle)
    assert generator.checked_traffic(dict(traffic)) == traffic
    for wrong in ({"concurrency": 8}, {"loop": "open"}, {"wire": "grpc"},
                  {"cycle": ["filter"]}, {"candidates": {"rule": "all"}},
                  {"churn": {"delete_oldest_over_target": False}}):
        with pytest.raises(ValueError):
            generator.checked_traffic({**traffic, **wrong})


def test_the_node_object_is_sized_by_the_kubelets_cap():
    with open(os.path.join(PERFBENCH, "configs", "tas-shipped-5k.json")) as handle:
        shape = json.load(handle)["node_object"]
    node = generator.node_object(3, 17, "node-00017", shape)
    assert len(node["status"]["images"]) == 50 == shape["images"]
    assert all(len(i["names"]) == 2 for i in node["status"]["images"])
    assert len(node["metadata"]["labels"]) == 11
    assert [c["type"] for c in node["status"]["conditions"]][-1] == "Ready"
    assert node == generator.node_object(3, 17, "node-00017", shape)
    assert 11_000 < len(generator.compact(node)) < 13_500


def test_tas_reference_by_hand():
    # five nodes; dontschedule: mem > 70 OR temp < 10
    columns = {"mem": np.array([10, 80, 30, 71, 70]),
               "temp": np.array([50, 50, 5, 50, 10])}
    rules = [("mem", "GreaterThan", 70), ("temp", "LessThan", 10)]
    violating = reference.tas_violating(rules, columns)
    assert violating.tolist() == [False, True, True, True, False]
    passed, failed = reference.tas_filter(np.array([4, 3, 0, 2]), violating)
    assert passed.tolist() == [4, 0] and failed.tolist() == [3, 2]
    # scheduleonmetric GreaterThan ranks the largest first, scores 10 - rank
    hosts, scores = reference.tas_prioritize(
        np.array([0, 1, 2, 4]), columns["mem"], "GreaterThan")
    assert hosts.tolist() == [1, 4, 2, 0] and scores.tolist() == [10, 9, 8, 7]
    hosts, _ = reference.tas_prioritize(np.array([0, 1, 2, 4]), columns["mem"], "LessThan")
    assert hosts.tolist() == [0, 2, 4, 1]
    # ranks past the eleventh go negative, as upstream's do
    _, scores = reference.tas_prioritize(np.arange(13), np.arange(13), "LessThan")
    assert scores[-1] == -2
    assert reference.rule_holds(np.array([3, 4]), "Equals", 4).tolist() == [False, True]


GAS_CONFIG = {
    "nodes": 2, "node_prefix": "n",
    "node_shapes": [{"share": 0.5, "cards": 2}, {"share": 0.5, "cards": 2}],
    "per_card": {"gpu.intel.com/i915": 2, "gpu.intel.com/millicores": 1000,
                 "gpu.intel.com/memory.max": 100},
    "occupancy_target": 0.0,
    "pod_templates": [
        {"weight": 1, "containers": [{"i915": 1, "millicores": 600, "memory": 10}]},
        {"weight": 1, "containers": [{"i915": 2, "millicores": 800, "memory": 20}]},
        {"weight": 1, "containers": [{"i915": 1, "millicores": 600, "memory": 10},
                                     {"i915": 1, "millicores": 600, "memory": 10}]},
        {"weight": 1, "containers": [{"i915": 1, "millicores": 100, "memory": 95}]},
    ],
}


def test_gas_first_fit_by_hand():
    cluster = reference.GasCluster(GAS_CONFIG, seed=0)
    rows = np.array([0, 1])
    # 600 millicores: card0 of an empty node
    fits, cards = cluster.fit(rows, 0)
    assert fits.all() and cluster.annotation(0, cards[0]) == "card0"
    cluster.book(0, 0, "card0")
    # a second 600 no longer fits card0 (1200 > 1000): first fit moves to card1
    _, cards = cluster.fit(rows, 0)
    assert cluster.annotation(0, cards[0]) == "card1"
    assert cluster.annotation(0, cards[1]) == "card0"
    # i915: 2 splits 800 into two shares of 400; on node 0 card0 has 600 in
    # use, so the first share goes there (1000 <= 1000) and the second cannot
    # (1400 > 1000) and goes to card1; on the empty node both fit card0
    fits, cards = cluster.fit(rows, 1)
    assert fits.all()
    assert cluster.annotation(1, cards[0]) == "card0,card1"
    assert cluster.annotation(1, cards[1]) == "card0,card0"
    # two containers of 600: one per card on the empty node; node 0 has room
    # for only one more 600 (card1), so the pod does not fit there
    fits, cards = cluster.fit(rows, 2)
    assert fits.tolist() == [False, True]
    assert cluster.annotation(2, cards[1]) == "card0|card1"
    # memory binds too: 95 of 100 fits an empty card once, never beside itself
    cluster.book(1, 3, "card0")
    _, cards = cluster.fit(rows, 3)
    assert cluster.annotation(3, cards[1]) == "card1"
    # i915 is a per-card resource as well: capacity 2 shares a card
    cluster.book(1, 3, "card1")
    fits, _ = cluster.fit(rows, 3)
    assert fits.tolist() == [True, False]
    # release gives the room back, and never books below zero
    cluster.book(1, 3, "card1", sign=-1)
    assert cluster.fit(rows, 3)[0].all() and not cluster.over_capacity(1)


def test_gas_prebooking_reaches_the_target_and_repeats():
    with open(os.path.join(PERFBENCH, "configs", "gas-pai-1800.json")) as handle:
        config = generator.sized(json.load(handle), rehearse=True)
    pods, cluster = reference.gas_prebook(config, seed=11)
    again, _ = reference.gas_prebook(config, seed=11)
    assert pods == again and pods != reference.gas_prebook(config, seed=12)[0]
    millicores = cluster.used[:, :, 1].sum()
    total = cluster.ncards.sum() * 1000
    assert 0.60 <= millicores / total < 0.66
    assert not any(cluster.over_capacity(n) for n in range(len(cluster.ncards)))
