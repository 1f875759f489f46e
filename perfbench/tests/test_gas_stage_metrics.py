"""The three per-layer metrics that read GAS Filter's device stages (PR 27):
one traced rehearsal of the GAS cell on the CPU has to print them; the numbers
are no device numbers."""

import json
import os

from conftest import PERFBENCH, rehearse

EXIT_REHEARSAL = 4
CELL = "gas-pai-1800.filter-bind"
STAGES = {"gas_state_upload_ms": "state_upload", "gas_req_upload_ms": "req_upload",
          "gas_solve_ms": "solve"}


def test_a_traced_rehearsal_of_the_gas_cell_prints_the_stage_metrics():
    code, line, err = rehearse(CELL, 1, seed=2147483693)
    assert code == EXIT_REHEARSAL and line, err[-3000:]
    for name in STAGES:
        assert line["metrics"][name]["unit"] == "ms", sorted(line["metrics"])
        assert line["metrics"][name]["value"] >= 0


def test_the_stage_metrics_are_in_the_benchmark(benchmark):
    names = {m["name"]: m for m in benchmark["per_layer"]}
    for name, stage in STAGES.items():
        assert names[name]["source"] == "program_span"
        assert names[name]["moves"] == "pods_per_s"
        assert names[name]["workloads"] == [CELL]
        with open(os.path.join(PERFBENCH, "layer_metrics", f"{name}.json")) as handle:
            assert json.load(handle) == {
                "reader": {"kind": "trace_stage_mean", "stage": stage}}
