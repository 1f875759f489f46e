"""The six per-layer metrics of PR 37 and the two reader kinds they brought:
``stage_mean`` (the window-wide counters where the program keeps them, else
the ring's mean) and ``counter_per`` (one counter's increase over another's,
no factor).  Each kind on a hand-made ``ctx``; then one traced CPU rehearsal
of the two cells that between them name all six (no device numbers)."""

import json
import os

import pytest

import contract
import readers
from conftest import PERFBENCH, rehearse

EXIT_REHEARSAL = 4
NEW = {
    "frontend_handle_ms": "stage_mean",
    "wire_scan_ms": "stage_mean",
    "refresh_device_calls_a_publish": "counter_per",
    "tas_filter_native_pct": "counter_ratio",
    "gas_verbs_overlap_pct": "counter_ratio",
    "refresh_parse_pct": "counter_ratio",
}


def spec_of(name: str) -> dict:
    with open(os.path.join(PERFBENCH, "layer_metrics", f"{name}.json")) as handle:
        loaded = json.load(handle)
    assert list(loaded) == ["reader"]
    return loaded["reader"]


def ring(stage: str, values_ms) -> list:
    """A /debug/traces ``recent`` list: one verb span a value, and one GET
    that carries the stage too and must not be read."""
    spans = [{"name": "POST /scheduler/filter",
              "stages": [{"name": "read", "duration_ms": 9.0},
                         {"name": stage, "duration_ms": v}]} for v in values_ms]
    spans.append({"name": "GET /metrics",
                  "stages": [{"name": stage, "duration_ms": 1e6}]})
    return spans


def test_the_files_name_their_kinds():
    for name, kind in NEW.items():
        assert spec_of(name)["kind"] == kind
        assert callable(readers.reader(kind))


def test_stage_mean_takes_the_counters_where_they_moved():
    spec = spec_of("frontend_handle_ms")
    before = {"pas_stage_handle_total": 10.0, "pas_stage_handle_seconds_total": 1.0}
    after = {"pas_stage_handle_total": 14.0, "pas_stage_handle_seconds_total": 1.002}
    ctx = {"counters": (before, after), "stages": ring("handle", [50.0, 70.0])}
    # 2 ms over 4 spans of the WINDOW, not the ring's 60 ms
    assert readers.reader("stage_mean")(spec, ctx) == pytest.approx(0.5)


def test_stage_mean_takes_the_ring_where_the_program_has_no_counters():
    spec = spec_of("wire_scan_ms")
    ctx = {"counters": ({}, {"pas_filter_native_total": 3.0}),
           "stages": ring("scan", [0.25, 0.75])}
    # the parent's program: the same quantity as trace_stage_mean gives
    assert readers.reader("stage_mean")(spec, ctx) == pytest.approx(0.5)
    assert readers.trace_stage_mean(spec, ctx) == pytest.approx(0.5)


def test_stage_mean_takes_the_ring_where_the_counters_stood_still():
    spec = spec_of("wire_scan_ms")
    still = {"pas_stage_scan_total": 7.0, "pas_stage_scan_seconds_total": 0.1}
    ctx = {"counters": (still, dict(still)), "stages": ring("scan", [0.2])}
    assert readers.reader("stage_mean")(spec, ctx) == pytest.approx(0.2)
    ctx["stages"] = []
    assert readers.reader("stage_mean")(spec, ctx) is None  # nothing to read


def test_counter_per_divides_with_no_factor_and_reads_nothing_over_zero():
    spec = spec_of("refresh_device_calls_a_publish")
    before = {"pas_refresh_warm_device_calls_total": 30.0,
              "pas_refresh_warm_total": 10.0}
    after = {"pas_refresh_warm_device_calls_total": 270.0,
             "pas_refresh_warm_total": 90.0}
    read = readers.reader("counter_per")
    assert read(spec, {"counters": (before, after)}) == pytest.approx(3.0)
    assert read(spec, {"counters": (after, after)}) is None
    assert read(spec, {"counters": ({}, {})}) is None


def test_the_overlap_share_reads_zero_not_nothing_in_a_serial_cell():
    spec = spec_of("gas_verbs_overlap_pct")
    ctx = {"counters": ({}, {}), "records": [{"second": "bind"}] * 5}
    assert readers.reader("counter_ratio")(spec, ctx) == 0.0


def test_the_entries_are_appended_and_name_their_cells(benchmark):
    names = [m["name"] for m in benchmark["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    by_name = {m["name"]: m for m in benchmark["per_layer"]}
    assert "workloads" not in by_name["frontend_handle_ms"]
    tas = ["tas-shipped-5k.nodes-wire", "tas-shipped-5k.names-wire"]
    gas = ["gas-pai-1800.filter-bind", "gas-pai-1800.filter-bind-overlap"]
    assert by_name["wire_scan_ms"]["workloads"][:2] == tas
    for name in ("refresh_device_calls_a_publish", "tas_filter_native_pct",
                 "refresh_parse_pct"):
        assert by_name[name]["workloads"] == tas
    assert by_name["gas_verbs_overlap_pct"]["workloads"] == gas
    assert by_name["refresh_device_calls_a_publish"]["unit"] == "calls"
    layers = {m["layer"] for m in benchmark["per_layer"][:-len(NEW)]}
    for name in NEW:  # no new layer: each is one BENCHMARK.json already names
        assert by_name[name]["layer"] in layers


@pytest.mark.parametrize("cell", ["tas-shipped-5k.names-wire",
                                  "gas-pai-1800.filter-bind-overlap"])
def test_a_traced_rehearsal_prints_the_new_names(benchmark, cell):
    code, line, err = rehearse(cell, 1, seconds=6.0, seed=3000000019)
    assert code == EXIT_REHEARSAL and line, err[-3000:]
    named = contract.cell_metrics(benchmark, cell, True)
    expected = [name for name in NEW if name in named]
    assert "frontend_handle_ms" in expected
    assert len(expected) == (5 if cell.startswith("tas-") else 2)
    for name in expected:
        assert line["metrics"][name]["value"] >= 0, sorted(line["metrics"])
    if cell.startswith("tas-"):
        # one round trip a publish since PR 36: 3 device calls
        assert line["metrics"]["refresh_device_calls_a_publish"]["value"] >= 3.0
        assert line["metrics"]["tas_filter_native_pct"]["value"] <= 100.0
    else:
        assert 0.0 <= line["metrics"]["gas_verbs_overlap_pct"]["value"] <= 100.0
