"""contract.check_line on good and bad result lines, among them the three
ways PR 22's traced GAS run could have been refused."""

import copy
import json

import pytest

import contract

GAS = "gas-pai-1800.filter-bind"
TAS = "tas-shipped-5k.names-wire"


def good_line(benchmark, workload, trace):
    metrics = {name: {"value": 1.5, "unit": unit} for name, unit in
               contract.cell_metrics(benchmark, workload, trace).items()}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 2038272}
    line = {"correct": True, "attempted": 1100, "failed": 0,
            "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=0.0135, window_s=6.0)
        line["breakdown"] = {"device_ops": [["while.30", 0.0115]],
                             "idle_gaps": [["before jit_binpack_kernel", 0.011]]}
    line["compared"] = {"filter_mismatched": {"value": 0, "limit": 0}}
    return line


def reasons(line, benchmark, workload, trace):
    return contract.check_line(json.dumps(line), benchmark, workload, trace, 1)


@pytest.mark.parametrize("workload", [GAS, TAS, "tas-shipped-5k.nodes-wire"])
@pytest.mark.parametrize("trace", [False, True])
def test_good_lines_pass(benchmark, workload, trace):
    assert reasons(good_line(benchmark, workload, trace), benchmark, workload, trace) == []


def test_cells_report_what_the_contract_asks(benchmark):
    for cell in benchmark["workloads"]:
        plain = contract.cell_metrics(benchmark, cell["name"], False)
        traced = contract.cell_metrics(benchmark, cell["name"], True)
        assert "setup_s" in plain and len(plain) >= 2 and traced
        assert ("telemetry_lag_ms" in plain) == cell["name"].startswith("tas-")
    moved = {m["name"]: m for m in benchmark["end_to_end"]}
    for metric in benchmark["per_layer"]:
        # a metric that names no cells is reported wherever what it moves is
        cells = metric.get("workloads") or [
            c["name"] for c in benchmark["workloads"]
            if metric["name"] in contract.cell_metrics(benchmark, c["name"], True)]
        assert cells
        for cell in cells:
            assert metric["moves"] in contract.cell_metrics(benchmark, cell, False)
            assert metric["name"] in contract.cell_metrics(benchmark, cell, True)
        assert metric["moves"] in moved and "mfu" not in metric["name"]
    # one tail metric, under one bound, in every cell
    assert "workloads" not in moved["cycle_p95_ms"]
    assert not [name for name in moved if "." in name]


def broken(line, how):
    line = copy.deepcopy(line)
    how(line)
    return line


PR22_FAILURES = {
    "a metric of the cell is missing":
        lambda l: l["metrics"].pop("binpack_roofline"),
    "busy_s reads 0":
        lambda l: l["device"].update(busy_s=0.0),
    "memory_peak_bytes is missing":
        lambda l: l["device"].pop("memory_peak_bytes"),
}


@pytest.mark.parametrize("what", sorted(PR22_FAILURES))
def test_pr22_traced_gas_failures_are_refused(benchmark, what):
    line = broken(good_line(benchmark, GAS, True), PR22_FAILURES[what])
    assert reasons(line, benchmark, GAS, True), what


OTHER_FAILURES = {
    "busy over window": lambda l: l["device"].update(busy_s=7.0),
    "no window_s": lambda l: l["device"].pop("window_s"),
    "value is a string": lambda l: l["metrics"]["filter_p50_ms"].update(value="1.5"),
    "value is NaN": lambda l: l["metrics"]["filter_p50_ms"].update(value=float("nan")),
    "wrong unit": lambda l: l["metrics"]["filter_p50_ms"].update(unit="us"),
    "unit with a space": lambda l: l["metrics"]["filter_p50_ms"].update(unit="m s"),
    "metric of another mode": lambda l: l["metrics"].update(
        pods_per_s={"value": 100.0, "unit": "pods/s"}),
    "roofline over 105": lambda l: l["metrics"]["binpack_roofline"].update(value=140.0),
    "roofline of 0": lambda l: l["metrics"]["binpack_roofline"].update(value=0.0),
    "correct is a string": lambda l: l.update(correct="true"),
    "no device": lambda l: l.pop("device"),
    "failed is negative": lambda l: l.update(failed=-1),
    "fewer chips than the cell": lambda l: l["device"].update(count=0),
    "eleven breakdown rows": lambda l: l["breakdown"].update(
        device_ops=[["op", 0.1]] * 11),
    "compared not last": lambda l: l.update(extra=1),
}


@pytest.mark.parametrize("what", sorted(OTHER_FAILURES))
def test_other_bad_lines_are_refused(benchmark, what):
    line = broken(good_line(benchmark, GAS, True), OTHER_FAILURES[what])
    assert reasons(line, benchmark, GAS, True), what


def test_not_json_and_not_an_object(benchmark):
    assert contract.check_line("Traceback (most recent call last):", benchmark, GAS, False)
    assert contract.check_line("[1, 2]", benchmark, GAS, False)


def test_untraced_line_needs_every_end_to_end_metric(benchmark):
    line = good_line(benchmark, TAS, False)
    del line["metrics"]["telemetry_lag_ms"]
    assert reasons(line, benchmark, TAS, False)
    # ... which a GAS cell does not report at all
    assert "telemetry_lag_ms" not in good_line(benchmark, GAS, False)["metrics"]
