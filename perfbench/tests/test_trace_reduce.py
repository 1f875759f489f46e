"""The reduction from a profiler trace to busy time, program times and
rooflines, on two small traces recorded from this PR's first chip runs
(TPU v5 lite; cut to 0.12 s of the GAS cell and one refresh pass of TAS)."""

import gzip
import json
import os

import pytest

import readers
import trace_reduce
import work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def recorded(name):
    with gzip.open(os.path.join(DATA, name), "rt") as handle:
        cut = json.load(handle)
    planes = [{"name": p["name"],
               "lines": {k: [tuple(e) for e in v] for k, v in p["lines"].items()}}
              for p in cut["planes"]]
    return planes, (cut["to_ns"] - cut["from_ns"]) / 1e9


def test_union_merges_overlaps_and_nesting():
    covered, merged = trace_reduce.union([(0, 10), (2, 5), (8, 14), (20, 21)])
    assert covered == 15 and merged == [[0, 14], [20, 21]]


def test_names():
    assert trace_reduce.module_name("jit__prioritize_kernel(1234567)") == "jit__prioritize_kernel"
    assert trace_reduce.module_name("PjitFunction(binpack_kernel)") == "jit_binpack_kernel"
    assert trace_reduce.op_name("%while.30 = (s32[]{:T(128)}) while(...)") == "while.30"


def test_gas_trace():
    planes, window = recorded("tpu_v5e_gas_filter_bind.json.gz")
    reduced = trace_reduce.reduce_trace(planes, window)
    assert not reduced["stand_in"] and reduced["devices"] == 1
    runs, seconds = trace_reduce.module_time(reduced, "^jit_binpack_kernel$")
    assert runs == 13 and seconds == pytest.approx(237.5e-6, rel=1e-3)
    # busy is the union of operations: at most the programs' own time
    assert 0 < reduced["busy_s"] <= seconds < window
    assert reduced["busy_s"] == pytest.approx(233.2e-6, rel=1e-3)
    assert len(reduced["device_ops"]) <= 10 and reduced["device_ops"][0][0].startswith("while")
    assert len(reduced["idle_gaps"]) <= 10
    # the longest gaps lie between two Filters' solves, one cycle apart
    assert all(gap[0].startswith("before jit_binpack_kernel; host: ")
               for gap in reduced["idle_gaps"][:5])
    assert reduced["idle_gaps"][0][1] == pytest.approx(9.76e-3, rel=1e-2)


def test_tas_trace_and_rooflines():
    planes, window = recorded("tpu_v5e_tas_names_wire.json.gz")
    reduced = trace_reduce.reduce_trace(planes, window)
    assert trace_reduce.module_time(reduced, "^jit__?prioritize_kernel$")[0] == 4
    assert trace_reduce.module_time(reduced, "^jit__filter_explain_kernel$")[0] == 5
    ctx = {"trace": reduced, "device_kind": "TPU v5 lite",
           "sizes": {"nodes": 5000}}
    share = readers.module_roofline(
        {"pattern": "^jit__?prioritize_kernel$", "work": "rank"}, ctx)
    # 4 rankings of 5,000 nodes are 65 KB each: 79 ns at 819 GB/s, 50 us measured
    assert share == pytest.approx(0.158, rel=0.02)
    assert readers.module_roofline({"pattern": "^jit_no_such$", "work": "rank"}, ctx) is None
    idle = readers.device_idle({}, ctx)
    assert 99.9 < idle < 100


def test_binpack_roofline_from_logical_sizes():
    planes, window = recorded("tpu_v5e_gas_filter_bind.json.gz")
    ctx = {"trace": trace_reduce.reduce_trace(planes, window),
           "device_kind": "TPU v5 lite",
           "sizes": {"nodes": 1800, "candidates": 648, "cards_mean": 3.5,
                     "shares_mean": 1.45, "resources": 3}}
    share = readers.module_roofline(
        {"pattern": "^jit_binpack_kernel$", "work": "binpack"}, ctx)
    assert share == pytest.approx(0.4717, rel=0.02)
    least, bound = work.roofline_seconds(work.binpack(ctx["sizes"]),
                                         work.peaks("TPU v5 lite"))
    assert bound == "bytes" and least == pytest.approx(86.2e-9, rel=0.01)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
    with pytest.raises(KeyError):
        work.peaks("_source")


def test_a_trace_without_device_work_is_refused():
    planes, window = recorded("tpu_v5e_tas_names_wire.json.gz")
    host_only = [p for p in planes if p["name"] != "/device:TPU:0"]
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce_trace(host_only, window)
