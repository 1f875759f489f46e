"""The per-layer metrics that read the program's span stages (PR 26):
each cell is rehearsed once on the CPU with the profiler on; the numbers
are no device numbers."""

import json
import os
import subprocess
import sys

import pytest

import contract
from conftest import CACHE, PERFBENCH, ROOT

EXIT_REHEARSAL = 4
CELLS = ("gas-pai-1800.filter-bind", "tas-shipped-5k.nodes-wire",
         "tas-shipped-5k.names-wire")
SPAN_METRICS = ("wire_decode_ms", "wire_encode_ms", "tas_kernel_ms",
                "tas_cache_probe_ms")


@pytest.mark.parametrize("cell", CELLS)
def test_a_rehearsal_prints_every_span_metric_named_for_the_cell(
        benchmark, cell, tmp_path):
    kept = tmp_path / "kept.xplane.pb"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": CACHE["dir"]}
    done = subprocess.run(
        [sys.executable, os.path.join(PERFBENCH, "run.py"), "--workload", cell,
         "--seed", "2147483693", "--seconds", "6", "--trace", "1",
         "--rehearse-cpu", "--keep-trace", str(kept)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [l for l in done.stdout.splitlines() if l.startswith("{")]
    assert done.returncode == EXIT_REHEARSAL and lines, done.stderr[-3000:]
    line = json.loads(lines[-1])
    named = contract.cell_metrics(benchmark, cell, True)
    assert ("wire_decode_ms" in named) == cell.startswith("tas-")
    for name in (n for n in SPAN_METRICS if n in named):
        assert line["metrics"][name]["value"] >= 0, sorted(line["metrics"])
    # the program's stages lie on the profiler's clock: the stand-in trace
    # holds host events named pas:*, and the idle gaps are named by them
    count = subprocess.run(
        [sys.executable, "-c",
         "import sys, trace_reduce\n"
         "spans = trace_reduce.host_spans(trace_reduce.load(sys.argv[1]))\n"
         "print(len({n for _s, _e, n in spans if n.startswith('pas:')}))",
         str(kept)],
        cwd=PERFBENCH, env=env, capture_output=True, text=True, timeout=300)
    assert count.returncode == 0, count.stderr[-2000:]
    assert int(count.stdout.strip()) >= 1
    gaps = line["breakdown"]["idle_gaps"]
    assert any("pas:" in name for name, _seconds in gaps), gaps


def test_the_span_metrics_are_in_the_benchmark(benchmark):
    names = {m["name"]: m for m in benchmark["per_layer"]}
    for name in SPAN_METRICS:
        assert names[name]["source"] == "program_span"
        assert names[name]["moves"] == "pods_per_s"
        with open(os.path.join(PERFBENCH, "layer_metrics", f"{name}.json")) as handle:
            assert list(json.load(handle)) == ["reader"]
