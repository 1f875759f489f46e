"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are data files
found by the names in BENCHMARK.json.  This process holds the chip,
assembles the extender as its mains do, plays the kube and custom-metrics
APIs and serves on a loopback port in threads; the generator is a child
process that never imports JAX.  Set-up (cluster, bodies, compilation, warm
cycles) ends at the first measured request; the window then runs for
``--seconds``; the comparison with the plain reference runs after the
window has closed and the program is stopped.  The last line on standard
output is the result object, checked by ``contract.py`` before it is
printed.  Without a TPU, or with fewer chips than the cell asks for, the
command exits non-zero and prints no result.

``--rehearse-cpu`` runs the same flow at the configuration's ``rehearsal``
size on the CPU backend: it says ``cpu`` and ``correct: false`` and exits 4.
``--fault <name>`` plants a fault under the harness (tests and controls).
"""

from __future__ import annotations

import time

PROCESS_BEGAN = time.monotonic()

import argparse  # noqa: E402
import http.client  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".trace")
TRACE_SECONDS = 6.0  # about three sync periods
EXIT_NO_CHIP, EXIT_BAD_LINE, EXIT_REHEARSAL, EXIT_NO_PROGRAM = 2, 3, 4, 5
SETTLE_LIMIT_S = 60.0


class RunFailure(Exception):
    """The run cannot give a result; exit non-zero with no result line."""


def say(message: str) -> None:
    print(message, file=sys.stderr, flush=True)


def stage(what: str) -> None:
    say(f"perfbench: t+{time.monotonic() - PROCESS_BEGAN:.2f}s {what}")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as handle:
        return json.load(handle)


def load_cell(workload: str) -> dict:
    import generator as world

    benchmark = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in benchmark["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise RunFailure(f"BENCHMARK.json has no workload {workload!r}")
    entry = next(c for c in benchmark["configs"] if c["name"] == cell["config"])
    return {
        "benchmark": benchmark,
        "cell": cell,
        "config": load_json(ROOT, entry["file"]),
        "traffic": world.checked_traffic(
            load_json(HERE, "traffic", cell["traffic"] + ".json")),
    }


def http_get(port: int, path: str) -> tuple:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("GET", path)
        response = conn.getresponse()
        return response.status, response.read()
    finally:
        conn.close()


SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)")


def scrape_counters(port: int) -> dict:
    """{family: sum over its label sets} of the live /metrics page."""
    status, payload = http_get(port, "/metrics")
    if status != 200:
        raise RunFailure(f"/metrics answered {status}")
    totals = {}
    for line in payload.decode().splitlines():
        if line.startswith("#"):
            continue
        found = SAMPLE.match(line)
        if found:
            try:
                value = float(found.group(3))
            except ValueError:
                continue
            totals[found.group(1)] = totals.get(found.group(1), 0.0) + value
    return totals


class Generator:
    """The child process, spoken to in JSON lines."""

    def __init__(self, job: dict):
        self.process = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "generator.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT)
        self.send(job)

    def send(self, obj: dict) -> None:
        self.process.stdin.write(json.dumps(obj).encode() + b"\n")
        self.process.stdin.flush()

    def receive(self):
        line = self.process.stdout.readline()
        if not line:
            raise RunFailure(
                f"the generator died (exit {self.process.poll()}); see above")
        head = json.loads(line)
        payload = self.process.stdout.read(head["payload"]) if head["payload"] else b""
        return head, (pickle.loads(payload) if payload else None)

    def ask(self, obj: dict):
        self.send(obj)
        return self.receive()[1]

    def close(self) -> None:
        if self.process.poll() is None:
            try:
                self.send({"cmd": "quit"})
                self.process.stdin.close()
            except OSError:
                pass
            try:
                self.process.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.process.kill()
                self.process.wait()


def percentile(values: list, share: float) -> float:
    """Nearest-rank percentile of all values."""
    ordered = sorted(values)
    rank = max(int(-(-share * len(ordered) // 1)), 1)
    return ordered[rank - 1]


def logical_sizes(config: dict, candidates: int) -> dict:
    """What work.py is given: the cell's own sizes, never a padded shape."""
    sizes = {"nodes": config["nodes"], "candidates": candidates}
    if config["assembler"] == "gas":
        shapes = config["node_shapes"]
        sizes["cards_mean"] = sum(s["share"] * s["cards"] for s in shapes)
        templates = config["pod_templates"]
        total = sum(t["weight"] for t in templates)
        sizes["shares_mean"] = sum(
            t["weight"] * sum(c["i915"] for c in t["containers"])
            for t in templates) / total
        sizes["resources"] = len(config["per_card"])
    return sizes


def trace_sub_window(seconds: float, began: float) -> tuple:
    """Profile a sub-window of about three sync periods in the middle of the
    measured window, in this (chip-holding) process.  Returns the trace's
    file and its seconds; the file is read once the window has closed and
    the program is stopped, so that reading it holds up no request."""
    import jax

    import trace_reduce

    length = min(TRACE_SECONDS, seconds * 0.6)
    start_at = began + (seconds - length) / 2
    time.sleep(max(start_at - time.monotonic(), 0))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    t0 = time.monotonic()
    time.sleep(length)
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    stage(f"profiler stopped {time.monotonic() - t1:.2f}s after the traced part")
    return trace_reduce.find_trace(TRACE_DIR), t1 - t0


def read_trace(found: str, traced_s: float, rehearse: bool, keep: str) -> dict:
    import trace_reduce

    if keep:
        os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
        shutil.copyfile(found, keep)
    try:
        return trace_reduce.reduce_trace(
            trace_reduce.load(found), traced_s, allow_host=rehearse)
    except ValueError as exc:
        raise RunFailure(f"traced run: {exc}") from exc
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


def hold_device(cell: dict, workload: str, rehearse: bool):
    """This process takes the chip (or, rehearsing, the CPU); None when JAX
    finds no accelerator or fewer chips than the cell asks for."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            raise RunFailure(f"--rehearse-cpu found platform {platform!r}")
    elif platform != "tpu" or len(devices) < cell["chips"]:
        say(f"perfbench: {workload} needs {cell['chips']} TPU chip(s); "
            f"JAX found platform={platform!r} with {len(devices)} device(s)")
        return None
    return devices


def set_up(args, config: dict, traffic: dict, child: Generator):
    """Assemble the extender, wait until it is ready and warm every shape the
    cell's traffic uses; everything up to the first measured request."""
    import assemble

    kinds = (len(config["policies"]) if config["assembler"] == "tas"
             else len(config["pod_templates"]))
    warm_pods = int(traffic.get("warm_cycles", 1)) * kinds
    system = assemble.assemble_system(config, traffic, args.seed, warm_pods)
    try:
        stage("assembled")
        if args.fault:
            assemble.plant_fault(system, args.fault)
        system.wait_ready(http_get)
        stage("ready")
        child.ask({"cmd": "connect", "port": system.port})
        broken = [r["error"] for r in child.ask({"cmd": "warm"}) if r["error"]]
        if broken and not args.fault:
            raise RunFailure(f"warm-up cycles failed: {broken[:3]}")
        if system.kind == "gas":
            system.delete_warm(warm_pods)
            system.start_churn()
            if not system.wait_settled(SETTLE_LIMIT_S):
                raise RunFailure("the GAS cache did not settle after warm-up")
        else:
            system.wait_pass_end()
        system.mark()
    except BaseException:
        system.close()
        raise
    return system


def measure(args, traffic: dict, child: Generator, system, seconds: float,
            devices) -> dict:
    """The measured window (with the profiler on for a part of it in a
    traced run), then what is read once it has closed."""
    before = scrape_counters(system.port)
    stage("warmed; compiled so far: " + ", ".join(
        f"{what}@{at - PROCESS_BEGAN:.1f}s({took:.2f}s)"
        for at, took, what in system.compiled))
    result = {}

    def drive():
        result["window"] = child.ask({"cmd": "window", "seconds": seconds})

    driver = threading.Thread(target=drive)
    began = time.monotonic()
    driver.start()
    traced = trace_error = None
    if args.trace:
        try:
            traced = trace_sub_window(seconds, began)
        except FileNotFoundError as exc:
            trace_error = exc
    driver.join()
    if "window" not in result:
        raise RunFailure("the generator gave no window; see above")
    if trace_error is not None:
        raise RunFailure(f"traced run: {trace_error}")
    run = {"window": result["window"], "traced": traced, "before": before,
           "after": scrape_counters(system.port), "probe": []}
    run["stages"] = json.loads(
        http_get(system.port, "/debug/traces")[1]).get("recent", [])
    if system.kind == "gas":
        system.wait_settled(SETTLE_LIMIT_S)
    if traffic.get("probe_after_window"):
        run["probe"] = child.ask({"cmd": "probe"})
    run["memory_peak"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    if not run["memory_peak"] and args.rehearse_cpu:
        run["memory_peak"] = 1  # the CPU backend reports none; a rehearsal never passes
    return run


def compare(config: dict, traffic: dict, seed: int, system, run: dict) -> dict:
    """Every number compared, each with the limit 0; runs with the program
    stopped."""
    import reference

    began = time.monotonic()
    window = run["window"]
    if system.kind == "tas":
        compared = reference.tas_compare(
            config, seed, window, system.kube.fetches, traffic["wire"])
        compared["lags"], compared["censored"] = reference.telemetry_lags(
            compared, window["began"], window["ended"])
    else:
        compared = reference.gas_compare(
            config, seed, window, run["probe"], system.kube.annotations,
            system.delete_log())
        compared["lags"], compared["censored"] = [], 0
    numbers = compared["numbers"]

    def moved(name):
        return int(run["after"].get(name, 0.0) - run["before"].get(name, 0.0))

    numbers["device_path_errors"] = moved("pas_device_path_errors_total")
    numbers["host_fallbacks"] = (moved("pas_prioritize_host_fallback_total")
                                 + moved("pas_gas_filter_host_total"))
    numbers["compiled_in_window"] = system.compiled_between(
        window["began"], window["ended"])
    numbers["retraced_in_window"] = moved("pas_jax_retrace_total")
    numbers["refresh_errors"] = moved("pas_telemetry_refresh_errors_total")
    if system.kind == "tas":
        # a round the API served more than two sync periods before the
        # window closed must have shown on the wire
        late = window["ended"] - 2 * config["sync_period_s"]
        numbers["rounds_never_seen"] = sum(
            1 for metric, served in compared["fetched"].items()
            for k, at in served.items()
            if window["began"] <= at <= late
            and k not in compared["reflected"][metric])
        numbers["window_without_pass"] = 0 if system.pass_intervals(
            window["began"], window["ended"]) else 1
    compared["seconds"] = time.monotonic() - began
    return compared


def per_layer_values(benchmark: dict, workload: str, ctx: dict,
                     rehearse: bool) -> tuple:
    """({metric: value}, metrics left out for want of a peak) by the readers
    the metrics' own files name."""
    import contract
    import readers

    values, no_peak = {}, set()
    for name in contract.cell_metrics(benchmark, workload, True):
        spec = load_json(HERE, "layer_metrics", name + ".json")["reader"]
        try:
            value = readers.READERS[spec["kind"]](spec, ctx)
        except KeyError as exc:
            # a CPU has no entry in peaks.json, and gets none
            if not rehearse:
                raise
            say(f"perfbench: {name} left out: {exc}")
            no_peak.add(name)
            continue
        if value is not None:
            values[name] = value
    return values, no_peak


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument("--fault", default="")
    parser.add_argument("--keep-trace", default="",
                        help="copy the traced run's .xplane.pb to this path")
    args = parser.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    spec = load_cell(args.workload)
    benchmark, cell, traffic = spec["benchmark"], spec["cell"], spec["traffic"]
    seconds = args.seconds if args.seconds is not None else benchmark["run_seconds"]
    if not os.path.isdir(os.path.join(ROOT, "platform_aware_scheduling_tpu")):
        say("perfbench: the program (platform_aware_scheduling_tpu/) is not in "
            "this directory; there is nothing to measure")
        return EXIT_NO_PROGRAM

    import contract
    import generator as world

    config = world.sized(spec["config"], args.rehearse_cpu)
    # the generator starts before this process initializes a backend
    child = Generator({"config": config, "traffic": traffic, "seed": args.seed})
    system = None
    try:
        devices = hold_device(cell, args.workload, args.rehearse_cpu)
        if devices is None:
            return EXIT_NO_CHIP
        from platform_aware_scheduling_tpu.utils import klog

        klog.set_verbosity(1)
        candidates = child.receive()[0]["candidates"]
        stage("backend up, generator ready")
        system = set_up(args, config, traffic, child)
        run = measure(args, traffic, child, system, seconds, devices)
        child.close()
        system.close()
        stage("window closed, program stopped")
        trace = (read_trace(*run["traced"], args.rehearse_cpu, args.keep_trace)
                 if args.trace else None)
        compared = compare(config, traffic, args.seed, system, run)

        window = run["window"]
        records = window["records"]
        spans = [world.cycle_span(r) for r in records]
        median = statistics.median(spans)
        elapsed = window["ended"] - window["began"]
        lags, no_peak = compared["lags"], set()
        if args.trace:
            say("perfbench: traced programs (runs, device s): "
                + json.dumps(trace["modules"]))
            values, no_peak = per_layer_values(benchmark, args.workload, {
                "records": records, "counters": (run["before"], run["after"]),
                "stages": run["stages"], "system": system, "window": window,
                "trace": trace, "device_kind": devices[0].device_kind,
                "sizes": logical_sizes(config, candidates),
            }, args.rehearse_cpu)
        else:
            values = {
                "pods_per_s": len(records) / elapsed,
                "cycle_p95_ms": percentile(spans, 0.95) * 1e3,
                "setup_s": records[0]["t"][0] - PROCESS_BEGAN,
            }
            if lags:
                values["telemetry_lag_ms"] = sum(lags) / len(lags) * 1e3
        wanted = contract.cell_metrics(benchmark, args.workload, bool(args.trace))
        numbers = compared["numbers"]
        device = {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(run["memory_peak"]),
        }
        line = {
            "correct": not args.rehearse_cpu and all(
                v == 0 for v in numbers.values()),
            "attempted": len(records), "failed": numbers["requests_failed"],
            "metrics": {name: {"value": values[name], "unit": unit}
                        for name, unit in wanted.items() if name in values},
            "device": device,
        }
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
        line["seed"] = args.seed
        line["counted"] = {
            **compared["counted"], "lag_samples": len(lags),
            "lag_censored": compared["censored"],
            "compare_s": compared["seconds"], "window_s": elapsed,
            "cycle_p50_ms": median * 1e3,
            # cycles held up (by a refresh pass, a collection, the host):
            # how many took over 1.5x the median, and the seconds they lost
            "stalled_cycles": sum(1 for s in spans if s > 1.5 * median),
            "stalled_s": sum(s - median for s in spans if s > 1.5 * median),
            # the three longest: [seconds into the window, cycle ms, Filter ms]
            "longest_cycles": [
                [r["t"][0] - window["began"], world.cycle_span(r) * 1e3,
                 (r["t"][1] - r["t"][0]) * 1e3]
                for r in sorted(records, key=world.cycle_span)[-3:]],
        }
        line["compared"] = {
            name: {"value": value, "limit": 0} for name, value in numbers.items()}

        text = json.dumps(line)
        reasons = contract.check_line(
            text, benchmark, args.workload, bool(args.trace),
            None if args.rehearse_cpu else cell["chips"], optional=no_peak)
        for note in compared["notes"][:12]:
            say(f"perfbench: {note}")
        say("perfbench: compared (value, limit): " + ", ".join(
            f"{name} {value} (0)" for name, value in numbers.items()))
        if reasons:
            for reason in reasons:
                say(f"perfbench: result line refused: {reason}")
            return EXIT_BAD_LINE
        print(text, flush=True)
        return EXIT_REHEARSAL if args.rehearse_cpu else 0
    finally:
        child.close()
        if system is not None:
            system.close()


if __name__ == "__main__":
    try:
        code = main()
    except RunFailure as exc:
        say(f"perfbench: FAILED: {exc}")
        code = 1
    except BaseException as exc:  # noqa: BLE001 — every failure is an exit code
        if isinstance(exc, SystemExit):
            raise
        import traceback

        traceback.print_exc()
        say(f"perfbench: FAILED: {exc!r}")
        code = 1
    # daemon threads (server, refresh loop, informers) must not keep the
    # process or the chip past the result
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
