"""The stage split of one benchmark cell, read from the program's own spans.

    python3 benchmarks/stage_split.py --workload <config>.<traffic> \
        --seed <n> --seconds <s> [--rehearse-cpu] [--out <file.jsonl>] \
        [--stage-cost]

Assembles the cell exactly as ``perfbench/run.py`` does (its own functions:
the generator child, ``set_up``, the closed loop), runs one untraced window
and then reads what ``run.py`` does not keep: BOTH lists of
``GET /debug/traces`` — ``recent`` (the ring's last 256 requests) for the
per-verb stage means and how far the stages tile their containers, and
``slowest`` (the 32 longest spans of the process's life) for what a long
request was made of: ``lock_wait``, ``mirror_wait``, ``state_upload``,
``solve``, ``gc_ms``.  One JSON object per run, appended to ``--out``
(default ``chiprun_out/stage_split.jsonl``) and printed.

With ``--stage-cost`` the same window also says what a stage costs WHERE IT
IS SERVED (a hot loop, ``benchmarks/observer_cost.py``, says less): every
third span is sampled, and of the others every second one (by the parity of
its start's microsecond) opens each sampled stage's no-op TWICE.  The mean
server-side span of the four groups, interleaved request by request in one
process, gives: recorded stages against no-op ones (``sampled`` less
``plain``), one no-op more per site (``twice`` less ``plain``), and the
noise (the two halves of ``sampled``, which are treated alike).  Medians
and means of the middle 80%: a plain mean is the tail's (the cycles a
refresh pass holds up).

A builder's tool, not a benchmark: it prints no result line, claims nothing
and is read by no driver.  The numbers are host times of the machine it
runs on; with ``--rehearse-cpu`` they are not device numbers of any kind.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFBENCH = os.path.join(ROOT, "perfbench")

#: the stages that contain others (recorded with ``leaf=False``)
CONTAINERS = ("handle", "kernel", "cache_probe")
TOP = ("read", "handle", "write_arm", "write")


def covered(entry: dict) -> dict:
    """{container: share of it that the stages lying directly inside it
    cover}, by the stages' own offsets, and ``span`` for the top level."""
    at = [(s["name"], s["start_ms"], s["start_ms"] + s["duration_ms"])
          for s in entry["stages"]]
    boxes = [a for a in at if a[0] in CONTAINERS and a[2] > a[1]]
    out = {"span": sum(e - b for n, b, e in at if n in TOP)
           / entry["duration_ms"]}
    eps = 1e-3  # ms: offsets are rounded to 0.1 us
    for box in boxes:
        inside = [a for a in at if a is not box
                  and box[1] - eps <= a[1] and a[2] <= box[2] + eps]
        # directly inside: in no other container that is itself inside
        direct = [a for a in inside if not any(
            o is not a and o in inside and o[0] in CONTAINERS
            and o[1] - eps <= a[1] and a[2] <= o[2] + eps for o in inside)]
        if inside:  # TAS Prioritize's kernel is a leaf of the same name
            out[box[0]] = sum(e - b for _n, b, e in direct) / (box[2] - box[1])
    return out


def summarize(spans: list) -> dict:
    """{verb: {n, duration_ms, stages: {name: [mean ms over the spans that
    carry it, spans that carry it]}, tiles: {container: mean covered share
    over the spans that carry ``handle`` (the sampled ones)}}}."""
    verbs = {}
    for entry in spans:
        if not entry.get("name", "").startswith("POST /scheduler/"):
            continue
        verbs.setdefault(entry["attrs"].get("verb", entry["name"]), []).append(entry)
    out = {}
    for verb, entries in sorted(verbs.items()):
        sums = {}
        for entry in entries:
            per_span = {}
            for stage in entry["stages"]:
                per_span[stage["name"]] = (
                    per_span.get(stage["name"], 0.0) + stage["duration_ms"])
            for name, ms in per_span.items():
                sums.setdefault(name, []).append(ms)
        shares = {}
        for entry in entries:
            if any(s["name"] == "handle" for s in entry["stages"]):
                for name, share in covered(entry).items():
                    shares.setdefault(name, []).append(share)
        out[verb] = {
            "n": len(entries),
            "duration_ms": sum(e["duration_ms"] for e in entries) / len(entries),
            "stages": {name: [sum(v) / len(v), len(v)]
                       for name, v in sorted(sums.items())},
            "tiles": {name: sum(v) / len(v) for name, v in sorted(shares.items())},
        }
    return out


def watch_stage_cost(trace) -> dict:
    """Switch the four groups on (see the module's text); returns what
    a span observer keeps: {(span name, group): [seconds of each span]}."""
    kept = {}
    trace.SAMPLE_EVERY = 3
    plain_stage, no_op = trace.Span.stage, trace._NULL_STAGE

    def odd(span) -> bool:
        return bool(int(span._t0 * 1e6) & 1)

    def stage(self, name, leaf=True, sampled=False):
        if sampled and not self.sampled and odd(self):
            with no_op:
                pass
        return plain_stage(self, name, leaf, sampled)

    def observe(span) -> None:
        group = (("sampled_odd" if odd(span) else "sampled_even")
                 if span.sampled else ("twice" if odd(span) else "plain"))
        kept.setdefault((span.name, group), []).append(span.duration_s or 0.0)

    trace.Span.stage = stage
    trace.SPAN_OBSERVERS.append(observe)
    return kept


def slowest(spans: list, since: float) -> list:
    """The served verbs of the ``slowest`` list that began inside the
    window (the list is over the process's life: warm-up fills its top)."""
    rows = []
    for entry in spans:
        if (not entry.get("name", "").startswith("POST /scheduler/")
                or entry["start"] < since):
            continue
        rows.append({
            "verb": entry["attrs"].get("verb"),
            "ms": entry["duration_ms"],
            "start": entry["start"],
            "gc_ms": entry["attrs"].get("gc_ms"),
            "stages": {s["name"]: s["duration_ms"] for s in entry["stages"]},
        })
    return rows


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument("--stage-cost", action="store_true")
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "stage_split.jsonl"))
    args = parser.parse_args(argv)
    args.fault, args.trace = "", 0

    sys.path.insert(0, PERFBENCH)
    sys.path.insert(0, ROOT)
    import generator as world
    import run

    spec = run.load_cell(args.workload)
    config = world.sized(spec["config"], args.rehearse_cpu)
    child = run.Generator(
        {"config": config, "traffic": spec["traffic"], "seed": args.seed})
    system = None
    try:
        devices = run.hold_device(spec["cell"], args.workload, args.rehearse_cpu)
        if devices is None:
            return run.EXIT_NO_CHIP
        from platform_aware_scheduling_tpu.utils import klog

        klog.set_verbosity(1)
        child.receive()
        system = run.set_up(args, config, spec["traffic"], child)
        stage_cost = None
        if args.stage_cost:
            from platform_aware_scheduling_tpu.utils import trace

            stage_cost = watch_stage_cost(trace)
        wall0 = time.time()
        before = run.scrape_counters(system.port)
        window = child.ask({"cmd": "window", "seconds": args.seconds})
        after = run.scrape_counters(system.port)
        traces = json.loads(run.http_get(system.port, "/debug/traces")[1])
        records = window["records"]
        spans = sorted(world.cycle_span(r) for r in records)
        row = {
            "workload": args.workload, "seed": args.seed,
            "platform": devices[0].platform,
            # first verbs sent: what pas_filter_native_total is a share of
            "filters": len(records),
            "pods_per_s": len(records) / (window["ended"] - window["began"]),
            "cycle_p50_ms": spans[len(spans) // 2] * 1e3,
            "window_began_wall": wall0,
            # [seconds into the window, cycle ms, Filter ms] of the longest
            "longest_cycles": [
                [r["t"][0] - window["began"], world.cycle_span(r) * 1e3,
                 (r["t"][1] - r["t"][0]) * 1e3]
                for r in sorted(records, key=world.cycle_span)[-5:]],
            "recent": summarize(traces["recent"]),
            "slowest": slowest(traces["slowest"], wall0),
            "counters": {
                name: after.get(name, 0.0) - before.get(name, 0.0)
                for name in after
                if name.startswith(
                    ("pas_refresh_", "pas_gc_", "pas_gas_", "pas_filter_"))},
        }
        if stage_cost is not None:
            # {span name: {group: [median ms, mean ms of the middle 80%, spans]}}
            row["stage_cost"] = {}
            for (name, group), seconds in sorted(stage_cost.items()):
                if name.startswith("POST /scheduler/"):
                    seconds = sorted(seconds)
                    n = len(seconds)
                    middle = seconds[n // 10: n - n // 10] or seconds
                    row["stage_cost"].setdefault(name, {})[group] = [
                        seconds[n // 2] * 1e3,
                        sum(middle) / len(middle) * 1e3, n]
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "a") as handle:
            handle.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)
        return 0
    finally:
        child.close()
        if system is not None:
            system.close()


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — every failure is an exit code
        import traceback

        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads (server, refresh loop, informers) must not keep the
    # process or the chip past the result
    os._exit(code)
