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

Who held the interpreter (PR 37), which the benchmark cannot print yet: per
verb the mean ``arrive`` (recv returned -> GIL held), thread CPU over wall
time, ``read_gil_ms``, ``read_calls`` and ``read_recvs`` (PR 38: the reads
made from Python, each one release of the GIL, and the kernel's recvs inside
a body's one native read) and each sampled stage's CPU beside its wall
milliseconds (``recent``); over EVERY verb span of the window, by a span
observer of its own (``interpreter``): the share that carried a stamp, the
arrival wait's mean and tail, the mean ``read`` with its ``read_gil_ms`` and
``read_calls``, how far arrive + read + handle + write_arm + write tile the
sampled spans (``write_arm`` where the socket's time-out is still armed a
request: TLS, no ``_wirec``), and the same for the verbs of the STALLED
cycles (over 1.5 medians on the generator's clock — one clock with the
spans': ``CLOCK_MONOTONIC``) beside the plain ones; and the window's five
``pas_cpu_*`` deltas as shares of ``pas_cpu_wall_seconds_total``'s (``cpu``).
``faults_a_cycle`` is the window's minor page faults by process (the program's
own, the generator child's) over its cycles: on the Nodes wire the generator's
allocator maps each of a cycle's 6 MB buffers in anew or reuses it, ~60 to
~7,000 a cycle in steps of ~1,500, and the cell's spread is that (PR 38).

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
TOP = ("arrive", "read", "handle", "write_arm", "write")


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
        cpu = {}  # sampled spans: {stage: [cpu ms of each entry]}
        for entry in entries:
            if any(s["name"] == "handle" for s in entry["stages"]):
                for name, share in covered(entry).items():
                    shares.setdefault(name, []).append(share)
            for stage in entry["stages"]:
                if "cpu_ms" in stage:
                    cpu.setdefault(stage["name"], []).append(stage["cpu_ms"])
        wall_ms = sum(e["duration_ms"] for e in entries)
        # the spans that read their CPU clock (trace.cpu_sample_due), and
        # the wall time their CPU is a share of: the span less its arrive
        read = [e for e in entries if "cpu_ms" in e]
        cpu_ms = sum(e["cpu_ms"] for e in read)
        cpu_wall_ms = sum(
            e["duration_ms"] - sum(s["duration_ms"] for s in e["stages"]
                                   if s["name"] == "arrive") for e in read)
        # span attributes: [mean over the spans that carry it, spans]
        attrs = {}
        for name in ("read_gil_ms", "read_calls", "read_recvs"):
            values = [e["attrs"][name] for e in entries if name in e["attrs"]]
            attrs[name] = [sum(values) / len(values), len(values)] if values else None
        out[verb] = {
            "n": len(entries),
            "duration_ms": wall_ms / len(entries),
            # thread CPU beside wall time (a parent's spans carry neither)
            "cpu_ms": [cpu_ms / len(read), len(read)] if read else None,
            "oncpu_pct": 100.0 * cpu_ms / cpu_wall_ms if cpu_wall_ms else None,
            **attrs,
            "stages": {name: [sum(v) / len(v), len(v)]
                       for name, v in sorted(sums.items())},
            "stage_cpu": {name: [sum(v) / len(v), len(v)]
                          for name, v in sorted(cpu.items())},
            "tiles": {name: sum(v) / len(v) for name, v in sorted(shares.items())},
        }
    return out


def watch_interpreter(trace) -> list:
    """A span observer that keeps, of every served verb from now on,
    (first byte there, wall s, arrive s or None, (cpu s, the wall s they
    are a share of) or None, sampled, share of the span its top stages
    tile or None, (read s, read_gil_ms or None, read_calls or None))."""
    kept = []

    def observe(span) -> None:
        if not span.name.startswith("POST /scheduler/"):
            return
        arrive, tiled, read = None, 0.0, 0.0
        for name, _start, seconds in span.stages:
            if name == "arrive":
                arrive = seconds
            elif name == "read":
                read = seconds
            if name in TOP:
                tiled += seconds
        cpu = getattr(span, "cpu_s", None)
        kept.append((
            span._t0, span.duration_s, arrive,
            None if cpu is None else (cpu, span.cpu_wall_s()),
            span.sampled,
            tiled / span.duration_s if span.sampled and span.duration_s else None,
            (read, span.attrs.get("read_gil_ms"), span.attrs.get("read_calls")),
        ))

    trace.SPAN_OBSERVERS.append(observe)
    return kept


def interpreter_split(kept: list, window: dict, cycle_span) -> dict:
    """The window's verbs, all of them, and those of the stalled cycles
    beside the plain ones (a verb belongs to the cycle whose first byte
    sent and last byte received enclose its own first byte)."""
    began, ended = window["began"], window["ended"]
    verbs = sorted(v for v in kept if began <= v[0] <= ended)
    if not verbs:
        return {}

    def digest(rows: list) -> dict:
        waits = sorted(r[2] for r in rows if r[2] is not None)
        wall = sum(r[1] for r in rows)
        cpu = [r[3] for r in rows if r[3] is not None]
        cpu_wall = sum(w for _c, w in cpu)
        out = {"verbs": len(rows),
               "duration_ms": wall / len(rows) * 1e3 if rows else None,
               # over the verbs that read their CPU clock
               "cpu_verbs": len(cpu),
               "oncpu_pct": (100.0 * sum(c for c, _w in cpu) / cpu_wall
                             if cpu_wall else None),
               "stamped_pct": 100.0 * len(waits) / len(rows) if rows else None}
        if rows:
            # the way in: mean read, what of it waited for the interpreter
            # after a read had returned, and the reads made from Python (a
            # parent's spans carry no count)
            gil = [r[6][1] for r in rows if r[6][1] is not None]
            calls = [r[6][2] for r in rows if r[6][2] is not None]
            out["read_ms"] = sum(r[6][0] for r in rows) / len(rows) * 1e3
            out["read_gil_ms"] = sum(gil) / len(rows) if gil else None
            out["read_gil_seconds"] = sum(gil) * 1e-3 if gil else None
            out["read_calls"] = sum(calls) / len(calls) if calls else None
        if waits:
            out["arrive_ms"] = {
                "mean": sum(waits) / len(waits) * 1e3,
                "p50": waits[len(waits) // 2] * 1e3,
                "p95": waits[min(int(len(waits) * 0.95), len(waits) - 1)] * 1e3,
                "max": waits[-1] * 1e3,
                "seconds": sum(waits)}
        return out

    records = window["records"]
    spans = sorted(cycle_span(r) for r in records)
    limit = 1.5 * spans[len(spans) // 2]
    slow = sorted((r["t"][0], max(x for x in r["t"] if x == x))
                  for r in records if cycle_span(r) > limit)
    stalled, plain, at = [], [], 0
    for verb in verbs:
        while at < len(slow) and slow[at][1] < verb[0]:
            at += 1
        inside = at < len(slow) and slow[at][0] <= verb[0] <= slow[at][1]
        (stalled if inside else plain).append(verb)
    tiles = [v[5] for v in verbs if v[5] is not None]
    return {
        "all": digest(verbs),
        "stalled_cycles_pct": 100.0 * len(slow) / len(records),
        "stalled": digest(stalled), "plain": digest(plain),
        # sampled spans: mean share arrive+read+handle+write_arm+write tile
        "tiles_span": [sum(tiles) / len(tiles), len(tiles)] if tiles else None,
    }


def cpu_shares(before: dict, after: dict) -> dict:
    """The window's ``pas_cpu_*`` deltas as shares (%) of the wall clock's,
    their seconds, and how far the four roles are from the process's CPU."""
    def moved(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    wall = moved("pas_cpu_wall_seconds_total")
    if wall <= 0:
        return {}
    roles = ("verbs", "refresh", "informers", "other")
    seconds = {r: moved(f"pas_cpu_{r}_seconds_total") for r in roles}
    verb_s = moved("pas_verb_seconds_total")
    pass_s = moved("pas_refresh_pass_seconds_total")
    return {"wall_s": wall, "seconds": seconds,
            "pct_of_wall": {r: 100.0 * s / wall for r, s in seconds.items()},
            "process_cpu_pct_of_wall": 100.0 * sum(seconds.values()) / wall,
            # every verb of the window, whole threads' clocks: the handler
            # threads' CPU over the verbs' wall seconds (arrive included)
            "verbs_oncpu_pct": 100.0 * seconds["verbs"] / verb_s if verb_s else None,
            "refresh_oncpu_pct": (
                100.0 * moved("pas_refresh_pass_cpu_seconds_total") / pass_s
                if pass_s else None)}


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


#: counter families whose label sets are kept apart (``run.scrape_counters``
#: sums a family over them): which path a publish, a round's ingest took
LABELLED = ("pas_refresh_warm_total", "pas_refresh_ingest_total")


def scrape_labelled(run, port: int) -> dict:
    """{``family{labels}``: value} of the LABELLED families' samples."""
    payload = run.http_get(port, "/metrics")[1].decode()
    out = {}
    for line in payload.splitlines():
        if line.startswith(LABELLED) and "{" in line:
            sample, _, value = line.rpartition(" ")
            out[sample] = float(value)
    return out


def lags_by_metric(system, config, traffic, seed, window) -> dict:
    """{metric: [rounds served in the window, mean ms from the fetch's
    answer to the first answer on the wire that shows the round, the
    longest]}, by the cell's own comparison (``perfbench/reference.py``);
    {} for a cell whose comparison keeps no rounds."""
    compared = system.compare(config, traffic, seed, {"window": window})
    out = {}
    for metric, served in (compared.get("fetched") or {}).items():
        shown = compared["reflected"][metric]
        lags = [(shown[k] - at) * 1e3 for k, at in served.items()
                if window["began"] <= at <= window["ended"] and k in shown]
        if lags:
            out[metric] = [len(lags), sum(lags) / len(lags), max(lags)]
    return out


def minor_faults(pid: int) -> int:
    """Pages a process has had mapped in so far without I/O (``minflt`` of
    ``/proc/<pid>/stat``): a 6 MB buffer that ``malloc`` takes fresh from the
    kernel is ~1,500 of them, one it reuses none."""
    with open(f"/proc/{pid}/stat") as handle:
        return int(handle.read().rsplit(")", 1)[1].split()[7])


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
        from platform_aware_scheduling_tpu.utils import trace

        stage_cost = watch_stage_cost(trace) if args.stage_cost else None
        verbs_seen = watch_interpreter(trace)
        wall0 = time.time()
        cpu0 = time.process_time()
        before = run.scrape_counters(system.port)
        paths_before = scrape_labelled(run, system.port)
        pids = {"program": os.getpid(), "generator": child.process.pid}
        faults0 = {who: minor_faults(pid) for who, pid in pids.items()}
        window = child.ask({"cmd": "window", "seconds": args.seconds})
        faults = {who: minor_faults(pid) - faults0[who]
                  for who, pid in pids.items()}
        after = run.scrape_counters(system.port)
        process_cpu = time.process_time() - cpu0
        paths = scrape_labelled(run, system.port)
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
            # minor page faults a cycle, by process: what the allocator took
            # fresh from the kernel (PR 38: the Nodes wire's two regimes)
            "faults_a_cycle": {who: n / len(records)
                               for who, n in faults.items()},
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
                    ("pas_refresh_", "pas_gc_", "pas_gas_", "pas_filter_",
                     "pas_verb_", "pas_stage_", "pas_cpu_"))},
            "interpreter": interpreter_split(
                verbs_seen, window, world.cycle_span),
            # the ledger's window; process_cpu_s is time.process_time()'s
            # own delta around the same two scrapes
            "cpu": {**cpu_shares(before, after), "process_cpu_s": process_cpu},
            "paths": {sample: value - paths_before.get(sample, 0.0)
                      for sample, value in paths.items()},
        }
        if system.kind == "tas" and spec["traffic"].get("wire"):
            row["lags_ms"] = lags_by_metric(
                system, config, spec["traffic"], args.seed, window)
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
