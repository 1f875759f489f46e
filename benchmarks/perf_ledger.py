"""Committed perf-regression ledger (docs/observability.md "Solve
observatory" — ledger workflow).

The bench trajectory (BENCH_r01..) is a time series with no enforced
anchor: a 20% solve regression would ship silently as long as the tests
stay green.  This module turns the solve observatory's per-stage
attribution into an enforceable floor:

  * ``measure()`` runs the REAL pipeline hermetically — a seeded
    10k-node-style extender (benchmarks/http_load.build_extender at a
    configurable scale), forced ranking solves with the observatory
    enabled for per-stage medians, forced view rebuilds for the
    snapshot/transfer stages, and a gc-fenced warm Filter verb floor
    with the observatory OFF (the production path);
  * ``write_anchor()`` commits the floors to ``benchmarks/
    perf_anchor.json`` with a NOISE-AWARE per-entry tolerance (scaled
    from the measured inter-rep IQR, clamped to [8%, 15%] so a 20%
    regression always flags while shared-runner jitter mostly doesn't);
  * ``drift()`` compares a fresh measurement against the committed
    anchor and flags entries past floor x (1 + tolerance);
  * ``overhead()`` is the hermetic instrumented-vs-off pin (the flight
    recorder's interleaved gc-fenced methodology): the warm Filter verb
    must stay <=5% with the observatory enabled — the warm path never
    touches the instrumentation, so this pins that it STAYS untouched —
    and the solve itself reports its marking cost.

``make bench-ledger`` runs the drift report (writing the anchor when
none is committed); bench.py folds the same report into every full
bench run so the trajectory carries its own regression gate.  Report
mode never exits nonzero on drift (shared CI runners jitter); pass
``--strict`` to gate.
"""

from __future__ import annotations

import argparse
import gc
import json
import statistics
import time
from pathlib import Path
from typing import Dict, List, Optional

ANCHOR_PATH = Path(__file__).resolve().parent / "perf_anchor.json"

#: tolerance clamp: the floor absorbs timer granularity on fast stages,
#: the cap guarantees a 20% regression can never hide inside "noise"
TOL_MIN_PCT = 8.0
TOL_MAX_PCT = 15.0

#: stages too fast/jittery to gate individually at small scale — they
#: still ride the ring and /debug/solve, just not the committed anchor
LEDGER_STAGES = ("execute", "readback")


def _median(values: List[float]) -> float:
    return statistics.median(values) if values else 0.0


def _tolerance_pct(values: List[float]) -> float:
    """Noise-aware tolerance: 3x the relative IQR, clamped."""
    if len(values) < 4:
        return TOL_MAX_PCT
    ordered = sorted(values)
    n = len(ordered)
    iqr = ordered[(3 * n) // 4] - ordered[n // 4]
    med = _median(ordered)
    if med <= 0:
        return TOL_MAX_PCT
    return round(min(TOL_MAX_PCT, max(TOL_MIN_PCT, 300.0 * iqr / med)), 1)


def _measure_shard(num_nodes: int, reps: int) -> Dict[str, List[float]]:
    """{shard_refresh_pass, shard_digest_build} sample lists (µs): a
    hermetic one-owner partition plane (static owner map, 4 partitions)
    over a seeded cache — the same assembly benchmarks/shard_load.py
    spawns per subprocess, minus the sockets."""
    from benchmarks.http_load import _policy_obj, node_names
    from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
    from platform_aware_scheduling_tpu.shard import ShardPlane
    from platform_aware_scheduling_tpu.shard.digest import (
        build_partition_digests,
    )
    from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
    from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
    from platform_aware_scheduling_tpu.testing.faults import FakeMetricsClient

    names = node_names(num_nodes)
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    cache.write_policy(
        "default", "load-pol", TASPolicy.from_obj(_policy_obj())
    )
    cache.write_metric("load_metric")
    client = FakeMetricsClient()
    client.set_all(
        "load_metric",
        {n: (i * 37) % 1_000_000 for i, n in enumerate(names)},
    )
    plane = ShardPlane(
        "ledger-owner",
        4,
        kube_client=None,
        static_owners={
            p: "ledger-owner" if p == 0 else f"other-{p}" for p in range(4)
        },
    )
    plane.attach(cache, mirror)
    cache.update_all_metrics(client)  # warm: interning + first digests
    out: Dict[str, List[float]] = {
        "shard_refresh_pass": [],
        "shard_digest_build": [],
    }
    for _ in range(reps):
        t0 = time.perf_counter()
        cache.update_all_metrics(client)
        out["shard_refresh_pass"].append((time.perf_counter() - t0) * 1e6)
    for _ in range(reps):
        t0 = time.perf_counter()
        build_partition_digests(
            mirror,
            plane.pmap,
            plane.coordinator.owned(),
            identity=plane.identity,
            epoch_of=plane.coordinator.epoch,
            topk_of=plane.topk_for,
            clock=plane.clock,
        )
        out["shard_digest_build"].append((time.perf_counter() - t0) * 1e6)
    return out


def measure(
    num_nodes: int = 2000, solve_reps: int = 30, verb_reps: int = 200
) -> Dict:
    """Per-stage solve floors + the warm Filter verb floor, measured
    against a seeded extender.  Returns ``{"num_nodes", "entries":
    {name: {"floor_us", "tolerance_pct", "reps"}}}`` — the exact anchor
    payload (minus commit metadata)."""
    from benchmarks.http_load import _PATHS, build_extender, make_bodies
    from platform_aware_scheduling_tpu.extender.server import HTTPRequest
    from platform_aware_scheduling_tpu.ops import solveobs
    from platform_aware_scheduling_tpu.ops.rules import OP_IDS

    ext, names = build_extender(num_nodes, device=True)
    saved = solveobs.ACTIVE
    samples: Dict[str, List[float]] = {}
    try:
        obs = solveobs.enable(capacity=max(64, solve_reps * 4))
        view = ext.mirror.device_view()
        op = OP_IDS["GreaterThan"]
        row = view.metric_index["load_metric"]
        ext.fastpath._ranking(view, row, op)  # compile outside the floor
        for _ in range(solve_reps):
            with ext.fastpath._lock:
                ext.fastpath._rank.clear()
            ext.fastpath._ranking(view, row, op)
        for sample in obs.ring:
            if sample["kind"] != "prioritize_rank":
                continue
            for stage, us in sample["stages"].items():
                if stage in LEDGER_STAGES:
                    samples.setdefault(f"solve_{stage}", []).append(us)
        # snapshot/transfer floors from forced view rebuilds: a version
        # bump invalidates the memoized view, so device_view() restages
        obs.ring.clear()
        for i in range(max(6, solve_reps // 3)):
            with ext.mirror._lock:
                ext.mirror._version += 1
            ext.mirror.device_view()
        for sample in obs.ring:
            if sample["kind"] != "view_build":
                continue
            for stage in ("snapshot", "transfer"):
                if stage in sample["stages"]:
                    samples.setdefault(f"view_{stage}", []).append(
                        sample["stages"][stage]
                    )
    finally:
        solveobs.ACTIVE = saved

    # sharded-refresh floors (docs/sharding.md): one telemetry pass
    # through the ~1/P ingest cut, and one digest build over the owned
    # partition — the partition plane's per-pass costs.  Anchored so a
    # regression in the refresh_filter walk or the top-k summarizer
    # flags here instead of shipping as slow refresh loops.
    samples.update(_measure_shard(num_nodes, reps=max(6, solve_reps // 3)))

    # warm Filter verb floor, observatory OFF — the production path the
    # wire SLOs actually see; gc-fenced so a pause can't land mid-batch
    bodies = make_bodies(names, "nodenames")
    path = _PATHS["filter"]

    def req(body):
        return HTTPRequest(
            method="POST",
            path=path,
            headers={"Content-Type": "application/json"},
            body=body,
        )

    for body in bodies[:5]:
        ext.filter(req(body))
    batch = max(20, verb_reps // 5)
    verb_means: List[float] = []
    for _ in range(5):
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            for i in range(batch):
                ext.filter(req(bodies[i % len(bodies)]))
            verb_means.append((time.perf_counter() - t0) / batch * 1e6)
        finally:
            gc.enable()
    samples["warm_filter_verb"] = verb_means

    entries = {
        name: {
            "floor_us": round(_median(values), 1),
            "tolerance_pct": _tolerance_pct(values),
            "reps": len(values),
        }
        for name, values in sorted(samples.items())
        if values
    }
    return {"num_nodes": num_nodes, "entries": entries}


def write_anchor(
    measurement: Dict, path: Path = ANCHOR_PATH
) -> Dict:
    """Commit a measurement as the anchor (the file bench.py gates
    against — meant to be checked in next to the bench trajectory)."""
    anchor = {
        "format": "pas-perf-anchor/1",
        "num_nodes": measurement["num_nodes"],
        "entries": measurement["entries"],
    }
    path.write_text(json.dumps(anchor, indent=2, sort_keys=True) + "\n")
    return anchor


def load_anchor(path: Path = ANCHOR_PATH) -> Optional[Dict]:
    if not path.exists():
        return None
    anchor = json.loads(path.read_text())
    if anchor.get("format") != "pas-perf-anchor/1":
        return None
    return anchor


def drift(measurement: Dict, anchor: Dict) -> List[Dict]:
    """Per-entry drift of ``measurement`` against ``anchor``; an entry
    is flagged when current > floor x (1 + tolerance).  Entries only
    one side measured are reported unflagged (a new stage isn't a
    regression; a vanished one is a measurement gap)."""
    rows: List[Dict] = []
    current = measurement.get("entries", {})
    committed = anchor.get("entries", {})
    for name in sorted(set(current) | set(committed)):
        cur = current.get(name)
        ref = committed.get(name)
        row: Dict = {"name": name, "flagged": False}
        if cur is not None:
            row["current_us"] = cur["floor_us"]
        if ref is not None:
            row["anchor_us"] = ref["floor_us"]
            row["tolerance_pct"] = ref["tolerance_pct"]
        if cur is None or ref is None or ref["floor_us"] <= 0:
            rows.append(row)
            continue
        pct = (cur["floor_us"] / ref["floor_us"] - 1.0) * 100.0
        row["drift_pct"] = round(pct, 1)
        row["flagged"] = pct > ref["tolerance_pct"]
        rows.append(row)
    return rows


def overhead(num_nodes: int = 2000, batches: int = 10, per_batch: int = 40) -> Dict:
    """Hermetic observatory cost, instrumented vs off, interleaved
    gc-fenced batches in ONE process (the flight recorder's <=5%
    methodology): the warm Filter verb (whose path the observatory
    never touches — this pins that it stays untouched) and the forced
    ranking solve (which pays the stage marks + block_until_ready)."""
    from benchmarks.http_load import _PATHS, build_extender, make_bodies
    from platform_aware_scheduling_tpu.extender.server import HTTPRequest
    from platform_aware_scheduling_tpu.ops import solveobs
    from platform_aware_scheduling_tpu.ops.rules import OP_IDS

    ext, names = build_extender(num_nodes, device=True)
    bodies = make_bodies(names, "nodenames")
    path = _PATHS["filter"]

    def req(body):
        return HTTPRequest(
            method="POST",
            path=path,
            headers={"Content-Type": "application/json"},
            body=body,
        )

    saved = solveobs.ACTIVE
    out: Dict = {"num_nodes": num_nodes}
    try:
        obs = solveobs.SolveObservatory(capacity=4096)
        for body in bodies[:5]:
            ext.filter(req(body))
        means: Dict[str, List[float]] = {"on": [], "off": []}
        for batch in range(batches):
            label = "on" if batch % 2 == 0 else "off"
            solveobs.ACTIVE = obs if label == "on" else None
            gc.collect()
            gc.disable()
            try:
                t0 = time.perf_counter()
                for i in range(per_batch):
                    ext.filter(req(bodies[i % len(bodies)]))
                means[label].append(
                    (time.perf_counter() - t0) / per_batch * 1e6
                )
            finally:
                gc.enable()
        on = _median(means["on"])
        off = _median(means["off"])
        out["warm_filter_on_us"] = round(on, 1)
        out["warm_filter_off_us"] = round(off, 1)
        out["warm_filter_overhead_pct"] = round((on / off - 1.0) * 100.0, 1)

        view = ext.mirror.device_view()
        op = OP_IDS["GreaterThan"]
        row = view.metric_index["load_metric"]
        ext.fastpath._ranking(view, row, op)  # compile once
        solve_means: Dict[str, List[float]] = {"on": [], "off": []}
        for batch in range(batches):
            label = "on" if batch % 2 == 0 else "off"
            solveobs.ACTIVE = obs if label == "on" else None
            gc.collect()
            gc.disable()
            try:
                t0 = time.perf_counter()
                for _ in range(per_batch):
                    with ext.fastpath._lock:
                        ext.fastpath._rank.clear()
                    ext.fastpath._ranking(view, row, op)
                solve_means[label].append(
                    (time.perf_counter() - t0) / per_batch * 1e6
                )
            finally:
                gc.enable()
        on = _median(solve_means["on"])
        off = _median(solve_means["off"])
        out["solve_on_us"] = round(on, 1)
        out["solve_off_us"] = round(off, 1)
        out["solve_overhead_pct"] = round((on / off - 1.0) * 100.0, 1)
    finally:
        solveobs.ACTIVE = saved
    return out


def report(
    num_nodes: int = 2000,
    anchor_path: Path = ANCHOR_PATH,
    include_overhead: bool = True,
) -> Dict:
    """The bench-ledger entrypoint: measure, then drift against the
    committed anchor (writing one when none exists)."""
    measurement = measure(num_nodes=num_nodes)
    anchor = load_anchor(anchor_path)
    out: Dict = {"measurement": measurement}
    if anchor is None:
        out["anchor"] = write_anchor(measurement, anchor_path)
        out["anchor_written"] = True
        out["drift"] = []
    else:
        out["anchor_written"] = False
        out["drift"] = drift(measurement, anchor)
    out["flagged"] = [r["name"] for r in out["drift"] if r["flagged"]]
    if include_overhead:
        out["overhead"] = overhead(num_nodes=num_nodes)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description="solve perf ledger: measure, anchor, drift"
    )
    parser.add_argument("--nodes", type=int, default=2000)
    parser.add_argument("--write", action="store_true",
                        help="re-anchor: commit this run's floors")
    parser.add_argument("--strict", action="store_true",
                        help="exit 1 when drift is flagged")
    parser.add_argument("--no-overhead", action="store_true",
                        help="skip the instrumented-vs-off pin")
    args = parser.parse_args(argv)
    from benchmarks import children

    identity = children.hold_chip("benchmarks.perf_ledger")
    if args.write:
        measurement = measure(num_nodes=args.nodes)
        anchor = write_anchor(measurement)
        print(json.dumps({"anchor": anchor, "written": True}, indent=2))
        return 0
    out = report(
        num_nodes=args.nodes, include_overhead=not args.no_overhead
    )
    out["platform"] = identity["platform"]
    print(json.dumps(out, indent=2))
    if args.strict and out["flagged"]:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
