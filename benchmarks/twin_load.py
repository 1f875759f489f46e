"""``make bench-twin``: the digital-twin scenario matrix at cluster
scale (testing/twin.py; docs/observability.md "SLOs & error budgets").

Runs every default scenario program — diurnal load, deployment wave,
node-failure wave, metric storm, leader-kill composite, gang wave —
through the fully assembled TAS(+GAS+gang) stack at ``--nodes`` scale
(default 10k), and reports each scenario's verdict, which is exactly
the SLO engine's judgment.  The compact matrix rides bench.py's ``twin``
section so every future PR's BENCH_DETAIL shows the per-scenario
regression surface; the 100k-node tier runs behind ``-m slow`` in
tests/test_twin.py (same code, bigger constructor arguments).

Exits nonzero when any default scenario fails its SLO gates — the
"production scale with a straight face" check of ROADMAP item 5.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, Optional, Tuple

from platform_aware_scheduling_tpu.testing.replay import (
    MAX_REPLAY_NODES,
    ReplayedDiurnal,
    ReplayScenario,
    parse_capture,
    whatif,
)
from platform_aware_scheduling_tpu.testing.twin import (
    DEFAULT_SCENARIOS,
    run_matrix,
)
from platform_aware_scheduling_tpu.utils.record import FlightRecorder

#: the matrix the bench runs: the six original programs plus the
#: record->replay round-trip fidelity gate (ISSUE 13)
BENCH_SCENARIOS = DEFAULT_SCENARIOS + (ReplayedDiurnal(),)


def _synth_capture(nodes: int, ticks: int) -> FlightRecorder:
    """A deterministic capture at bench scale: a linear load ramp per
    telemetry pass, four verb arrivals per tick window — the input for
    the replay-throughput and what-if sections (a fake-clock stand-in
    for a production /debug/record export)."""
    state = {"t": 0.0}
    rec = FlightRecorder(capacity=1 << 16, clock=lambda: state["t"])
    values = [
        100.0 + (700.0 * i) / max(1, nodes - 1) for i in range(nodes)
    ]
    for tick in range(ticks):
        state["t"] = tick * 5.0
        rec.record_telemetry("node_load", values)
        for v in range(4):
            state["t"] = tick * 5.0 + 0.5 * (v + 1)
            rec.record_verb(
                "prioritize" if v % 2 == 0 else "filter",
                candidates=nodes,
            )
    return rec


def replay_report(
    num_nodes: int = MAX_REPLAY_NODES,
    ticks: int = 6,
    whatif_nodes: int = 512,
) -> Dict:
    """The ``replay`` bench numbers: replay throughput (ticks/s through
    the SAME ReplayScenario with the vectorized load model off vs on)
    and the headline what-if demo — the recorded peak becomes the
    admission budget, so a 2x load multiplier must degrade the
    availability SLO a 1x replay keeps green."""
    nodes = min(int(num_nodes), MAX_REPLAY_NODES)
    rec = _synth_capture(nodes, ticks)
    capture = parse_capture(rec)
    out: Dict = {"num_nodes": nodes, "ticks": ticks}
    for label, vectorized in (("legacy", False), ("vectorized", True)):
        scenario = ReplayScenario(capture, vectorized=vectorized)
        twin = scenario.build({})
        try:
            # time the tick loop only: construction cost is a one-off,
            # the per-tick rate is what the 100k-scale gate bounds
            t0 = time.perf_counter()
            for t in range(scenario.ticks({})):
                scenario.apply(twin, t)
                twin.tick()
            wall = time.perf_counter() - t0
            out[f"ticks_per_s_{label}"] = round(ticks / wall, 2)
            if vectorized:
                out["replay_passed"] = all(
                    c["ok"] for c in scenario.checks(twin)
                )
        finally:
            twin.close()
    out["vectorized_speedup"] = round(
        out["ticks_per_s_vectorized"] / out["ticks_per_s_legacy"], 2
    )
    base = whatif(rec, num_nodes=whatif_nodes)
    doubled = whatif(rec, num_nodes=whatif_nodes, load_multiplier=2.0)
    avail = next(
        (n for n in sorted(base["verdicts"]) if "availability" in n),
        None,
    )
    out["whatif"] = {
        "availability_slo": avail,
        "compliance_1x": (base["verdicts"].get(avail) or {}).get(
            "compliance"
        ),
        "compliance_2x": (doubled["verdicts"].get(avail) or {}).get(
            "compliance"
        ),
        "errors_1x": base["traffic"]["errors"],
        "errors_2x": doubled["traffic"]["errors"],
    }
    out["whatif"]["degraded_at_2x"] = bool(
        avail
        and out["whatif"]["compliance_2x"] is not None
        and out["whatif"]["compliance_1x"] is not None
        and out["whatif"]["compliance_2x"]
        < out["whatif"]["compliance_1x"]
    )
    return out


def run(
    num_nodes: int = 10_000,
    pods: Optional[int] = None,
    period_s: float = 5.0,
    requests_per_tick: int = 2,
    latency_threshold_ms: float = 25.0,
    scenarios: Optional[Tuple] = None,
) -> Dict:
    """The ``twin`` bench section: the scenario matrix at scale, with
    wall-time accounting per scenario (the simulator itself must stay
    cheap enough to run every round)."""
    t0 = time.perf_counter()
    out = run_matrix(
        num_nodes=num_nodes,
        pods=pods,
        period_s=period_s,
        requests_per_tick=requests_per_tick,
        latency_threshold_ms=latency_threshold_ms,
        scenarios=scenarios if scenarios is not None else BENCH_SCENARIOS,
    )
    out["wall_s"] = round(time.perf_counter() - t0, 1)
    out["replay"] = replay_report()
    # the compact per-scenario line bench.py reports: pass/fail plus the
    # scenario's telling number
    matrix = {}
    for name, result in out["scenarios"].items():
        entry = {"passed": result["passed"], "ticks": result["ticks"]}
        failing = [c["check"] for c in result["checks"] if not c["ok"]]
        if failing:
            entry["failing"] = failing
        judgment = result.get("judgment") or {}
        fresh = judgment.get("telemetry_freshness") or {}
        if name == "metric_storm" and fresh:
            entry["page_breaches"] = (fresh.get("breaches") or {}).get("page")
            entry["budget_remaining"] = fresh.get("error_budget_remaining")
        matrix[name] = entry
    out["matrix"] = matrix
    return out


def main() -> int:
    from benchmarks import children

    identity = children.hold_chip("benchmarks.twin_load")
    result = run()
    result["platform"] = identity["platform"]
    compact = {
        name: ("pass" if entry["passed"] else f"FAIL {entry.get('failing')}")
        for name, entry in result["matrix"].items()
    }
    replay = result["replay"]
    print(
        f"twin: {result['num_nodes']} nodes / {result['pods']} pods, "
        f"{result['wall_s']}s wall — "
        + ", ".join(f"{k}={v}" for k, v in sorted(compact.items()))
        + f"; replay {replay['num_nodes']} nodes: "
        f"{replay['ticks_per_s_legacy']} -> "
        f"{replay['ticks_per_s_vectorized']} ticks/s "
        f"({replay['vectorized_speedup']}x), "
        f"2x what-if degraded={replay['whatif']['degraded_at_2x']}",
        file=sys.stderr,
    )
    print(json.dumps(result))
    return 0 if result["all_passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
