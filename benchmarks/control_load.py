"""``make bench-control``: the budget controller's head-to-head A/B
(testing/twin.py ``control_headtohead``; docs/observability.md "Budget
feedback control").

Each head-to-head program runs twice on identical twins — static
configuration vs self-tuning controller — and the verdict compares the
trigger SLO's FINAL error-budget ledger:

  * ``metric_storm``: a metric-API outage plus a demand surge on the
    queued-admission model with a retry storm armed.  Static depth turns
    the surge into timeouts that retry (metastable amplification); the
    controller converts the excess into cheap early 503s that never
    retry.  Compared on ``verb_availability``.
  * ``deployment_wave``: the rolling-update wave with the eviction API
    down for a window.  Static ``max_moves`` slams the broken dependency
    every cycle (and trips the kube circuit — collateral degradation);
    the controller throttles the churn budget and lengthens the drift
    fuse, backing off until the API heals.  Compared on
    ``eviction_safety``.

Plus the null hypothesis: a healthy diurnal day with the controller
ARMED must end with zero actuations — a controller that fidgets on a
quiet cluster is itself a defect.

The compact ledgers ride bench.py's ``control`` section; this module's
``main`` exits nonzero unless self-tuning is strictly better on BOTH
programs and the quiet day stayed quiet (the ISSUE 15 acceptance).

Scale note: the programs run at their design scale (16 nodes) — the
control dynamics under test are queue/ladder/circuit interactions whose
tick arithmetic is scale-invariant, and the twin matrix already covers
the 10k-node tier.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, Optional

from platform_aware_scheduling_tpu.testing.twin import control_headtohead


def run(
    num_nodes: int = 16,
    pods: Optional[int] = None,
    period_s: float = 5.0,
) -> Dict:
    start = time.perf_counter()
    out = control_headtohead(
        num_nodes=num_nodes, pods=pods, period_s=period_s
    )
    out["num_nodes"] = num_nodes
    out["wall_s"] = round(time.perf_counter() - start, 2)
    return out


def compact(out: Dict) -> Dict:
    """The bench-line shape: per-program final ledgers + the verdicts
    (full checks and judgments stay in BENCH_DETAIL)."""
    line = {"num_nodes": out["num_nodes"]}
    for name, entry in sorted(out["scenarios"].items()):
        line[name] = {
            "slo": entry["slo"],
            "static_budget": entry["static"]["budget"],
            "self_tuning_budget": entry["self_tuning"]["budget"],
            "actuations": entry["self_tuning"]["actuations"],
            "strictly_better": entry["strictly_better"],
        }
    line["diurnal_quiet_actuations"] = out["diurnal_quiet"]["actuations"]
    line["all_strictly_better"] = out["all_strictly_better"]
    return line


def main() -> int:
    from benchmarks import children

    identity = children.hold_chip("benchmarks.control_load")
    out = run()
    print(json.dumps({**compact(out), "platform": identity["platform"]}, indent=1))
    ok = out["all_strictly_better"] and out["diurnal_quiet"]["ok"]
    if not ok:
        print(
            "bench-control FAILED: "
            + json.dumps(
                {
                    "all_strictly_better": out["all_strictly_better"],
                    "diurnal_quiet": out["diurnal_quiet"],
                }
            ),
            file=sys.stderr,
        )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
