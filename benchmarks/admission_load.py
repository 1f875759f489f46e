"""``make bench-admission``: the admission plane's acceptance A/B.

Three measurements (docs/admission.md):

  * **preemption cascade head-to-head** — the mixed-priority wave from
    testing/twin.py driven through the REAL verbs (Filter -> Prioritize
    -> Bind) on a 4x4 mesh twin: two batch gangs fill the mesh, then a
    high-priority gang arrives.  With ``--preemption=on`` the planner
    evicts the cheapest whole batch gang all-or-nothing and the high
    gang binds within a bounded number of ticks; with the planner OFF
    the high gang starves forever (the deadlock) while not a single pod
    is evicted.  The verdict compares the HIGH class's final
    error-budget ledgers — ON must finish strictly better — plus the
    quiet-diurnal null (an armed plane on an uncontended cluster must
    never queue, block, or preempt).

  * **gate overhead** — wall time of one ``AdmissionPlane.review`` on
    the uncontended hot path (Filter passed, queue empty): the tax every
    Filter decision pays while ``--admission=on``, worth knowing next to
    the microsecond wire floor.

  * **queue churn throughput** — enqueue/hold/admit cycles per second
    through a full queue: the gatekeeper under a storm of capacity
    misses (bounded-depth shedding included).

Hermetic like the other benches: fake kube, fake clocks inside the twin,
in-process verbs.  Exits nonzero unless the head-to-head verdict is
clean — this is the ISSUE 16 acceptance gate in executable form.
"""

from __future__ import annotations

import json
import time
from typing import Dict

from platform_aware_scheduling_tpu.admission import AdmissionPlane
from platform_aware_scheduling_tpu.testing.builders import make_pod
from platform_aware_scheduling_tpu.utils import decisions
from platform_aware_scheduling_tpu.utils import labels as shared_labels


def _pod(name: str, klass: str):
    return make_pod(name, labels={shared_labels.PRIORITY_LABEL: klass})


def gate_overhead(n: int = 2000) -> Dict:
    """Mean/worst ns for one uncontended review (Filter passed, empty
    queue) — the per-decision tax of ``--admission=on``."""
    plane = AdmissionPlane()
    pod = _pod("hot", "normal")
    nodes = [f"n{i}" for i in range(32)]
    worst = 0.0
    start = time.perf_counter()
    for _ in range(n):
        t0 = time.perf_counter()
        plane.review(pod, nodes, {}, {})
        worst = max(worst, time.perf_counter() - t0)
    total = time.perf_counter() - start
    return {
        "reviews": n,
        "mean_us": round(total / n * 1e6, 2),
        "worst_us": round(worst * 1e6, 2),
    }


def queue_churn(n: int = 2000, depth: int = 64) -> Dict:
    """Capacity-miss storm throughput: every review either enqueues,
    ages a queued entry, or sheds against the bounded depth."""
    plane = AdmissionPlane(max_depth=depth)
    classes = ("high", "normal", "batch")
    nodes = ["n0", "n1"]
    failed = {name: "capacity" for name in nodes}
    codes = {name: decisions.CODE_GANG_INFEASIBLE for name in nodes}
    start = time.perf_counter()
    for i in range(n):
        pod = _pod(f"p-{i % (depth * 2)}", classes[i % 3])
        plane.review(pod, nodes, dict(failed), dict(codes))
    wall = time.perf_counter() - start
    snap = plane.snapshot()
    return {
        "reviews": n,
        "reviews_per_s": round(n / wall),
        "final_depth": snap["depth"],
        "shed": snap["counters"]["rejected"],
    }


def run() -> Dict:
    from platform_aware_scheduling_tpu.testing.twin import (
        admission_headtohead,
    )

    start = time.time()
    out = admission_headtohead()
    out["gate_overhead"] = gate_overhead()
    out["queue_churn"] = queue_churn()
    out["wall_s"] = round(time.time() - start, 1)
    return out


def compact(out: Dict) -> Dict:
    """The bench-line shape (full checks stay in BENCH_DETAIL)."""
    on = out["preemption_on"]
    off = out["preemption_off"]
    return {
        "slo": out["slo"],
        "preemption_on_budget": on["budget"],
        "high_gang_admitted_on": on["admitted"],
        "preemption_off_budget": off["budget"],
        "strictly_better": out["strictly_better"],
        "diurnal_quiet_ok": out["diurnal_quiet"]["ok"],
        "gate_overhead_us": out["gate_overhead"]["mean_us"],
        "queue_reviews_per_s": out["queue_churn"]["reviews_per_s"],
        "all_ok": out["all_ok"],
    }


def main() -> int:
    from benchmarks import children

    identity = children.hold_chip("benchmarks.admission_load")
    out = run()
    print(json.dumps({**compact(out), "platform": identity["platform"]}, indent=1))
    return 0 if out["all_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
