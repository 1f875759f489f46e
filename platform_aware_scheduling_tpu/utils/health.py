"""Health & readiness: /healthz (process liveness) and /readyz (composite
readiness) behind both HTTP front-ends (docs/observability.md).

The reference's TAS health-metric story (docs/health-metric-example.md)
is about scheduling around unhealthy *nodes*; this module applies the
same discipline to the scheduler itself: a process that is alive but
serving from cold kernels, stale telemetry, or a saturated admission
queue must say so BEFORE traffic is routed to it, not after p99 shows
it.  Readiness is a conjunction of named conditions:

  * ``kernels_warmed`` — the device fastpath's warm pass has completed
    (MetricsExtender.readiness_conditions);
  * ``telemetry_fresh`` — the TAS cache has completed a refresh pass and
    every registered metric's age is within bound
    (AutoUpdatingCache.telemetry_freshness);
  * ``policy_informer_synced`` / ``informers_synced`` — the CRD / pod /
    node informers delivered their initial list;
  * ``admission_queue`` — the async front-end's bounded queue is below
    saturation (registered by AsyncServer).

``/readyz`` answers 200 with the condition list when all hold, 503 with
the same list (failing conditions carry their reason) otherwise.  Each
evaluation updates the ``pas_ready`` gauge and counts ready <-> unready
flips on ``pas_ready_transitions_total`` — the flap count
benchmarks/http_load.py scrapes into its A/B result.  Both endpoints
bypass the async admission queue, same bar as /metrics: they must stay
readable exactly when the queue is saturated.

This module must stay importable without jax (the host layer's rule).
"""

from __future__ import annotations

import json
import threading
from typing import Callable, Dict, List, Optional, Tuple

from platform_aware_scheduling_tpu.utils import klog, trace
from platform_aware_scheduling_tpu.utils.tracing import CounterSet

#: a condition callable: () -> (ok, reason) — or a bare bool, normalized.
Check = Callable[[], Tuple[bool, str]]

HEALTHZ_BODY = b'{"status": "ok"}\n'


class ReadinessProbe:
    """Named readiness conditions, evaluated per /readyz request.

    Zero registered conditions means ready (a scheduler with nothing to
    warm or sync has nothing to wait for).  A condition that raises is
    treated as NOT ready with the exception as its reason — a broken
    check must fail closed, not report ready."""

    def __init__(self, counters: Optional[CounterSet] = None):
        self._lock = threading.Lock()
        self._conditions: List[Tuple[str, Check]] = []
        self._last_ready: Optional[bool] = None
        self.counters = counters if counters is not None else trace.COUNTERS

    def register(self, name: str, check: Check) -> "ReadinessProbe":
        with self._lock:
            self._conditions.append((name, check))
        return self

    def condition_names(self) -> List[str]:
        with self._lock:
            return [name for name, _ in self._conditions]

    def evaluate(self) -> Tuple[bool, List[Dict]]:
        """(ready, condition results); updates the gauge + flap counter."""
        with self._lock:
            conditions = list(self._conditions)
        results: List[Dict] = []
        ready = True
        for name, check in conditions:
            try:
                res = check()
                ok, reason = res if isinstance(res, tuple) else (bool(res), "")
            except Exception as exc:  # fail closed
                ok, reason = False, f"check raised: {exc!r}"
            results.append(
                {"name": name, "ok": bool(ok), "reason": reason or "ok"}
            )
            ready = ready and bool(ok)
        with self._lock:
            flipped = self._last_ready is not None and self._last_ready != ready
            self._last_ready = ready
        self.counters.set_gauge("pas_ready", 1 if ready else 0)
        if flipped:
            self.counters.inc("pas_ready_transitions_total")
        return ready, results

    def readyz_response(self) -> Tuple[int, bytes]:
        """(status, JSON body) for GET /readyz: 200 when every condition
        holds, 503 with the reason list otherwise."""
        ready, results = self.evaluate()
        body = (
            json.dumps({"ready": ready, "conditions": results}).encode()
            + b"\n"
        )
        return (200 if ready else 503), body


def probe_for(
    scheduler, counters: Optional[CounterSet] = None
) -> ReadinessProbe:
    """A probe seeded from the scheduler's ``readiness_conditions()``
    duck-type (a list of (name, check) pairs); schedulers without one
    get an empty — always ready — probe.  Front-ends layer their own
    conditions on top (AsyncServer registers admission-queue headroom)."""
    probe = ReadinessProbe(counters=counters)
    conditions = getattr(scheduler, "readiness_conditions", None)
    if callable(conditions):
        try:
            for name, check in conditions():
                probe.register(name, check)
        except Exception as exc:
            # fail CLOSED: a provider that raised may have registered
            # nothing — an always-ready probe here would route traffic
            # to a scheduler whose real conditions were never installed
            reason = f"readiness_conditions provider raised: {exc!r}"
            klog.error("readiness_conditions failed: %s", exc)
            probe.register(
                "readiness_conditions", lambda reason=reason: (False, reason)
            )
    return probe


def informer_synced(informer, name: str = "informer") -> Check:
    """A condition over an Informer's ``has_synced`` (kube/informer.py)."""

    def check() -> Tuple[bool, str]:
        ok = bool(informer.has_synced())
        return ok, ("synced" if ok else f"{name} cache not yet synced")

    return check
