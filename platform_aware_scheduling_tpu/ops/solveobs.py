"""Solve observatory: per-stage device-solve attribution + refresh churn
(docs/observability.md "Solve observatory").

The device solve sat at ~1.3 ms through the early rounds while everything
around it got 4x faster, and ROADMAP item 4's incremental solve cannot
be designed — or gated — without knowing WHERE those microseconds go and
HOW MUCH of the world actually changes per refresh.  Neither was
measured: the spans watch the wire, the SLO engine watches verdicts, the
event spine watches control flow, and all of them treat the solve as one
opaque box between "request in" and "bytes out".

This module opens the box, along two axes:

  * **stage attribution** — every instrumented solve (ranking pass,
    batched warm, filter-explain pass, batch replan, warm pass) is
    timed per stage with marks at the pipeline's natural seams:

      ``snapshot``   host-side staging: numpy copies, i64 hi/lo split,
                     pending-set assembly (ops/state._view_locked,
                     tas/planner.replan)
      ``transfer``   host->device upload (``jnp.asarray`` conversions)
      ``compile``    XLA trace+lower+compile, attributed when the
                     watched kernel's jit cache grew during the call
      ``execute``    device execution, timed across
                     ``block_until_ready`` so dispatch overlap cannot
                     hide it
      ``readback``   device->host (``np.asarray``, scalar ``int()``)
      ``encode``     rank slicing, reason decoding, skeleton renders

    Samples land in a bounded ring (``/debug/solve`` serves the tail)
    and in ``pas_solve_stage_us{stage}`` histograms.  The timer records
    the measured end-to-end total alongside the marks, so the ring
    itself proves the attribution is exhaustive (stages sum to the
    total; gated at 10% by tests/test_solveobs.py).

  * **refresh churn** — the mirror counts, per metric write, how many
    node columns actually changed (first sighting of a metric counts
    every present column — to a cold solver the whole row is news; a
    byte-identical refresh counts zero; a delete counts the columns it
    tore down).  Each refresh pass flushes the per-metric counts into
    ``pas_state_churn_rows{metric}`` / ``pas_state_churn_fraction``
    histograms, publishes a ``kind="churn"`` event into the causal
    spine (so ``/debug/explain`` can say "the world changed under
    you"), and — when a flight recorder is wired — exports the
    anonymized pass shape so replayed captures carry production churn.
    This is the delta-aware staging groundwork ROADMAP item 4 calls
    for: the measured steady-state fraction bounds what an incremental
    upload could save.

Off by default.  The whole subsystem hangs off one module-global slot
(``ACTIVE``); every instrumented site reads it once and proceeds
untouched when it is None, so the off path stays wire byte-identical
(pinned by tests/test_solveobs.py) and costs one attribute load.  The
exposition provider registered in ``trace.EXTRA_PROVIDERS`` returns ""
while disabled — no ``pas_solve_*``/``pas_state_churn_*`` families leak
into /metrics until an observatory is enabled (the flight recorder's
off-path convention).
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.tracing import CounterSet

#: the stage vocabulary, in pipeline order (docs/observability.md table)
STAGES = ("snapshot", "transfer", "compile", "execute", "readback", "encode")

#: stage-latency bucket bounds in MICROSECONDS — the solve lives in the
#: 10 us..10 ms band, far below tracing.BUCKETS' seconds-scale grid
STAGE_BOUNDS_US = (
    5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 25000.0, 100000.0,
)

#: changed-row-count bounds: zero is its own bucket on purpose — the
#: steady-state question is "how often does a refresh change NOTHING"
CHURN_ROW_BOUNDS = (
    0.0, 1.0, 2.0, 5.0, 10.0, 25.0, 100.0, 500.0, 2500.0, 10000.0, 50000.0,
)

#: fraction-of-world bounds (changed columns / world size, per metric)
CHURN_FRACTION_BOUNDS = (
    0.0, 0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0,
)

DEFAULT_CAPACITY = 256

#: passes kept for the steady-state churn summary served by /debug/solve
CHURN_RING = 256


class _Histogram:
    """One labeled cumulative histogram family with a fixed bucket grid.

    ``tracing.LatencyRecorder`` hardcodes the request-latency seconds
    grid in ``histograms_text``; solve stages live three orders of
    magnitude lower and churn counts aren't latencies at all, so each
    family here carries its own bounds.  NOT thread-safe — callers hold
    the observatory lock."""

    def __init__(self, bounds: Tuple[float, ...]):
        self.bounds = bounds
        # label -> (per-bound counts + [+Inf], sum, count)
        self._series: Dict[str, List] = {}

    def observe(self, label: str, value: float) -> None:
        series = self._series.get(label)
        if series is None:
            series = [[0] * (len(self.bounds) + 1), 0.0, 0]
            self._series[label] = series
        counts, _total, _n = series
        for i, bound in enumerate(self.bounds):
            if value <= bound:
                counts[i] += 1
                break
        else:
            counts[len(self.bounds)] += 1
        series[1] += value
        series[2] += 1

    def quantile(self, label: str, q: float) -> float:
        """Bucket upper-bound estimate of the q-quantile (the bound the
        cumulative count crosses q*n at) — exposition-grade, not exact."""
        series = self._series.get(label)
        if series is None or series[2] == 0:
            return 0.0
        counts, _total, n = series
        target = q * n
        seen = 0
        for i, count in enumerate(counts):
            seen += count
            if seen >= target:
                if i < len(self.bounds):
                    return self.bounds[i]
                break
        return float("inf")

    def summary(self, label: str) -> Dict:
        series = self._series.get(label)
        if series is None or series[2] == 0:
            return {"count": 0, "sum": 0.0, "mean": 0.0, "p50": 0.0, "p99": 0.0}
        _counts, total, n = series
        return {
            "count": n,
            "sum": round(total, 1),
            "mean": round(total / n, 2),
            "p50": self.quantile(label, 0.5),
            "p99": self.quantile(label, 0.99),
        }

    def labels(self) -> List[str]:
        return sorted(self._series)

    def text(self, metric: str, label_name: str, help_text: str) -> str:
        """Valid Prometheus exposition for the family ("" when empty)."""
        if not self._series:
            return ""
        lines = [
            f"# HELP {metric} {help_text}",
            f"# TYPE {metric} histogram",
        ]
        for label in sorted(self._series):
            counts, total, n = self._series[label]
            cumulative = 0
            for bound, count in zip(self.bounds, counts):
                cumulative += count
                le = format(bound, "g")
                lines.append(
                    f'{metric}_bucket{{{label_name}="{label}",le="{le}"}}'
                    f" {cumulative}"
                )
            lines.append(
                f'{metric}_bucket{{{label_name}="{label}",le="+Inf"}} {n}'
            )
            lines.append(
                f'{metric}_sum{{{label_name}="{label}"}} {round(total, 3)}'
            )
            lines.append(f'{metric}_count{{{label_name}="{label}"}} {n}')
        return "\n".join(lines) + "\n"


class SolveTimer:
    """Stage marks for ONE solve.  ``mark(stage)`` attributes the time
    since the previous mark; ``done()`` commits the sample with the
    independently measured end-to-end total (so the ring itself shows
    whether the marks are exhaustive).  Cheap enough to leave inline:
    two clock reads per stage boundary."""

    __slots__ = ("obs", "kind", "stages", "_t0", "_last")

    def __init__(self, obs: "SolveObservatory", kind: str):
        self.obs = obs
        self.kind = kind
        self.stages: Dict[str, float] = {}
        self._t0 = obs.clock()
        self._last = self._t0

    def mark(self, stage: str) -> float:
        """Close the current stage; returns its duration in us."""
        now = self.obs.clock()
        us = (now - self._last) * 1e6
        self._last = now
        self.stages[stage] = self.stages.get(stage, 0.0) + us
        return us

    def done(self, **extra) -> float:
        """Commit the sample; returns the measured total in us."""
        total_us = (self.obs.clock() - self._t0) * 1e6
        self.obs._commit(self.kind, self.stages, total_us, extra)
        return total_us


class SolveObservatory:
    """Bounded per-stage solve rings + refresh-churn accumulation.

    One instance per process while enabled (the ``ACTIVE`` slot); every
    method is thread-safe behind one leaf lock that is never held
    around device work or other subsystems' locks.  ``flight`` is an
    optional FlightRecorder churn passes are exported into."""

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        clock: Callable[[], float] = time.perf_counter,
    ):
        self.capacity = max(1, int(capacity))
        self.clock = clock
        self._lock = threading.Lock()
        # observatory-local CounterSet, merged into /metrics only while
        # enabled — the flight recorder's off-path convention
        self.counters = CounterSet()
        self.ring: deque = deque(maxlen=self.capacity)
        self._stage_histo = _Histogram(STAGE_BOUNDS_US)
        self._churn_rows = _Histogram(CHURN_ROW_BOUNDS)
        self._churn_fraction = _Histogram(CHURN_FRACTION_BOUNDS)
        self._churn_ring: deque = deque(maxlen=CHURN_RING)
        self._last_pass: Dict = {}
        self.world = 0
        #: optional FlightRecorder (record_churn) — wired by assembly
        self.flight = None
        #: optional TensorStateMirror whose churn accumulator this
        #: observatory drains on each refresh pass
        self.mirror = None

    # -- stage attribution ------------------------------------------------

    def begin(self, kind: str) -> SolveTimer:
        """Start timing one solve of the given pipeline kind."""
        return SolveTimer(self, kind)

    def _commit(
        self, kind: str, stages: Dict[str, float], total_us: float, extra: Dict
    ) -> None:
        sample = {
            "kind": kind,
            "stages": {s: round(us, 1) for s, us in stages.items()},
            "total_us": round(total_us, 1),
        }
        if extra:
            sample.update(extra)
        with self._lock:
            self.ring.append(sample)
            for stage, us in stages.items():
                self._stage_histo.observe(stage, us)
        self.counters.inc("pas_solve_samples_total", labels={"kind": kind})

    # -- refresh churn ----------------------------------------------------

    def flush_refresh_pass(self) -> None:
        """End-of-refresh-pass hook (``cache.on_refresh_pass``): drain
        the mirror's per-metric changed-column counts into the churn
        histograms, publish one spine event, export to the flight
        recorder.  Runs on the telemetry refresh thread; never raises."""
        try:
            self._flush_refresh_pass()
        except Exception as exc:  # never break the refresh thread
            from platform_aware_scheduling_tpu.utils import klog

            klog.error("solve observatory churn flush failed: %r", exc)

    def _flush_refresh_pass(self) -> None:
        mirror = self.mirror
        if mirror is None:
            return
        pending, world = mirror.drain_churn()
        if not pending:
            return
        total = sum(changed for changed, _deleted in pending.values())
        metrics: Dict[str, Dict] = {}
        with self._lock:
            self.world = world
            for metric, (changed, deleted) in sorted(pending.items()):
                fraction = (changed / world) if world > 0 else 0.0
                self._churn_rows.observe(metric, float(changed))
                self._churn_fraction.observe(metric, fraction)
                entry = {"rows": changed, "fraction": round(fraction, 4)}
                if deleted:
                    entry["deleted"] = True
                metrics[metric] = entry
            denom = world * len(pending)
            pass_fraction = (total / denom) if denom > 0 else 0.0
            self._last_pass = {
                "metrics": metrics,
                "total_rows": total,
                "world": world,
                "fraction": round(pass_fraction, 4),
            }
            self._churn_ring.append(pass_fraction)
        self.counters.inc("pas_state_churn_passes_total")
        self.counters.inc("pas_state_churn_rows_changed_total", total)
        self._publish_churn(len(pending), total, world, pass_fraction)
        flight = self.flight
        if flight is not None:
            recorder = getattr(flight, "record_churn", None)
            if recorder is not None:
                recorder(len(pending), total, world, pass_fraction)

    def _publish_churn(
        self, metric_count: int, rows: int, world: int, fraction: float
    ) -> None:
        from platform_aware_scheduling_tpu.utils import events

        events.JOURNAL.publish(
            "churn",
            f"refresh changed {rows} rows across {metric_count} metrics",
            data={
                "rows": rows,
                "metrics": metric_count,
                "world": world,
                "fraction": round(fraction, 4),
            },
        )

    # -- read path --------------------------------------------------------

    def churn_summary(self) -> Dict:
        with self._lock:
            passes = list(self._churn_ring)
            last = dict(self._last_pass)
            world = self.world
        if passes:
            ordered = sorted(passes)
            p50 = ordered[len(ordered) // 2]
            mean = sum(passes) / len(passes)
        else:
            p50 = mean = 0.0
        return {
            "world": world,
            "passes": len(passes),
            "last_pass": last,
            "fraction_mean": round(mean, 4),
            "fraction_p50": round(p50, 4),
        }

    def to_json_dict(self) -> Dict:
        with self._lock:
            recent = list(self.ring)[-32:]
            stages = {
                stage: self._stage_histo.summary(stage)
                for stage in self._stage_histo.labels()
            }
        compiles = {
            watch.name: watch.compile_count for watch in trace.JIT_WATCHES
        }
        return {
            "enabled": True,
            "capacity": self.capacity,
            "samples": int(
                self.counters.get("pas_solve_samples_total", kind="counter")
            ),
            "stages": stages,
            "recent": recent,
            "churn": self.churn_summary(),
            "compiles": compiles,
        }

    def to_json(self) -> bytes:
        """The ``GET /debug/solve`` payload (both front-ends)."""
        return json.dumps(self.to_json_dict()).encode() + b"\n"

    def metrics_text(self) -> str:
        """Exposition for the observatory-local families — the single
        ``trace.EXTRA_PROVIDERS`` entry renders this while enabled."""
        helps = trace.help_texts()
        with self._lock:
            parts = [
                self._stage_histo.text(
                    "pas_solve_stage_us",
                    "stage",
                    helps.get("pas_solve_stage_us", ""),
                ),
                self._churn_rows.text(
                    "pas_state_churn_rows",
                    "metric",
                    helps.get("pas_state_churn_rows", ""),
                ),
                self._churn_fraction.text(
                    "pas_state_churn_fraction",
                    "metric",
                    helps.get("pas_state_churn_fraction", ""),
                ),
            ]
        parts.append(self.counters.prometheus_text(help_texts=helps))
        return "".join(parts)


#: THE off-path gate: every instrumented site reads this once per solve
#: and takes the untouched path when it is None.  Module-global (not an
#: extender attribute) because the pipeline spans layers that never see
#: the extender — ops/state.py, the models, the planner.
ACTIVE: Optional[SolveObservatory] = None


def enable(
    capacity: int = DEFAULT_CAPACITY,
    clock: Callable[[], float] = time.perf_counter,
) -> SolveObservatory:
    """Install (and return) a fresh process-wide observatory."""
    global ACTIVE
    obs = SolveObservatory(capacity=capacity, clock=clock)
    ACTIVE = obs
    return obs


def disable() -> None:
    """Tear the observatory down; instrumented sites revert to the
    untouched path on their next ``ACTIVE`` read."""
    global ACTIVE
    ACTIVE = None


def _provider() -> str:
    obs = ACTIVE
    return obs.metrics_text() if obs is not None else ""


# one provider for the process, registered at import (the gang tracker's
# histogram precedent) — renders "" until an observatory is enabled
trace.EXTRA_PROVIDERS.append(_provider)
