"""Flight recorder: a bounded ring of anonymized control-plane events
(docs/observability.md "Flight recorder & what-if").

Production traffic becomes twin scenarios: both front-ends can record
what they actually see — verb arrivals, telemetry movement, eviction and
leadership flips — into a fixed-size in-memory ring, exportable as
versioned JSONL via ``GET /debug/record`` and replayable through the
digital twin (testing/replay.py) to answer "what if yesterday's traffic
arrived at 2x load?" with projected SLO verdicts.

The anonymization contract (gated by tests/test_record.py, not merely
promised here): a capture NEVER contains node, pod, or namespace names.

  * verb events carry the PR-11 interned-universe digest (a 64-bit span
    hash over the candidate-name bytes — irreversible) plus the
    candidate COUNT and the pod's gang size label, nothing more; when no
    universe is interned (cold span, host path) the key is simply null —
    the recorder must stay O(1) on the hot path, so it never hashes a
    10k-name list itself;
  * telemetry events summarize each refresh pass as a per-metric DECILE
    curve (11 quantiles + node count) — the load SHAPE replays, the
    node->value map never leaves the process;
  * eviction and leadership events are bare counts/flips.

Off by default (``--flightRecorder=off``): while no recorder is wired
the verbs skip a single attribute check and the wire stays
byte-identical (pinned by tests/test_record.py).  The ring is bounded
(``DEFAULT_CAPACITY``); overflow drops the OLDEST event and counts it in
``pas_record_dropped_total`` — a flight recorder keeps the latest
window, like its aviation namesake.

All stamps come from the injectable clock, so a twin-hosted recorder
produces replayable fake-clock timelines and a production recorder
produces wall-clock ones, through the same code.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from collections.abc import Mapping
from typing import Dict, Iterable, List, Optional

from platform_aware_scheduling_tpu.utils import klog, trace
from platform_aware_scheduling_tpu.utils.tracing import CounterSet

#: capture format version: bumped on any event-schema change so a
#: replay loader can refuse captures it would misread.  /2 added the
#: causal-spine passthrough events (kind "spine": utils/events.py
#: forwards journal events with an irreversible process-local
#: correlation hash); /3 added refresh-churn summaries (kind "churn":
#: counts + fraction-of-world per pass, ops/solveobs.py — replayed
#: captures carry production churn shape for ROADMAP item 4's
#: delta-aware staging).  /4 added partition-plane events (kind
#: "shard": ownership assigns/handoffs as partition id + fencing epoch,
#: utils/record.record_shard — ids and epochs only, no member names).
#: Loaders that fold a capture into a twin scenario ignore kinds they
#: don't infer from, so all stay replayable.
FORMAT = "pas-flight-record/4"

#: ring capacity; overflow drops the OLDEST event (the recorder keeps the
#: latest window) and counts it in pas_record_dropped_total
DEFAULT_CAPACITY = 4096

#: decile grid for telemetry summaries (0%, 10%, ..., 100%)
QUANTILES = tuple(i / 10.0 for i in range(11))


def decile_summary(values: Iterable[float]) -> Optional[List[float]]:
    """The 11-point decile curve of ``values`` (linear interpolation
    between order statistics), or None for an empty pass.  This is the
    WHOLE anonymized representation of a telemetry refresh: enough to
    replay the load distribution at recorded scale, nothing to join back
    to a node name."""
    data = sorted(float(v) for v in values)
    if not data:
        return None
    last = len(data) - 1
    curve: List[float] = []
    for q in QUANTILES:
        pos = q * last
        lo = int(pos)
        hi = min(lo + 1, last)
        frac = pos - lo
        curve.append(round(data[lo] * (1.0 - frac) + data[hi] * frac, 3))
    return curve


class FlightRecorder:
    """Bounded, clock-injectable ring of anonymized control-plane events.

    Hot-path cost budget: :meth:`record_verb` is one lock, one deque
    append, one counter increment — measured <=5% p99 against the
    recorder-off path by benchmarks/http_load.record_overhead.  The
    heavier summarizers (:meth:`record_telemetry`, :meth:`poll_control`)
    run on the telemetry refresh thread, never on a request."""

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        clock=time.monotonic,
    ):
        self.capacity = max(1, int(capacity))
        self.clock = clock
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._dropped = 0
        # recorder-local CounterSet, merged into /metrics only while a
        # recorder is wired — the SLO engine's off-path convention:
        # --flightRecorder=off emits no pas_record_* families at all
        self.counters = CounterSet()
        # control-event baselines for poll_control(): the recorder
        # watches fleet counters it does not own and emits events on
        # movement (one subscription point instead of N call sites)
        self._seen_evictions: Optional[float] = None
        self._seen_leader: Optional[bool] = None

    # -- event intake ----------------------------------------------------------

    def _append(self, event: Dict) -> None:
        with self._lock:
            if len(self._ring) == self.capacity:
                self._dropped += 1
                self.counters.inc("pas_record_dropped_total")
            self._ring.append(event)
            self.counters.inc("pas_record_events_total")

    def record_verb(
        self,
        verb: str,
        universe_uid: Optional[int] = None,
        candidates: int = 0,
        gang_size: int = 0,
    ) -> None:
        """One verb arrival.  ``universe_uid`` is the interned-universe
        digest when the wire path interned this candidate span, else
        None — the recorder never derives a key itself (O(1) rule)."""
        event = {
            "t": round(self.clock(), 6),
            "kind": "verb",
            "verb": verb,
            "universe": (
                format(universe_uid & 0xFFFFFFFFFFFFFFFF, "016x")
                if universe_uid is not None
                else None
            ),
            "candidates": int(candidates),
        }
        if gang_size:
            event["gang_size"] = int(gang_size)
        self._append(event)

    def record_telemetry(
        self, metric: str, values: Iterable[float]
    ) -> None:
        """One refresh pass's movement for ``metric``, anonymized to a
        decile curve + node count.  Metric NAMES are operator-chosen
        policy vocabulary (``node_load``), not cluster topology, so they
        stay."""
        data = list(values)
        curve = decile_summary(data)
        if curve is None:
            return
        self._append(
            {
                "t": round(self.clock(), 6),
                "kind": "telemetry",
                "metric": str(metric),
                "nodes": len(data),
                "deciles": curve,
            }
        )

    def record_eviction(self, count: int = 1) -> None:
        if count <= 0:
            return
        self._append(
            {
                "t": round(self.clock(), 6),
                "kind": "eviction",
                "count": int(count),
            }
        )

    def record_leader(self, is_leader: bool) -> None:
        self._append(
            {
                "t": round(self.clock(), 6),
                "kind": "leader",
                "leader": bool(is_leader),
            }
        )

    def record_spine(
        self, kind: str, event: str, tick: int, corr: str
    ) -> None:
        """One causal-spine event (utils/events.py forwards every
        journal publish here while wired).  Anonymization holds: the
        correlation keys (pod/gang/node/request id) are collapsed into
        ``corr``, an irreversible process-local hash — chains stay
        joinable within one capture, nothing joins back to a name."""
        self._append(
            {
                "t": round(self.clock(), 6),
                "kind": "spine",
                "spine_kind": str(kind),
                "event": str(event),
                "tick": int(tick),
                "corr": str(corr),
            }
        )

    def record_churn(
        self, metrics: int, rows: int, world: int, fraction: float
    ) -> None:
        """One refresh pass's churn shape (ops/solveobs.py flushes this
        while an observatory is wired next to the recorder).
        Anonymization holds by construction: counts and a fraction, no
        metric or node names — the pass SHAPE replays, nothing joins
        back to a cluster."""
        self._append(
            {
                "t": round(self.clock(), 6),
                "kind": "churn",
                "metrics": int(metrics),
                "rows": int(rows),
                "world": int(world),
                "fraction": round(float(fraction), 4),
            }
        )

    def record_shard(self, event: str, partition: int, epoch: int) -> None:
        """One partition-ownership event (shard/partition.py publishes
        assigns/handoffs here while wired).  Anonymization holds by
        construction: a partition id and a fencing epoch — replica
        identities and node names never enter the capture."""
        self._append(
            {
                "t": round(self.clock(), 6),
                "kind": "shard",
                "event": str(event),
                "partition": int(partition),
                "epoch": int(epoch),
            }
        )

    # -- control-event polling -------------------------------------------------

    def poll_control(self) -> None:
        """Diff the fleet's eviction/leadership families since the last
        pass and emit events on movement.  Runs on the telemetry refresh
        thread (subscribed via ``cache.on_refresh_pass``), so one
        subscription covers every actuator instead of hooking each."""
        try:
            executed = trace.COUNTERS.get(
                "pas_rebalance_moves_executed_total", kind="counter"
            )
            if self._seen_evictions is None:
                self._seen_evictions = executed
            elif executed > self._seen_evictions:
                self.record_eviction(int(executed - self._seen_evictions))
                self._seen_evictions = executed
            leader_val = trace.COUNTERS.get("pas_leader", kind="gauge")
            is_leader = bool(leader_val and leader_val > 0)
            if self._seen_leader is None or is_leader != self._seen_leader:
                # the FIRST observation is itself an event: a capture
                # should say which role the window started in
                self.record_leader(is_leader)
                self._seen_leader = is_leader
        except Exception as exc:  # never break the refresh thread
            klog.error("flight recorder control poll failed: %r", exc)

    def observe_cache(self, cache) -> None:
        """One telemetry refresh pass: summarize every registered
        metric's current values (milli-exact, scaled back to metric
        units) and poll the control families.  This is the single
        ``cache.on_refresh_pass`` subscription assembly wires."""
        try:
            for name in cache.registered_metric_names():
                try:
                    info = cache.read_metric(name)
                except Exception:
                    continue
                if not isinstance(info, Mapping) or not info:
                    continue
                values = []
                for metric in info.values():
                    try:
                        milli, _exact = metric.value.milli_value_exact()
                        values.append(milli / 1000.0)
                    except Exception:
                        continue
                self.record_telemetry(name, values)
        except Exception as exc:  # never break the refresh thread
            klog.error("flight recorder telemetry pass failed: %r", exc)
        self.poll_control()

    # -- export ----------------------------------------------------------------

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "format": FORMAT,
                "capacity": self.capacity,
                "events": len(self._ring),
                "dropped": self._dropped,
            }

    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._ring)

    def to_jsonl(self) -> bytes:
        """Versioned JSONL: a header object line, then one event per
        line — streamable, greppable, and the exact payload
        testing/replay.parse_capture consumes."""
        # snapshot under the lock, serialize after release: dumping the
        # whole ring is O(capacity) and this lock sits on the record path
        with self._lock:
            header = {
                "format": FORMAT,
                "capacity": self.capacity,
                "events": len(self._ring),
                "dropped": self._dropped,
            }
            events = list(self._ring)
        lines = [json.dumps(header, separators=(",", ":"))]
        lines.extend(
            json.dumps(event, separators=(",", ":")) for event in events
        )
        return ("\n".join(lines) + "\n").encode()
