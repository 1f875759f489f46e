"""Scheduling decision provenance: per-decision explain records, the
bounded DecisionLog ring behind ``GET /debug/decisions``, and the
integer reason-code taxonomy shared by the host and device paths.

The reference PAS answers Filter/Prioritize with opaque verdicts — the
wire's per-node ``FailedNodes`` map carries the literal "Node violates"
(telemetryscheduler.go:206) — so an operator can never answer "why
didn't pod X land on node Y?" or "are our placements actually good?".
This module closes that gap without touching the hot path's cost
profile:

  * **Reason codes are small integers.**  The device kernels return a
    per-node *first-matching-rule index* vector alongside the violation
    verdict (ops/scoring.filter_explain_kernel); the host strategies
    produce the identical indexes (tas/strategies/dontschedule.py
    ``violated_details``), so native↔host provenance is byte-comparable.
    Rule indexes decode host-side — once per state change, never per
    request — into reason strings via :func:`rule_reason`.

  * **A DecisionRecord is O(1) to create.**  Per-node detail is held by
    REFERENCE to structures shared across requests (the per-state
    violation-reason map, the per-ranking score head), so recording a
    decision on the native fastpath costs an object allocation, a deque
    append, and a few counter bumps — the ≤5 % serving-p99 budget the
    http_load decision A/B pins.

  * **Outcome feedback closes the loop.**  Pod-bind observations (TAS
    Bind parses the body before its reference-parity 404; GAS Bind on
    success) flow back into the pod's open records: the chosen node's
    score rank and whether it was violating at decision time become the
    ``pas_decision_*`` placement-quality metric families.  The
    rebalancer's evict/skip causes land as events on the evicted pod's
    open records.

Everything is served on ``GET /debug/decisions`` (both front-ends,
admission-queue bypass like /debug/traces) with ``?pod=``, ``?verb=``
and ``?limit=`` filters; 404 while the log is disabled
(``--decisionLog=off``).  See docs/observability.md "Decision
provenance".
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Mapping, Optional, Tuple

from platform_aware_scheduling_tpu.utils import trace

# ---------------------------------------------------------------------------
# reason-code taxonomy
# ---------------------------------------------------------------------------

#: integer decision codes — the compact form the device fastpaths carry.
#: TAS rule violations additionally carry the violated RULE INDEX (the
#: first matching rule, by policy rule-list position) as their detail.
CODE_ELIGIBLE = 0
CODE_RULE_VIOLATION = 1
CODE_FAIL_CLOSED = 2
CODE_GAS_UNKNOWN_NODE = 3
CODE_GAS_NO_GPUS = 4
CODE_GAS_CAPACITY = 5
CODE_GAS_ERROR = 6  # host-loop unexpected failure; no device analog
CODE_GANG_RESERVED = 7  # node held by another gang's reservation
CODE_GANG_INFEASIBLE = 8  # no feasible slice / node outside the gang's slice
CODE_ADMISSION_BLOCKED = 9  # admission queue holding the pod back

#: code -> bounded Prometheus ``reason`` label (never per-rule/per-node:
#: label cardinality stays fixed; per-rule detail lives in the records
#: and the wire reason strings)
CODE_LABELS: Dict[int, str] = {
    CODE_RULE_VIOLATION: "rule_violation",
    CODE_FAIL_CLOSED: "fail_closed",
    CODE_GAS_UNKNOWN_NODE: "gas_unknown_node",
    CODE_GAS_NO_GPUS: "gas_no_gpus",
    CODE_GAS_CAPACITY: "gas_capacity",
    CODE_GAS_ERROR: "gas_error",
    CODE_GANG_RESERVED: "gang_reserved",
    CODE_GANG_INFEASIBLE: "gang_infeasible",
    CODE_ADMISSION_BLOCKED: "admission_blocked",
}

#: the capacity-vs-policy split the admission queue keys on.  A
#: QUEUEABLE failure is transient cluster state — someone else holds the
#: capacity right now (gang reservations, GAS card occupancy, no feasible
#: slice THIS tick) — so retrying from the queue can succeed without any
#: policy change.  Everything else is TERMINAL for the queue: a
#: ``dontschedule`` policy rejection, fail-closed degradation, or a node
#: that structurally cannot host the pod will fail identically on every
#: retry, so enqueueing it would only burn fairness budget (the
#: never-retry-a-policy-rejection pin in tests/test_admission.py).
QUEUEABLE_CODES = frozenset(
    {CODE_GAS_CAPACITY, CODE_GANG_RESERVED, CODE_GANG_INFEASIBLE}
)


def queueable(code: int) -> bool:
    """Whether one Filter failure code is capacity-class (retryable from
    the admission queue) rather than policy/error-class (terminal)."""
    return code in QUEUEABLE_CODES


def queueable_counts(reason_counts: Mapping[int, int]) -> bool:
    """Whether a whole Filter failure is queueable: every failed node's
    reason must be capacity-class.  One terminal reason anywhere makes
    the decision terminal — a pod rejected by policy on half the mesh
    and capacity on the other half would never bind even if the capacity
    half freed up, unless the policy verdict changes (which re-enters
    Filter on its own)."""
    counted = False
    for code, count in reason_counts.items():
        if not count:
            continue
        counted = True
        if code not in QUEUEABLE_CODES:
            return False
    return counted

REASON_FAIL_CLOSED = "degraded fail-closed"
REASON_GAS_UNKNOWN = "gas: node unknown to cache"
REASON_GAS_NO_GPUS = "gas: node has no GPUs"
REASON_GAS_ERROR = "gas: node could not be evaluated"

_OP_SYMBOLS = {"LessThan": "<", "GreaterThan": ">", "Equals": "=="}


def fmt_milli(milli: int) -> str:
    """Decimal string of a milli-unit int64 ("93000" -> "93", "500" ->
    "0.5").  Both provenance paths format observed values and thresholds
    through this one function from the SAME milli integers the device
    mirror stores, so native and host reason strings are byte-identical
    wherever the device path is eligible at all."""
    sign = "-" if milli < 0 else ""
    whole, frac = divmod(abs(int(milli)), 1000)
    if frac == 0:
        return f"{sign}{whole}"
    return f"{sign}{whole}.{str(frac).zfill(3).rstrip('0')}"


def rule_reason(
    policy: str, metric: str, operator: str, value_str: str, target_str: str
) -> str:
    """The concrete Filter ``FailedNodes`` reason for one violated rule:
    which policy, which metric, observed value vs threshold — e.g.
    ``policy cpu-pol: metric cpu=93 > threshold 80``."""
    sym = _OP_SYMBOLS.get(operator, operator)
    return f"policy {policy}: metric {metric}={value_str} {sym} threshold {target_str}"


def gas_reason(code: int, request_summary: str = "") -> str:
    """The concrete GAS Filter reason for one failed node; identical on
    the device (vmapped binpack) and host (per-node loop) paths because
    both derive it from the same code + the pod's own request."""
    if code == CODE_GAS_UNKNOWN_NODE:
        return REASON_GAS_UNKNOWN
    if code == CODE_GAS_NO_GPUS:
        return REASON_GAS_NO_GPUS
    if code == CODE_GAS_ERROR:
        return REASON_GAS_ERROR
    if request_summary:
        return f"gas: no card fits request ({request_summary})"
    return "gas: no card fits request"


def _rank_bucket(rank: Optional[int]) -> str:
    if rank is None:
        return "unknown"
    if rank <= 3:
        return str(rank)
    if rank <= 8:
        return "4_8"
    if rank <= 16:
        return "9_16"
    return "17_plus"


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------

#: per-record bound on materialized per-node detail in to_dict(); the
#: underlying shared reason maps are complete — only the JSON rendering
#: truncates (the endpoint must stay bounded at 10k-node scale)
DETAIL_NODE_CAP = 32

#: retention bound for a record's OWN per-request violating map
#: (violating_scope="request"): a fail-closed Filter at 10k nodes must
#: not pin a fresh 10k-entry dict per ring slot.  Shared per-state maps
#: (scope "policy_state") stay full by reference — they are one object
#: per state, not per record.
RETAIN_NODE_CAP = 128


class DecisionRecord:
    """One Filter/Prioritize/rebalance decision, keyed by request-id +
    pod.  Open until an outcome observation (pod bind, rebalance
    eviction) closes it or the ring overwrites it."""

    __slots__ = (
        "seq",
        "request_id",
        "verb",
        "pod_namespace",
        "pod_name",
        "policy",
        "path",
        "ts",
        "candidates",
        "eligible",
        "filtered",
        "violating",
        "violating_scope",
        "violating_total",
        "metric",
        "operator",
        "score_head",
        "planned",
        "detail",
        "outcome",
        "events",
        "_ranked",
        "_node_index",
    )

    def __init__(
        self,
        verb: str,
        request_id: str = "",
        pod_namespace: str = "",
        pod_name: str = "",
        policy: str = "",
        path: str = "",
        candidates: int = 0,
        filtered: int = 0,
        violating: Optional[Mapping[str, str]] = None,
        violating_scope: str = "request",
        metric: str = "",
        operator: str = "",
        score_head: Optional[List[Tuple[str, int]]] = None,
        planned: Optional[str] = None,
        detail: Optional[Dict] = None,
        ranked=None,
        node_index: Optional[Mapping[str, int]] = None,
    ):
        self.seq = 0  # assigned by the log
        self.request_id = request_id
        self.verb = verb
        self.pod_namespace = pod_namespace
        self.pod_name = pod_name
        self.policy = policy
        self.path = path
        self.ts = 0.0  # stamped by the log's clock in add(), like seq
        self.candidates = candidates
        self.filtered = filtered
        self.eligible = max(0, candidates - filtered)
        # shared, state-level reason map (device paths) or the request's
        # own failed map (exact path) — ``violating_scope`` says which
        violating = violating if violating is not None else {}
        self.violating_total = len(violating)
        if (
            violating_scope == "request"
            and len(violating) > RETAIN_NODE_CAP
        ):
            violating = dict(
                pair
                for pair, _ in zip(violating.items(), range(RETAIN_NODE_CAP))
            )
        self.violating = violating
        self.violating_scope = violating_scope
        self.metric = metric
        self.operator = operator
        self.score_head = score_head if score_head is not None else []
        self.planned = planned
        self.detail = detail
        self.outcome: Optional[Dict] = None
        self.events: List[Dict] = []
        # device-path rank lookup at bind time: the shared global
        # ranking + interning table (references, not copies)
        self._ranked = ranked
        self._node_index = node_index

    @property
    def pod_key(self) -> str:
        return f"{self.pod_namespace}/{self.pod_name}"

    def chosen_rank(self, node: str) -> Optional[int]:
        """1-based score rank of ``node`` in this decision's ordering, or
        None when unknown (host-path records keep only the score head)."""
        if self._ranked is not None and self._node_index is not None:
            row = self._node_index.get(node)
            if row is None:
                return None
            import numpy as np

            at = np.nonzero(self._ranked == row)[0]
            return int(at[0]) + 1 if at.size else None
        for i, (name, _score) in enumerate(self.score_head):
            if name == node:
                return i + 1
        return None

    def to_dict(self) -> Dict:
        violating = {}
        truncated = self.violating_total > len(self.violating)
        for i, (name, reason) in enumerate(self.violating.items()):
            if i >= DETAIL_NODE_CAP:
                truncated = True
                break
            violating[name] = reason
        out = {
            "seq": self.seq,
            "request_id": self.request_id,
            "verb": self.verb,
            "pod": self.pod_key,
            "policy": self.policy,
            "path": self.path,
            "ts": round(self.ts, 6),
            "candidates": self.candidates,
            "eligible": self.eligible,
            "filtered": self.filtered,
            "violating": violating,
            "violating_scope": self.violating_scope,
            "open": self.outcome is None,
        }
        if truncated:
            out["violating_truncated"] = True
            out["violating_total"] = self.violating_total
        if self.metric:
            out["metric"] = self.metric
            out["operator"] = self.operator
        if self.score_head:
            out["score_head"] = [
                {"node": n, "score": s} for n, s in self.score_head
            ]
        if self.planned is not None:
            out["planned"] = self.planned
        if self.detail is not None:
            out["detail"] = self.detail
        if self.outcome is not None:
            out["outcome"] = self.outcome
        if self.events:
            out["events"] = list(self.events)
        return out


# ---------------------------------------------------------------------------
# the log
# ---------------------------------------------------------------------------


class DecisionLog:
    """Bounded ring of DecisionRecords + a pod-keyed index of the OPEN
    ones (awaiting bind/rebalance feedback).  Lock-light: one short lock
    per record/feedback event; /debug/decisions serves a snapshot."""

    def __init__(
        self,
        capacity: int = 512,
        enabled: bool = True,
        clock: Callable[[], float] = time.time,
    ):
        self.capacity = max(1, capacity)
        self.enabled = enabled
        self._clock = clock
        self._lock = threading.Lock()
        self._records: deque = deque()
        self._open_by_pod: Dict[str, List[DecisionRecord]] = {}
        self._seq = 0
        self._recorded_total = 0
        self._open = 0

    # -- configuration ---------------------------------------------------------

    def configure(
        self, enabled: Optional[bool] = None, capacity: Optional[int] = None
    ) -> None:
        """Apply --decisionLog (and a ring capacity); resets the ring (the
        records recorded under the old configuration keyed a different
        retention contract)."""
        with self._lock:
            if capacity is not None:
                self.capacity = max(1, int(capacity))
            if enabled is not None:
                self.enabled = bool(enabled)
            self._records.clear()
            self._open_by_pod.clear()
            self._open = 0
            self._recorded_total = 0
        trace.COUNTERS.set_gauge("pas_decision_open", 0.0)

    def clear(self) -> None:
        self.configure()

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    # -- recording -------------------------------------------------------------

    def add(self, record: DecisionRecord) -> None:
        if not self.enabled:
            return
        evicted_open = 0
        with self._lock:
            self._seq += 1
            record.seq = self._seq
            record.ts = self._clock()
            self._recorded_total += 1
            self._records.append(record)
            # records born closed (rebalance cycle summaries) never count
            # open: nothing can ever feed them back, and counting them
            # would fire the ring-too-small counter on every eviction
            if record.outcome is None:
                self._open += 1
                self._open_by_pod.setdefault(record.pod_key, []).append(
                    record
                )
            while len(self._records) > self.capacity:
                old = self._records.popleft()
                if old.outcome is None:
                    self._open -= 1
                    evicted_open += 1
                bucket = self._open_by_pod.get(old.pod_key)
                if bucket is not None:
                    try:
                        bucket.remove(old)
                    except ValueError:
                        pass
                    if not bucket:
                        del self._open_by_pod[old.pod_key]
            open_now = self._open
        trace.COUNTERS.inc(
            "pas_decision_records_total", labels={"verb": record.verb}
        )
        if evicted_open:
            trace.COUNTERS.inc(
                "pas_decision_evicted_open_total", evicted_open
            )
        trace.COUNTERS.set_gauge("pas_decision_open", float(open_now))

    def record_filter(
        self,
        verb: str = "filter",
        reason_code: int = CODE_RULE_VIOLATION,
        reason_counts: Optional[Dict[int, int]] = None,
        **kwargs,
    ) -> None:
        """One Filter decision.  ``filtered`` is the request's exact
        failed-node count (the per-reason counters must be exact even
        when the per-node map is a shared state-level reference); pass
        ``reason_counts`` ({code: node count}) when one request mixes
        reason classes (GAS: no-GPUs nodes next to capacity misses)."""
        if not self.enabled:
            return
        record = DecisionRecord(verb=verb, **kwargs)
        self.add(record)
        if reason_counts:
            for code, count in reason_counts.items():
                if count:
                    trace.COUNTERS.inc(
                        "pas_decision_filtered_nodes_total",
                        count,
                        labels={"reason": CODE_LABELS.get(code, "other")},
                    )
        elif record.filtered:
            trace.COUNTERS.inc(
                "pas_decision_filtered_nodes_total",
                record.filtered,
                labels={"reason": CODE_LABELS.get(reason_code, "other")},
            )

    def record_prioritize(self, verb: str = "prioritize", **kwargs) -> None:
        if not self.enabled:
            return
        self.add(DecisionRecord(verb=verb, **kwargs))

    def record_rebalance(self, detail: Dict) -> None:
        """One rebalance cycle's plan/actuation summary as a record
        (pod-less: the per-pod linkage happens via observe_rebalance)."""
        if not self.enabled:
            return
        record = DecisionRecord(
            verb="rebalance",
            pod_namespace="-",
            pod_name="rebalance",
            path=detail.get("mode", ""),
            detail=detail,
        )
        # a cycle summary IS its own outcome — born closed, so it never
        # inflates pas_decision_open or the evicted-open counter
        record.outcome = {"completed": True}
        self.add(record)

    def record_control(self, detail: Dict) -> None:
        """One budget-controller actuation (utils/control.py): the knob,
        direction, trigger SLO and before/after settings, pod-less and
        born closed like a rebalance cycle summary — an actuation is its
        own outcome."""
        if not self.enabled:
            return
        record = DecisionRecord(
            verb="control",
            pod_namespace="-",
            pod_name=str(detail.get("knob", "control")),
            path=str(detail.get("direction", "")),
            detail=detail,
        )
        record.outcome = {"completed": True}
        self.add(record)

    def record_admission(self, detail: Dict) -> None:
        """One admission-plane event (admission/plane.py): enqueue,
        backfill, overflow shed, or starvation promotion — keyed by the
        subject pod but born closed (the pod's own Filter records carry
        the open/bind lifecycle; the admission event is its own
        outcome)."""
        if not self.enabled:
            return
        pod = str(detail.get("pod", "-/admission"))
        namespace, _, name = pod.partition("/")
        record = DecisionRecord(
            verb="admission",
            request_id=str(detail.get("request_id", "")),
            pod_namespace=namespace or "-",
            pod_name=name or "admission",
            path=str(detail.get("event", "")),
            detail=detail,
        )
        record.outcome = {"completed": True}
        self.add(record)

    def record_preemption(self, detail: Dict) -> None:
        """One gang preemption (admission/preempt.py): which gang was
        admitted over which victims, the per-victim eviction counts, and
        the slice reserved for the preemptor — the provenance record the
        acceptance gate requires for EVERY preemption.  Born closed like
        a rebalance cycle summary."""
        if not self.enabled:
            return
        pod = str(detail.get("target", "-/preemption"))
        namespace, _, name = pod.partition("/")
        record = DecisionRecord(
            verb="preemption",
            request_id=str(detail.get("request_id", "")),
            pod_namespace=namespace or "-",
            pod_name=name or "preemption",
            path=str(detail.get("outcome", "")),
            detail=detail,
        )
        record.outcome = {"completed": True}
        self.add(record)

    # -- outcome feedback ------------------------------------------------------

    def observe_bind(self, namespace: str, name: str, node: str) -> None:
        """A pod-bind observation: close the pod's open records, scoring
        placement quality against what was decided — the chosen node's
        rank in the Prioritize ordering, and whether Filter had marked it
        violating at decision time."""
        if not self.enabled:
            return
        key = f"{namespace}/{name}"
        bound_at = self._clock()
        violated = False
        rank: Optional[int] = None
        # outcomes are assigned UNDER the lock: a record must never sit
        # decremented-from-_open but still outcome-None, or a concurrent
        # add()'s ring eviction would double-decrement it (binds are
        # rare, so the rank lookup's numpy scan is fine to hold here)
        with self._lock:
            open_records = self._open_by_pod.pop(key, [])
            closed = [r for r in open_records if r.outcome is None]
            for record in closed:
                outcome: Dict = {
                    "bound_node": node,
                    "bound_at": round(bound_at, 6),
                }
                if record.verb.endswith("prioritize"):
                    r = record.chosen_rank(node)
                    outcome["rank"] = r
                    if rank is None:
                        rank = r
                if record.violating and node in record.violating:
                    outcome["violated_at_bind"] = True
                    outcome["violation_reason"] = record.violating[node]
                    violated = True
                record.outcome = outcome
            self._open -= len(closed)
            open_now = self._open
        if not closed:
            return
        trace.COUNTERS.inc("pas_decision_closed_total", len(closed))
        if any(r.verb.endswith("prioritize") for r in closed):
            trace.COUNTERS.inc(
                "pas_decision_chosen_rank_total",
                labels={"rank": _rank_bucket(rank)},
            )
        if violated:
            trace.COUNTERS.inc("pas_decision_violated_at_bind_total")
        trace.COUNTERS.set_gauge("pas_decision_open", float(open_now))

    def observe_rebalance(
        self, namespace: str, name: str, action: str, detail: str = ""
    ) -> None:
        """Rebalancer evict/skip feedback: appended as an event to the
        pod's open records (an evicted pod's decision is superseded — the
        pod will be rescheduled — but the record stays open so the NEXT
        bind closes it with the post-eviction placement)."""
        if not self.enabled:
            return
        key = f"{namespace}/{name}"
        event = {
            "ts": round(self._clock(), 6),
            "action": action,
        }
        if detail:
            event["detail"] = detail
        with self._lock:
            for record in self._open_by_pod.get(key, []):
                record.events.append(event)

    # -- the debug surface -----------------------------------------------------

    def snapshot(
        self,
        pod: Optional[str] = None,
        verb: Optional[str] = None,
        limit: int = 64,
    ) -> Dict:
        with self._lock:
            records = list(self._records)
            recorded_total = self._recorded_total
            open_count = self._open
        selected = []
        for record in reversed(records):  # newest first
            if pod is not None and pod not in (record.pod_name, record.pod_key):
                continue
            if verb is not None and record.verb != verb:
                continue
            selected.append(record.to_dict())
            if len(selected) >= max(1, limit):
                break
        return {
            "enabled": self.enabled,
            "capacity": self.capacity,
            "recorded_total": recorded_total,
            "open": open_count,
            "returned": len(selected),
            "records": selected,
        }

    def to_json(
        self,
        pod: Optional[str] = None,
        verb: Optional[str] = None,
        limit: int = 64,
    ) -> bytes:
        return (
            json.dumps(self.snapshot(pod=pod, verb=verb, limit=limit)).encode()
            + b"\n"
        )


#: the process-wide log every layer records into (like trace.TRACES);
#: --decisionLog=off flips ``enabled`` via configure()
DECISIONS = DecisionLog()
