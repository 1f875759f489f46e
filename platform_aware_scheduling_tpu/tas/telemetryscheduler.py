"""TAS scheduling logic: the Prioritize/Filter/Bind verbs over policy rules.

Reference: telemetry-aware-scheduling/pkg/telemetryscheduler/
telemetryscheduler.go.  Wire behavior is reproduced quirk-for-quirk
(callers depend on it):

  * decode failures and empty node lists return an empty 200 body
    (telemetryscheduler.go:41-48 — the Go handler just returns);
  * a pod without the ``telemetry-policy`` label gets status 400 but the
    handler STILL runs and writes ``[]`` (no return after WriteHeader,
    telemetryscheduler.go:50-53);
  * a nil filter result is 404 with body ``null`` (:170-175);
  * FailedNodes messages carry the CONCRETE violation reason ("policy P:
    metric cpu=93 > threshold 80" — docs/observability.md "Decision
    provenance") where the reference emitted the opaque literal
    "Node violates" (:206); native and host paths produce byte-identical
    strings (tests/test_decisions.py), a deliberate wire improvement
    within the scheduler's contract (FailedNodes values are
    free-form diagnostics);
  * in the legacy Nodes branch FilterResult.NodeNames is built by
    splitting "n1 n2 " on spaces and so carries a trailing empty string
    (:212) — harmless there because the scheduler ignores NodeNames; the
    nodeCacheCapable branch instead emits exactly the passing names (the
    scheduler consumes them and rejects unknown entries);
  * Bind is 404 — TAS does not bind (:179-181).

Two execution paths produce identical wire bytes — but for the objects a
Nodes-wire Filter echoes: the native path copies each passing ``v1.Node``
out of the request as it arrived (``_wirec.filter_encode_nodes``), so an
echoed item is JSON-equal to the exact path's ``json.dumps`` of it, not
byte-equal, while everything around the items is byte-identical
(docs/architecture.md "Filter on the two wires"):

  * **device path** (default): the jitted kernels of ops/scoring.py over the
    TensorStateMirror — one fused XLA pass instead of the per-node Go loop;
  * **host path**: exact-semantics Python (strategies/core.py), used as
    fallback whenever the mirror marks a policy/metric host-only (inexact
    milli conversion, unknown operator) and as the control in tests.

For non-sorting operators the reference's output order is Go map iteration
— randomized per process.  The device path is deterministic (node interning
order), which is within the reference's behavior envelope.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

from platform_aware_scheduling_tpu.extender.server import (
    HTTPRequest,
    HTTPResponse,
)
from platform_aware_scheduling_tpu.extender.types import (
    Args,
    FilterResult,
    HostPriority,
    encode_host_priority_list,
)
from platform_aware_scheduling_tpu.kube.objects import Node, Pod
from platform_aware_scheduling_tpu.ops.state import (
    CompiledPolicy,
    DeviceView,
    TensorStateMirror,
    filter_device_eligible,
    prioritize_device_eligible,
)
from platform_aware_scheduling_tpu.ops import solveobs
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache, CacheMissError
from platform_aware_scheduling_tpu.tas import degraded as degraded_mode
from platform_aware_scheduling_tpu.native import get_wirec
from platform_aware_scheduling_tpu.tas.fastpath import PrioritizeFastPath, count_plan
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy, TASPolicyRule
from platform_aware_scheduling_tpu.tas.strategies import core, dontschedule
from platform_aware_scheduling_tpu.utils import decisions, events, klog, trace
from platform_aware_scheduling_tpu.utils import labels as shared_labels
from platform_aware_scheduling_tpu.utils.tracing import LatencyRecorder

import jax.numpy as jnp

TAS_POLICY_LABEL = "telemetry-policy"


class _HostArgsShortcut:
    """Probe result marking a host-only-policy request whose candidate
    span is interned: the verb runs the EXACT Python filter flow over
    these Args (built from the native wire view + the universe's
    interned name tuple) instead of re-decoding the full body with
    json.loads.  Wire bytes are identical by construction — the Args
    content matches what the exact decode would produce for every field
    the Filter path reads."""

    __slots__ = ("args",)

    def __init__(self, args: Args):
        self.args = args


class MetricsExtender:
    """extender.Scheduler implementation for TAS
    (reference telemetryscheduler.go:25-34)."""

    def __init__(
        self,
        cache: AutoUpdatingCache,
        mirror: Optional[TensorStateMirror] = None,
        recorder: Optional[LatencyRecorder] = None,
        planner=None,
        node_cache_capable: bool = False,
    ):
        """``node_cache_capable``: serve Prioritize/Filter from
        ``Args.NodeNames`` when ``Args.Nodes`` is absent — the wire mode a
        ``nodeCacheCapable: true`` extender registration receives
        (extender/types.go:44-49; required by GAS, scheduler.go:455-461).
        The reference TAS ignores NodeNames and returns the empty-200
        quirk; that behavior is preserved when this flag is off (the
        default), so large clusters opt in via --nodeCacheCapable."""
        self.cache = cache
        self.mirror = mirror
        self.node_cache_capable = node_cache_capable
        self.recorder = recorder or LatencyRecorder()
        trace.install_jax_hooks()  # compile visibility from process start
        # opt-in tas.planner.BatchPlanner: prioritize answers steer planned
        # pods onto their batch-assigned node (see planner module doc)
        self.planner = planner
        # opt-in rebalance.Rebalancer, set by the service main when
        # --rebalance != off; the front-ends serve its last plan on
        # GET /debug/rebalance (404 while this is None)
        self.rebalancer = None
        # opt-in gang.GangTracker, set by assembly when --gang=on: gang
        # members Filter/Prioritize against their reserved slice, other
        # pods fail gang-held nodes, Bind promotes reservations, and the
        # front-ends serve GET /debug/gangs (404 while this is None).
        # While set, a member's body bypasses the Filter response cache
        # (the native encoder answers it from the tracker's compact
        # verdict) and the native Prioritize scanner — the gang verdict
        # is pod-label-dependent state the span-keyed caches cannot key;
        # other pods' cached verdicts key on the reservation version
        # (docs/gang.md)
        self.gangs = None
        # opt-in forecast.Forecaster, set by assembly when --forecast=on:
        # scheduleonmetric ranks on predicted-at-bind values through the
        # SAME fastpath/host machinery (the forecaster publishes a
        # DeviceView of predicted milli values), decision records carry
        # "predicted cpu=93 (slope +2.1/s)" provenance, and the
        # front-ends serve GET /debug/forecast (404 while this is None).
        # Off (None) keeps snapshot ranking byte-identical to before.
        self.forecaster = None
        # opt-in utils.slo.SLOEngine, set by assembly when --slo=on: the
        # engine reads this extender's recorder + the counter families
        # and judges the declared SLOs over sliding windows; the
        # front-ends serve GET /debug/slo (404 while this is None) and
        # /metrics gains the pas_slo_* gauges.  Off (None) registers no
        # gauges and leaves the wire byte-identical — the engine never
        # touches the request path either way (docs/observability.md
        # "SLOs & error budgets")
        self.slo = None
        # opt-in utils.control.BudgetController, set by assembly when
        # --sloControl=on (requires --slo=on): subscribes to the SLO
        # engine's post-tick hook and steps the attached knobs; the
        # front-ends serve GET /debug/control (404 while this is None)
        # and /metrics gains the pas_control_* families.  Off (None)
        # constructs nothing and leaves the wire byte-identical — the
        # controller only ever mutates knobs other components already
        # read live (docs/observability.md "Budget feedback control")
        self.control = None
        # opt-in utils.record.FlightRecorder, set by assembly when
        # --flightRecorder=on: the verbs append one anonymized arrival
        # event each (universe digest + candidate count, never names),
        # the telemetry refresh pass appends decile summaries, and the
        # front-ends serve GET /debug/record + POST /debug/whatif (404
        # while this is None).  Off (None) costs the verbs a single
        # attribute check and keeps the wire byte-identical — pinned by
        # tests/test_record.py.  NOT self.recorder: that name is the
        # latency-histogram LatencyRecorder above.
        self.flight = None
        # opt-in ops.solveobs.SolveObservatory, set by assembly when
        # --solveObs=on: per-stage device-solve attribution rings +
        # refresh churn telemetry, served at GET /debug/solve (404 while
        # this is None).  The instrumented sites gate on the module
        # global ops.solveobs.ACTIVE (the pipeline spans layers that
        # never see this extender); this attribute only routes the debug
        # endpoint and documents ownership.  Off (None) costs the solve
        # one module-global read and keeps the wire byte-identical —
        # pinned by tests/test_solveobs.py.
        self.solveobs = None
        # opt-in tas.degraded.DegradedModeController, set by assembly:
        # when telemetry goes stale or a circuit opens, Filter fails
        # open/closed per --degradedMode and Prioritize degrades to
        # last-known-good then neutral scores (docs/robustness.md).
        # None (the default) keeps exact reference behavior.
        self.degraded = None
        # opt-in kube.lease.LeaseElector, set by assembly when
        # --leaderElect: leadership state surfaces on /readyz (an
        # informational condition — followers stay ready) and the
        # front-ends serve GET /debug/leader (404 while this is None).
        # Verb behavior is role-independent: every replica serves
        # Filter/Prioritize; only the actuation loops are gated
        # (docs/robustness.md "HA & leader election")
        self.leadership = None
        # opt-in admission.AdmissionPlane, set by assembly when
        # --admission=on: capacity-class Filter failures enqueue into a
        # bounded per-class queue, an otherwise-admissible pod may be
        # HELD behind higher-priority queued work (every candidate fails
        # CODE_ADMISSION_BLOCKED), small gangs backfill a large gang's
        # pending reservation, and the front-ends serve GET
        # /debug/admission (404 while this is None).  While set, the
        # Filter response cache is bypassed — the admission verdict is
        # per-pod queue state the span-keyed cache cannot key
        # (docs/admission.md).  Off (None) costs the verb one attribute
        # check and keeps the wire byte-identical — pinned by
        # tests/test_admission.py.
        self.admission = None
        # opt-in shard.ShardPlane, set by assembly when --shard=on: the
        # mirror holds only OWNED partitions, Filter merges remote
        # partitions' digest violators into the local verdict, Prioritize
        # ranks over local values + remote top-k summaries, and the
        # front-ends serve GET /debug/shard (404 while this is None).
        # While set, the Filter response cache is bypassed — the merged
        # verdict depends on digest freshness the span-keyed cache cannot
        # key (docs/sharding.md).  Off (None) costs the verbs one
        # attribute check and keeps the wire byte-identical — pinned by
        # tests/test_shard.py.
        self.shard = None
        # request-independent ranking/violation caches + byte-fragment
        # encoder (tas/fastpath.py) — the per-request device dispatch and
        # per-node Python objects the round-1 verdict flagged are gone
        self.fastpath = PrioritizeFastPath() if mirror is not None else None
        # /readyz "kernels_warmed": flips true at the end of the first
        # SUCCESSFUL warm pass (a warm that raised leaves it false)
        self._warmed = False
        if mirror is not None:
            # warm the fastpath from the state-refresh threads: every
            # mirror publish precomputes rankings/violations/tables for the
            # new version, so under metric churn (2-5 s syncPeriod,
            # tas-deployment.yaml) no request pays the device dispatch
            mirror.on_state_change.append(self.warm_fastpath)
            self.warm_fastpath()  # cover state written before construction

    # -- fastpath warming ------------------------------------------------------

    def warm_fastpath(self, staged=None) -> None:
        """Precompute the request-time caches for the mirror's current
        state: the ranking of every in-use (metric row, op) pair and the
        dontschedule violation sets — one round trip for all of them —
        and the response-encode table.  Runs in whatever thread published
        the state change (the metric-refresh loop in production, reference
        cmd/main.go:76-78), keeping the device dispatch off the request
        path entirely.  A one-row publish calls first with the view it
        ``staged`` (not yet published, its answers attached): the entries
        are installed so that the swap publishes view and caches
        together; the call that follows every publish finds them."""
        fastpath = self.fastpath
        if fastpath is None:
            return
        if staged is not None:
            fastpath.warm_round(staged, staged.round.asks)
            return
        with trace.stage("rf.warm", "pas_refresh_warm_seconds_total"):
            self._warm_fastpath(fastpath)

    def _warm_fastpath(self, fastpath) -> None:
        obs = solveobs.ACTIVE
        warm_t0 = obs.clock() if obs is not None else 0.0
        try:
            policies, view, asks = self.mirror.warm_snapshot()
            # what the round derives IS what requests use: both read
            # ops/state's eligibility (_prioritize_device_eligible below)
            pairs = set(asks.pairs)
            ruled = set(asks.rule_keys)
            wirec = get_wirec()
            fastpath.precompute(view, pairs, wirec=wirec, asks=asks)
            for key, compiled in policies.items():
                name = key[1]
                filter_ok = key in ruled
                if filter_ok:
                    # one call warms the violation set AND its decoded
                    # provenance (reason strings keyed by policy name)
                    fastpath.violation_reasons(compiled, view, name)
                if self.gangs is None:
                    # pre-render response skeletons for every interned
                    # universe at the NEW state, so the first request of
                    # the sync window still splices (a metric refresh
                    # mints a new violation-set/ranking identity; without
                    # this, one request per window pays the re-render).
                    # Gang mode skips: the skeleton key carries the live
                    # reservation version, which moves between passes.
                    fastpath.warm_skeletons(
                        wirec, compiled, view, name,
                        filter_ok=filter_ok,
                        prioritize_ok=(
                            compiled.scheduleonmetric_row,
                            compiled.scheduleonmetric_op,
                        )
                        in pairs,
                    )
            if self.forecaster is not None:
                # forecast rankings warm AFTER precompute (whose pruning
                # keeps only real-view entries); the forecast view's
                # negative version markers can never collide with them
                self.warm_forecast_rankings()
            self._warmed = True
            if obs is not None:
                # the warm pass is the production solve cadence: one
                # "solve" event per pass into the causal spine, so
                # /debug/explain narratives can place verb answers
                # relative to when their rankings were recomputed
                events.JOURNAL.publish(
                    "solve",
                    "fastpath warmed",
                    data={
                        "pairs": len(pairs),
                        "policies": len(policies),
                        "version": view.version,
                        "duration_us": round(
                            (obs.clock() - warm_t0) * 1e6, 1
                        ),
                    },
                )
        except Exception as exc:  # warming must never break the writer
            trace.COUNTERS.inc(
                "pas_device_path_errors_total",
                labels={"site": "warm_fastpath"},
            )
            klog.error("fastpath warm failed: %s", exc)

    def warm_forecast_rankings(self) -> None:
        """Warm the ranking cache for every device-eligible policy
        against the CURRENT forecast view.  Called from warm_fastpath,
        and — decisively — registered by assembly on the cache's
        refresh-pass hook AFTER the forecaster's own refit subscription:
        warm_fastpath fires on state change MID-pass, before the
        end-of-pass refit replaces the forecast view, so without this
        post-refit pass every fresh fit would go cold to its first
        request.  Never raises."""
        fastpath = self.fastpath
        if self.forecaster is None or fastpath is None:
            return
        try:
            policies, _view, host_only_map = self.mirror.policies_snapshot()

            def host_only(name: str) -> bool:
                return host_only_map.get(name, False)

            for compiled in policies.values():
                if not self._prioritize_device_eligible(compiled, host_only):
                    continue
                fview = self._forecast_rank_view(compiled)
                if fview is not None:
                    fastpath.warm_pairs(
                        fview,
                        [(
                            compiled.scheduleonmetric_row,
                            compiled.scheduleonmetric_op,
                        )],
                    )
        except Exception as exc:  # warming must never break the refresher
            trace.COUNTERS.inc(
                "pas_device_path_errors_total",
                labels={"site": "warm_forecast"},
            )
            klog.error("forecast ranking warm failed: %s", exc)

    # -- readiness (utils/health.py) -------------------------------------------

    def readiness_conditions(self):
        """The /readyz conditions this extender contributes: kernels
        warmed (device fastpath precomputed at least once) and telemetry
        freshness (cache synced + every registered metric's age within
        bound).  The front-end layers queue headroom on top."""
        conditions = [
            ("kernels_warmed", self._warm_status),
            ("telemetry_fresh", self.cache.telemetry_freshness),
        ]
        if self.degraded is not None:
            # degraded state surfaces on /readyz with its reason — the
            # service keeps serving (degraded), but rollouts see why it
            # is not fully ready (docs/robustness.md)
            conditions.append(
                ("degraded_mode", self.degraded.readiness_condition)
            )
        if self.leadership is not None:
            # informational: always ok (followers serve traffic at full
            # quality), the reason names the role and fencing token
            conditions.append(
                ("leadership", self.leadership.readiness_condition)
            )
        if self.slo is not None:
            # informational: always ok — a burning SLO pages an operator
            # via pas_slo_burn_rate; yanking the replica from the Service
            # would only burn the availability SLO faster
            conditions.append(("slo_burn", self.slo.readiness_condition))
        return conditions

    def _warm_status(self):
        if self.fastpath is None:
            return True, "host-only mode (no device path to warm)"
        if self._warmed:
            return True, "fastpath warmed"
        return False, "fastpath warm has not completed"

    def warm_batch(self, path: str, requests: List[HTTPRequest]) -> int:
        """Serving micro-batch hook (serving/batch.py): warm every device
        artifact the coalesced batch needs, so the per-request demux that
        follows serves entirely from caches — a batch of N concurrent
        requests costs a handful of device solves, not N.  Prioritize
        batches warm ALL needed rankings in ONE fused dispatch per state
        view (fastpath.warm_rankings_batched); Filter batches warm one
        violation set per distinct policy (each request-independent and
        cached thereafter).  Responses stay byte-identical to the
        per-request path because only cache WARMTH changes, never the
        encode path.  Returns the number of device computations actually
        performed (0 = everything already warm).  Must never raise: any
        trouble degrades to the per-request path, which owns correctness."""
        if self.fastpath is None:
            return 0
        wirec = get_wirec()
        pair_groups: Dict[int, tuple] = {}  # id(view) -> (view, set of pairs)
        filter_policies: Dict[tuple, tuple] = {}
        for request in requests:
            try:
                label = None
                namespace = ""
                if wirec is not None:
                    parsed = wirec.parse_prioritize(request.body)
                    label = parsed.policy_label
                    namespace = parsed.pod_namespace or ""
                else:
                    import json

                    obj = json.loads(request.body)
                    pod = obj.get("Pod") or obj.get("pod") or {}
                    md = pod.get("metadata") or {}
                    label = (md.get("labels") or {}).get(TAS_POLICY_LABEL)
                    namespace = md.get("namespace") or ""
                if not label:
                    continue
                policy = self.cache.read_policy(namespace, label)
                compiled, view = self._device_policy(policy)
                if compiled is None:
                    continue
                if path.endswith("/prioritize"):
                    if self._prioritize_device_eligible(
                        compiled, self.mirror.metric_host_only
                    ):
                        _, pairs = pair_groups.setdefault(
                            id(view), (view, set())
                        )
                        pairs.add(
                            (
                                compiled.scheduleonmetric_row,
                                compiled.scheduleonmetric_op,
                            )
                        )
                elif path.endswith("/filter"):
                    if self._filter_device_eligible(
                        compiled, self.mirror.metric_host_only
                    ):
                        filter_policies[(namespace, label)] = (compiled, view)
            except Exception:
                continue  # malformed member: the per-request path answers it
        solves = 0
        try:
            for view, pairs in pair_groups.values():
                if self.fastpath.warm_rankings_batched(view, pairs):
                    solves += 1
            for compiled, view in filter_policies.values():
                solves += self.fastpath.warm_violations(compiled, view)
        except Exception as exc:
            trace.COUNTERS.inc(
                "pas_device_path_errors_total",
                labels={"site": "warm_batch"},
            )
            klog.error("batch warm failed, per-request path serves: %s", exc)
        return solves

    # -- verbs ----------------------------------------------------------------

    def metrics_text(self) -> str:
        """The /metrics provider for this extender: verb latency
        histograms + the process-wide path-attribution and JAX compile
        counters (utils/trace.py exposition), plus — only while an SLO
        engine is wired — its pas_slo_* gauges (the engine owns its own
        CounterSet precisely so --slo=off emits nothing)."""
        counter_sets = [self.slo.counters] if self.slo is not None else []
        if self.control is not None:
            counter_sets.append(self.control.counters)
        if self.flight is not None:
            counter_sets.append(self.flight.counters)
        if self.admission is not None:
            counter_sets.append(self.admission.counters)
        if self.shard is not None:
            counter_sets.append(self.shard.counters)
        return trace.exposition(
            recorders=[self.recorder], counter_sets=counter_sets
        )

    def _record_flight_verb(self, verb: str, request: HTTPRequest) -> None:
        """One anonymized arrival event in the verb's finally: the
        universe digest + candidate count stashed by the wire path (or
        nulls — the recorder never hashes names itself) and the gang
        size stashed by the exact decode.  Must never raise into the
        verb."""
        try:
            uid, candidates = getattr(
                request, "flight_universe", (None, 0)
            )
            self.flight.record_verb(
                verb,
                uid,
                candidates,
                getattr(request, "flight_gang", 0),
            )
        except Exception as exc:
            klog.error("flight record failed: %r", exc)

    def _stash_flight_exact(
        self, request: HTTPRequest, args, candidates: Optional[int] = None
    ) -> None:
        """Exact-path stash for the flight recorder: candidate count
        (unless the wire path already stashed an interned key) and the
        pod's gang size — the one pod-shape label a capture keeps."""
        try:
            if not hasattr(request, "flight_universe"):
                if candidates is None:
                    candidates = len(self._candidate_names(args))
                request.flight_universe = (None, int(candidates))
            gang = args.pod.get_labels().get(shared_labels.GANG_SIZE_LABEL)
            if gang:
                request.flight_gang = int(gang)
        except Exception:
            pass

    def prioritize(self, request: HTTPRequest) -> HTTPResponse:
        start = time.perf_counter()
        span = trace.of(request)
        span.set("verb", "prioritize")
        try:
            if self.degraded is not None:
                action, reason = self.degraded.prioritize_decision()
                if action == degraded_mode.ACTION_NEUTRAL:
                    # telemetry too stale even for last-known-good:
                    # neutral priorities (every candidate scored equally)
                    # keep the scheduler unblocked without letting a
                    # stale ranking mis-order placements
                    span.set("degraded", reason)
                    span.set("path", "neutral")
                    return self._neutral_prioritize(request, span)
                if action == degraded_mode.ACTION_LAST_KNOWN_GOOD:
                    span.set("degraded", reason)  # serving retained scores
            if self.shard is not None:
                # scatter/gather: local partitions from the mirror,
                # remote partitions from fresh digests; None falls
                # through to the full-world paths (which then answer
                # from whatever the partition-scoped mirror holds)
                response = self._shard_prioritize(request, span)
                if response is not None:
                    return response
            # the native path attributes itself (native vs native_host —
            # partition counters, see trace.py declarations)
            response = self._prioritize_native(request)
            if response is not None:
                return response
            trace.COUNTERS.inc("pas_prioritize_exact_total")
            span.set("path", "exact")
            klog.v(2).info_s("Received prioritize request", component="extender")
            decoded = self._decode_prioritize_args(request, span)
            if isinstance(decoded, HTTPResponse):
                return decoded
            args, names, status = decoded
            if self.flight is not None:
                self._stash_flight_exact(request, args, candidates=len(names))
            span.set("pod", f"{args.pod.namespace}/{args.pod.name}")
            body = self._prioritize_body(args, names, span=span)
            events.JOURNAL.publish(
                "verdict",
                "prioritize",
                request_id=span.trace_id,
                pod=f"{args.pod.namespace}/{args.pod.name}",
                data={
                    "candidates": len(names),
                    "path": str(span.attrs.get("path", "exact")),
                },
            )
            return HTTPResponse.json(body, status=status)
        finally:
            self.recorder.observe(
                "prioritize", time.perf_counter() - start,
                trace_id=span.trace_id,
            )
            if self.flight is not None:
                self._record_flight_verb("prioritize", request)

    def _decode_prioritize_args(self, request: HTTPRequest, span):
        """The exact path's decode quirks, shared with the degraded
        neutral path so they can never drift: decode failure / empty
        candidate list -> empty 200; missing policy label -> 400 but the
        verb still answers (telemetryscheduler.go:41-54).  Returns
        ``(args, names, status)`` or the quirk HTTPResponse."""
        with span.stage("decode"):
            args = self._decode(request)
        if args is None:
            return HTTPResponse()
        names = self._candidate_names(args)
        if not names:
            klog.v(2).info_s(
                "bad extender arguments. No nodes in list", component="extender"
            )
            return HTTPResponse()
        status = 200
        if TAS_POLICY_LABEL not in args.pod.get_labels():
            klog.v(2).info_s("no policy associated with pod", component="extender")
            status = 400  # and still prioritize (telemetryscheduler.go:50-54)
        return args, names, status

    def _neutral_prioritize(self, request: HTTPRequest, span) -> HTTPResponse:
        """Degraded Prioritize: every candidate gets the same score, on
        top of the exact path's shared decode quirks."""
        decoded = self._decode_prioritize_args(request, span)
        if isinstance(decoded, HTTPResponse):
            return decoded
        args, names, status = decoded
        with span.stage("encode"):
            body = encode_host_priority_list(
                [HostPriority(host=name, score=0) for name in names]
            )
        self._record_prioritize(
            span, args.pod.namespace, args.pod.name,
            args.pod.get_labels().get(TAS_POLICY_LABEL, ""),
            "neutral", None, len(names),
        )
        return HTTPResponse.json(body, status=status)

    def filter(self, request: HTTPRequest) -> HTTPResponse:
        start = time.perf_counter()
        span = trace.of(request)
        span.set("verb", "filter")
        try:
            klog.v(2).info_s("Filter request received", component="extender")
            degraded_action = None
            if self.degraded is not None:
                action, reason = self.degraded.filter_decision()
                if action in (
                    degraded_mode.ACTION_FAIL_OPEN,
                    degraded_mode.ACTION_FAIL_CLOSED,
                ):
                    # fail open/closed per --degradedMode; the response
                    # cache must not serve (its entries were keyed on
                    # healthy state), so the probe is skipped -> bypass
                    degraded_action = action
                    span.set("degraded", reason)
            probe = None
            if degraded_action is None:
                # gang mode: the cache serves NON-gang pods, keyed on the
                # gang reservation version; a body that mentions the gang
                # SIZE label at all may be a member's, whose verdict is
                # its gang's and whose Filter has side effects (TTL
                # refresh, membership, the reservation): the native
                # encoder answers it from the tracker's compact verdict,
                # never from a cache
                gang_token = None
                member = False
                if self.gangs is not None:
                    member = (
                        shared_labels.GANG_SIZE_LABEL.encode() in request.body
                    )
                    if not member:
                        gang_token = self._gang_cache_token()
                if member:
                    if self.admission is None and self.shard is None:
                        with span.stage("cache_probe", leaf=False):
                            probe = self._gang_member_filter(request)
                elif (
                    (self.gangs is None or gang_token is not None)
                    and self.admission is None
                    and (
                        self.shard is None
                        or not self.shard.remote_holds_possible()
                    )
                ):
                    # admission mode bypasses entirely: whether a pod is
                    # admitted, held, or queued is per-pod queue state
                    # that changes between identical request bodies;
                    # shard mode bypasses only while a remote digest
                    # actually lists violators — otherwise the merged
                    # verdict equals the local one for ANY candidate
                    # set, so the native fastpath (and its ~1/P-size
                    # problem) serves sharded Filter at full speed
                    # (shard/plane.py remote_holds_possible)
                    with span.stage("cache_probe", leaf=False):
                        probe = self._filter_cache_probe(
                            request, gang_token
                        )
            # hit/miss attribution happens inside the probe, at its
            # non-None return sites only (it alone can tell a true
            # span-cache hit from the native encode that merely SEEDS the
            # cache); every None return — uncacheable OR device trouble —
            # is a bypass, so hit+miss+bypass counts each request once
            if isinstance(probe, HTTPResponse):
                return probe
            args_override = None
            if isinstance(probe, _HostArgsShortcut):
                # host-only policy over an interned span: the exact flow
                # below runs on Args built from the wire view — same
                # bytes out, no 10k-name json.loads in (still counted a
                # bypass: the span caches cannot serve host verdicts)
                args_override = probe.args
                probe = None
            if probe is None:
                span.set("filter_cache", "bypass")
                trace.COUNTERS.inc("pas_filter_cache_bypass_total")
            with span.stage("decode"):
                args = (
                    args_override
                    if args_override is not None
                    else self._decode(request)
                )
            if args is None:
                return HTTPResponse()
            if self.flight is not None:
                self._stash_flight_exact(request, args)
            gang_codes: Dict[str, int] = {}
            with span.stage("kernel"):
                result = self._filter_nodes(
                    args, degraded=degraded_action, gang_codes=gang_codes,
                    span=span,
                )
            if result is None:
                klog.v(2).info_s("No filtered nodes returned", component="extender")
                return HTTPResponse.json(b"null\n", status=404)
            span.set("pod", f"{args.pod.namespace}/{args.pod.name}")
            if self.shard is not None:
                with span.stage("shard"):
                    result = self._shard_review(args, result, span)
            if self.admission is not None:
                with span.stage("admission"):
                    result = self._admission_review(
                        args, result, gang_codes, degraded_action,
                        span.trace_id,
                    )
            with span.stage("encode"):
                body = result.to_json()
            if probe is not None:
                parsed, violations, use_node_names, gang_version, universe = (
                    probe
                )
                with span.stage("store"):
                    self.fastpath.filter_store(
                        violations, use_node_names, parsed, body,
                        len(result.failed_nodes), gang_version,
                        universe=universe,
                    )
            # what is left of the exact path: the always-on observers
            # (decision record, journal event)
            with span.stage("record"):
                if decisions.DECISIONS.enabled:
                    path = span.attrs.get("filter_cache", "exact")
                    reason_code = decisions.CODE_RULE_VIOLATION
                    if degraded_action == degraded_mode.ACTION_FAIL_CLOSED:
                        path = "fail_closed"
                        reason_code = decisions.CODE_FAIL_CLOSED
                    elif degraded_action == degraded_mode.ACTION_FAIL_OPEN:
                        path = "fail_open"
                    candidates = self._candidate_names(args)
                    reason_counts = None
                    if gang_codes:
                        # a gang overlay mixes reason classes in one request:
                        # count each failed node under its own code so the
                        # per-reason counters stay exact
                        reason_counts = {}
                        for name in result.failed_nodes:
                            code = gang_codes.get(name, reason_code)
                            reason_counts[code] = reason_counts.get(code, 0) + 1
                    decisions.DECISIONS.record_filter(
                        request_id=span.trace_id,
                        pod_namespace=args.pod.namespace,
                        pod_name=args.pod.name,
                        policy=args.pod.get_labels().get(TAS_POLICY_LABEL, ""),
                        path=path,
                        candidates=len(candidates),
                        filtered=len(result.failed_nodes),
                        violating=dict(result.failed_nodes),
                        violating_scope="request",
                        reason_code=reason_code,
                        reason_counts=reason_counts,
                    )
                events.JOURNAL.publish(
                    "verdict",
                    "filter",
                    request_id=span.trace_id,
                    pod=f"{args.pod.namespace}/{args.pod.name}",
                    data={
                        "failed": len(result.failed_nodes),
                        "path": str(span.attrs.get("filter_cache", "exact")),
                    },
                )
            return HTTPResponse.json(body)
        finally:
            self.recorder.observe(
                "filter", time.perf_counter() - start,
                trace_id=span.trace_id,
            )
            if self.flight is not None:
                self._record_flight_verb("filter", request)

    def _admission_review(
        self, args, result, gang_codes, degraded_action, request_id=""
    ):
        """Consult the admission plane over one computed Filter verdict
        (admission/plane.py review contract): None keeps the verdict
        unchanged (admitted, or a failure that was enqueued/judged as a
        side effect); a replacement ``(failed, codes)`` pair means HELD
        — every candidate fails with CODE_ADMISSION_BLOCKED.  The held
        codes merge into ``gang_codes`` so the decision record counts
        holds under their own reason family.  Fails open: plane trouble
        must never take down Filter."""
        try:
            default_code = decisions.CODE_RULE_VIOLATION
            if degraded_action == degraded_mode.ACTION_FAIL_CLOSED:
                default_code = decisions.CODE_FAIL_CLOSED
            failed = dict(result.failed_nodes)
            codes = {
                name: gang_codes.get(name, default_code)
                for name in failed
            }
            verdict = self.admission.review(
                args.pod, self._candidate_names(args), failed, codes,
                request_id=request_id,
            )
        except Exception as exc:
            klog.error("admission review failed open: %r", exc)
            return result
        if verdict is None:
            return result
        held, held_codes = verdict
        gang_codes.update(held_codes)
        merged = dict(result.failed_nodes)
        merged.update(held)
        nodes = result.nodes
        if nodes is not None:
            nodes = [n for n in nodes if n.name not in held]
        node_names = result.node_names
        if node_names is not None:
            node_names = [n for n in node_names if n not in held]
        return FilterResult(
            nodes=nodes,
            node_names=node_names,
            failed_nodes=merged,
            error=result.error,
        )

    def _shard_review(self, args, result, span):
        """Merge REMOTE partitions' digest violators into the locally
        computed Filter verdict (shard/plane.py review contract): the
        local solve already judged every owned-partition candidate; a
        fresh remote digest contributes its violator set; a
        missing/stale/fenced digest contributes nothing — fail open, the
        node passes on remote facts and the degradation is visible on
        the gather counters + digest_stale events.  Plane trouble must
        never take down Filter."""
        try:
            policy_name = args.pod.get_labels().get(TAS_POLICY_LABEL, "")
            if not policy_name:
                return result
            held, consulted = self.shard.review_filter(
                policy_name, self._candidate_names(args)
            )
            span.set("shard_remote_partitions", str(consulted))
            held_set = set(held) - set(result.failed_nodes)
            if not held_set:
                return result
            merged = dict(result.failed_nodes)
            for name in held_set:
                merged[name] = (
                    f"node {name} violates policy {policy_name} "
                    "(remote partition digest)"
                )
            nodes = result.nodes
            if nodes is not None:
                nodes = [n for n in nodes if n.name not in held_set]
            node_names = result.node_names
            if node_names is not None:
                node_names = [n for n in node_names if n not in held_set]
            return FilterResult(
                nodes=nodes,
                node_names=node_names,
                failed_nodes=merged,
                error=result.error,
            )
        except Exception as exc:
            klog.error("shard filter review failed open: %r", exc)
            return result

    def _shard_prioritize(self, request: HTTPRequest, span):
        """Scatter/gather Prioritize: rank candidates over the merged
        {node: milli} map — owned partitions from the mirror's exact
        values, remote partitions from digest top-k summaries — with the
        host path's ordering semantics (GreaterThan descending, LessThan
        ascending, anything else input order; nodes absent from the
        merged map are dropped exactly like nodes absent from metric
        data).  Returns None to fall through: gang pods (the overlay
        owns the exact path), unresolvable policy/rule, an unusable
        local view, or any plane trouble — a local-only full-world
        answer beats no answer."""
        try:
            if self.gangs is not None:
                return None
            decoded = self._decode_prioritize_args(request, span)
            if isinstance(decoded, HTTPResponse):
                return decoded
            args, names, status = decoded
            try:
                policy = self._policy_from_pod(args.pod)
            except Exception:
                return None
            rule = self._scheduling_rule(policy)
            if rule is None:
                return None
            merged = self.shard.gather_metric(rule.metricname, names)
            if merged is None:
                return None
            entries = [(name, merged[name]) for name in names if name in merged]
            if rule.operator == "GreaterThan":
                entries.sort(key=lambda kv: kv[1], reverse=True)
            elif rule.operator == "LessThan":
                entries.sort(key=lambda kv: kv[1])
            result = self._apply_plan(
                args.pod,
                [
                    HostPriority(host=name, score=10 - i)
                    for i, (name, _milli) in enumerate(entries)
                ],
            )
            span.set("path", "shard")
            span.set("pod", f"{args.pod.namespace}/{args.pod.name}")
            with span.stage("encode"):
                body = encode_host_priority_list(result)
            self._record_prioritize(
                span, args.pod.namespace, args.pod.name, policy.name,
                "shard", rule, len(names), result=result,
            )
            events.JOURNAL.publish(
                "verdict",
                "prioritize",
                request_id=span.trace_id,
                pod=f"{args.pod.namespace}/{args.pod.name}",
                data={"candidates": len(names), "path": "shard"},
            )
            return HTTPResponse.json(body, status=status)
        except Exception as exc:
            klog.error("shard prioritize failed open: %r", exc)
            return None

    def _gang_cache_token(self):
        """(reservation version, held map) for a Filter under gang mode
        whose body never mentions the GANG SIZE label, and so is no
        member's: the response cache keys its merged verdict on the
        version.  The test is ``pas-gang-size``, not
        ``pas-workload-group``: gang membership requires BOTH
        (labels.gang_id_for), and the group label alone is the
        rebalancer's min-available grouping that ordinary non-gang
        workloads carry — those must keep their cache hits.  A body that
        does mention it reads its labels from the native wire view
        (``ParsedArgs.pod_labels_span``) in :meth:`_gang_member_filter`.
        None bypasses, on any trouble."""
        try:
            return self.gangs.cache_token()
        except Exception as exc:
            klog.error("gang cache token failed, cache bypass: %s", exc)
            return None

    def _gang_member_filter(
        self, request: HTTPRequest
    ) -> Optional[HTTPResponse]:
        """A gang member's NodeNames-wire Filter answered by the native
        encoder from the tracker's compact verdict (gang/group.py
        ``member_verdict``; tas/fastpath.py ``gang_member_filter``), or
        None and the exact path answers: no native scanner, the Nodes
        wire, labels that make no member, a host-only policy, a name the
        encoder will not vouch for or the name table lacks, any
        exception.  Its bytes are the exact path's (tests/
        test_gang_domains.py); it is counted a cache miss answered
        natively, and in ``pas_gang_filter_native_total``."""
        if self.fastpath is None:
            return None
        wirec = get_wirec()
        if wirec is None or not hasattr(wirec, "candidate_rows"):
            return None
        span = trace.of(request)
        try:
            with span.stage("scan", sampled=True):
                parsed = wirec.parse_prioritize(request.body)
            with span.stage("policy", sampled=True):
                if (
                    not self.node_cache_capable
                    or (parsed.nodes_present and parsed.num_nodes > 0)
                    or not parsed.node_names_present
                    or parsed.num_node_names == 0
                ):
                    return None
                raw_labels = parsed.pod_labels_span
                policy_name = parsed.policy_label
                if raw_labels is None or policy_name is None:
                    return None
                # the exact decode's labels: a null value is Go's ""
                pod_labels = {
                    key: "" if value is None else value
                    for key, value in json.loads(raw_labels).items()
                }
                namespace = parsed.pod_namespace or ""
                name = parsed.pod_name or ""
                try:
                    policy = self.cache.read_policy(namespace, policy_name)
                except Exception:
                    return None
                compiled, view = self._device_policy(policy)
                if compiled is None or not self._device_filter_ok(compiled):
                    return None
                explained = self.fastpath.violation_reasons(
                    compiled, view, policy.name
                )
                if explained is None:
                    return None

            def verdict_of(clean_names, clean_cells):
                with span.stage("gang_overlay", leaf=False):
                    return self.gangs.member_verdict(
                        namespace, name, pod_labels, clean_names, span=span,
                        clean_cells=clean_cells,
                    )

            answer = self.fastpath.gang_member_filter(
                wirec, compiled, view, policy.name, explained, parsed,
                verdict_of, span=span,
            )
            if answer is None:
                return None
            body, n_failed, counts, head = answer
            with span.stage("record", sampled=True):
                span.set("filter_cache", "miss")
                trace.COUNTERS.inc("pas_filter_cache_miss_total")
                trace.COUNTERS.inc(
                    "pas_filter_native_total", labels={"wire": "names"}
                )
                trace.COUNTERS.inc("pas_gang_filter_native_total")
                span.set("pod", f"{namespace}/{name}")
                if self.flight is not None:
                    request.flight_universe = (None, parsed.num_node_names)
                    request.flight_gang = int(
                        pod_labels[shared_labels.GANG_SIZE_LABEL]
                    )
                if decisions.DECISIONS.enabled:
                    decisions.DECISIONS.record_filter(
                        request_id=span.trace_id,
                        pod_namespace=namespace,
                        pod_name=name,
                        policy=policy_name,
                        path="native",
                        candidates=parsed.num_node_names,
                        filtered=n_failed,
                        violating=head,
                        violating_total=n_failed,
                        violating_scope="request",
                        reason_counts=counts or None,
                    )
            return HTTPResponse.json(body)
        except (ValueError, TypeError):
            return None
        except Exception as exc:
            klog.error("gang member native filter failed, exact path: %s", exc)
            return None

    def _filter_cache_probe(self, request: HTTPRequest, gang_token=None):
        """Filter response reuse (same burst-amortization as Prioritize's
        span cache): a cached HTTPResponse on hit; on a miss the
        natively built HTTPResponse, on either wire (NodeNames, or Nodes
        with the passing objects echoed as slices of the request); a
        (parsed, violations, use_node_names, gang_version, universe)
        token when cacheable and missed but the native encoder is absent
        or will not vouch for the names (one empty, or with a space, on
        the Nodes wire: the verb stores its exact Python-built bytes
        under that key); None when the request isn't cacheable
        (host-only policy, odd shapes, no native scanner) — the exact
        path then owns the response alone.

        Correctness: the key pairs the request's raw candidate-span bytes
        (memcmp, zero false positives) with the IDENTITY of the device
        violation frozenset — any state change produces a new frozenset,
        so stale bytes can never match.  Under gang mode
        (``gang_token``), the verdict additionally reflects gang-held
        nodes: the violation set/reasons are the MERGED overlay
        (fastpath.gang_merged) and the key carries the reservation
        version, so a reservation change misses instead of serving a
        stale verdict."""
        if self.fastpath is None:
            return None
        wirec = get_wirec()
        if wirec is None:
            return None
        span = trace.of(request)
        try:
            # sampled leaves of the cache_probe container: scan, policy,
            # intern (every span), lookup, fencode, record
            with span.stage("scan", sampled=True):
                parsed = wirec.parse_prioritize(request.body)
            # from the scan to the universe probe: the policy read, its
            # compiled form and the violation set with its reasons (on the
            # host-only path the shortcut's intern lies inside)
            with span.stage("policy", sampled=True):
                use_node_names = False
                if not parsed.nodes_present or parsed.num_nodes == 0:
                    if (
                        self.node_cache_capable
                        and parsed.node_names_present
                        and parsed.num_node_names > 0
                    ):
                        use_node_names = True
                    else:
                        return None
                policy_name = parsed.policy_label
                if policy_name is None:
                    return None
                try:
                    policy = self.cache.read_policy(
                        parsed.pod_namespace or "", policy_name
                    )
                except Exception:
                    return None
                compiled, view = self._device_policy(policy)
                if compiled is None or not self._device_filter_ok(compiled):
                    # host-only policy: the span caches cannot serve (the
                    # verdict is host-computed), but an interned span still
                    # spares the exact path its full json.loads
                    return self._host_filter_shortcut(
                        wirec, parsed, use_node_names, span
                    )
                # one call resolves the violation set AND its decoded per-node
                # provenance (the shared reason map the wire FailedNodes and
                # the decision records both reference)
                explained = self.fastpath.violation_reasons(
                    compiled, view, policy.name
                )
                if explained is None:
                    return None
                violations, reasons, _indexes = explained
            with span.stage("intern"):
                universe = self.fastpath.universe_probe(
                    wirec, parsed, use_node_names
                )
            gang_version = None
            reason_table = None
            if gang_token is not None:
                gang_version, held = gang_token
                if held:
                    # merge the reservation overlay into the verdict the
                    # cached bytes will encode (non-gang pods fail
                    # gang-held nodes with the concrete gang reason)
                    merged = self.fastpath.gang_merged(
                        compiled, view, policy.name, violations,
                        reasons, held, gang_version, span,
                    )
                    violations, reasons, reason_table = (
                        merged.violations, merged.reasons, merged.table
                    )
            candidates = (
                parsed.num_node_names if use_node_names else parsed.num_nodes
            )
            if self.flight is not None:
                # the anonymized arrival key for the verb's finally: the
                # interned digest (or None on a cold span) + the count —
                # computed here where both already exist, O(1)
                request.flight_universe = (
                    universe.uid if universe is not None else None,
                    int(candidates),
                )
            with span.stage("lookup", sampled=True):
                cached = self.fastpath.filter_lookup(
                    violations, use_node_names, parsed, gang_version,
                    universe=universe,
                )
            if cached is not None:
                body, n_failed = cached
                with span.stage("record", sampled=True):
                    span.set("filter_cache", "hit")
                    trace.COUNTERS.inc("pas_filter_cache_hit_total")
                    self._record_device_filter(
                        span, parsed, policy_name, "cache_hit",
                        candidates, n_failed, reasons,
                    )
                return HTTPResponse.json(body)
            if hasattr(
                wirec,
                "filter_encode" if use_node_names else "filter_encode_nodes",
            ):
                # span-cache miss: build the response natively (row
                # lookup + violation partition + byte assembly in C)
                # instead of paying the exact path's full Python decode;
                # the result seeds the span cache.  On the NodeNames
                # wire, with an interned universe the partition runs
                # over its cached row map (filter_respond — zero
                # hashing) and the body seeds the skeleton layer
                # instead.  On the Nodes wire the passing v1.Node
                # objects are echoed as slices of the request's own
                # bytes; that assembly is the whole of what ``encode``
                # was there, so it keeps the always-on name (at 6 MB a
                # request a stage's microsecond is nothing; on the
                # NodeNames wire ``fencode`` stays sampled).  The miss
                # counts ONLY once the encode succeeded — a raise here
                # lands in the outer except -> None -> the caller counts
                # it a bypass, never miss+bypass
                stage = (
                    span.stage("fencode", sampled=True)
                    if use_node_names
                    else span.stage("encode")
                )
                with stage:
                    answer = self.fastpath.filter_parsed(
                        wirec, view, parsed, violations, compiled, policy.name,
                        reason_table=reason_table,
                        universe=universe,
                    )
                    if answer is not None:
                        body, n_failed = answer
                        self.fastpath.filter_store(
                            violations, use_node_names, parsed, body,
                            n_failed, gang_version, universe=universe,
                        )
                if answer is not None:
                    with span.stage("record", sampled=True):
                        span.set("filter_cache", "miss")
                        trace.COUNTERS.inc("pas_filter_cache_miss_total")
                        wire = "names" if use_node_names else "nodes"
                        trace.COUNTERS.inc(
                            "pas_filter_native_total", labels={"wire": wire}
                        )
                        self._record_device_filter(
                            span, parsed, policy_name, "native",
                            candidates, n_failed, reasons,
                        )
                    return HTTPResponse.json(body)
                # the encoder would not vouch for the reference's
                # split(" ") quirk on these names (one empty, or with a
                # space): the exact path answers, as for any miss
            # cacheable but missed: the exact path builds (and stores) the
            # response via the returned token — still a miss
            span.set("filter_cache", "miss")
            trace.COUNTERS.inc("pas_filter_cache_miss_total")
            return parsed, violations, use_node_names, gang_version, universe
        except (ValueError, TypeError):
            return None
        except Exception as exc:
            # device trouble (XlaRuntimeError, OOM, ...) must never fail
            # the verb: degrade to the exact path, whose host fallback
            # owns the response — same invariant Prioritize keeps
            trace.COUNTERS.inc(
                "pas_device_path_errors_total",
                labels={"site": "filter_probe"},
            )
            klog.error("filter cache probe failed, exact path: %s", exc)
            return None

    def _host_filter_shortcut(
        self, wirec, parsed, use_node_names: bool, span
    ) -> Optional[_HostArgsShortcut]:
        """Args for a host-only-policy Filter over an interned span, or
        None (exact decode serves).  Only NodeNames-mode bodies qualify —
        a Nodes-mode response echoes the request's node OBJECTS, which
        the native wire view does not retain.  The returned Args feed
        the unchanged exact flow (_filter_nodes, violated_details), so
        bytes match the exact path by construction; the interned name
        tuple replaces a per-request 10k-string json.loads."""
        if not use_node_names or self.fastpath is None:
            return None
        with span.stage("intern"):
            universe = self.fastpath.universe_probe(
                wirec, parsed, use_node_names
            )
        if universe is None:
            return None
        return _HostArgsShortcut(Args.from_parsed(parsed, universe.names()))

    def _record_device_filter(
        self, span, parsed, policy_name, path, candidates, n_failed, reasons
    ) -> None:
        """Decision record for the device Filter paths: O(1) — per-node
        detail is the SHARED per-state reason map, counts come from the
        native encoder / the response-cache entry."""
        if not decisions.DECISIONS.enabled:
            return
        decisions.DECISIONS.record_filter(
            request_id=span.trace_id,
            pod_namespace=parsed.pod_namespace or "",
            pod_name=parsed.pod_name or "",
            policy=policy_name,
            path=path,
            candidates=int(candidates),
            filtered=int(n_failed),
            violating=reasons,
            violating_scope="policy_state",
        )

    def bind(self, request: HTTPRequest) -> HTTPResponse:
        # TAS does not implement Bind (telemetryscheduler.go:179-181) —
        # the 404 wire behavior is untouched.  A kube-scheduler POSTs
        # BindingArgs only to an extender configured with a bindVerb
        # (IsBinder), which upstream TAS's deployment does not set, so in
        # most clusters this verb never arrives and the gang tracker
        # learns bindings from its pod feed (gang/group.py watch).  Where
        # a Bind does arrive its body is outcome feedback: which node the
        # pod actually landed on closes the pod's open decision records
        # AND promotes its gang reservation toward fully-bound
        # (gang/group.py observe_bind)
        if (
            decisions.DECISIONS.enabled
            or self.gangs is not None
            or self.admission is not None
        ) and request.body:
            try:
                from platform_aware_scheduling_tpu.extender.types import (
                    BindingArgs,
                )

                args = BindingArgs.from_json(request.body)
                if args.pod_name and args.node:
                    # verb + correlation attrs on the span: its completion
                    # becomes the chain-closing "bind responded" wire
                    # event in the causal spine (utils/events.py), 404
                    # status and all — the 404 IS the wire response here
                    span = trace.of(request)
                    span.set("verb", "bind")
                    span.set(
                        "pod", f"{args.pod_namespace}/{args.pod_name}"
                    )
                    span.set("node", args.node)
                    if decisions.DECISIONS.enabled:
                        decisions.DECISIONS.observe_bind(
                            args.pod_namespace, args.pod_name, args.node
                        )
                    if self.gangs is not None:
                        self.gangs.observe_bind(
                            args.pod_namespace, args.pod_name, args.node
                        )
                    if self.admission is not None:
                        self.admission.observe_bind(
                            args.pod_namespace, args.pod_name
                        )
                    events.JOURNAL.publish(
                        "verdict",
                        "bind observed",
                        request_id=trace.of(request).trace_id,
                        pod=f"{args.pod_namespace}/{args.pod_name}",
                        node=args.node,
                    )
            except Exception:
                pass  # feedback is best-effort; the verb stays a 404
        return HTTPResponse(status=404)

    # -- native fast path ------------------------------------------------------

    def _prioritize_native(self, request: HTTPRequest) -> Optional[HTTPResponse]:
        """Serve Prioritize through the _wirec zero-copy scanner when the
        body has the common well-formed shape; None -> exact Python path
        (which owns every decode-failure/empty-list wire quirk).  Byte
        parity between the two is pinned by tests/test_wirec.py.

        The whole native body is guarded by ValueError (which covers
        JSONDecodeError, UnicodeDecodeError, and UnicodeEncodeError): the
        scanner validates escapes/UTF-8 at parse time (wirec.c
        scan_string), so most malformed bodies fail the parse up front —
        but slice materialization can still raise on inputs the scan
        cannot reject, e.g. a ``\\u``-escaped lone surrogate whose
        materialized str cannot UTF-8-encode for the name-table lookup.
        Either way the request must fall back to the exact path, never
        drop the connection (round-2 advisor finding)."""
        if self.gangs is not None and (
            shared_labels.GANG_SIZE_LABEL.encode() in request.body
        ):
            # a body that mentions the gang SIZE label at all serves
            # through the exact path, whose overlay answers a member with
            # its slice alone (a few hosts, not a ranking of the
            # candidates: nothing there for the native path to save).
            # Size-label-free bodies
            # are provably non-gang (membership requires pas-gang-size,
            # labels.gang_id_for — the group label alone is ordinary
            # rebalance grouping), and a non-gang pod's Prioritize never
            # consults reservations (prioritize_overlay returns None
            # before any side effect), so the native path stays exact
            # (docs/gang.md)
            return None
        if self.fastpath is None:
            return None
        wirec = get_wirec()
        if wirec is None:
            return None
        try:
            return self._prioritize_native_inner(wirec, request)
        except (ValueError, TypeError):
            return None

    def _prioritize_native_inner(
        self, wirec, request: HTTPRequest
    ) -> Optional[HTTPResponse]:
        span = trace.of(request)
        # parse errors (ValueError/TypeError) propagate to the outer guard
        with span.stage("decode"):
            parsed = wirec.parse_prioritize(request.body)
        # everything between the parse and the universe probe: the
        # policy read, its compiled form, the plan
        with span.stage("policy", sampled=True):
            use_node_names = False
            if not parsed.nodes_present or parsed.num_nodes == 0:
                if (
                    self.node_cache_capable
                    and parsed.node_names_present
                    and parsed.num_node_names > 0
                ):
                    use_node_names = True
                else:
                    return None  # empty-200 quirks belong to the exact path
            status = 200
            policy_name = parsed.policy_label
            if policy_name is None:
                status = 400  # no label: 400 but still prioritize (-> empty)
                trace.COUNTERS.inc("pas_prioritize_native_total")
                return HTTPResponse.json(encode_host_priority_list([]), status)
            namespace = parsed.pod_namespace or ""
            try:
                policy = self.cache.read_policy(namespace, policy_name)
            except Exception:
                trace.COUNTERS.inc("pas_prioritize_native_total")
                return HTTPResponse.json(encode_host_priority_list([]), status)
            rule = self._scheduling_rule(policy)
            if rule is None:
                trace.COUNTERS.inc("pas_prioritize_native_total")
                return HTTPResponse.json(encode_host_priority_list([]), status)
            pod = Pod(
                {"metadata": {"name": parsed.pod_name or "", "namespace": namespace}}
            )
            # correlation key for the causal spine: the native path must
            # stamp the span and publish its verdict exactly like the exact
            # path below, or /debug/explain loses the score step for every
            # fastpath-served pod
            pod_key = f"{namespace}/{parsed.pod_name or ''}"
            span.set("pod", pod_key)
            planned = (
                self.planner.planned_node(pod) if self.planner is not None else None
            )
            compiled, view = self._device_policy(policy)
            candidates = (
                parsed.num_node_names if use_node_names else parsed.num_nodes
            )
        with span.stage("intern"):
            universe = self.fastpath.universe_probe(
                wirec, parsed, use_node_names
            )
        if self.flight is not None:
            request.flight_universe = (
                universe.uid if universe is not None else None,
                int(candidates),
            )
        if compiled is not None and self._device_prioritize_ok(compiled, rule):
            try:
                rank_view = self._forecast_rank_view(compiled) or view
                body = self.fastpath.prioritize_parsed(
                    wirec, compiled, rank_view, parsed, planned,
                    use_node_names, span=span, universe=universe,
                )
                span.set("path", "native")
                if rank_view is not view:
                    span.set("ranking", "forecast")
                # the always-on observers of this verb: decision record and
                # journal event
                with span.stage("record", sampled=True):
                    trace.COUNTERS.inc("pas_prioritize_native_total")
                    self._record_prioritize(
                        span, namespace, parsed.pod_name or "", policy_name,
                        "native", rule, int(candidates), planned,
                        compiled=compiled, view=rank_view,
                        forecast=rank_view is not view,
                    )
                    events.JOURNAL.publish(
                        "verdict",
                        "prioritize",
                        request_id=span.trace_id,
                        pod=pod_key,
                        data={"candidates": int(candidates), "path": "native"},
                    )
                return HTTPResponse.json(body, status)
            except Exception as exc:
                trace.COUNTERS.inc("pas_prioritize_host_fallback_total")
                klog.error("native prioritize failed, host fallback: %s", exc)
        # host-only policy/metric: exact host semantics over the parsed
        # names — served from the universe's interned tuple when warm
        # (zero per-request unicode materialization)
        span.set("path", "native_host")
        if universe is not None:
            names = universe.names()
        else:
            names = (
                parsed.node_names_list()
                if use_node_names
                else parsed.node_names()
            )
        with span.stage("kernel"):
            result = self._apply_plan(pod, self._prioritize_host(rule, names))
        with span.stage("encode"):
            body = encode_host_priority_list(result)
        # partition counter only once the answer actually exists — an
        # exception above falls to the exact path, which counts itself
        with span.stage("record", sampled=True):
            trace.COUNTERS.inc("pas_prioritize_native_host_total")
            self._record_prioritize(
                span, namespace, parsed.pod_name or "", policy_name,
                "native_host", rule, int(candidates), planned, result=result,
            )
            events.JOURNAL.publish(
                "verdict",
                "prioritize",
                request_id=span.trace_id,
                pod=pod_key,
                data={"candidates": int(candidates), "path": "native_host"},
            )
        return HTTPResponse.json(body, status)

    def _record_prioritize(
        self,
        span,
        namespace: str,
        pod_name: str,
        policy_name: str,
        path: str,
        rule: Optional[TASPolicyRule],
        candidates: int,
        planned: Optional[str] = None,
        compiled: Optional[CompiledPolicy] = None,
        view: Optional[DeviceView] = None,
        result: Optional[List[HostPriority]] = None,
        forecast: bool = False,
    ) -> None:
        """One Prioritize decision record.  Device-path records reference
        the SHARED per-state score head + ranking (O(1) per request);
        host-path records copy the already-materialized top of their own
        result list.  ``forecast`` marks a ranking served from predicted
        values — the record's detail then carries the concrete forecast
        provenance ("predicted cpu=93 (slope +2.1/s)") for the top node.
        Never raises into the verb."""
        log = decisions.DECISIONS
        if not log.enabled:
            return
        try:
            head: List = []
            ranked = None
            node_index = None
            if compiled is not None and view is not None:
                head, ranked, node_index = self.fastpath.explain_prioritize(
                    compiled, view
                )
            elif result:
                head = [(hp.host, hp.score) for hp in result[:10]]
            detail = None
            if forecast and self.forecaster is not None:
                detail = {"ranking": "forecast"}
                if head and rule is not None:
                    described = self.forecaster.describe(
                        rule.metricname, head[0][0]
                    )
                    if described:
                        detail["top"] = described
            log.record_prioritize(
                request_id=span.trace_id,
                pod_namespace=namespace,
                pod_name=pod_name,
                policy=policy_name,
                path=path,
                candidates=candidates,
                metric=rule.metricname if rule is not None else "",
                operator=rule.operator if rule is not None else "",
                score_head=head,
                planned=planned,
                ranked=ranked,
                node_index=node_index,
                detail=detail,
            )
        except Exception as exc:  # provenance must never fail the verb
            klog.error("prioritize decision record failed: %r", exc)

    # -- decode ---------------------------------------------------------------

    def _decode(self, request: HTTPRequest) -> Optional[Args]:
        """DecodeExtenderRequest (telemetryscheduler.go:63-78): errors —
        including a missing Nodes list — log and produce an empty 200.
        With node_cache_capable, a body carrying only NodeNames is valid."""
        if not request.body:
            klog.v(2).info_s("request body empty", component="extender")
            return None
        try:
            args = Args.from_json(request.body)
        except Exception as exc:
            klog.v(2).info_s(f"error decoding request: {exc}", component="extender")
            return None
        if args.nodes is None:
            if self.node_cache_capable and args.node_names is not None:
                return args
            klog.v(2).info_s("no nodes in list", component="extender")
            return None
        return args

    def _candidate_names(self, args: Args) -> List[str]:
        """The request's candidate node names: Nodes.items when present,
        else (nodeCacheCapable only) the NodeNames list."""
        if args.nodes:
            return [node.name for node in args.nodes]
        if self.node_cache_capable and args.node_names:
            return list(args.node_names)
        return []

    # -- prioritize logic ------------------------------------------------------

    def _prioritize_body(
        self, args: Args, names: List[str], span=trace.NULL_SPAN
    ) -> bytes:
        """prioritizeNodes (telemetryscheduler.go:81-100) down to response
        bytes: any failure degrades to an empty priority list."""
        if self.gangs is not None:
            if self.shard is not None and not self.shard.owns_anchor(names):
                # sharded mode: a slice that straddles partitions
                # resolves through the owner of the ANCHOR partition
                # (the first candidate's partition — deterministic, so
                # every front-end agrees).  A non-owner serves the plain
                # ranking; the journaled reservation the owner creates
                # is visible to everyone (docs/sharding.md "Straddling
                # gangs")
                self.shard.counters.inc("pas_shard_gang_deferred_total")
                span.set("shard_gang", "deferred")
                gang_result = None
            else:
                try:
                    # a Prioritize-FIRST arrival drives the same
                    # reservation path Filter would, so it must solve
                    # over the same telemetry-clean candidate set —
                    # otherwise it could reserve a slice containing a
                    # violating node that Filter will then never pass
                    # (the livelock the Filter path explicitly excludes)
                    with span.stage("gang_overlay", leaf=False):
                        gang_result = self.gangs.prioritize_overlay(
                            args.pod, self._telemetry_clean(args.pod, names),
                            span=span,
                        )
                except Exception as exc:  # overlay fails open to the ranking
                    klog.error(
                        "gang prioritize overlay failed open: %s", exc
                    )
                    gang_result = None
            if gang_result is not None:
                # gang member: the reserved slice in row-major order (the
                # anchor already minimizes stranded fragments); empty
                # when the gang cannot fully place — no node is a good
                # home for an unplaceable gang
                span.set("path", "gang")
                with span.stage("encode"):
                    body = encode_host_priority_list(gang_result)
                self._record_prioritize(
                    span, args.pod.namespace, args.pod.name,
                    args.pod.get_labels().get(TAS_POLICY_LABEL, ""),
                    "gang", None, len(names), result=gang_result,
                )
                return body
        try:
            policy = self._policy_from_pod(args.pod)
        except Exception as exc:
            klog.v(2).info_s(
                f"get policy from pod failed: {exc}", component="extender"
            )
            return encode_host_priority_list([])
        rule = self._scheduling_rule(policy)
        if rule is None:
            klog.v(2).info_s(
                "get scheduling rule from policy failed: no scheduling rule found",
                component="extender",
            )
            return encode_host_priority_list([])
        compiled, view = self._device_policy(policy)
        if compiled is not None and self._device_prioritize_ok(compiled, rule):
            try:
                planned = (
                    self.planner.planned_node(args.pod) if self.planner else None
                )
                rank_view = self._forecast_rank_view(compiled) or view
                body = self.fastpath.prioritize_bytes(
                    compiled, rank_view, names, planned, span=span
                )
                span.set("path", "device")
                if rank_view is not view:
                    span.set("ranking", "forecast")
                self._record_prioritize(
                    span, args.pod.namespace, args.pod.name, policy.name,
                    "device", rule, len(names), planned,
                    compiled=compiled, view=rank_view,
                    forecast=rank_view is not view,
                )
                return body
            except Exception as exc:  # device trouble must never fail the verb
                trace.COUNTERS.inc("pas_prioritize_host_fallback_total")
                klog.error("device prioritize failed, host fallback: %s", exc)
        span.set("path", "host")
        with span.stage("kernel"):
            result = self._apply_plan(
                args.pod, self._prioritize_host(rule, names)
            )
        with span.stage("encode"):
            body = encode_host_priority_list(result)
        self._record_prioritize(
            span, args.pod.namespace, args.pod.name, policy.name,
            "host", rule, len(names), result=result,
        )
        return body

    def _telemetry_clean(self, pod: Pod, names: List[str]) -> List[str]:
        """``names`` minus the pod policy's current dontschedule
        violation set — the candidate pool a gang reservation may solve
        over.  Best-effort: with no policy/strategy resolvable, the full
        list stands (Filter's own resolution owns the error paths)."""
        try:
            policy = self._policy_from_pod(pod)
            strategy = self._dontschedule_strategy(policy)
            if strategy is None:
                return names
            violating = self._violating_nodes(policy, strategy)
        except Exception:
            return names
        if not violating:
            return names
        return [name for name in names if name not in violating]

    def _apply_plan(
        self, pod: Pod, result: List[HostPriority]
    ) -> List[HostPriority]:
        """Promote the batch-planned node (if any, current, and among the
        scored candidates) to rank 1; scores stay ordinal 10-i."""
        if self.planner is None or not result:
            return result
        planned = self.planner.planned_node(pod)
        if planned is None:
            return result
        hosts = [hp.host for hp in result]
        if planned not in hosts:
            count_plan(0)
            return result
        count_plan(2 if planned != hosts[0] else 1)
        reordered = [planned] + [h for h in hosts if h != planned]
        return [
            HostPriority(host=h, score=10 - i) for i, h in enumerate(reordered)
        ]

    def _forecast_rank_view(self, compiled: Optional[CompiledPolicy]):
        """The forecast DeviceView to rank this policy's scheduleonmetric
        rule on, or None (snapshot ranking).  Never raises into a verb —
        forecasting trouble degrades to snapshot behavior."""
        forecaster = self.forecaster
        if forecaster is None or compiled is None:
            return None
        try:
            return forecaster.ranking_view(compiled.scheduleonmetric_metric)
        except Exception as exc:
            klog.error("forecast ranking view failed, snapshot serves: %s", exc)
            return None

    def _prioritize_host(
        self, rule: TASPolicyRule, candidate_names: List[str]
    ) -> List[HostPriority]:
        """prioritizeNodesForRule (telemetryscheduler.go:128-149), exact
        host semantics.  With a forecaster wired, ranking reads the SAME
        predicted milli values the device forecast view carries (the
        native<->host byte-comparability contract extends to forecasts);
        forecasting trouble falls back to the snapshot read.

        HOST-ONLY metrics never forecast: they are host-only precisely
        because their values are not milli-exact (sub-milli Quantities,
        milli-domain overflow — ops/state.py), and the history rings
        hold milli-truncated samples, so a forecast would silently
        replace the exact-Quantity ranking this path exists to provide
        with lossy-domain garbage."""
        if self.forecaster is not None and not (
            self.mirror is not None
            and self.mirror.metric_host_only(rule.metricname)
        ):
            try:
                predicted = self.forecaster.host_metric(rule.metricname)
            except Exception as exc:
                klog.error(
                    "forecast host metric failed, snapshot serves: %s", exc
                )
                predicted = None
            if predicted is not None:
                filtered = {
                    name: predicted[name]
                    for name in candidate_names
                    if name in predicted
                }
                ordered = core.ordered_list(filtered, rule.operator)
                return [
                    HostPriority(host=entry.node_name, score=10 - i)
                    for i, entry in enumerate(ordered)
                ]
        try:
            node_data = self.cache.read_metric(rule.metricname)
        except CacheMissError as exc:
            klog.v(2).info_s(
                f"failed to prioritize: {exc}, {rule.metricname}",
                component="extender",
            )
            return []
        filtered = {
            name: node_data[name] for name in candidate_names if name in node_data
        }
        ordered = core.ordered_list(filtered, rule.operator)
        return [
            HostPriority(host=entry.node_name, score=10 - i)
            for i, entry in enumerate(ordered)
        ]

    # -- filter logic ----------------------------------------------------------

    def _filter_nodes(
        self,
        args: Args,
        degraded: Optional[str] = None,
        gang_codes: Optional[Dict[str, int]] = None,
        span=trace.NULL_SPAN,
    ) -> Optional[FilterResult]:
        """filterNodes (telemetryscheduler.go:184-225).  ``degraded``
        overrides ONLY the telemetry-dependent violation set: fail_open
        -> no node violates, fail_closed -> every candidate violates;
        policy resolution (informer-fed, not telemetry) stays exact.

        With a gang tracker wired, its overlay merges OVER the telemetry
        verdict: gang members pass only their reserved slice, other pods
        fail gang-held nodes (docs/gang.md); ``gang_codes`` (when given)
        is filled with {node: decision reason code} for the overlay's
        failures so the caller's decision record counts them exactly;
        the overlay is the stage ``gang_overlay`` of ``span``."""
        try:
            policy = self._policy_from_pod(args.pod)
        except Exception as exc:
            klog.v(2).info_s(
                f"get policy from pod failed {exc}", component="extender"
            )
            return None
        strategy = self._dontschedule_strategy(policy)
        if strategy is None:
            klog.v(2).info_s(
                "Don't scheduler strategy failed no dontschedule strategy found",
                component="extender",
            )
            return None
        if degraded == degraded_mode.ACTION_FAIL_OPEN:
            violating: Dict[str, str] = {}
        elif degraded == degraded_mode.ACTION_FAIL_CLOSED:
            names = (
                [node.name for node in args.nodes]
                if args.nodes
                else list(args.node_names or [])
            )
            violating = {
                name: decisions.REASON_FAIL_CLOSED for name in names
            }
        else:
            violating = self._violating_nodes(policy, strategy)
        if self.gangs is not None:
            try:
                # the overlay sees only telemetry-CLEAN candidates: a
                # violating node must not enter the reservation solve's
                # free mask, or a gang could deterministically reserve a
                # slice it can never fully bind (livelock) while a clean
                # slice elsewhere goes unused.  Violating nodes keep
                # their telemetry reason in the merge below.
                clean = [
                    name
                    for name in self._candidate_names(args)
                    if name not in violating
                ]
                with span.stage("gang_overlay", leaf=False):
                    gang_failed, codes = self.gangs.filter_overlay(
                        args.pod, clean, span=span
                    )
            except Exception as exc:
                # the overlay fails OPEN: gang trouble must never take
                # down plain telemetry filtering
                klog.error("gang filter overlay failed open: %s", exc)
                gang_failed, codes = {}, {}
            if gang_failed:
                # the gang verdict wins a collision: "reserved by gang X"
                # is the actionable reason for an operator
                violating = {**violating, **gang_failed}
                if gang_codes is not None:
                    gang_codes.update(codes)
        if not args.nodes:
            if self.node_cache_capable and args.node_names:
                return self._filter_node_names(policy, args.node_names, violating)
            klog.v(2).info_s("No nodes to compare", component="extender")
            return None
        filtered: List[Node] = []
        failed: Dict[str, str] = {}
        available = ""
        for node in args.nodes:
            if node.name in violating:
                failed[node.name] = violating[node.name]
            else:
                filtered.append(node)
                available += node.name + " "
        node_names = available.split(" ")  # trailing "" kept (see module doc)
        if available:
            klog.v(2).info_s(
                f"Filtered nodes for {policy.name}: {available}",
                component="extender",
            )
        return FilterResult(
            nodes=filtered, node_names=node_names, failed_nodes=failed, error=""
        )

    def _filter_node_names(
        self, policy: TASPolicy, names: List[str], violating: Dict[str, str]
    ) -> FilterResult:
        """nodeCacheCapable Filter: answer with NodeNames only (the
        kube-scheduler reads NodeNames from a nodeCacheCapable extender;
        Nodes stays null).  Unlike the legacy Nodes branch — where the
        scheduler ignores NodeNames and the trailing-"" split quirk is
        harmless wire trivia — here kube-scheduler consumes every entry
        and rejects names absent from its input list, so the list must
        hold exactly the passing names (the reference's own
        nodeCacheCapable extender appends cleanly, GAS scheduler.go:
        467-476)."""
        failed: Dict[str, str] = {}
        node_names: List[str] = []
        for name in names:
            if name in violating:
                failed[name] = violating[name]
            else:
                node_names.append(name)
        if node_names:
            available = " ".join(node_names)
            klog.v(2).info_s(
                f"Filtered nodes for {policy.name}: {available}",
                component="extender",
            )
        return FilterResult(
            nodes=None, node_names=node_names, failed_nodes=failed, error=""
        )

    def _violating_nodes(
        self, policy: TASPolicy, strategy: dontschedule.Strategy
    ) -> Dict[str, str]:
        """{violating node: concrete reason string}.  The device path's
        strings decode the kernel's rule-index vector; the host path's
        come from violated_details — byte-identical wherever both can
        run (tests/test_decisions.py pins the parity)."""
        compiled, view = self._device_policy(policy)
        if compiled is not None and self._device_filter_ok(compiled):
            try:
                explained = self.fastpath.violation_reasons(
                    compiled, view, policy.name
                )
                if explained is not None:
                    return explained[1]
            except Exception as exc:
                trace.COUNTERS.inc(
                    "pas_device_path_errors_total",
                    labels={"site": "filter_violations"},
                )
                klog.error("device filter failed, host fallback: %s", exc)
        return {
            name: detail[1]
            for name, detail in strategy.violated_details(self.cache).items()
        }

    # -- shared helpers --------------------------------------------------------

    def _policy_from_pod(self, pod: Pod) -> TASPolicy:
        """getPolicyFromPod (telemetryscheduler.go:103-112)."""
        policy_name = pod.get_labels().get(TAS_POLICY_LABEL)
        if policy_name is None:
            raise CacheMissError(f"no policy found in pod spec for pod {pod.name}")
        return self.cache.read_policy(pod.namespace, policy_name)

    def _scheduling_rule(self, policy: TASPolicy) -> Optional[TASPolicyRule]:
        """getSchedulingRule (telemetryscheduler.go:115-124): rule[0] of
        scheduleonmetric, requiring a non-empty metric name."""
        strat = policy.strategies.get("scheduleonmetric")
        if strat and strat.rules and strat.rules[0].metricname:
            return strat.rules[0]
        return None

    def _dontschedule_strategy(
        self, policy: TASPolicy
    ) -> Optional[dontschedule.Strategy]:
        """getDontScheduleStrategy (telemetryscheduler.go:228-235)."""
        strat = policy.strategies.get("dontschedule")
        if strat is None or not strat.rules:
            return None
        return dontschedule.Strategy.from_policy_strategy(strat)

    # -- device-path eligibility ----------------------------------------------

    def _device_policy(self, policy: TASPolicy):
        """Atomic (compiled, view) snapshot — see
        TensorStateMirror.policy_with_view for why both come from one lock
        acquisition."""
        if self.mirror is None:
            return None, None
        return self.mirror.policy_with_view(policy.namespace, policy.name)

    # the single source of truth for "can the device fastpath serve this
    # policy" is ops/state.py's, shared between the request path
    # (host_only = live mirror lookup) and the round's asks (host_only =
    # snapshotted map) so the warmed set can never drift from what
    # requests actually use

    _prioritize_device_eligible = staticmethod(prioritize_device_eligible)
    _filter_device_eligible = staticmethod(filter_device_eligible)

    def _device_prioritize_ok(
        self, compiled: CompiledPolicy, rule: TASPolicyRule
    ) -> bool:
        return self._prioritize_device_eligible(
            compiled, self.mirror.metric_host_only
        )

    def _device_filter_ok(self, compiled: CompiledPolicy) -> bool:
        return self._filter_device_eligible(
            compiled, self.mirror.metric_host_only
        )
