"""Flags and startup shared by the TAS and GAS service mains.

One helper owns the ``--profilePort`` flag AND the
``jax.profiler.start_server`` startup so the two mains cannot drift
(the GAS main historically lacked the flag entirely); same for the
device/observability wiring (cost-analysis hooks + the memory-watermark
sampler, utils/devicewatch.py).
"""

from __future__ import annotations

import argparse
import threading
from typing import Optional

from platform_aware_scheduling_tpu.utils import backend, devicewatch, klog


def add_profile_flag(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--profilePort", type=int, default=0,
                        help="start the JAX profiler server on this port "
                        "(0 = off): connect TensorBoard/xprof on demand to "
                        "trace the device kernels with zero steady-state "
                        "overhead (SURVEY §5.1 — the reference has no "
                        "tracing at all)")


def add_robustness_flags(
    parser: argparse.ArgumentParser, degraded: bool = True
) -> None:
    """Fault-tolerance flag surface shared by both mains
    (docs/robustness.md): retry/backoff and circuit-breaker tuning.
    ``--degradedMode`` only exists where a DegradedModeController is
    actually built (TAS); offering a flag GAS would silently ignore is
    worse than not offering it."""
    parser.add_argument("--retryMaxAttempts", type=int, default=4,
                        help="max attempts per idempotent API read "
                        "(writes never blind-retry)")
    parser.add_argument("--retryBaseDelay", default="100ms",
                        help="first retry backoff (Go duration); doubles "
                        "per attempt with deterministic jitter")
    parser.add_argument("--circuitFailureThreshold", type=int, default=5,
                        help="consecutive transport failures that open an "
                        "endpoint group's circuit")
    parser.add_argument("--circuitResetTimeout", default="30s",
                        help="how long an open circuit waits before the "
                        "half-open probe (Go duration)")
    if degraded:
        parser.add_argument("--degradedMode", default="last-known-good",
                            choices=["fail-open", "fail-closed",
                                     "last-known-good"],
                            help="dontschedule Filter policy while telemetry "
                            "is degraded: fail-open passes every candidate, "
                            "fail-closed passes none, last-known-good keeps "
                            "serving retained values within a bounded age "
                            "then fails open.  Evictions are ALWAYS "
                            "suspended while degraded (not configurable)")


def add_decision_flags(parser: argparse.ArgumentParser) -> None:
    """Decision-provenance flag surface shared by both mains
    (docs/observability.md "Decision provenance")."""
    parser.add_argument("--decisionLog", default="on",
                        choices=["off", "on"],
                        help="per-decision explain records behind "
                        "GET /debug/decisions: every Filter/Prioritize/"
                        "rebalance decision keeps its per-node reasons "
                        "and score breakdown, closed by pod-bind "
                        "feedback into pas_decision_* placement-quality "
                        "metrics.  Costs <=5%% serving p99 (pinned by "
                        "the http_load decision A/B); off disables "
                        "recording and 404s the endpoint")


def add_event_flags(parser: argparse.ArgumentParser) -> None:
    """Causal-event-spine flag surface shared by both mains
    (docs/observability.md "Explain plane")."""
    parser.add_argument("--events", default="on",
                        choices=["off", "on"],
                        help="causal event journal behind GET "
                        "/debug/explain: every subsystem publishes typed "
                        "events (wire spans, verdicts, admission holds, "
                        "preemptions, rebalance moves, controller "
                        "actuations, SLO flips) carrying correlation "
                        "keys, so one query returns the ordered causal "
                        "chain for a pod/gang/request/node.  Publication "
                        "costs <=5 us per warm verb (pinned by "
                        "obs_smoke); off publishes nothing and 404s the "
                        "endpoint")


def configure_events(args) -> None:
    """Apply the shared event flags to the process-wide EventJournal."""
    from platform_aware_scheduling_tpu.utils import events

    events.JOURNAL.configure(enabled=getattr(args, "events", "on") == "on")


def add_gang_flags(parser: argparse.ArgumentParser) -> None:
    """Gang & topology-aware scheduling flag surface (docs/gang.md).
    One helper so a future GAS adoption cannot drift from TAS."""
    parser.add_argument("--gang", default="off", choices=["off", "on"],
                        help="all-or-nothing co-scheduling of multi-host "
                        "TPU slices: pods labeled pas-workload-group + "
                        "pas-gang-size (+ pas-gang-topology, e.g. 4x4) "
                        "atomically reserve a contiguous mesh slice at "
                        "Filter time, or fail every candidate.  Bypasses "
                        "the Filter response cache and the native "
                        "Prioritize scanner while on (the gang verdict is "
                        "pod-label-dependent state those caches cannot "
                        "key)")


def add_admission_flags(
    parser: argparse.ArgumentParser, preemption: bool = True
) -> None:
    """Priority-aware admission plane flag surface (docs/admission.md).
    One helper for both mains; GAS passes ``preemption=False`` — with
    no gang tracker there are no whole-gang victims to evict."""
    parser.add_argument("--admission", default="off", choices=["off", "on"],
                        help="priority-aware admission plane: pods carry "
                        "a pas-priority class label, capacity-class "
                        "Filter failures enqueue into a bounded per-class "
                        "queue, lower-priority pods are held behind "
                        "queued higher-priority work (with backfill and "
                        "per-class fairness), and the front-ends serve "
                        "GET /debug/admission.  Bypasses the Filter "
                        "response cache while on (the verdict is per-pod "
                        "queue state).  Off (the default) constructs "
                        "nothing and leaves the wire byte-identical")
    parser.add_argument("--admissionClasses", default="high,normal,batch",
                        help="comma-separated priority class ladder, most "
                        "important first (the pas-priority label values)")
    parser.add_argument("--admissionDefaultClass", default="normal",
                        help="class assigned to unlabeled (or unknown-"
                        "label) pods; must appear in --admissionClasses")
    parser.add_argument("--admissionDepth", type=int, default=64,
                        help="bounded queue depth; overflow sheds the "
                        "worst-ranked entry (or rejects the arrival when "
                        "it ranks worst)")
    if preemption:
        parser.add_argument("--preemption", default="off",
                            choices=["off", "on"],
                            help="gang-aware preemption: a starving "
                            "higher-priority gang may displace strictly "
                            "lower-class gangs — whole gangs only, "
                            "all-or-nothing through the SafeActuator's "
                            "fenced atomic gang path, the freed slice "
                            "reserved before the victims finish "
                            "draining.  Requires --admission=on and "
                            "--gang=on")
        parser.add_argument("--preemptionMaxVictims", type=int, default=8,
                            help="max victim PODS one preemption plan "
                            "may evict (the budget controller's "
                            "aggressiveness knob steps this down under "
                            "availability burn)")


def admission_classes(args) -> tuple:
    """The parsed --admissionClasses ladder."""
    return tuple(
        s.strip() for s in args.admissionClasses.split(",") if s.strip()
    )


def validate_admission_flags(parser: argparse.ArgumentParser, args) -> None:
    """Fail fast (exit 2 with usage) on contradictory admission wiring
    instead of silently no-opping at runtime."""
    if getattr(args, "admission", "off") == "on":
        classes = admission_classes(args)
        if not classes or len(set(classes)) != len(classes):
            parser.error(
                f"--admissionClasses {args.admissionClasses!r} is not a "
                f"valid ladder: need at least one class, no duplicates"
            )
        if args.admissionDefaultClass not in classes:
            parser.error(
                f"--admissionDefaultClass {args.admissionDefaultClass!r} "
                f"is not in --admissionClasses {args.admissionClasses!r}"
            )
    if getattr(args, "preemption", "off") == "on":
        if getattr(args, "admission", "off") != "on":
            parser.error(
                "--preemption=on requires --admission=on: the planner "
                "triggers from the admission queue's starving gangs; "
                "without the plane there is no trigger"
            )
        if getattr(args, "gang", "off") != "on":
            parser.error(
                "--preemption=on requires --gang=on: victims are whole "
                "gangs from the tracker's census and the freed slice is "
                "reserved through it; without the tracker there is "
                "nothing to preempt or reserve"
            )


def build_admission_plane(
    args, extender, kube_client=None, gang_tracker=None, leadership=None
):
    """The AdmissionPlane for --admission=on (None when off), attached
    as ``extender.admission`` (the verbs, /metrics, and
    /debug/admission all key off that attr).  With --preemption=on a
    PreemptionPlanner rides along over its own dedicated SafeActuator —
    active mode by definition (preemption that cannot evict is just
    queueing), its own token bucket so a preemption burst cannot starve
    the rebalancer's budget (or vice versa)."""
    if getattr(args, "admission", "off") != "on":
        return None
    from platform_aware_scheduling_tpu.admission import (
        AdmissionPlane,
        PreemptionPlanner,
    )

    plane = AdmissionPlane(
        classes=admission_classes(args),
        default_class=args.admissionDefaultClass,
        max_depth=args.admissionDepth,
    )
    plane.gangs = gang_tracker
    if (
        getattr(args, "preemption", "off") == "on"
        and gang_tracker is not None
        and kube_client is not None
    ):
        from platform_aware_scheduling_tpu.admission.preempt import (
            ACTUATOR_BURST,
        )
        from platform_aware_scheduling_tpu.rebalance.actuator import (
            MODE_ACTIVE,
            SafeActuator,
        )

        actuator = SafeActuator(
            kube_client, mode=MODE_ACTIVE, burst=ACTUATOR_BURST
        )
        # NOT actuator.gang_tracker: the rebalancer path's full-gang
        # auto-release would fight reservation-while-draining — the
        # planner marks victims DRAINING itself and the tracker's sweep
        # releases them when the pods are gone
        actuator.leadership = leadership
        plane.preemption = PreemptionPlanner(
            plane,
            gang_tracker,
            actuator,
            max_victims=args.preemptionMaxVictims,
            leadership=leadership,
        )
    extender.admission = plane
    return plane


def add_shard_flags(parser: argparse.ArgumentParser) -> None:
    """Partition-plane flag surface (docs/sharding.md)."""
    parser.add_argument("--shard", default="off", choices=["off", "on"],
                        help="consistent-hash partition plane: the node "
                        "universe hashes into --shardPartitions "
                        "partitions, ownership is journaled+fenced in a "
                        "ConfigMap, each replica refreshes and mirrors "
                        "ONLY its owned partitions, and Filter/"
                        "Prioritize answer scatter/gather from the "
                        "local solve plus gossiped remote digests "
                        "(peer /debug/shard pulls).  Bypasses the "
                        "Filter response cache while on (the merged "
                        "verdict depends on digest freshness).  Off "
                        "(the default) constructs nothing and leaves "
                        "the wire byte-identical")
    parser.add_argument("--shardPartitions", type=int, default=4,
                        help="partition count P; every replica must "
                        "agree on it (it is the modulus of the "
                        "consistent hash)")
    parser.add_argument("--shardPeers", default="",
                        help="comma-separated peer base URLs "
                        "(http://host:port) whose /debug/shard this "
                        "replica pulls remote-partition digests from; "
                        "empty serves local partitions only")
    parser.add_argument("--shardConfigMap", default="pas-shard-partitions",
                        help="ConfigMap name holding the journaled "
                        "partition-ownership state")


def shard_peers(args) -> tuple:
    """The parsed --shardPeers URL list."""
    return tuple(
        s.strip() for s in getattr(args, "shardPeers", "").split(",")
        if s.strip()
    )


def validate_shard_flags(parser: argparse.ArgumentParser, args) -> None:
    """Fail fast (exit 2 with usage) on contradictory shard wiring."""
    if getattr(args, "shard", "off") != "on":
        return
    if args.shardPartitions < 1:
        parser.error(
            f"--shardPartitions {args.shardPartitions} must be >= 1"
        )
    for peer in shard_peers(args):
        if not (peer.startswith("http://") or peer.startswith("https://")):
            parser.error(
                f"--shardPeers entry {peer!r} is not a base URL "
                f"(expected http://host:port)"
            )


def build_shard_plane(
    args, extender, kube_client, cache, mirror, leadership=None
):
    """The ShardPlane for --shard=on (None when off), attached as
    ``extender.shard`` (the verbs, /metrics, and /debug/shard all key
    off that attr) and wired into the cache/mirror: the refresh filter
    drops non-owned nodes at ingest and the refresh pass drives
    coordination + digest publish + gossip — no new threads."""
    if getattr(args, "shard", "off") != "on":
        return None
    from platform_aware_scheduling_tpu.shard import ShardPlane

    plane = ShardPlane(
        identity=replica_identity(args),
        partitions=args.shardPartitions,
        kube_client=kube_client,
        namespace=getattr(args, "leaseNamespace", "default") or "default",
        configmap=args.shardConfigMap,
        leadership=leadership,
        peers=shard_peers(args),
    )
    if cache is not None and mirror is not None:
        plane.attach(cache, mirror)
    extender.shard = plane
    return plane


def add_forecast_flags(
    parser: argparse.ArgumentParser, forecast: bool = True
) -> None:
    """Predictive-telemetry flag surface (docs/forecast.md).  Like
    ``--degradedMode``, the flags only exist where a Forecaster is
    actually built (TAS): GAS has no telemetry cache to forecast over,
    and offering flags it would silently ignore is worse than not
    offering them (``add_forecast_flags(parser, forecast=False)`` is the
    explicit no-op adoption both mains share)."""
    if not forecast:
        return
    parser.add_argument("--forecast", default="off", choices=["off", "on"],
                        help="schedule on forecasts, not snapshots: a "
                        "batched on-device EWMA/Holt fit over the "
                        "telemetry refresh history ranks scheduleonmetric "
                        "on predicted-at-bind values, holds eviction "
                        "streaks on transient spikes trending back down, "
                        "and lets degraded last-known-good mode serve "
                        "bounded extrapolations (docs/forecast.md)")
    parser.add_argument("--forecastWindow", type=int, default=32,
                        help="refresh-history samples kept per metric "
                        "(the fit's lookback window)")
    parser.add_argument("--forecastHorizon", default="",
                        help="how far ahead predictions target (Go "
                        "duration); empty = one refresh period ahead "
                        "(the value at the next refresh); capped at "
                        "--forecastWindow refresh steps — no fit "
                        "predicts further ahead than it looked back")


def add_ha_flags(parser: argparse.ArgumentParser, ha: bool = True) -> None:
    """HA control-plane flag surface (docs/robustness.md "HA & leader
    election"): leader election over a coordination.k8s.io Lease plus
    the crash-safe gang reservation journal.  Like ``--degradedMode``
    and ``--forecast``, the flags only exist where the machinery does
    (TAS): GAS runs no singleton actuation loops and keeps no gang
    state, and offering flags it would silently ignore is worse than
    not offering them (``add_ha_flags(parser, ha=False)`` is the
    explicit no-op adoption both mains share)."""
    if not ha:
        return
    parser.add_argument("--leaderElect", action="store_true",
                        help="run N replicas behind one Service with "
                        "exactly one executing the actuation loops "
                        "(rebalancer, deschedule labels, gang sweep): "
                        "leadership rides a coordination.k8s.io Lease "
                        "with a monotonic fencing token; followers keep "
                        "serving Filter/Prioritize at full quality.  Off "
                        "(the default) changes nothing on the wire")
    parser.add_argument("--leaseName", default="pas-tas-extender",
                        help="name of the leadership Lease object")
    parser.add_argument("--leaseNamespace", default="default",
                        help="namespace of the leadership Lease")
    parser.add_argument("--leaseDuration", default="15s",
                        help="how long a leadership grant survives "
                        "without renew before standbys may take over "
                        "(Go duration); also the deposed leader's "
                        "self-demotion deadline")
    parser.add_argument("--leaseRenewPeriod", default="",
                        help="interval between renew/acquire attempts "
                        "(Go duration); empty = a third of "
                        "--leaseDuration, jittered deterministically "
                        "per replica")
    parser.add_argument("--replicaId", default="",
                        help="this replica's lease holder identity; "
                        "empty derives hostname-pid")
    parser.add_argument("--gangJournal", default="off",
                        choices=["off", "on"],
                        help="journal gang slice reservations and binds "
                        "to a ConfigMap (write-behind, breaker-gated) "
                        "and recover them at startup, reconciled "
                        "against live pods — a restart no longer "
                        "orphans in-flight gangs (docs/gang.md)")
    parser.add_argument("--gangJournalName", default="pas-gang-journal",
                        help="name of the journal ConfigMap")
    parser.add_argument("--gangJournalNamespace", default="default",
                        help="namespace of the journal ConfigMap")


def add_slo_flags(parser: argparse.ArgumentParser) -> None:
    """SLO engine flag surface shared by both mains
    (docs/observability.md "SLOs & error budgets")."""
    parser.add_argument("--slo", default="off", choices=["off", "on"],
                        help="evaluate first-class SLOs over the process's "
                        "own metrics: verb availability, Filter/Prioritize "
                        "latency, telemetry freshness and eviction safety "
                        "(TAS), with Google-SRE multi-window burn-rate "
                        "alerting (page 5m/1h, warn 6h/3d) on "
                        "pas_slo_burn_rate and GET /debug/slo.  Off (the "
                        "default) registers no gauges and changes nothing "
                        "on the wire — the engine never touches the "
                        "request path")
    parser.add_argument("--sloConfig", default="",
                        help="JSON SLO overrides merged by name over the "
                        "default set: a list (or {\"slos\": [...]}) of "
                        "{name, sli, objective, verbs, threshold_ms, "
                        "good, bad, page_burn, warn_burn} entries; "
                        "{\"name\": ..., \"disabled\": true} removes a "
                        "default.  Malformed input fails startup")
    parser.add_argument("--sloPeriod", default="",
                        help="SLO evaluation tick period (Go duration); "
                        "empty = the sync period (TAS) or 5s (GAS)")


def build_slo_engine(args, extender, cache=None, period_s: float = 5.0):
    """The SLOEngine for --slo=on (None when off): the default SLO set
    for this main (TAS when a telemetry cache is given, GAS otherwise)
    merged with --sloConfig, reading the extender's recorder and — on
    TAS — the cache's freshness signal.  Attached as ``extender.slo``
    (the /debug/slo + /metrics + readiness wiring keys off that attr);
    the caller starts the tick loop."""
    if getattr(args, "slo", "off") != "on":
        return None
    from platform_aware_scheduling_tpu.utils.slo import (
        SLOEngine,
        default_slos,
        merge_config,
    )

    slos = merge_config(
        default_slos(tas=cache is not None),
        getattr(args, "sloConfig", ""),
    )
    engine = SLOEngine(
        slos,
        recorders=[extender.recorder],
        freshness=cache.telemetry_freshness if cache is not None else None,
    )
    extender.slo = engine
    return engine


def add_control_flags(parser: argparse.ArgumentParser) -> None:
    """Budget-controller flag surface shared by both mains
    (docs/observability.md "Budget feedback control")."""
    parser.add_argument("--sloControl", default="off", choices=["off", "on"],
                        help="close the SLO loop: a budget controller "
                        "subscribes to the engine's burn-rate evaluations "
                        "and steps bounded knobs — admission queue depth "
                        "(availability), rebalancer max-moves/hysteresis "
                        "(eviction safety), extrapolation band/horizon/LKG "
                        "bounds (freshness) — one ladder step per tick, "
                        "hysteretic loosening, every actuation on "
                        "pas_control_* and GET /debug/control.  Requires "
                        "--slo=on; off (the default) constructs nothing "
                        "and leaves the wire byte-identical")


def validate_control_flags(parser: argparse.ArgumentParser, args) -> None:
    """Fail fast at flag parse on contradictory wiring: the controller
    actuates on the SLO engine's evaluations, so --sloControl=on with
    --slo=off could only ever no-op silently — reject it loudly
    instead (parser.error exits 2 with usage, like any bad flag)."""
    if (
        getattr(args, "sloControl", "off") == "on"
        and getattr(args, "slo", "off") != "on"
    ):
        parser.error(
            "--sloControl=on requires --slo=on: the budget controller "
            "actuates on the SLO engine's burn-rate evaluations; "
            "without the judge there is nothing to control"
        )


def build_budget_controller(args, extender, engine):
    """The BudgetController for --sloControl=on (None when off),
    subscribed to ``engine`` and attached as ``extender.control`` (the
    /debug/control + /metrics wiring keys off that attr).  Every
    actuator the extender actually has gets a knob: the rebalancer's
    aggressiveness pair, the forecaster's extrapolation bounds (plus
    its surge signal as the trend pre-arm source), and the degraded
    controller's last-known-good trust.  The admission knob is the
    async front-end's dispatcher — the caller attaches it after
    build_server (assembly order: the server does not exist yet
    here)."""
    if getattr(args, "sloControl", "off") != "on":
        return None
    from platform_aware_scheduling_tpu.utils.control import BudgetController

    forecaster = getattr(extender, "forecaster", None)
    controller = BudgetController(
        engine,
        trend_source=(
            forecaster.predicts_surge if forecaster is not None else None
        ),
    )
    rebalancer = getattr(extender, "rebalancer", None)
    if rebalancer is not None:
        controller.attach_rebalancer(rebalancer)
    if forecaster is not None:
        controller.attach_forecaster(forecaster)
    degraded = getattr(extender, "degraded", None)
    if degraded is not None:
        controller.attach_degraded(degraded)
    admission = getattr(extender, "admission", None)
    if admission is not None and admission.preemption is not None:
        # preemption aggressiveness: sustained availability burn steps
        # the per-plan victim budget down (utils/control.py)
        controller.attach_preemption(admission.preemption)
    extender.control = controller
    return controller


def add_record_flags(parser: argparse.ArgumentParser) -> None:
    """Flight-recorder flag surface shared by both mains
    (docs/observability.md "Flight recorder & what-if")."""
    parser.add_argument("--flightRecorder", default="off",
                        choices=["off", "on"],
                        help="bounded ring of ANONYMIZED control-plane "
                        "events (verb arrivals keyed by the interned-"
                        "universe digest + candidate count, per-refresh "
                        "telemetry decile curves, eviction/leader flips "
                        "— never node, pod, or namespace names), "
                        "exported as versioned JSONL on GET /debug/record "
                        "and replayable through the digital twin "
                        "(POST /debug/whatif, python -m ...cmd.whatif). "
                        "Costs <=5%% serving p99 (pinned by the http_load "
                        "recorder A/B); off records nothing and 404s "
                        "both endpoints")


def build_flight_recorder(args, extender, cache=None):
    """The FlightRecorder for --flightRecorder=on (None when off),
    attached as ``extender.flight`` (the /debug/record + /debug/whatif +
    /metrics wiring keys off that attr).  With a telemetry ``cache``
    (TAS), one ``on_refresh_pass`` subscription summarizes each pass's
    metric values into decile events and polls the eviction/leadership
    families — the same hook the forecaster refits on, so control
    events cost nothing on the request path."""
    if getattr(args, "flightRecorder", "off") != "on":
        return None
    from platform_aware_scheduling_tpu.utils.record import FlightRecorder

    recorder = FlightRecorder()
    extender.flight = recorder
    if cache is not None:
        cache.on_refresh_pass.append(
            lambda: recorder.observe_cache(cache)
        )
    # the causal spine exports through the same capture (anonymized to
    # kind/event/tick + an irreversible correlation hash — record_spine)
    from platform_aware_scheduling_tpu.utils import events

    events.JOURNAL.flight = recorder
    return recorder


def add_solveobs_flags(parser: argparse.ArgumentParser) -> None:
    """Solve-observatory flag surface shared by both mains
    (docs/observability.md "Solve observatory")."""
    parser.add_argument("--solveObs", default="off",
                        choices=["off", "on"],
                        help="per-stage device-solve attribution "
                        "(snapshot/transfer/compile/execute/readback/"
                        "encode rings + pas_solve_stage_us histograms), "
                        "refresh churn telemetry (changed rows per "
                        "metric per pass, pas_state_churn_*), and the "
                        "per-kernel recompile watch, served on GET "
                        "/debug/solve.  Off instruments nothing — the "
                        "solve pays one module-global read and the wire "
                        "stays byte-identical")
    parser.add_argument("--solveObsSize", type=int, default=256,
                        help="solve-observatory sample ring capacity; "
                        "overflow drops the OLDEST sample (stage "
                        "histograms keep the full history)")


def build_solve_observatory(args, extender, cache=None):
    """The SolveObservatory for --solveObs=on (None when off), installed
    in the process-wide ``ops.solveobs.ACTIVE`` slot (the instrumented
    sites span layers that never see the extender) and attached as
    ``extender.solveobs`` for the /debug/solve route.  With a telemetry
    ``cache`` (TAS), one ``on_refresh_pass`` subscription drains the
    mirror's per-metric churn counts into histograms, the causal spine,
    and — when a flight recorder is also wired — the capture, so churn
    accounting costs nothing on the request path."""
    if getattr(args, "solveObs", "off") != "on":
        return None
    from platform_aware_scheduling_tpu.ops import solveobs

    observatory = solveobs.enable(
        capacity=getattr(args, "solveObsSize", 256)
    )
    observatory.mirror = getattr(extender, "mirror", None)
    observatory.flight = getattr(extender, "flight", None)
    extender.solveobs = observatory
    if cache is not None:
        cache.on_refresh_pass.append(observatory.flush_refresh_pass)
    return observatory


def slo_period(args, default_s: float) -> float:
    """The --sloPeriod in seconds (default: the caller's sync period)."""
    raw = getattr(args, "sloPeriod", "")
    if not raw:
        return default_s
    from platform_aware_scheduling_tpu.utils.duration import parse_duration

    return parse_duration(raw)


def replica_identity(args) -> str:
    """The lease holder identity: --replicaId or hostname-pid."""
    explicit = getattr(args, "replicaId", "")
    if explicit:
        return explicit
    import os
    import socket

    return f"{socket.gethostname()}-{os.getpid()}"


def build_lease_elector(args, kube_client):
    """The LeaseElector for --leaderElect (None when off).  The client
    should already be the fault-tolerant proxy: lease verbs are
    classified idempotent-by-fencing there, so acquire/renew retry
    within the lease duration (kube/retry.py)."""
    if not getattr(args, "leaderElect", False):
        return None
    from platform_aware_scheduling_tpu.kube.lease import LeaseElector
    from platform_aware_scheduling_tpu.utils.duration import parse_duration

    duration_s = parse_duration(args.leaseDuration)
    renew_s = (
        parse_duration(args.leaseRenewPeriod)
        if getattr(args, "leaseRenewPeriod", "")
        else None
    )
    return LeaseElector(
        kube_client,
        identity=replica_identity(args),
        lease_name=args.leaseName,
        namespace=args.leaseNamespace,
        lease_duration_s=duration_s,
        renew_period_s=renew_s,
    )


def build_gang_journal(args, kube_client, breakers=None):
    """The GangJournal for --gangJournal=on (None when off, or when
    --gang is off — there is no state to journal).

    The reservation ledger is REPLICA-LOCAL (each tracker journals its
    own full-state snapshots), so under --leaderElect the journal name
    is suffixed with the replica identity — N replicas sharing one
    ConfigMap would last-writer-wins erase each other's reservations.
    For recovery to find the journal across restarts, give replicas a
    STABLE --replicaId (e.g. the StatefulSet pod name); the hostname-pid
    default changes on every restart and orphans the previous journal
    (docs/gang.md "Crash-safe reservations")."""
    if getattr(args, "gangJournal", "off") != "on":
        return None
    if getattr(args, "gang", "off") != "on":
        return None
    from platform_aware_scheduling_tpu.gang import GangJournal

    name = args.gangJournalName
    if getattr(args, "leaderElect", False):
        name = f"{name}-{replica_identity(args)}"
    return GangJournal(
        kube_client,
        name=name,
        namespace=args.gangJournalNamespace,
        breakers=breakers,
    )


def forecast_options(args, sync_period_s: float) -> Optional[dict]:
    """The --forecast* flags as the options dict ``assemble`` builds a
    Forecaster from (None = off)."""
    if getattr(args, "forecast", "off") != "on":
        return None
    from platform_aware_scheduling_tpu.utils.duration import parse_duration

    horizon_s = None
    if getattr(args, "forecastHorizon", ""):
        horizon_s = parse_duration(args.forecastHorizon)
    return {
        "window": args.forecastWindow,
        "horizon_s": horizon_s,
        "period_s": sync_period_s,
    }


def build_forecaster(cache, mirror, options: Optional[dict]):
    """The Forecaster for --forecast=on (None when off or when the
    assembly is host-only — the forecast views ride the device mirror)."""
    if options is None or mirror is None:
        return None
    from platform_aware_scheduling_tpu.forecast import Forecaster

    return Forecaster(cache, mirror, **options)


def build_gang_tracker(args, kube_client):
    """The GangTracker for --gang=on (None when off), over the kube
    client's node list as the mesh-coordinate source."""
    if getattr(args, "gang", "off") != "on":
        return None
    from platform_aware_scheduling_tpu.gang import GangTracker

    return GangTracker(
        nodes_provider=kube_client.list_nodes,
        pods_provider=kube_client.list_pods,
    )


def configure_decisions(args) -> None:
    """Apply the shared decision flags to the process-wide DecisionLog."""
    from platform_aware_scheduling_tpu.utils import decisions

    decisions.DECISIONS.configure(enabled=args.decisionLog == "on")


def build_fault_tolerance(args):
    """(RetryPolicy, CircuitBreakerRegistry) from the shared flags."""
    from platform_aware_scheduling_tpu.kube.retry import (
        CircuitBreakerRegistry,
        RetryPolicy,
    )
    from platform_aware_scheduling_tpu.utils.duration import parse_duration

    policy = RetryPolicy(
        max_attempts=args.retryMaxAttempts,
        base_delay_s=parse_duration(args.retryBaseDelay),
    )
    breakers = CircuitBreakerRegistry(
        failure_threshold=args.circuitFailureThreshold,
        reset_timeout_s=parse_duration(args.circuitResetTimeout),
    )
    return policy, breakers


def wrap_kube_client(kube_client, policy, breakers):
    """The fault-tolerant proxy both mains put in front of every API
    consumer (kube/retry.py)."""
    from platform_aware_scheduling_tpu.kube.retry import FaultTolerantClient

    return FaultTolerantClient(kube_client, policy=policy, breakers=breakers)


def maybe_start_profiler(port: int) -> bool:
    """Start the JAX profiler server when ``port`` is nonzero; returns
    whether it is serving.  Profiling must never block serving — any
    failure logs and the main continues."""
    if not port:
        return False
    try:
        import jax.profiler

        jax.profiler.start_server(port)
        klog.v(1).info_s(
            f"JAX profiler serving on :{port}", component="extender"
        )
        return True
    except Exception as exc:
        klog.error("profiler server failed: %s", exc)
        return False


def prepare_device_runtime() -> None:
    """Everything a service main does about the device BEFORE assembly
    (whose warm pass runs the first compiles): place the persistent
    compile cache (utils/backend.py — JAX_COMPILATION_CACHE_DIR when set,
    else the fixed in-checkout path), log and export which device and
    which wire path this replica actually runs on, and install the
    one-shot per-kernel cost-analysis capture (utils/devicewatch.py),
    which hangs off each watched kernel's FIRST compile."""
    from platform_aware_scheduling_tpu.native import wirec_origin

    cache_dir = backend.enable_compile_cache()
    backend.export_device_identity()
    origin = wirec_origin()
    klog.v(1).info_s(
        f"compile cache: {cache_dir}; wire path: "
        + (f"native _wirec ({origin})" if origin else "pure Python"),
        component="extender",
    )
    devicewatch.install_cost_hooks()


def start_device_watch(
    stop: Optional[threading.Event] = None, sample_period_s: float = 10.0
) -> devicewatch.DeviceWatcher:
    """Start the device memory-watermark sampler on a daemon thread
    (graceful no-op on CPU); returns the watcher."""
    watcher = devicewatch.DeviceWatcher(period_s=sample_period_s)
    watcher.start(stop=stop)
    return watcher
