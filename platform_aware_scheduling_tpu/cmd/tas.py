"""TAS service main: flags, assembly, signal handling.

Reference: telemetry-aware-scheduling/cmd/main.go:31-117.  Identical flag
surface (``--kubeConfig --port --cert --key --cacert --unsafe --syncPeriod``
plus klog ``--v``); assembly adds the TPU twist: a TensorStateMirror is
attached to the cache so the extender's hot path runs the jitted scoring
kernels, with the exact host path as automatic fallback.
"""

from __future__ import annotations

import argparse
import os
import signal
import threading
from typing import List, Optional

from platform_aware_scheduling_tpu.cmd import common
from platform_aware_scheduling_tpu.extender.server import Server
from platform_aware_scheduling_tpu.kube.client import KubeClient, get_kube_client
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.controller import TelemetryPolicyController
from platform_aware_scheduling_tpu.tas.metrics import CustomMetricsClient
from platform_aware_scheduling_tpu.tas.strategies import (
    core,
    deschedule,
    dontschedule,
    scheduleonmetric,
)
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.utils import klog
from platform_aware_scheduling_tpu.utils.duration import parse_duration


def build_arg_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tas-extender",
        description="Telemetry-aware scheduling extender (TPU-native)",
    )
    default_kubeconfig = os.path.join(
        os.environ.get("HOME", "/root"), ".kube", "config"
    )
    parser.add_argument("--kubeConfig", default=default_kubeconfig,
                        help="location of kubernetes config file")
    parser.add_argument("--port", default="9001",
                        help="port on which the scheduler extender will listen")
    parser.add_argument("--cert", default="/etc/kubernetes/pki/ca.crt",
                        help="cert file extender will use")
    parser.add_argument("--key", default="/etc/kubernetes/pki/ca.key",
                        help="key file extender will use")
    parser.add_argument("--cacert", default="/etc/kubernetes/pki/ca.crt",
                        help="ca file extender will use")
    parser.add_argument("--unsafe", action="store_true",
                        help="unsafe instances of extender will be served over http")
    parser.add_argument("--syncPeriod", default="5s",
                        help="interval between cache syncs, e.g. 1m or 2s")
    parser.add_argument("--v", type=int, default=2, help="klog verbosity")
    parser.add_argument("--batchPlanner", action="store_true",
                        help="solve the whole pending set each sync period "
                        "and steer pods onto their batch-assigned nodes")
    parser.add_argument("--batchSolver", default="greedy",
                        choices=["greedy", "sinkhorn"],
                        help="batch planner solver: greedy (sequential-"
                        "equivalent; pods of unlike requests each book "
                        "their own cpu, memory and pod slot) or sinkhorn "
                        "(globally coordinated; the one form that takes a "
                        "count only, so unlike pods are each counted as the "
                        "largest request pending: never an overcommit, not "
                        "exact)")
    parser.add_argument("--batchPlannerDevices", type=int, default=1,
                        help="devices the batch planner's solve spans: 1 "
                        "(default) solves on one device; n > 1 solves "
                        "node-sharded over a mesh of the first n (greedy "
                        "solver only; the same plan, pods of unlike "
                        "requests each booking their own; "
                        "docs/architecture.md)")
    parser.add_argument("--nodeCacheCapable", action="store_true",
                        help="serve Prioritize/Filter from Args.NodeNames "
                        "(register the extender nodeCacheCapable: true); "
                        "large clusters avoid shipping full node objects")
    parser.add_argument("--serving", default="threaded",
                        choices=["threaded", "async"],
                        help="HTTP front-end: threaded (reference-parity "
                        "default) or async (event loop + micro-batched "
                        "device dispatch, docs/serving.md)")
    parser.add_argument("--rebalance", default="off",
                        choices=["off", "dry-run", "active"],
                        help="closed-loop rebalancer (docs/rebalance.md): "
                        "dry-run computes and publishes plans on "
                        "/debug/rebalance without touching the cluster; "
                        "active evicts through pods/eviction behind "
                        "rate-limit, cooldown and min-available guards")
    parser.add_argument("--rebalanceSolver", default="greedy",
                        choices=["greedy", "sinkhorn"],
                        help="replan solver (mirrors --batchSolver)")
    common.add_profile_flag(parser)
    common.add_robustness_flags(parser)
    common.add_decision_flags(parser)
    common.add_event_flags(parser)
    common.add_gang_flags(parser)
    common.add_admission_flags(parser)
    common.add_shard_flags(parser)
    common.add_forecast_flags(parser)
    common.add_ha_flags(parser)
    common.add_slo_flags(parser)
    common.add_control_flags(parser)
    common.add_record_flags(parser)
    common.add_solveobs_flags(parser)
    return parser


def assemble(
    kube_client: KubeClient,
    metrics_client,
    sync_period_s: float,
    enable_device_path: bool = True,
    enable_batch_planner: bool = False,
    batch_solver: str = "greedy",
    planner_devices: int = 1,
    node_cache_capable: bool = False,
    rebalance_mode: str = "off",
    rebalance_options: Optional[dict] = None,
    breakers=None,
    degraded_mode: Optional[str] = None,
    gang_tracker=None,
    forecast_options: Optional[dict] = None,
    leadership=None,
    gang_journal=None,
):
    """Wire cache + mirror + extender + controller + enforcer (the body of
    ``tasController``, reference cmd/main.go:53-95).  Returns the pieces and
    a stop Event controlling every background loop.

    ``breakers``/``degraded_mode``: when either is given, a
    DegradedModeController (tas/degraded.py) is built over the cache's
    freshness signal and the circuit states and attached to the
    extender, the enforcer, and the rebalancer — degraded Filter/
    Prioritize policy plus the unconditional eviction suspension.

    ``gang_tracker``: the --gang=on GangTracker
    (common.build_gang_tracker); attached to the extender so Filter/
    Prioritize/Bind consult gang reservations and the front-ends serve
    GET /debug/gangs, and fed the cluster's pods (its members' bindings
    and departures) from ``kube_client`` (docs/gang.md).

    ``forecast_options``: the --forecast=on options dict
    (common.forecast_options); a Forecaster (forecast/engine.py) is
    built over the cache's history rings + the mirror and attached to
    the extender (predicted-value ranking, /debug/forecast), the
    degraded controller (bounded extrapolation), and the rebalancer
    (trend-aware hysteresis) — docs/forecast.md.

    ``leadership``: the --leaderElect LeaseElector
    (common.build_lease_elector); attached to the enforcer (deschedule
    label pass), the rebalancer + its actuator (cycle gate + per-
    eviction fencing), the gang tracker (dead-sweep), and the extender
    (/readyz condition, /debug/leader).  None — the default single-
    replica assembly — leaves every behavior byte-identical.

    ``gang_journal``: the --gangJournal=on GangJournal
    (common.build_gang_journal); the tracker journals reservation/bind
    mutations write-behind and recovers them here, reconciled against
    live pods, before any verb is served (docs/gang.md)."""
    cache = AutoUpdatingCache()
    mirror: Optional[TensorStateMirror] = None
    if enable_device_path:
        mirror = TensorStateMirror()
        mirror.attach(cache)
    planner = None
    if enable_batch_planner and mirror is not None:
        from platform_aware_scheduling_tpu.tas.planner import BatchPlanner

        # raises planner.MeshRefused, before any thread is started, when
        # the solve cannot span ``planner_devices`` devices
        planner = BatchPlanner(
            cache, mirror, solver=batch_solver, devices=planner_devices
        )
    # the forecaster must exist BEFORE the extender: MetricsExtender's
    # constructor runs the first warm pass, and the history rings must
    # already be recording when the initial metric seeds land
    forecaster = common.build_forecaster(cache, mirror, forecast_options)
    extender = MetricsExtender(
        cache,
        mirror=mirror,
        planner=planner,
        node_cache_capable=node_cache_capable,
    )
    if forecaster is not None:
        extender.forecaster = forecaster
        # after the forecaster's own refit subscription (appended at its
        # construction above), so each refresh pass re-warms rankings
        # against the fit it JUST published — warm_fastpath alone fires
        # mid-pass, before the refit, and would leave every fresh
        # forecast view cold to its first request
        cache.on_refresh_pass.append(extender.warm_forecast_rankings)
    if gang_tracker is not None:
        extender.gangs = gang_tracker
        if gang_journal is not None:
            # crash-safe reservations: recover the journaled slices —
            # reconciled against live pods — BEFORE any verb can reserve
            # over them, then journal every durable mutation from here on
            gang_tracker.journal = gang_journal
            gang_tracker.recover()
    if leadership is not None:
        extender.leadership = leadership
        if gang_tracker is not None:
            gang_tracker.leadership = leadership

    enforcer = core.MetricEnforcer(kube_client, mirror=mirror)
    enforcer.leadership = leadership
    enforcer.register_strategy_type(deschedule.Strategy())
    enforcer.register_strategy_type(scheduleonmetric.Strategy())
    enforcer.register_strategy_type(dontschedule.Strategy())

    degraded = None
    if breakers is not None or degraded_mode is not None:
        from platform_aware_scheduling_tpu.tas.degraded import (
            MODE_LAST_KNOWN_GOOD,
            DegradedModeController,
        )

        degraded = DegradedModeController(
            cache,
            breakers=breakers,
            mode=degraded_mode or MODE_LAST_KNOWN_GOOD,
        )
        degraded.forecaster = forecaster  # bounded LKG extrapolation
        extender.degraded = degraded
        enforcer.degraded = degraded

    # closed-loop rebalancer (docs/rebalance.md): each deschedule
    # enforcement cycle feeds the drift detector; past the hysteresis
    # threshold the evictable pods are replanned on-device and (active
    # mode) evicted behind the actuator's guards.  Needs the mirror —
    # host-only assemblies stay label-only like the reference.
    if rebalance_mode != "off" and mirror is not None:
        from platform_aware_scheduling_tpu.rebalance import Rebalancer

        rebalancer = Rebalancer(
            kube_client, mirror, mode=rebalance_mode,
            **(rebalance_options or {}),
        )
        rebalancer.degraded = degraded
        rebalancer.forecaster = forecaster  # trend-aware hysteresis
        # singleton gating + per-eviction fencing (kube/lease.py): the
        # cycle idles as "follower" off-leader, and even the leader's
        # actuator re-verifies its fencing token before each eviction
        rebalancer.leadership = leadership
        rebalancer.actuator.leadership = leadership
        rebalancer.attach(enforcer)
        extender.rebalancer = rebalancer
        # gang-atomic eviction completes the loop: a whole-gang eviction
        # releases the gang's slice reservation (docs/gang.md)
        if gang_tracker is not None:
            rebalancer.actuator.gang_tracker = gang_tracker

    controller = TelemetryPolicyController(kube_client, cache, enforcer)

    stop = threading.Event()
    cache.start_periodic_update(sync_period_s, metrics_client, stop=stop)
    controller.run(stop)
    enforcer.start_enforcing(cache, sync_period_s, stop=stop)
    if gang_tracker is not None and kube_client is not None:
        # the gang tracker learns its members' bindings and departures
        # from the cluster's pods: kube-scheduler sends no Bind to an
        # extender without a bindVerb (docs/gang.md)
        gang_feed = gang_tracker.watch(kube_client)
        threading.Thread(
            target=lambda: (stop.wait(), gang_feed.stop()),
            name="pas-stop-gang-feed",
            daemon=True,
        ).start()
    if planner is not None:
        planner_informer = planner.watch(kube_client)
        # the plan's one trigger: the end of a refresh pass, after its
        # publishes and warms (and the forecaster's refit, subscribed
        # above), so the plan for a version exists as soon as it serves
        cache.on_refresh_pass.append(planner.replan)
        threading.Thread(
            target=lambda: (stop.wait(), planner_informer.stop()),
            name="pas-stop-planner",
            daemon=True,
        ).start()
    return cache, mirror, extender, controller, enforcer, stop


def build_server(extender, serving: str = "threaded"):
    """The selected HTTP front-end over an extender: the reference-parity
    threaded server (default) or the event-loop micro-batching one
    (serving/, opt-in via --serving=async).  Shared by the TAS and GAS
    mains — both serve the same verbs through the same wire stack.

    /metrics serves the full exposition (verb histograms + serving
    counters + path-attribution and JAX compile counters — utils/trace.py);
    the async server composes the same page itself from the extender's
    shared recorder."""
    if serving == "async":
        from platform_aware_scheduling_tpu.serving import AsyncServer

        return AsyncServer(extender)
    provider = getattr(
        extender, "metrics_text", extender.recorder.prometheus_text
    )
    return Server(extender, metrics_provider=provider)


def main(argv: Optional[List[str]] = None) -> int:
    parser = build_arg_parser()
    args = parser.parse_args(argv)
    common.validate_control_flags(parser, args)
    common.validate_admission_flags(parser, args)
    common.validate_shard_flags(parser, args)
    if args.batchPlannerDevices != 1 and not args.batchPlanner:
        parser.error("--batchPlannerDevices needs --batchPlanner")
    klog.set_verbosity(args.v)
    sync_period_s = parse_duration(args.syncPeriod)
    # decision provenance + causal event journal on/off + ring sizes,
    # before any verb can record or publish
    common.configure_decisions(args)
    common.configure_events(args)

    # every remote call goes through the fault-tolerant proxy: retried
    # reads, breaker-gated writes, per-endpoint-group circuits
    # (kube/retry.py; docs/robustness.md).  The metrics client rides the
    # same proxy — its get_node_custom_metric verb lands in the
    # "metrics" circuit group
    retry_policy, breakers = common.build_fault_tolerance(args)
    kube_client = common.wrap_kube_client(
        get_kube_client(args.kubeConfig), retry_policy, breakers
    )
    metrics_client = CustomMetricsClient(kube_client)
    # HA control plane (docs/robustness.md "HA & leader election"):
    # leader election + crash-safe gang journal, both optional and both
    # riding the fault-tolerant client built above
    leadership = common.build_lease_elector(args, kube_client)
    gang_journal = common.build_gang_journal(args, kube_client, breakers)
    # compile cache, device identity and the cost capture all precede
    # the first compile, which assemble's warm pass triggers
    common.prepare_device_runtime()
    gang_tracker = common.build_gang_tracker(args, kube_client)
    from platform_aware_scheduling_tpu.tas.planner import MeshRefused

    try:
        cache, mirror, extender, controller, _, stop = assemble(
            kube_client,
            metrics_client,
            sync_period_s,
            enable_batch_planner=args.batchPlanner,
            batch_solver=args.batchSolver,
            planner_devices=args.batchPlannerDevices,
            node_cache_capable=args.nodeCacheCapable,
            breakers=breakers,
            degraded_mode=args.degradedMode,
            gang_tracker=gang_tracker,
            forecast_options=common.forecast_options(args, sync_period_s),
            leadership=leadership,
            gang_journal=gang_journal,
            rebalance_mode=args.rebalance,
            rebalance_options={"solver": args.rebalanceSolver},
        )
    except MeshRefused as exc:
        parser.error(f"--batchPlannerDevices: {exc}")

    # admission plane (--admission=on; docs/admission.md): the priority
    # queue both verbs consult, plus — with --preemption=on — the gang
    # preemption planner over its own dedicated active-mode actuator.
    # Built BEFORE the budget controller so the preemption-
    # aggressiveness knob can attach.  Off (the default) builds nothing
    common.build_admission_plane(
        args,
        extender,
        kube_client=kube_client,
        gang_tracker=gang_tracker,
        leadership=leadership,
    )

    # partition plane (--shard=on; docs/sharding.md): consistent-hash
    # partition ownership journaled in a ConfigMap, the telemetry
    # refresh cut to owned partitions, scatter/gather serving over
    # gossiped digests.  Built BEFORE the budget controller so the
    # per-partition shed knobs can attach.  Off (the default) builds
    # nothing — the wire stays byte-identical
    shard_plane = common.build_shard_plane(
        args,
        extender,
        kube_client=kube_client,
        cache=cache,
        mirror=mirror,
        leadership=leadership,
    )

    # SLO engine (--slo=on; docs/observability.md "SLOs & error
    # budgets"): judged over the extender's recorder + the cache's
    # freshness signal, ticked on its own daemon loop; attaching it to
    # the extender lights up /debug/slo, the pas_slo_* gauges, and the
    # informational slo_burn readiness condition.  Off (the default)
    # builds nothing — the wire stays byte-identical
    slo_engine = common.build_slo_engine(args, extender, cache=cache)
    if slo_engine is not None:
        slo_engine.start(common.slo_period(args, sync_period_s), stop=stop)

    # budget feedback controller (--sloControl=on; docs/observability.md
    # "Budget feedback control"): subscribed to the engine's post-tick
    # hook, stepping the rebalancer/forecaster/degraded knobs — the
    # admission knob joins below once the server (and so the dispatcher)
    # exists.  Off (the default) builds nothing
    budget_controller = common.build_budget_controller(
        args, extender, slo_engine
    )
    if budget_controller is not None and shard_plane is not None:
        # per-partition digest top-k shed knobs
        # (pas_control_knob_setting{knob=shard_topk_p<N>, partition=})
        budget_controller.attach_shard(shard_plane)

    # flight recorder (--flightRecorder=on; docs/observability.md
    # "Flight recorder & what-if"): anonymized verb/telemetry/control
    # events into a bounded ring behind GET /debug/record and
    # POST /debug/whatif.  Off (the default) builds nothing — the verbs
    # skip one attribute check and the wire stays byte-identical
    flight_recorder = common.build_flight_recorder(args, extender, cache=cache)
    if flight_recorder is not None and shard_plane is not None:
        # ownership changes land in the capture as anonymized shard
        # events (partition ids + fencing epochs only — record_shard)
        shard_plane.coordinator.flight = flight_recorder

    # solve observatory (--solveObs=on; docs/observability.md "Solve
    # observatory"): per-stage solve attribution + refresh churn behind
    # GET /debug/solve.  Built AFTER the flight recorder so churn passes
    # ride an enabled capture.  Off (the default) builds nothing — the
    # solve pays one module-global read and the wire stays byte-identical
    common.build_solve_observatory(args, extender, cache=cache)

    common.maybe_start_profiler(args.profilePort)
    common.start_device_watch(stop=stop)
    if leadership is not None:
        # the election loop starts AFTER assembly so a recovered gang
        # journal and warmed caches are in place before this replica can
        # win the lease and begin actuating
        leadership.start(stop)

    from platform_aware_scheduling_tpu.utils.gctuning import tune_for_serving

    tune_for_serving()
    server = build_server(extender, serving=args.serving)
    if budget_controller is not None and hasattr(server, "dispatcher"):
        # the shed knob actuates the async front-end's live-read
        # admission bound; the threaded server has no admission queue,
        # so there the availability path simply has no knob
        budget_controller.attach_admission(server.dispatcher)
    # /readyz also waits on the TASPolicy CRD informer's initial list —
    # the extender's own conditions (warm + telemetry freshness) come
    # from its readiness_conditions() via the server's probe
    if controller.informer is not None:
        from platform_aware_scheduling_tpu.utils import health

        server.probe.register(
            "policy_informer_synced",
            health.informer_synced(controller.informer, "taspolicy"),
        )
    done = threading.Event()
    failed = []

    def serve():
        try:
            server.start_server(
                port=args.port,
                cert_file=args.cert,
                key_file=args.key,
                ca_file=args.cacert,
                unsafe=args.unsafe,
                block=True,
            )
        except Exception as exc:
            # a dead server must take the process down so the kubelet
            # restarts it, not leave a Running pod that serves nothing
            klog.error("extender server failed: %s", exc)
            failed.append(exc)
            done.set()

    threading.Thread(target=serve, name="pas-serve", daemon=True).start()

    # catchInterrupt (reference cmd/main.go:113-117)
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: done.set())
    done.wait()
    stop.set()
    server.shutdown()
    klog.v(1).info_s("Exiting", component="extender")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
