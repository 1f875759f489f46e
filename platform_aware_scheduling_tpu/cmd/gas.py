"""GAS service main.

Reference: gpu-aware-scheduling/cmd/gas-scheduler-extender/main.go:11-35 —
flags, extender assembly, HTTP(S) serving.
"""

from __future__ import annotations

import argparse
import os
import signal
import threading
from typing import List, Optional

from platform_aware_scheduling_tpu.cmd import common
from platform_aware_scheduling_tpu.gas.scheduler import GASExtender
from platform_aware_scheduling_tpu.kube.client import get_kube_client
from platform_aware_scheduling_tpu.utils import klog


def build_arg_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="gas-extender",
        description="GPU-aware scheduling extender (TPU-native)",
    )
    default_kubeconfig = os.path.join(
        os.environ.get("HOME", "/root"), ".kube", "config"
    )
    parser.add_argument("--kubeConfig", default=default_kubeconfig)
    parser.add_argument("--port", default="9001")
    parser.add_argument("--cert", default="/etc/kubernetes/pki/ca.crt")
    parser.add_argument("--key", default="/etc/kubernetes/pki/ca.key")
    parser.add_argument("--cacert", default="/etc/kubernetes/pki/ca.crt")
    parser.add_argument("--unsafe", action="store_true")
    parser.add_argument("--v", type=int, default=4, help="klog verbosity")
    parser.add_argument("--serving", default="threaded",
                        choices=["threaded", "async"],
                        help="HTTP front-end: threaded (reference-parity "
                        "default) or async (event loop + micro-batched "
                        "dispatch, docs/serving.md)")
    # parity with cmd/tas.py via the one shared helper (cmd/common.py);
    # forecast=False: GAS has no telemetry cache to forecast over, so the
    # --forecast* flags are explicitly NOT offered (no dead flags — the
    # same stance --degradedMode takes above)
    common.add_profile_flag(parser)
    common.add_robustness_flags(parser, degraded=False)
    common.add_decision_flags(parser)
    common.add_event_flags(parser)
    # queue-only admission: GAS has no gang tracker, so the --preemption
    # surface is explicitly NOT offered (no dead flags)
    common.add_admission_flags(parser, preemption=False)
    common.add_forecast_flags(parser, forecast=False)
    common.add_ha_flags(parser, ha=False)
    common.add_slo_flags(parser)
    common.add_control_flags(parser)
    common.add_record_flags(parser)
    common.add_solveobs_flags(parser)
    return parser


def main(argv: Optional[List[str]] = None) -> int:
    parser = build_arg_parser()
    args = parser.parse_args(argv)
    common.validate_control_flags(parser, args)
    common.validate_admission_flags(parser, args)
    klog.set_verbosity(args.v)
    common.configure_decisions(args)
    common.configure_events(args)

    # fault-tolerant proxy in front of every API consumer — GAS has no
    # telemetry cache so no degraded-mode controller, but its informers
    # and bind/annotate traffic get the same retry/backoff/circuit
    # treatment as TAS (docs/robustness.md)
    retry_policy, breakers = common.build_fault_tolerance(args)
    kube_client = common.wrap_kube_client(
        get_kube_client(args.kubeConfig), retry_policy, breakers
    )
    # before the extender warms its device binpack kernels: compile
    # cache, device identity, cost capture (rides each first compile)
    common.prepare_device_runtime()
    extender = GASExtender(kube_client, retry_policy=retry_policy)
    # admission plane (--admission=on): queue-only here — no gang
    # tracker, so backfill runs size-only and preemption never attaches
    common.build_admission_plane(args, extender, kube_client=kube_client)

    common.maybe_start_profiler(args.profilePort)
    watch_stop = threading.Event()
    common.start_device_watch(stop=watch_stop)
    # SLO engine (--slo=on): GAS gets the verb-availability +
    # gas_filter-latency defaults (no telemetry cache to judge freshness
    # over); off builds nothing (docs/observability.md)
    slo_engine = common.build_slo_engine(args, extender)
    if slo_engine is not None:
        slo_engine.start(common.slo_period(args, 5.0), stop=watch_stop)
    # budget controller (--sloControl=on): GAS has no rebalancer/
    # forecaster/degraded actuators, so only the admission knob (async
    # serving) can attach below; the controller still observes
    budget_controller = common.build_budget_controller(
        args, extender, slo_engine
    )
    # flight recorder (--flightRecorder=on): verb arrivals only — GAS
    # has no telemetry cache, so no decile/control events here
    common.build_flight_recorder(args, extender)
    # solve observatory (--solveObs=on): GAS has no telemetry mirror, so
    # no churn passes — the device binpack solves still attribute stages
    common.build_solve_observatory(args, extender)

    from platform_aware_scheduling_tpu.cmd.tas import build_server
    from platform_aware_scheduling_tpu.utils.gctuning import tune_for_serving

    tune_for_serving()
    server = build_server(extender, serving=args.serving)
    if budget_controller is not None and hasattr(server, "dispatcher"):
        budget_controller.attach_admission(server.dispatcher)
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
            klog.error("extender server failed: %s", exc)
            failed.append(exc)
            done.set()

    threading.Thread(target=serve, name="pas-serve", daemon=True).start()

    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: done.set())
    done.wait()
    watch_stop.set()
    extender.cache.stop()
    server.shutdown()
    klog.v(1).info_s("Exiting", component="extender")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
