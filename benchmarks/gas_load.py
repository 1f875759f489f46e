"""GAS device path through the wire (VERDICT r4 #7).

The TAS A/B (benchmarks/http_load.py) measures the full HTTP serving
path; GAS's vmapped card bin-packing was previously benched only as a
bare kernel (configs.py config #3).  This drives ``/scheduler/filter``
against a LIVE GASExtender — fake cluster state via
testing/fake_kube.py, informer-replayed usage from pre-booked annotated
pods — and reports per-request latency for

  * **device**: ``DeviceBinpacker.batch_fit`` — ONE XLA pass evaluating
    every candidate node (gas/device.py), and
  * **control**: the host loop — the reference's sequential per-node
    ``runSchedulingLogic`` walk under the global lock
    (gpuscheduler/scheduler.go:449-482), same server, same wire.

Same client, same harness rules as the TAS bench: raw keep-alive
sockets, full-size measured control, repeats with the lower-p99 run
reported and per-repeat spread surfaced.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List

from benchmarks.http_load import _best_of, _spawn_service, drive

CARDS = 8


def node_names(num_nodes: int) -> List[str]:
    return [f"gpu-node-{i:05d}" for i in range(num_nodes)]


def build_gas_service(num_nodes: int, device: bool, seed: int = 5):
    """(server, node names): a live unsafe-HTTP GAS extender over a fake
    cluster — every node carries the cards label + gpu.intel.com
    allocatable, ~30% of nodes have one pre-booked annotated pod whose
    usage the cache ingests through the informer replay (the reference's
    restart semantics, node_resource_cache.go:493-538)."""
    import numpy as np

    from platform_aware_scheduling_tpu.extender.server import Server
    from platform_aware_scheduling_tpu.gas.cache import Cache
    from platform_aware_scheduling_tpu.gas.scheduler import GASExtender
    from platform_aware_scheduling_tpu.gas.utils import (
        CARD_ANNOTATION,
        TS_ANNOTATION,
    )
    from platform_aware_scheduling_tpu.testing.builders import (
        make_node,
        make_pod,
    )
    from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient

    rng = np.random.default_rng(seed)
    kube = FakeKubeClient()
    names = node_names(num_nodes)
    cards_label = ".".join(f"card{i}" for i in range(CARDS))
    for name in names:
        kube.add_node(
            make_node(
                name,
                labels={"gpu.intel.com/cards": cards_label},
                allocatable={
                    "gpu.intel.com/i915": str(CARDS),
                    "gpu.intel.com/millicores": "8000",
                    "gpu.intel.com/memory.max": "64000",
                },
            )
        )
    for i, name in enumerate(names):
        if rng.random() < 0.3:
            kube.add_pod(
                make_pod(
                    f"booked-{i}",
                    container_requests=[
                        {
                            "gpu.intel.com/i915": "1",
                            "gpu.intel.com/millicores": "1000",
                        }
                    ],
                    node_name=name,
                    annotations={
                        CARD_ANNOTATION: f"card{int(rng.integers(CARDS))}",
                        TS_ANNOTATION: "1",
                    },
                    phase="Running",
                )
            )
    cache = Cache(kube)
    cache.wait_settled()
    ext = GASExtender(kube, cache=cache, use_device=device)
    server = Server(ext)
    server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
    server.wait_ready()
    return server, names


def make_bodies(names: List[str], count: int = 20) -> List[bytes]:
    """Filter bodies: a GPU-requesting pod (rotating name, as within one
    scheduling burst) over the full NodeNames candidate list — the wire
    mode GAS REQUIRES (scheduler.go:455-461)."""
    bodies = []
    for i in range(count):
        pod = {
            "metadata": {"name": f"gas-bench-{i}", "namespace": "default"},
            "spec": {
                "containers": [
                    {
                        "name": "c0",
                        "resources": {
                            "requests": {
                                "gpu.intel.com/i915": "2",
                                "gpu.intel.com/millicores": "500",
                            }
                        },
                    },
                    {
                        "name": "c1",
                        "resources": {
                            "requests": {
                                "gpu.intel.com/i915": "1",
                                "gpu.intel.com/millicores": "1500",
                            }
                        },
                    },
                ]
            },
        }
        bodies.append(
            json.dumps({"Pod": pod, "NodeNames": names}).encode()
        )
    return bodies


def run(
    num_nodes: int = 2000,
    device_requests: int = 200,
    control_requests: int = 104,
    concurrency_sweep: tuple = (1, 8),
    warmup: int = 5,
    repeats: int = 2,
    platform: str = "tpu",
) -> Dict:
    """The GAS A/B: device batch_fit vs sequential host loop, through the
    live /scheduler/filter socket at full cluster size."""
    names = node_names(num_nodes)
    bodies = make_bodies(names)
    out: Dict = {"num_nodes": num_nodes, "cards": CARDS}
    for label, device in (("device", True), ("control", False)):
        proc, port, served_on = _spawn_service(
            num_nodes, device=device, module="benchmarks.gas_load",
            platform=platform,
        )
        if device:
            out["platform"] = served_on
        n_req = device_requests if device else control_requests
        try:
            side: Dict = {}
            for conc in concurrency_sweep:
                key = f"gas_filter_c{conc}"
                best = None
                repeat_p99: List[float] = []
                for _rep in range(max(repeats, 1)):
                    drive(port, bodies[:5], warmup, concurrency=1,
                          path="/scheduler/filter")
                    measured = drive(
                        port,
                        bodies,
                        n_req,
                        concurrency=conc,
                        path="/scheduler/filter",
                    )
                    repeat_p99.append(measured["p99_ms"])
                    best = (
                        measured if best is None else _best_of(best, measured)
                    )
                best = dict(best)
                if len(repeat_p99) > 1:
                    best["repeat_p99_ms"] = repeat_p99
                side[key] = best
            out[label] = side
        finally:
            proc.terminate()
            proc.wait(timeout=10)
    speedups: Dict[str, Dict[str, float]] = {}
    for key, dev in out["device"].items():
        ctl = out["control"].get(key)
        if ctl:
            speedups[key] = {
                "p50": round(ctl["p50_ms"] / dev["p50_ms"], 1),
                "p99": round(ctl["p99_ms"] / dev["p99_ms"], 1),
            }
    out["speedup"] = speedups
    c0 = concurrency_sweep[0]
    out["speedup_p99_gas_filter"] = speedups[f"gas_filter_c{c0}"]["p99"]
    # measurement transparency (same spirit as the TAS miss tier): the
    # device side amortizes ONE binpack dispatch per (usage-state
    # version, pod template) across the burst (gas/device.py fits
    # cache); requests here rotate pod names within one template, the
    # kube-scheduler burst pattern.  A template/state miss re-pays the
    # kernel (configs config3's chained measurement) plus one dispatch
    # and readback.
    out["notes"] = (
        "device amortizes one kernel dispatch per (state version, pod "
        "template) across the burst; cold template cost = config3 kernel "
        "time + dispatch"
    )
    return out


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--serve":
        from benchmarks.http_load import _serve_forever

        _serve_forever(
            int(sys.argv[2]),
            sys.argv[3] == "1",
            builder=build_gas_service,
            platform=sys.argv[8] if len(sys.argv) > 8 else "tpu",
        )
    else:
        from benchmarks import children

        nodes = int(sys.argv[1]) if len(sys.argv) > 1 else 2000
        result = run(num_nodes=nodes)
        children.assert_launcher("benchmarks.gas_load")
        print(json.dumps(result, indent=2))
