"""Replica-aware serving bench + failover measurement
(docs/robustness.md "HA & leader election").

Two numbers back the HA claim:

  * **Horizontal scale-out** — the same c=8 request stream through ONE
    live extender vs SPREAD over 3 replicas (each its own process-like
    service on its own port, as behind a Service).  Filter/Prioritize
    hold no cross-replica state, so the fleet should deliver ~linear
    aggregate throughput with per-replica tail latency at the lighter
    per-replica concurrency — measured here, not assumed.
  * **Failover latency** — the multi-replica harness (testing/ha.py) on
    a fake clock: leader killed mid-convergence, ticks until a standby
    holds the lease, total evictions vs the single-replica baseline,
    duplicate evictions (must be zero — the exactly-one-actuator
    invariant).

Feeds the ``ha`` section of bench.py's line and the BENCH_DETAIL
artifact; ``make bench-ha`` runs it alone.
"""

from __future__ import annotations

import json
import sys
import threading
from typing import Dict, List, Optional


def _drive_fleet(
    ports: List[int],
    bodies_per_port,
    requests: int,
    concurrency: int,
) -> Dict:
    """Split ``requests`` at total ``concurrency`` across the fleet's
    ports; aggregate throughput is summed, the fleet p99 is the WORST
    replica's p99 (a Service's tail is its slowest backend)."""
    from benchmarks import http_load

    n = len(ports)
    per_port_reqs = requests // n
    conc = [concurrency // n] * n
    for i in range(concurrency % n):
        conc[i] += 1
    results: List[Dict] = [{} for _ in range(n)]
    errors: List[str] = []

    def worker(i: int) -> None:
        try:
            results[i] = http_load.drive(
                ports[i],
                bodies_per_port[i],
                requests=per_port_reqs,
                concurrency=max(1, conc[i]),
            )
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errors.append(f"replica {i}: {exc}")

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"fleet drive errors: {errors[:3]}")
    return {
        "per_replica": results,
        "p99_ms": max(r["p99_ms"] for r in results),
        "p50_ms": max(r["p50_ms"] for r in results),
        "requests_per_s": round(
            sum(r["requests_per_s"] for r in results), 1
        ),
    }


def serving_scale_out(
    num_nodes: int = 256,
    requests: int = 480,
    concurrency: int = 8,
    replicas: int = 3,
) -> Dict:
    """c=8 against one replica vs the same c=8 spread over ``replicas``
    independent services (independent caches, same seeded state)."""
    from benchmarks import http_load

    out: Dict = {
        "num_nodes": num_nodes,
        "requests": requests,
        "concurrency": concurrency,
        "replicas": replicas,
    }

    def warm(port: int, bodies) -> None:
        # unmeasured warm-up: every service in this one process must be
        # past first-request compile/caching before its measured run, or
        # whichever side runs first pays the one-time jit cost for all
        http_load.drive(port, bodies, requests=32, concurrency=2)

    server, names = http_load.build_service(num_nodes, device=True)
    try:
        bodies = http_load.make_bodies(names, "nodenames", count=8)
        warm(server.port, bodies)
        out["single"] = http_load.drive(
            server.port, bodies, requests=requests, concurrency=concurrency
        )
    finally:
        server.shutdown()
    fleet = []
    try:
        for _ in range(replicas):
            fleet.append(http_load.build_service(num_nodes, device=True))
        fleet_bodies = [
            http_load.make_bodies(fleet_names, "nodenames", count=8)
            for _, fleet_names in fleet
        ]
        for (s, _), b in zip(fleet, fleet_bodies):
            warm(s.port, b)
        out["multi"] = _drive_fleet(
            [s.port for s, _ in fleet],
            fleet_bodies,
            requests=requests,
            concurrency=concurrency,
        )
    finally:
        for s, _ in fleet:
            s.shutdown()
    single_p99 = out["single"]["p99_ms"] or 0.0
    multi_p99 = out["multi"]["p99_ms"] or 0.0
    out["p99_ratio_multi_vs_single"] = (
        round(multi_p99 / single_p99, 3) if single_p99 else None
    )
    single_rps = out["single"]["requests_per_s"] or 0.0
    out["rps_ratio_multi_vs_single"] = (
        round(out["multi"]["requests_per_s"] / single_rps, 3)
        if single_rps
        else None
    )
    return out


def failover(
    replicas: int = 3, kill_tick: int = 1, max_ticks: int = 24
) -> Dict:
    """Leader kill on the fake-clock harness: failover latency in ticks
    plus the exactly-one-actuator eviction accounting.  One shared
    implementation (``testing.ha.leader_kill``) backs this and the
    chaos bench's probed variant — they cannot drift apart."""
    from platform_aware_scheduling_tpu.testing import ha

    return ha.leader_kill(
        replicas=replicas, kill_tick=kill_tick, max_ticks=max_ticks
    )


def run(
    num_nodes: int = 256,
    requests: int = 480,
    failover_result: Optional[Dict] = None,
) -> Dict:
    """``failover_result``: an already-computed leader-kill dict (e.g.
    the chaos section's) to reuse instead of re-simulating the same
    fleet — bench.py passes it so the full bench runs the scenario
    once."""
    out = serving_scale_out(num_nodes=num_nodes, requests=requests)
    out["failover"] = (
        failover_result if failover_result is not None else failover()
    )
    return out


def main() -> None:
    from benchmarks import children

    identity = children.hold_chip("benchmarks.ha_load")
    result = run()
    result["platform"] = identity["platform"]
    fo = result["failover"]
    print(
        f"ha: c=8 over {result['replicas']} replicas rps "
        f"x{result['rps_ratio_multi_vs_single']} (p99 "
        f"x{result['p99_ratio_multi_vs_single']} vs single); failover "
        f"{fo['failover_ticks']} ticks (lease "
        f"{fo['lease_duration_ticks']}), evictions "
        f"{fo['evictions']}=={fo['evictions_baseline']} baseline, "
        f"{fo['duplicate_evictions']} duplicates",
        file=sys.stderr,
    )
    print(json.dumps(result))


if __name__ == "__main__":
    main()
