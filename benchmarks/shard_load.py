"""Sharded serving scale-out bench (docs/sharding.md "Proving it").

The partition plane's whole bet is horizontal: split the node universe
into P partitions, give each replica ONE partition to refresh and
mirror, and serve the scheduler's verbs scatter-style against partition
owners.  This bench measures both halves of that bet with real
processes and real sockets:

  * **serving scale-out**: 1 full-world replica at N nodes (the exact
    ``--shard=off`` assembly) versus P partition-owner subprocesses —
    each its own process, GIL, and device mirror, each serving Filter
    over its owned slice of the same N-node universe.  Aggregate owner
    rps must beat the full-world replica by ``RPS_RATIO_FLOOR`` (the
    ISSUE bar is 2.5x).  Both sides are driven in the ALWAYS-SOLVE
    regime (rotated candidate spans, the http_load miss-tier
    methodology): the response-reuse caches are orthogonal to sharding
    — both modes have them — so the quantity under test is the
    scheduling work itself, which is what scatter makes 1/P-sized.
    The ratio holds even on a single-core runner, where timesharing
    caps aggregate rps at one owner's solo rate: a 1/P-size request
    costs < 1/RPS_RATIO_FLOOR of a full-world one (the native filter
    path is ~linear in candidates past the HTTP floor), so throughput
    per core multiplies with or without core-level parallelism;
  * **refresh cut**: every owner's ``pas_shard_refresh_nodes_total``
    counters are scraped off its live ``/metrics`` after a fixed number
    of telemetry passes — the measured per-replica ingest volume must
    land at ~1/P of the world (the ``owned`` fraction within
    ``REFRESH_BAND`` of 1/P; consistent hashing is uniform, not exact).

Topology note: each owner subprocess runs the plane in
``static_owners`` mode (shard/partition.py) — a fixed partition map, no
ownership journal — because the bench processes share no API server.
Journaled ownership, handoff, and fencing are proved by the HA harness
and the twin's ``partition_handoff`` scenario (tests/test_ha.py,
testing/twin.py); THIS bench isolates the steady-state scale-out claim.

Feeds the ``shard`` section of bench.py's line and the BENCH_DETAIL
artifact; ``make bench-shard`` runs it alone and exits nonzero when
either half of the bet fails.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from typing import Dict, List, Optional

NUM_NODES = 40_000
PARTITIONS = 4
#: telemetry passes each subprocess runs before READY — the refresh-cut
#: denominator (counters scraped afterward divide by this)
REFRESH_PASSES = 8
REQUESTS = 200
CONCURRENCY = 4
WARM_REQUESTS = 32
#: distinct rotated-span bodies per target (each request a span-cache
#: miss, same as http_load's miss tier)
BODY_ROTATION = 64
#: the ISSUE acceptance bar: aggregate sharded Filter rps vs full-world
RPS_RATIO_FLOOR = 2.5
#: measured owned-fraction band around the ideal 1/P (consistent
#: hashing is uniform in expectation, not exact per partition)
REFRESH_BAND = (0.5, 2.0)


def build_shard_service(
    num_nodes: int, partitions: int, index: Optional[int]
):
    """(server, names) — a live unsafe-HTTP extender whose cache has run
    ``REFRESH_PASSES`` telemetry passes against an in-memory metrics
    API.  ``index=None`` is the full-world baseline (no shard plane —
    the exact ``--shard=off`` assembly); ``index=i`` owns partition i of
    ``partitions`` via a static owner map, so the refresh passes pay the
    ~1/P ingest cut and the mirror interns only owned nodes."""
    from benchmarks.http_load import _policy_obj, node_names
    from platform_aware_scheduling_tpu.extender.server import Server
    from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
    from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
    from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
    from platform_aware_scheduling_tpu.tas.telemetryscheduler import (
        MetricsExtender,
    )
    from platform_aware_scheduling_tpu.testing.faults import FakeMetricsClient

    names = node_names(num_nodes)
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    cache.write_policy(
        "default", "load-pol", TASPolicy.from_obj(_policy_obj())
    )
    cache.write_metric("load_metric")  # register; passes fill the values
    client = FakeMetricsClient()
    client.set_all(
        "load_metric",
        {n: (i * 37) % 1_000_000 for i, n in enumerate(names)},
    )
    ext = MetricsExtender(cache, mirror=mirror, node_cache_capable=True)
    if index is not None:
        from platform_aware_scheduling_tpu.shard import ShardPlane

        # static owner map: partition p belongs to owner-p, fixed for
        # the process lifetime — no journal, no kube I/O (the bench
        # fleet shares no API server; see module docstring)
        plane = ShardPlane(
            f"owner-{index}",
            partitions,
            kube_client=None,
            static_owners={p: f"owner-{p}" for p in range(partitions)},
        )
        plane.attach(cache, mirror)
        ext.shard = plane
    for _ in range(REFRESH_PASSES):
        cache.update_all_metrics(client)
    server = Server(ext, metrics_provider=ext.metrics_text)
    server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
    server.wait_ready()
    return server, names


def _serve_main(role: str, num_nodes: int, partitions: int, index: int):
    """Subprocess entry: start the service, print ``READY <port>``,
    block (the http_load protocol — each owner gets its own process and
    GIL so aggregate rps measures real parallelism, not thread
    interleaving)."""
    from benchmarks import children
    from platform_aware_scheduling_tpu.utils import devicewatch
    from platform_aware_scheduling_tpu.utils.gctuning import tune_for_serving

    identity = children.hold_chip(f"shard service {role}/{index}")
    devicewatch.install_cost_hooks()
    server, _ = build_shard_service(
        num_nodes, partitions, None if role == "full" else index
    )
    tune_for_serving()
    print(f"READY {server.port} {identity['platform']}", flush=True)
    threading.Event().wait()


def _spawn(role: str, num_nodes: int, partitions: int, index: int, chip: int):
    """(process, port) for one isolated service subprocess pinned to chip
    ``chip`` of this host (libtpu's one-process-per-chip environment) —
    a service that cannot get its chip exits instead of serving from the
    CPU."""
    env = dict(os.environ)
    env["TPU_VISIBLE_CHIPS"] = str(chip)
    env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
    env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "benchmarks.shard_load",
            "--serve",
            role,
            str(num_nodes),
            str(partitions),
            str(index),
        ],
        stdout=subprocess.PIPE,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    line = proc.stdout.readline().strip()
    if not line.startswith("READY "):
        proc.terminate()
        raise RuntimeError(f"shard service failed to start: {line!r}")
    return proc, int(line.split()[1])


def _scrape_refresh(port: int) -> Dict[str, float]:
    """{owned, skipped} node counts from a live owner's
    ``pas_shard_refresh_nodes_total`` (the ingest-cut counters the
    plane's refresh_filter maintains — shard/plane.py)."""
    from benchmarks.http_load import http_get
    from platform_aware_scheduling_tpu.utils import trace

    status, payload = http_get(port, "/metrics")
    if status != 200:
        raise RuntimeError(f"/metrics scrape failed: status {status}")
    families = trace.parse_prometheus_text(payload.decode())
    family = families.get("pas_shard_refresh_nodes_total")
    out = {"owned": 0.0, "skipped": 0.0}
    for _name, labels, value in (family or {}).get("samples", ()):
        scope = labels.get("scope")
        if scope in out:
            out[scope] += value
    return out


def run(
    num_nodes: int = NUM_NODES,
    partitions: int = PARTITIONS,
    requests: int = REQUESTS,
    concurrency: int = CONCURRENCY,
) -> Dict:
    """The multi-process shard tier: 1 full-world replica vs
    ``partitions`` partition-owner subprocesses at ``num_nodes``.  All
    ``partitions + 1`` services compute on a device at the same moment,
    so the host needs that many chips; on fewer this reports ``not_run``
    — it never puts an owner on the CPU."""
    from benchmarks import children
    from benchmarks.http_load import _PATHS, drive, make_bodies, node_names
    from platform_aware_scheduling_tpu.shard.partition import PartitionMap

    identity = children.probe_devices()
    needed = partitions + 1
    if identity["platform"] != "tpu" or identity["count"] < needed:
        return {
            "bench": "shard_load",
            "num_nodes": num_nodes,
            "partitions": partitions,
            "platform": identity["platform"],
            "not_run": (
                f"needs {needed} device processes at once, one chip each; "
                f"this host has {identity['count']} x "
                f"{identity['platform']} ({identity['kind']})"
            ),
        }

    names = node_names(num_nodes)
    # the parent computes each owner's slice with the same pure math the
    # owners use — consistent hashing is process-independent, which is
    # exactly what lets a scatter front route without asking anyone
    slices = PartitionMap(partitions).group(names)
    path = _PATHS["filter"]
    procs: List[subprocess.Popen] = []
    try:
        base_proc, base_port = _spawn(
            "full", num_nodes, partitions, -1, chip=partitions
        )
        procs.append(base_proc)
        owners = []
        for p in range(partitions):
            proc, port = _spawn("owner", num_nodes, partitions, p, chip=p)
            procs.append(proc)
            owners.append((p, port))

        # always-solve regime on BOTH sides: every body a distinct span
        # rotation, so neither side serves response-cache hits (see
        # module docstring)
        full_bodies = make_bodies(
            names, "nodenames", rotate_span=True, count=BODY_ROTATION
        )
        owner_bodies = {
            p: make_bodies(
                slices.get(p, names[:1]), "nodenames",
                rotate_span=True, count=BODY_ROTATION,
            )
            for p, _port in owners
        }
        # warm both sides (first-request compile/intern tails are not
        # steady-state serving)
        drive(base_port, full_bodies, WARM_REQUESTS, concurrency=2, path=path)
        for p, port in owners:
            drive(port, owner_bodies[p], WARM_REQUESTS, concurrency=2,
                  path=path)

        baseline = drive(
            base_port, full_bodies, requests, concurrency=concurrency,
            path=path,
        )
        # all owners driven CONCURRENTLY — aggregate rps is the fleet's
        # real parallel throughput, same wall clock for every owner;
        # client pressure matches the baseline drive (concurrency split
        # across the fleet)
        per_owner_conc = max(1, concurrency // len(owners))
        owner_results: List[Optional[Dict]] = [None] * len(owners)
        errors: List[str] = []

        def _drive_owner(i: int, port: int, bodies):
            try:
                owner_results[i] = drive(
                    port, bodies, requests, concurrency=per_owner_conc,
                    path=path,
                )
            except Exception as exc:
                errors.append(f"owner {i}: {exc!r}")

        threads = [
            threading.Thread(
                target=_drive_owner, args=(i, port, owner_bodies[p])
            )
            for i, (p, port) in enumerate(owners)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError(f"owner drive failed: {errors}")

        per_owner = []
        fractions = []
        for (p, port), res in zip(owners, owner_results):
            refresh = _scrape_refresh(port)
            total = refresh["owned"] + refresh["skipped"]
            fraction = refresh["owned"] / total if total else 0.0
            fractions.append(fraction)
            per_owner.append(
                {
                    "partition": p,
                    "nodes": len(slices.get(p, ())),
                    "requests_per_s": res["requests_per_s"],
                    "p99_ms": res["p99_ms"],
                    "refresh_nodes_per_pass": round(
                        refresh["owned"] / REFRESH_PASSES, 1
                    ),
                    "refresh_fraction_of_world": round(fraction, 4),
                }
            )
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()

    aggregate_rps = round(
        sum(o["requests_per_s"] for o in per_owner), 1
    )
    rps_ratio = round(aggregate_rps / baseline["requests_per_s"], 2)
    ideal = 1.0 / partitions
    refresh_ok = all(
        REFRESH_BAND[0] * ideal <= f <= REFRESH_BAND[1] * ideal
        for f in fractions
    )
    checks = [
        {
            "name": "aggregate_rps_floor",
            "ok": rps_ratio >= RPS_RATIO_FLOOR,
            "detail": f"x{rps_ratio} vs floor x{RPS_RATIO_FLOOR}",
        },
        {
            "name": "refresh_volume_one_over_p",
            "ok": refresh_ok,
            "detail": (
                f"owned fractions {[round(f, 3) for f in fractions]} "
                f"vs ideal {round(ideal, 3)}"
            ),
        },
    ]
    return {
        "bench": "shard_load",
        "num_nodes": num_nodes,
        "partitions": partitions,
        "platform": identity["platform"],
        "refresh_passes": REFRESH_PASSES,
        "baseline": {
            **baseline,
            # full-world by construction: no plane, every pass ingests
            # the whole universe
            "refresh_nodes_per_pass": num_nodes,
        },
        "owners": per_owner,
        "aggregate_requests_per_s": aggregate_rps,
        "rps_ratio_sharded_vs_full": rps_ratio,
        "max_owner_p99_ms": max(o["p99_ms"] for o in per_owner),
        "refresh_fraction_mean": round(
            sum(fractions) / len(fractions), 4
        ),
        "refresh_fraction_ideal": round(ideal, 4),
        "checks": checks,
        "passed": all(c["ok"] for c in checks),
    }


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--serve":
        _serve_main(argv[1], int(argv[2]), int(argv[3]), int(argv[4]))
        return 0
    num_nodes = int(argv[argv.index("--nodes") + 1]) if "--nodes" in argv \
        else NUM_NODES
    partitions = int(argv[argv.index("--partitions") + 1]) \
        if "--partitions" in argv else PARTITIONS
    out = run(num_nodes=num_nodes, partitions=partitions)
    if "not_run" in out:
        print(f"shard: NOT RUN — {out['not_run']}", file=sys.stderr)
        print(json.dumps(out, sort_keys=True))
        return 1
    print(
        f"shard: {out['partitions']} owners @ {out['num_nodes']} nodes — "
        f"aggregate filter {out['aggregate_requests_per_s']} rps vs "
        f"full-world {out['baseline']['requests_per_s']} rps "
        f"(x{out['rps_ratio_sharded_vs_full']}, floor x{RPS_RATIO_FLOOR}); "
        f"per-replica refresh {out['refresh_fraction_mean']:.1%} of world "
        f"(ideal {out['refresh_fraction_ideal']:.1%})",
        file=sys.stderr,
    )
    print(json.dumps(out, sort_keys=True))
    return 0 if out["passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
