"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process holds the TPU, serves in threads, and drives its own sockets:

  device       resolve the backend once; anything but ``tpu`` is a
               non-zero exit naming what was found (no CPU fallback)
  tas          the body of ``cmd/tas.py:main`` without a kubeconfig —
               ``assemble()`` + ``build_server()`` over a FakeKubeClient,
               ``--syncPeriod 2s``, 10,000 nodes x 4 metrics x 3 policies —
               then Filter/Prioritize over HTTP in both wire modes, every
               body compared byte for byte with a second, host-only
               assembly (``enable_device_path=False``, the repo's plain
               reference) over the same cluster
  refresh      >= 3 full-cluster metric rewrites; each must travel
               refresh -> publish -> warm on the device before the verbs
               are re-driven and re-compared
  batch_solve  ``scheduling_step`` at 1,000 pods x 10,000 nodes: the
               compiled Pallas assigner must run and equal the XLA scan
               element for element
  gas          GASExtender at 2,000 nodes x 8 cards through
               ``build_server()``: Filter, Bind, Filter against
               ``use_device=False`` on an identical cluster
  counters     scraped from /metrics: device-path counters > 0, every
               fallback / caught-device-failure counter and post-warm
               retraces 0, device memory exported, ``_wirec`` native
  mesh         with >= 4 devices: ``__graft_entry__.multichip_on_chips``

All data comes from ``--seed``.  Any phase's failure is a non-zero exit and
no result line.  A pass ends with two stdout lines: ``[summary]`` followed
by one JSON object (phases, assigner, seed, observations), then — last, and
with exactly these keys, because the driver's check parses it —
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

``--rehearse-cpu`` is for debugging the script itself in a sandbox without a
chip: the same flow at a tiny size on the CPU backend, Pallas in interpret
mode.  It says ``platform: cpu``, reports ``"ok": false`` and exits 4 — it
can never print a chip pass.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import statistics
import sys
import time

FULL = {"nodes": 10_000, "window": 500, "pods": 1_000, "gas_nodes": 2_000}
REHEARSAL = {"nodes": 640, "window": 64, "pods": 64, "gas_nodes": 96}
SYNC_PERIOD_S = 2.0  # upstream's shipped --syncPeriod (tas-deployment.yaml)
REFRESH_ROUNDS = 3
READY_LIMIT_S = 600.0
REFRESH_LIMIT_S = 120.0
GAS_CARDS = 8
METRICS = ("smoke_load", "smoke_mem", "smoke_net", "smoke_temp")
VALUE_STEP = 97  # metric values are a seeded permutation x this step
EXIT_REHEARSAL = 4


class SmokeFailure(Exception):
    """A phase did not hold; the run exits non-zero with no result line."""


def say(message: str) -> None:
    print(message, flush=True)


def check(ok, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


# -- wire helpers -------------------------------------------------------------


def request(port: int, method: str, path: str, body: bytes = None):
    """(status, body) of one HTTP exchange with a local server."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request(
            method, path, body=body,
            headers={"Content-Type": "application/json"} if body else {},
        )
        response = conn.getresponse()
        return response.status, response.read()
    finally:
        conn.close()


def post(port: int, path: str, body: bytes):
    return request(port, "POST", path, body)


def get(port: int, path: str):
    return request(port, "GET", path)


def scrape(port: int) -> dict:
    """{family: {label string: value}} from the live /metrics page."""
    from platform_aware_scheduling_tpu.utils import trace

    status, payload = get(port, "/metrics")
    check(status == 200, f"/metrics answered {status}")
    out = {}
    for family, data in trace.parse_prometheus_text(payload.decode()).items():
        out[family] = {
            ",".join(f"{k}={v}" for k, v in sorted(labels.items())): value
            for _name, labels, value in data["samples"]
        }
    return out


def total(families: dict, family: str) -> float:
    return sum(families.get(family, {}).values())


def serve(server):
    """Start a built front-end on an ephemeral loopback port."""
    server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
    check(server.wait_ready(), "server did not start listening")
    return server


# -- TAS: cluster, traffic, comparison -----------------------------------------


def metric_values(seed: int, round_index: int, num_nodes: int):
    """{metric: int array}: every metric a fresh seeded permutation per
    round, so a refresh moves ~every column and values never tie (tie
    order is the one place the device ranking and the host sort are
    allowed to differ — telemetryscheduler.py module doc)."""
    import numpy as np

    out = {}
    for index, metric in enumerate(METRICS):
        rng = np.random.default_rng([seed, round_index, index])
        out[metric] = rng.permutation(num_nodes) * VALUE_STEP + round_index
    return out


def publish_metrics(kube, names, values) -> None:
    for metric, column in values.items():
        kube.replace_node_metric(
            metric,
            {name: str(int(v)) for name, v in zip(names, column)},
            timestamp="2026-01-01T00:00:00Z",
        )


def tas_policies(num_nodes: int):
    from platform_aware_scheduling_tpu.testing.builders import make_policy, rule

    top = (num_nodes - 1) * VALUE_STEP
    load, mem, net, temp = METRICS
    return [
        make_policy("smoke-greater", strategies={
            "scheduleonmetric": [rule(load, "GreaterThan", 0)],
            "dontschedule": [rule(mem, "GreaterThan", int(top * 0.90))],
        }),
        make_policy("smoke-less", strategies={
            "scheduleonmetric": [rule(net, "LessThan", 0)],
            "dontschedule": [rule(temp, "LessThan", int(top * 0.05))],
            "deschedule": [rule(mem, "GreaterThan", int(top * 0.99))],
        }),
        make_policy("smoke-multi", strategies={
            "scheduleonmetric": [rule(mem, "GreaterThan", 0)],
            "dontschedule": [
                rule(load, "GreaterThan", int(top * 0.95)),
                rule(net, "LessThan", int(top * 0.02)),
            ],
            "deschedule": [rule(temp, "GreaterThan", int(top * 0.995))],
        }),
    ]


def tas_requests(names, window: int, round_index: int):
    """[(label, path, candidate count, body)]: per policy and verb, both
    wire modes, two pods over the full candidate list and three over a
    rotating ``window``-name slice (kube-scheduler's
    percentageOfNodesToScore hands an extender a different window per
    pod)."""
    requests = []
    n = len(names)
    for policy in ("smoke-greater", "smoke-less", "smoke-multi"):
        for verb in ("prioritize", "filter"):
            for mode in ("NodeNames", "Nodes"):
                for pod_index in range(5):
                    if pod_index < 2:
                        candidates = names
                        shape = "full"
                    else:
                        start = (
                            (round_index * 5 + pod_index) * 1777
                        ) % n
                        candidates = (names[start:] + names[:start])[:window]
                        shape = f"window@{start}"
                    pod = {
                        "metadata": {
                            "name": f"smoke-{policy}-r{round_index}-{pod_index}",
                            "namespace": "default",
                            "labels": {"telemetry-policy": policy},
                        }
                    }
                    if mode == "NodeNames":
                        args = {"Pod": pod, "NodeNames": candidates}
                    else:
                        args = {"Pod": pod, "Nodes": {"items": [
                            {"metadata": {"name": name}} for name in candidates
                        ]}}
                    requests.append((
                        f"{verb}/{policy}/{mode}/{shape}",
                        f"/scheduler/{verb}",
                        len(candidates),
                        json.dumps(args).encode(),
                    ))
    return requests


def drive_and_compare(device_port, reference_port, requests) -> dict:
    """Send every request to both extenders; bodies must be identical and
    must be real answers (every candidate accounted for)."""
    filtered_out = 0
    for label, path, count, body in requests:
        status, got = post(device_port, path, body)
        ref_status, want = post(reference_port, path, body)
        check(status == 200, f"{label}: device extender answered {status}")
        check(
            (status, got) == (ref_status, want),
            f"{label}: device body differs from the host-only extender "
            f"({len(got)} vs {len(want)} bytes)",
        )
        answer = json.loads(got)
        if path.endswith("/prioritize"):
            check(
                len(answer) == count,
                f"{label}: {len(answer)} priorities for {count} candidates",
            )
        else:
            passed = (
                answer["Nodes"]["items"] if answer.get("Nodes")
                else [n for n in answer.get("NodeNames") or [] if n]
            )
            failed = answer.get("FailedNodes") or {}
            check(
                len(passed) + len(failed) == count,
                f"{label}: {len(passed)} passed + {len(failed)} failed "
                f"!= {count} candidates",
            )
            filtered_out += len(failed)
    check(filtered_out > 0, "no Filter request failed a single node")
    return {"requests": len(requests), "filtered_out": filtered_out}


class PassLog:
    """Records, at the END of each telemetry refresh pass (in the refresh
    thread, after that pass's writes, publishes and warm passes ran), which
    metric values the cache holds for one probe node."""

    def __init__(self, cache, probe: str):
        self.cache = cache
        self.probe = probe
        self.stamps = []  # (monotonic time, {metric: milli or None})
        cache.on_refresh_pass.append(self._record)

    def _record(self) -> None:
        stamp = {}
        for metric in METRICS:
            try:
                stamp[metric] = (
                    self.cache.read_metric(metric)[self.probe]
                    .value.milli_value_exact()[0]
                )
            except KeyError:
                stamp[metric] = None
        self.stamps.append((time.monotonic(), stamp))

    def wait_for(self, values, probe_index: int, limit_s: float) -> float:
        """Block until a completed pass left exactly ``values`` in the
        cache; returns when (monotonic) that pass ended."""
        want = {m: int(values[m][probe_index]) * 1000 for m in METRICS}
        deadline = time.monotonic() + limit_s
        while time.monotonic() < deadline:
            if self.stamps and self.stamps[-1][1] == want:
                return self.stamps[-1][0]
            time.sleep(0.05)
        raise SmokeFailure(
            f"no refresh pass delivered the published metrics within "
            f"{limit_s:.0f}s (last pass saw "
            f"{self.stamps[-1][1] if self.stamps else 'nothing'})"
        )


def wait_policies(cache, limit_s: float = 60.0) -> None:
    """The policy informer delivers asynchronously: block until the cache
    holds all three TASPolicies."""
    deadline = time.monotonic() + limit_s
    missing = []
    while time.monotonic() < deadline:
        missing = []
        for name in ("smoke-greater", "smoke-less", "smoke-multi"):
            try:
                cache.read_policy("default", name)
            except KeyError:
                missing.append(name)
        if not missing:
            return
        time.sleep(0.05)
    raise SmokeFailure(f"policies never reached the cache: {missing}")


def phase_tas(sizes, seed, observations):
    """Returns the live pieces the refresh and counters phases reuse."""
    from platform_aware_scheduling_tpu.cmd import common
    from platform_aware_scheduling_tpu.cmd.tas import assemble, build_server
    from platform_aware_scheduling_tpu.tas.metrics import CustomMetricsClient
    from platform_aware_scheduling_tpu.testing.builders import make_node
    from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
    from platform_aware_scheduling_tpu.utils import health
    from platform_aware_scheduling_tpu.utils.gctuning import tune_for_serving

    num_nodes = sizes["nodes"]
    names = [f"node-{i:05d}" for i in range(num_nodes)]
    kube = FakeKubeClient()
    for name in names:
        kube.add_node(make_node(name))
    for policy in tas_policies(num_nodes):
        kube.create_taspolicy(policy)
    values = metric_values(seed, 0, num_nodes)
    publish_metrics(kube, names, values)
    say(
        f"  cluster: {num_nodes} nodes x {len(METRICS)} metrics, 3 "
        f"TASPolicies, syncPeriod {SYNC_PERIOD_S:g}s, nodeCacheCapable"
    )

    started = time.monotonic()
    # cmd/tas.py main(), minus the kubeconfig: device runtime (compile
    # cache, identity, cost capture) -> assemble -> device watch -> server
    common.prepare_device_runtime()
    cache, mirror, extender, controller, _enforcer, stop = assemble(
        kube,
        CustomMetricsClient(kube),
        SYNC_PERIOD_S,
        node_cache_capable=True,  # both wire modes: Nodes AND NodeNames
    )
    common.start_device_watch(stop=stop, sample_period_s=2.0)
    tune_for_serving()
    server = build_server(extender)
    if controller.informer is not None:
        server.probe.register(
            "policy_informer_synced",
            health.informer_synced(controller.informer, "taspolicy"),
        )
    device_log = PassLog(cache, names[-1])
    serve(server)

    ready = None
    deadline = started + READY_LIMIT_S
    while time.monotonic() < deadline:
        status, payload = get(server.port, "/readyz")
        if status == 200:
            ready = json.loads(payload)
            break
        time.sleep(0.1)
    check(
        ready is not None,
        f"/readyz not 200 within {READY_LIMIT_S:.0f}s: {payload[:400]!r}",
    )
    time_to_ready = time.monotonic() - started
    for condition in ready.get("conditions", []):
        check(
            "host-only" not in str(condition.get("reason", "")),
            f"/readyz is ready in host-only mode: {condition}",
        )
    observations["time_to_ready_s"] = round(time_to_ready, 3)
    say(f"  /readyz 200 after {time_to_ready:.2f}s (limit {READY_LIMIT_S:.0f}s)")
    at_ready = scrape(server.port)

    # the plain reference: the same assembly with no device path at all
    _rc, _rm, reference, _rctl, _renf, ref_stop = assemble(
        kube,
        CustomMetricsClient(kube),
        SYNC_PERIOD_S,
        enable_device_path=False,
        node_cache_capable=True,
    )
    check(reference.fastpath is None, "reference extender has a device path")
    reference_log = PassLog(reference.cache, names[-1])
    reference_server = serve(build_server(reference))

    tas = {
        "kube": kube, "names": names, "mirror": mirror, "extender": extender,
        "server": server, "reference_server": reference_server,
        "device_log": device_log, "reference_log": reference_log,
        "stops": [stop, ref_stop], "at_ready": at_ready,
    }
    device_log.wait_for(values, num_nodes - 1, REFRESH_LIMIT_S)
    reference_log.wait_for(values, num_nodes - 1, REFRESH_LIMIT_S)
    for side in (cache, reference.cache):
        wait_policies(side)
    view = mirror.device_view()
    check(
        tuple(view.values.hi.shape) == tuple(view.present.shape)
        and view.node_capacity >= num_nodes,
        f"mirror view {view.values.hi.shape} does not hold {num_nodes} nodes",
    )
    say(
        f"  mirror bucket {list(view.values.hi.shape)} at state version "
        f"{view.version}"
    )
    result = drive_and_compare(
        server.port, reference_server.port,
        tas_requests(names, sizes["window"], 0),
    )
    say(
        f"  round 0: {result['requests']} requests byte-identical to the "
        f"host-only extender ({result['filtered_out']} nodes filtered out)"
    )
    return tas


def phase_refresh(tas, sizes, seed, observations):
    num_nodes = sizes["nodes"]
    names = tas["names"]
    waits = []
    for round_index in range(1, REFRESH_ROUNDS + 1):
        version_before = tas["mirror"].version
        values = metric_values(seed, round_index, num_nodes)
        published = time.monotonic()
        publish_metrics(tas["kube"], names, values)
        warmed = tas["device_log"].wait_for(
            values, num_nodes - 1, REFRESH_LIMIT_S
        )
        tas["reference_log"].wait_for(values, num_nodes - 1, REFRESH_LIMIT_S)
        version_after = tas["mirror"].version
        check(
            version_after > version_before,
            f"round {round_index}: mirror state version stayed at "
            f"{version_before} after a full-cluster metric rewrite",
        )
        status, payload = get(tas["server"].port, "/readyz")
        check(
            status == 200,
            f"round {round_index}: /readyz {status} after refresh: "
            f"{payload[:300]!r}",
        )
        result = drive_and_compare(
            tas["server"].port, tas["reference_server"].port,
            tas_requests(names, sizes["window"], round_index),
        )
        waits.append(warmed - published)
        say(
            f"  round {round_index}: state version {version_before} -> "
            f"{version_after}, published -> refreshed+warmed in "
            f"{warmed - published:.2f}s (incl. waiting for the "
            f"{SYNC_PERIOD_S:g}s tick); {result['requests']} requests "
            f"byte-identical ({result['filtered_out']} nodes filtered out)"
        )
    observations["publish_to_warm_s"] = [round(w, 3) for w in waits]


# -- batch solve -----------------------------------------------------------------


def phase_batch_solve(sizes, seed, identity, observations):
    import jax
    import numpy as np

    from platform_aware_scheduling_tpu.models.batch_scheduler import (
        ASSIGNER_PALLAS,
        _scheduling_step,
        choose_assigner,
        example_inputs,
        scheduling_step,
    )
    from platform_aware_scheduling_tpu.ops.assign import greedy_assign_kernel
    from platform_aware_scheduling_tpu.ops.pallas_assign import (
        greedy_assign_pallas,
    )

    num_pods, num_nodes = sizes["pods"], sizes["nodes"]
    state, pods = example_inputs(
        num_metrics=4, num_nodes=num_nodes, num_pods=num_pods, seed=seed
    )
    assigner = choose_assigner(state, pods)
    say(f"  {num_pods} pods x {num_nodes} nodes; assigner chosen: {assigner}")
    on_tpu = identity["platform"] == "tpu"
    if on_tpu:
        check(
            assigner == ASSIGNER_PALLAS,
            f"unsharded operands on a TPU chose {assigner!r}, not the "
            f"Pallas assigner",
        )
        lowered = _scheduling_step.lower(state, pods, assigner=assigner)
        check(
            "tpu_custom_call" in lowered.as_text(),
            "the Pallas assigner did not lower to a compiled Mosaic "
            "kernel (no tpu_custom_call in the program)",
        )
    out = scheduling_step(state, pods)
    got_nodes = np.asarray(out.assignment.node_for_pod)
    got_capacity = np.asarray(out.assignment.capacity_left)
    scan = greedy_assign_kernel(out.score, out.eligible, state.capacity)
    if not on_tpu:
        # rehearsal: scheduling_step ran the scan; cover the Pallas kernel
        # through the interpreter so the comparison below still means
        # "Pallas == scan"
        interpreted = greedy_assign_pallas(
            out.score, out.eligible, state.capacity, interpret=True
        )
        got_nodes = np.asarray(interpreted.node_for_pod)
        got_capacity = np.asarray(interpreted.capacity_left)
    check(
        np.array_equal(got_nodes, np.asarray(scan.node_for_pod)),
        "node_for_pod differs from the XLA scan",
    )
    check(
        np.array_equal(got_capacity, np.asarray(scan.capacity_left)),
        "capacity_left differs from the XLA scan",
    )
    capacity = np.asarray(state.capacity)
    eligible = np.asarray(out.eligible)
    assigned = got_nodes >= 0
    booked = np.bincount(got_nodes[assigned], minlength=num_nodes)
    check((booked <= capacity).all(), "a node was booked past its capacity")
    check(
        np.array_equal(capacity - booked, got_capacity),
        "capacity_left is not capacity minus bookings",
    )
    check(
        eligible[np.nonzero(assigned)[0], got_nodes[assigned]].all(),
        "a pod was assigned to a node it is not eligible for",
    )
    check(assigned.any(), "no pod was assigned at all")
    say(
        f"  {int(assigned.sum())}/{num_pods} pods assigned; equals the XLA "
        f"scan exactly; capacity respected; every assignment eligible"
    )

    walls = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(scheduling_step(state, pods).assignment.node_for_pod)
        walls.append(time.perf_counter() - t0)
    observations["scheduling_step_dispatch_readback_ms"] = {
        "median": round(statistics.median(walls) * 1e3, 3),
        "min": round(min(walls) * 1e3, 3),
        "max": round(max(walls) * 1e3, 3),
        "n": len(walls),
        "assigner": assigner,
    }
    jax.block_until_ready(out)
    return assigner


def observe_prioritize_round_trip(tas, observations):
    """Wall of one dispatched-and-read-back prioritize_kernel at the
    mirror's serving bucket — the local chip's answer to the 73-105 ms per
    dispatched solve the design was built around.  An observation."""
    import jax.numpy as jnp
    import numpy as np

    from platform_aware_scheduling_tpu.ops.rules import OP_GREATER_THAN
    from platform_aware_scheduling_tpu.ops.scoring import prioritize_kernel

    view = tas["mirror"].device_view()
    # the exact argument shapes/dtypes fastpath._ranking dispatches, so
    # this reuses the warmed executable instead of minting a retrace
    row, op = jnp.int32(0), jnp.int32(OP_GREATER_THAN)
    mask = jnp.ones(view.node_capacity, dtype=bool)
    walls = []
    for _ in range(20):
        t0 = time.perf_counter()
        result = prioritize_kernel(view.values, view.present, row, op, mask)
        int(result.valid_count)
        np.asarray(result.perm)
        walls.append(time.perf_counter() - t0)
    observations["prioritize_kernel_dispatch_readback_ms"] = {
        "shape": list(view.values.hi.shape),
        "median": round(statistics.median(walls) * 1e3, 3),
        "min": round(min(walls) * 1e3, 3),
        "max": round(max(walls) * 1e3, 3),
        "n": len(walls),
    }


# -- GAS -------------------------------------------------------------------------


def build_gas(num_nodes: int, seed: int, use_device: bool):
    """(server, extender, {node: free cards}): a GAS extender over a fake
    cluster shaped like benchmarks/gas_load.py — every node carries the
    cards label + gpu.intel.com allocatable, ~30% of nodes hold pre-booked
    annotated pods (here 1-8 fully booked cards each, so fits differ by
    node) ingested through the informer replay."""
    import numpy as np

    from platform_aware_scheduling_tpu.cmd.tas import build_server
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

    rng = np.random.default_rng([seed, 7])
    kube = FakeKubeClient()
    names = [f"gpu-node-{i:05d}" for i in range(num_nodes)]
    cards_label = ".".join(f"card{i}" for i in range(GAS_CARDS))
    free = {}
    for i, name in enumerate(names):
        kube.add_node(make_node(
            name,
            labels={"gpu.intel.com/cards": cards_label},
            allocatable={
                "gpu.intel.com/i915": str(GAS_CARDS),
                "gpu.intel.com/millicores": str(1000 * GAS_CARDS),
                "gpu.intel.com/memory.max": str(8000 * GAS_CARDS),
            },
        ))
        booked = ()
        if rng.random() < 0.3:
            booked = rng.permutation(GAS_CARDS)[: int(rng.integers(1, 9))]
        if i == 0:
            booked = range(GAS_CARDS - 2)  # the Bind target: 2 cards free
        free[name] = GAS_CARDS - len(booked)
        for card in booked:
            kube.add_pod(make_pod(
                f"booked-{i}-{int(card)}",
                container_requests=[{
                    "gpu.intel.com/i915": "1",
                    "gpu.intel.com/millicores": "1000",
                }],
                node_name=name,
                annotations={
                    CARD_ANNOTATION: f"card{int(card)}", TS_ANNOTATION: "1",
                },
                phase="Running",
            ))
    pending = [
        make_pod(f"smoke-gas-{k}", container_requests=[
            {"gpu.intel.com/i915": "1", "gpu.intel.com/millicores": "600"},
            {"gpu.intel.com/i915": "1", "gpu.intel.com/millicores": "600",
             "gpu.intel.com/memory.max": "2000"},
        ])
        for k in range(2)
    ]
    for pod in pending:
        kube.add_pod(pod)
    extender = GASExtender(kube, use_device=use_device)
    check(extender.cache.wait_settled(60.0), "GAS cache did not settle")
    server = serve(build_server(extender))
    return server, extender, names, free, pending


def gas_flow(server, extender, names, pending, target: str):
    """Filter, Bind the first pod onto ``target``, Filter again; returns
    the three (status, body) answers."""
    def filter_body(pod):
        return json.dumps({"Pod": pod.raw, "NodeNames": names}).encode()

    first = post(server.port, "/scheduler/filter", filter_body(pending[0]))
    bind = post(server.port, "/scheduler/bind", json.dumps({
        "PodName": pending[0].name,
        "PodNamespace": pending[0].namespace,
        "PodUID": pending[0].uid,
        "Node": target,
    }).encode())
    check(extender.cache.wait_settled(60.0), "GAS cache did not settle")
    second = post(server.port, "/scheduler/filter", filter_body(pending[1]))
    return first, bind, second


def phase_gas(sizes, seed, tas_port):
    num_nodes = sizes["gas_nodes"]
    server, extender, names, free, pending = build_gas(num_nodes, seed, True)
    target = names[0]
    say(
        f"  cluster: {num_nodes} nodes x {GAS_CARDS} cards, "
        f"{sum(1 for f in free.values() if f < GAS_CARDS)} nodes pre-booked"
    )
    status, payload = get(server.port, "/readyz")
    check(status == 200, f"GAS /readyz {status}: {payload[:300]!r}")
    before = scrape(tas_port)
    device_answers = gas_flow(server, extender, names, pending, target)
    after = scrape(tas_port)
    # nothing host-side has run yet in this process: every gas Filter so
    # far must have been the device binpack
    check(
        total(after, "pas_gas_filter_device_total")
        - total(before, "pas_gas_filter_device_total") == 2,
        "the device GAS extender did not serve both Filters on the device",
    )
    check(
        total(after, "pas_gas_filter_host_total") == 0,
        "the device GAS extender fell back to the host loop",
    )

    ref_server, ref_extender, ref_names, _free, ref_pending = build_gas(
        num_nodes, seed, False
    )
    reference_answers = gas_flow(
        ref_server, ref_extender, ref_names, ref_pending, target
    )
    for step, got, want in zip(
        ("filter", "bind", "filter after bind"),
        device_answers, reference_answers,
    ):
        check(got[0] == 200, f"GAS {step}: device extender answered {got[0]}")
        check(
            got == want,
            f"GAS {step}: device answer differs from use_device=False",
        )
    first = json.loads(device_answers[0][1])
    second = json.loads(device_answers[2][1])
    # two 600-millicore shares need two cards with room: a node passes iff
    # it has >= 2 cards free; the bound pod takes the target's last two
    expected_pass = [n for n in names if free[n] >= 2]
    check(
        first["NodeNames"] == expected_pass,
        f"GAS filter passed {len(first['NodeNames'])} nodes, the cluster "
        f"as built has {len(expected_pass)} that fit",
    )
    check(target in first["NodeNames"], "Bind target did not fit before Bind")
    check(
        target in (second.get("FailedNodes") or {})
        and target not in second["NodeNames"],
        "the booking did not change the fits: target still passes",
    )
    say(
        f"  Filter {len(first['NodeNames'])}/{num_nodes} fit -> Bind "
        f"{target} -> Filter {len(second['NodeNames'])}/{num_nodes} fit; "
        f"all three answers equal use_device=False"
    )
    for srv, ext in ((server, extender), (ref_server, ref_extender)):
        ext.cache.stop()
        srv.shutdown()


# -- counters ----------------------------------------------------------------------


def phase_counters(tas, identity, observations):
    from platform_aware_scheduling_tpu.native import wirec_origin

    families = scrape(tas["server"].port)
    at_ready = tas["at_ready"]
    for family in ("pas_prioritize_native_total", "pas_gas_filter_device_total"):
        check(total(families, family) > 0, f"{family} is 0: device path unused")
    for family in (
        "pas_prioritize_host_fallback_total",
        "pas_device_path_errors_total",
        "pas_telemetry_refresh_errors_total",
    ):
        check(
            total(families, family) == 0,
            f"{family} = {families.get(family)}: a device failure was "
            f"caught and served around",
        )
    retraces = total(families, "pas_jax_retrace_total") - total(
        at_ready, "pas_jax_retrace_total"
    )
    check(retraces == 0, f"{retraces:g} watched-kernel retraces after warm-up")
    info = families.get("pas_device_info", {})
    check(
        any(f"platform={identity['platform']}" in key for key in info),
        f"pas_device_info does not name {identity['platform']}: {info}",
    )
    memory = families.get("pas_device_memory_in_use_bytes")
    if identity["platform"] == "tpu":
        check(
            memory,
            "pas_device_memory_in_use_bytes absent: the sampler saw no "
            "device memory_stats()",
        )
        observations["device_memory_in_use_bytes"] = memory
        observations["device_memory_peak_bytes"] = families.get(
            "pas_device_memory_peak_bytes"
        )
    origin = wirec_origin()
    check(origin is not None, "_wirec is not native: the wire path is Python")
    say(
        f"  native prioritize {total(families, 'pas_prioritize_native_total'):g}, "
        f"filter cache hit/miss "
        f"{total(families, 'pas_filter_cache_hit_total'):g}/"
        f"{total(families, 'pas_filter_cache_miss_total'):g}, GAS device "
        f"filters {total(families, 'pas_gas_filter_device_total'):g}; "
        f"fallbacks 0, caught device errors 0, post-warm retraces 0; "
        f"_wirec native ({origin}); device memory "
        f"{memory if memory else 'not reported (cpu)'}"
    )
    observations["wirec"] = origin
    observations["watched_kernel_compiles"] = families.get(
        "pas_xla_compiles_total"
    )


# -- main ----------------------------------------------------------------------------


def verdict_line(ok: bool, identity: dict) -> str:
    """The last stdout line: exactly ``ok`` and ``device`` (``platform``,
    ``kind``, ``count`` as JAX reports them) — everything else the run
    learned goes on the ``[summary]`` line before it."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": str(identity["platform"]),
            "kind": str(identity["kind"]),
            "count": int(identity["count"]),
        },
    })


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="debug the script on the CPU backend at a tiny size; never a "
        "chip pass (reports ok=false, exits 4)",
    )
    args = parser.parse_args(argv)
    sizes = REHEARSAL if args.rehearse_cpu else FULL
    started = time.monotonic()
    phases = {}
    observations = {}

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax
    import jaxlib
    from jax import monitoring

    from platform_aware_scheduling_tpu.utils import backend, klog

    # the mains' start-up lines (v1) without --v=2's per-request node lists
    klog.set_verbosity(1)

    # -- device ----------------------------------------------------------------
    say("[device]")
    cache_dir = backend.enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    identity = (
        backend.device_identity() if args.rehearse_cpu
        else backend.require_tpu("chip_smoke.py")
    )
    if args.rehearse_cpu:
        check(
            identity["platform"] == "cpu",
            f"--rehearse-cpu found platform {identity['platform']!r}; run "
            f"it with JAX_PLATFORMS=cpu",
        )
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # the package is optional wherever there is no TPU
        libtpu = "not installed"
    say(
        f"  platform: {identity['platform']}  device_kind: "
        f"{identity['kind']}  count: {identity['count']}"
    )
    say(
        f"  jax {jax.__version__}  jaxlib {jaxlib.__version__}  libtpu "
        f"{libtpu}"
    )
    say(f"  compile cache: {cache_dir} ({entries_before} entries)")
    phases["device"] = "passed"

    compile_seconds = {}
    cache_events = {"hits": 0, "writes": 0}

    def on_duration(name, duration, **kw):
        if name.endswith("backend_compile_duration"):
            fun = kw.get("fun_name", "?")
            compile_seconds[fun] = compile_seconds.get(fun, 0.0) + duration

    def on_event(name, **kw):
        if name.endswith("compilation_cache/cache_hits"):
            cache_events["hits"] += 1
        elif name.endswith("compilation_cache/cache_misses"):
            cache_events["writes"] += 1  # JAX counts a miss when it stores

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)

    # -- tas + refresh ----------------------------------------------------------
    say("[tas]")
    tas = phase_tas(sizes, args.seed, observations)
    phases["tas"] = "passed"
    say("[refresh]")
    phase_refresh(tas, sizes, args.seed, observations)
    phases["refresh"] = "passed"
    observe_prioritize_round_trip(tas, observations)

    # -- batch solve ------------------------------------------------------------
    say("[batch_solve]")
    assigner = phase_batch_solve(sizes, args.seed, identity, observations)
    phases["batch_solve"] = "passed"

    # -- gas --------------------------------------------------------------------
    say("[gas]")
    phase_gas(sizes, args.seed, tas["server"].port)
    phases["gas"] = "passed"

    # -- counters ---------------------------------------------------------------
    say("[counters]")
    phase_counters(tas, identity, observations)
    phases["counters"] = "passed"

    # -- mesh -------------------------------------------------------------------
    if identity["platform"] == "tpu" and identity["count"] >= 4:
        say("[mesh]")
        import __graft_entry__

        __graft_entry__.multichip_on_chips(report=say)
        phases["mesh"] = "passed"

    for stop in tas["stops"]:
        stop.set()
    tas["server"].shutdown()
    tas["reference_server"].shutdown()

    observations["compile_seconds_by_function"] = {
        name: round(seconds, 3)
        for name, seconds in sorted(
            compile_seconds.items(), key=lambda kv: -kv[1]
        )[:12]
    }
    observations["compile_seconds_total"] = round(
        sum(compile_seconds.values()), 3
    )
    observations["compile_cache"] = {
        "dir": cache_dir,
        "entries_before": entries_before,
        "entries_after": cache_entries(cache_dir),
        "hits": cache_events["hits"],
        "writes": cache_events["writes"],
    }
    observations["wall_s"] = round(time.monotonic() - started, 3)
    say("[observations] (not metrics: one run, host clock)")
    for key, value in observations.items():
        say(f"  {key}: {json.dumps(value)}")

    passed = not args.rehearse_cpu
    say("[summary] " + json.dumps({
        "rehearsal": args.rehearse_cpu,
        "phases": phases,
        "assigner": assigner,
        "seed": args.seed,
        "observations": observations,
    }))
    say(verdict_line(passed, identity))
    return 0 if passed else EXIT_REHEARSAL


if __name__ == "__main__":
    try:
        code = main()
    except BaseException as exc:  # noqa: BLE001 — every failure is an exit code
        if isinstance(exc, SystemExit):
            raise
        import traceback

        traceback.print_exc()
        print(f"chip_smoke.py FAILED: {exc}", file=sys.stderr, flush=True)
        code = 1
    # daemon threads (servers, refresh loops) must not keep the process or
    # the chip past the verdict
    sys.stdout.flush()
    os._exit(code)
