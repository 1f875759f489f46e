"""The ``tas-planner`` assembler: TAS as ``tas-shipped-5k`` runs it plus
``--batchPlanner`` (``cmd/tas.assemble(..., enable_batch_planner=True)``),
over nodes that report the configuration's allocatable and a backlog of
pending pods that is there before the first request.

What it inherits from ``assemble.TasSystem``: readiness, the wait for a pass
to end after the warm-up, the faults ``answer-altered`` and ``stale-round``.
What it brings: its own assembly (``TasSystem.__init__`` calls ``assemble``
without the planner), the played kube API's ``pods/binding`` endpoint (the
generator may not import the program, so the bindings it writes are taken
here, on an abstract Unix socket named after this process), the stamps the
plan's reference needs — each replan's begin and end, through two hooks
around the planner's on ``cache.on_refresh_pass``; each binding as the API
took it and as the planner's informer had fed it in (a wrap of
``planner.pod_observed``, as the faults wrap ``planner.planned_node``) — and
the faults ``plan-shifted`` and ``plan-dropped``.

It needs a planner that replans on the refresh pass and the planner's
counters: a program without them is refused at assembly, at once.
"""

from __future__ import annotations

import bisect
import json
import os
import socket
import threading
import time

import assemble as built_in
import batch_world
from generator import bench_pod_name, node_names, tas_policies


class PlannerSystem(built_in.TasSystem):
    kind = "tas_planner"

    def __init__(self, config: dict, traffic: dict, seed: int, warm_pods: int):
        built_in.System.__init__(self)
        from platform_aware_scheduling_tpu.cmd import common
        from platform_aware_scheduling_tpu.cmd.tas import assemble
        from platform_aware_scheduling_tpu.tas import planner as planner_module
        from platform_aware_scheduling_tpu.tas.metrics import CustomMetricsClient
        from platform_aware_scheduling_tpu.utils import health, trace

        import played_api

        if (not hasattr(planner_module, "padded_size")
                or "pas_planner_promoted_total" not in trace.METRICS):
            raise RuntimeError(
                "this program's batch planner neither replans on the refresh "
                "pass at padded sizes nor counts its promotions: the cell "
                "batch-10k.backlog-drain cannot run on it")
        self.config = config
        self.names = node_names(config["node_prefix"], config["nodes"])
        self.kube = played_api.PlayedTas(config, seed)
        for name in self.names:
            self.kube.add_node(batch_world.node_raw(config, name))
        for policy in tas_policies(config):
            self.kube.create_taspolicy({
                "apiVersion": "telemetry.intel.com/v1alpha1", "kind": "TASPolicy",
                "metadata": {"name": policy["name"], "namespace": "default"},
                "spec": {"strategies": {
                    kind: {"policyName": policy["name"], "rules": [
                        {"metricname": m, "operator": op, "target": target}
                        for m, op, target in rules]}
                    for kind, rules in policy["strategies"].items()}},
            })
        # pods in creation order: those bound before the warm-up, the
        # warm-up's own, then the backlog
        labels = [p["name"] for p in config["policies"]]
        for index, node in enumerate(batch_world.init_pod_nodes(config, seed)):
            self.kube.add_pod(batch_world.pod_raw(
                config, batch_world.init_pod_name(index),
                node=self.names[int(node)]))
        for index in range(warm_pods):
            self.kube.add_pod(batch_world.pod_raw(
                config, f"warm-{index:05d}", labels[index % len(labels)]))
        for index, which in enumerate(batch_world.pod_policies(config, seed)):
            self.kube.add_pod(batch_world.pod_raw(
                config, bench_pod_name(index), labels[int(which)]))
        self.warm_pods = warm_pods
        self.pending_at_start = warm_pods + config["measure_pods"]
        # cmd/tas.py main() with --batchPlanner, minus the kubeconfig
        common.prepare_device_runtime()
        cache, mirror, extender, controller, _enforcer, stop = assemble(
            self.kube, CustomMetricsClient(self.kube),
            float(config["sync_period_s"]),
            enable_batch_planner=True,
            node_cache_capable=traffic["wire"] == "names",
        )
        common.start_device_watch(stop=stop)
        self.cache, self.mirror, self.extender = cache, mirror, extender
        self.planner = extender.planner
        self.stops.append(stop.set)
        self.passes = []  # when each refresh pass ended (after its replan)
        self.replans = []  # [begin, end] of each replan, on this clock
        self.bindings = []  # (taken at, pod, node): what the played API saw
        self.observed = {}  # pod -> when the planner's informer had fed it in
        hooks = cache.on_refresh_pass
        if self.planner is None or self.planner.replan not in hooks:
            raise RuntimeError("the planner is not hung on cache.on_refresh_pass")
        hooks.insert(hooks.index(self.planner.replan),
                     lambda: self.replans.append([time.monotonic(), None]))

        def replanned():
            now = time.monotonic()
            self.replans[-1][1] = now
            self.passes.append(now)

        hooks.append(replanned)

        pod_observed = self.planner.pod_observed

        def fed_in(pod, deleted: bool = False) -> None:
            # under the planner's lock, which its snapshot takes too: a
            # replan that begins after this stamp has read the binding
            pod_observed(pod, deleted=deleted)
            if pod.spec_node_name:
                self.observed.setdefault(pod.name, time.monotonic())

        self.planner.pod_observed = fed_in
        self._listen()
        built_in.serve(self, extender, config["serving"])
        if controller.informer is not None:
            self.server.probe.register(
                "policy_informer_synced",
                health.informer_synced(controller.informer, "taspolicy"))

    # -- the played kube API's binding endpoint -----------------------------------

    def _listen(self) -> None:
        listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        listener.bind(batch_world.bind_address(os.getpid()))
        listener.listen(8)
        self.stops.append(listener.close)

        def accept() -> None:
            while True:
                try:
                    conn, _ = listener.accept()
                except OSError:
                    return
                threading.Thread(
                    target=self._take_bindings, args=(conn,), daemon=True).start()

        threading.Thread(target=accept, daemon=True).start()

    def _take_bindings(self, conn: socket.socket) -> None:
        """One keep-alive connection: ``POST .../pods/<pod>/binding``."""
        buf = bytearray()
        with conn:
            while True:
                while b"\r\n\r\n" not in buf:
                    chunk = conn.recv(1 << 16)
                    if not chunk:
                        return
                    buf += chunk
                end = buf.index(b"\r\n\r\n")
                head = bytes(buf[:end]).split(b"\r\n")
                length = next((int(line[15:]) for line in head[1:]
                               if line[:15].lower() == b"content-length:"), 0)
                while len(buf) < end + 4 + length:
                    chunk = conn.recv(1 << 16)
                    if not chunk:
                        return
                    buf += chunk
                body = bytes(buf[end + 4: end + 4 + length])
                del buf[: end + 4 + length]
                status, answer = self._bind(
                    head[0].split(b" ")[1].decode(), body)
                payload = json.dumps(answer).encode()
                conn.sendall(
                    f"HTTP/1.1 {status} \r\nContent-Type: application/json\r\n"
                    f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)

    def _bind(self, path: str, body: bytes) -> tuple:
        try:
            pod = path.split("/")[6]
            if path != batch_world.binding_path(pod):
                raise ValueError(path)
            node = json.loads(body)["target"]["name"]
            if self.kube.get_pod("default", pod).spec_node_name:
                return 409, {"kind": "Status", "status": "Failure",
                             "reason": "Conflict", "message": f"pod {pod} is "
                             "already assigned to a node"}
            self.kube.bind_pod("default", pod, f"uid-{pod}", node)
        except Exception as exc:  # noqa: BLE001 — an answer, not a crash
            return 400, {"kind": "Status", "status": "Failure",
                         "message": repr(exc)}
        self.bindings.append((time.monotonic(), pod, node))
        return 201, {"kind": "Status", "status": "Success"}

    # -- what run.py asks -------------------------------------------------------

    def logical_sizes(self, config: dict, candidates: int) -> dict:
        """The plan's own sizes, as this harness counted them: the pods
        pending at each replan of the window are those of the start less the
        bindings the planner had been fed by then."""
        sizes = super().logical_sizes(config, candidates)
        began, ended = self.window
        fed = sorted(self.observed.values())
        pending = [
            self.pending_at_start - bisect.bisect_left(fed, begin)
            for begin, end in self.replans
            if end is not None and began <= begin and end <= ended]
        sizes["pending_mean"] = sum(pending) / len(pending) if pending else 0.0
        sizes["policies"] = len(config["policies"])
        return sizes

    def compare(self, config: dict, traffic: dict, seed: int, run: dict) -> dict:
        import plan_reference

        window = run["window"]
        self.window = (window["began"], window["ended"])

        def moved(name: str) -> int:
            return int(run["after"].get(name, 0.0) - run["before"].get(name, 0.0))

        compared = plan_reference.compare(
            config, seed, window, self.kube.fetches,
            [r for r in self.replans if r[1] is not None], self.bindings,
            self.observed, self.warm_pods,
            led=moved("pas_planner_promoted_total"))
        # of those the program led with a plan's node, the answers the plan
        # changed (PERF.md's plan_applied_pct reads this counter)
        compared["counted"]["reordered"] = moved("pas_planner_reordered_total")
        compared["lags"], compared["censored"] = [], 0
        compared["numbers"]["window_without_replan"] = 0 if any(
            window["began"] <= begin and end <= window["ended"]
            for begin, end in compared["replans"]) else 1
        return compared

    def plant_fault(self, fault: str) -> None:
        """``plan-shifted``: every 5th promotion is moved to the next node;
        ``plan-dropped``: every 5th current plan entry is withheld."""
        if fault not in ("plan-shifted", "plan-dropped"):
            return super().plant_fault(fault)
        planner, names = self.planner, self.names
        index = {name: i for i, name in enumerate(names)}
        inner = planner.planned_node
        count = {"n": 0}

        def planned_node(pod):
            node = inner(pod)
            if node is None:
                return None
            count["n"] += 1
            if count["n"] % 5:
                return node
            if fault == "plan-dropped":
                return None
            return names[(index[node] + 1) % len(names)]

        planner.planned_node = planned_node


def assemble(config: dict, traffic: dict, seed: int, warm_pods: int):
    return PlannerSystem(config, traffic, seed, warm_pods)
