"""Assemble the system under test as its mains do, over played kube and
custom-metrics APIs, and keep the logs the comparison and the readers need.

Of the program this file imports its assembly (``cmd/tas.assemble`` +
``build_server``, ``GASExtender``) and reads two of its hooks
(``cache.on_refresh_pass``, ``cache.on_booking_change``) and two public
attributes of the GAS cache (``annotated_pods``, ``work_queue``); the played
APIs are the benchmark's own (``played_api.py``, which takes the client
interface's types and nothing else).  Everything is imported inside
functions: importing this module touches neither the program nor JAX.

``plant_fault`` breaks the program underneath the harness, for the tests and
controls that must see ``correct`` come out false.  No cell runs with one.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

from generator import (
    bench_pod_name,
    gas_cards,
    gas_node,
    gas_pod,
    gas_template_sequence,
    gas_templates,
    node_names,
    tas_policies,
)

READY_LIMIT_S = 900.0


class System:
    """What ``run.py`` holds of an assembled extender."""

    kind = ""

    def __init__(self):
        self.server = None
        self.port = 0
        self.stops = []
        self.log_from = 0.0  # stamps before this belong to set-up
        # (ended at, seconds, what) of every XLA compilation in this process
        self.compiled = []
        from jax import monitoring

        def on_duration(name, duration, **kw):
            if name.endswith("backend_compile_duration"):
                self.compiled.append(
                    (time.monotonic(), duration, kw.get("fun_name", "?")))

        monitoring.register_event_duration_secs_listener(on_duration)

    def compiled_between(self, began: float, ended: float) -> int:
        return sum(1 for at, _s, _w in self.compiled if began <= at <= ended)

    def mark(self) -> None:
        self.log_from = time.monotonic()

    def close(self) -> None:
        for stop in self.stops:
            stop()
        if self.server is not None:
            self.server.shutdown()


def serve(system: System, extender, serving: str) -> None:
    from platform_aware_scheduling_tpu.cmd.tas import build_server
    from platform_aware_scheduling_tpu.utils.gctuning import tune_for_serving

    tune_for_serving()
    system.server = build_server(extender, serving=serving)
    system.server.start_server(
        port="0", unsafe=True, host="127.0.0.1", block=False)
    if not system.server.wait_ready():
        raise RuntimeError("the extender's server did not start listening")
    system.port = system.server.port


# -- TAS ------------------------------------------------------------------------


class TasSystem(System):
    kind = "tas"

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__()
        from platform_aware_scheduling_tpu.cmd import common
        from platform_aware_scheduling_tpu.cmd.tas import assemble
        from platform_aware_scheduling_tpu.tas.metrics import CustomMetricsClient
        from platform_aware_scheduling_tpu.utils import health

        import played_api

        self.config = config
        self.kube = played_api.PlayedTas(config, seed)
        for name in node_names(config["node_prefix"], config["nodes"]):
            self.kube.add_node({"metadata": {"name": name, "labels": {}},
                                "status": {"allocatable": {}}})
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
        # cmd/tas.py main(), minus the kubeconfig
        common.prepare_device_runtime()
        cache, mirror, extender, controller, _enforcer, stop = assemble(
            self.kube, CustomMetricsClient(self.kube),
            float(config["sync_period_s"]),
            node_cache_capable=traffic["wire"] == "names",
        )
        common.start_device_watch(stop=stop)
        self.cache, self.mirror, self.extender = cache, mirror, extender
        self.stops.append(stop.set)
        self.passes = []  # when each refresh pass ended (after its warm)
        cache.on_refresh_pass.append(
            lambda: self.passes.append(time.monotonic()))
        serve(self, extender, config["serving"])
        if controller.informer is not None:
            self.server.probe.register(
                "policy_informer_synced",
                health.informer_synced(controller.informer, "taspolicy"))

    def wait_ready(self, get) -> None:
        """/readyz 200, every policy cached, and two whole refresh passes
        (and enforcement ticks) gone by without a compilation: the passes
        before them compile every program the sync loop uses."""
        deadline = time.monotonic() + READY_LIMIT_S
        quiet = 2 * float(self.config["sync_period_s"])
        while time.monotonic() < deadline:
            status, payload = get(self.port, "/readyz")
            last = self.compiled[-1][0] if self.compiled else 0.0
            settled = [p for p in self.passes if p > last]
            if (status == 200 and len(settled) >= 2 and self._policies_in()
                    and time.monotonic() - last > quiet):
                if b"host-only" in payload:
                    raise RuntimeError(f"/readyz is ready in host-only mode: {payload[:300]!r}")
                return
            time.sleep(0.05)
        raise RuntimeError(f"TAS not ready within {READY_LIMIT_S:.0f}s: {payload[:300]!r}")

    def wait_pass_end(self, limit_s: float = 30.0) -> None:
        """Return just after the next refresh pass has ended, so that every
        window holds the same number of passes at the same phase."""
        seen = len(self.passes)
        deadline = time.monotonic() + limit_s
        while len(self.passes) == seen and time.monotonic() < deadline:
            time.sleep(0.002)

    def _policies_in(self) -> bool:
        try:
            for policy in self.config["policies"]:
                self.cache.read_policy("default", policy["name"])
        except KeyError:
            return False
        return True

    def pass_intervals(self, began: float, ended: float) -> list:
        """Seconds from a pass's first fetch at the played API to the
        program's end-of-pass hook, for each pass wholly inside the window."""
        firsts = {}
        for at, _metric, round_index in self.kube.fetches:
            firsts[round_index] = min(at, firsts.get(round_index, at))
        starts = sorted(t for t in firsts.values() if began <= t <= ended)
        out = []
        for start in starts:
            end = next((p for p in self.passes if p >= start), None)
            if end is not None and end <= ended:
                out.append(end - start)
        return out


# -- GAS ------------------------------------------------------------------------


class GasSystem(System):
    kind = "gas"

    def __init__(self, config: dict, traffic: dict, seed: int, warm_pods: int):
        super().__init__()
        from platform_aware_scheduling_tpu.cmd import common
        from platform_aware_scheduling_tpu.gas.scheduler import GASExtender

        import played_api
        import reference

        self.config, self.seed = config, seed
        self.kube = played_api.PlayedGas()
        names = node_names(config["node_prefix"], config["nodes"])
        for name, cards in zip(names, gas_cards(config, seed)):
            self.kube.add_node(gas_node(name, int(cards), config["per_card"]))
        self.templates = gas_templates(config)
        self.prebooked, _cluster = reference.gas_prebook(config, seed)
        for name, template, node, note in self.prebooked:
            self.kube.add_pod(gas_pod(
                name, self.templates[template], node=names[node],
                annotations={"gas-container-cards": note, "gas-ts": "1"},
                phase="Running"))
            self.kube.bound_to[name] = names[node]
        self.live = collections.deque(p[0] for p in self.prebooked)
        self.target_live = len(self.live)
        self.sequence = gas_template_sequence(config, seed)
        for index in range(warm_pods):
            self.kube.add_pod(gas_pod(
                f"warm-{index:05d}", self.templates[index % len(self.templates)]))
        self.ahead = int(traffic["churn"]["pending_ahead"])
        self.created = 0
        self._create_pending(self.ahead)
        common.prepare_device_runtime()
        self.extender = GASExtender(self.kube, use_device=config["use_device"])
        watch_stop = threading.Event()
        common.start_device_watch(stop=watch_stop)
        self.stops += [watch_stop.set, self.extender.cache.stop]
        self.deletes = []  # [issued at, pod], in issue order
        self.released = {}  # pod -> when the program took its booking back
        self._in_flight = {}  # node -> pods deleted whose booking still stands
        self._flight_lock = threading.Lock()
        booked = self.extender.cache.annotated_pods

        def on_booking(node: str) -> None:
            """Fired (cache lock held) after every booking change on ``node``:
            a deleted pod of that node that is no longer booked was released."""
            with self._flight_lock:
                waiting = self._in_flight.get(node)
                if not waiting:
                    return
                now = time.monotonic()
                for pod in [p for p in waiting if f"default&{p}" not in booked]:
                    self.released[pod] = now
                    waiting.remove(pod)

        self.extender.cache.on_booking_change(on_booking)
        self._stop = threading.Event()
        self.stops.append(self._stop.set)
        self._churn = threading.Thread(target=self._churn_run, daemon=True)
        serve(self, self.extender, config["serving"])

    def _create_pending(self, upto: int) -> None:
        while self.created < upto:
            template = int(self.sequence[self.created % len(self.sequence)])
            self.kube.add_pod(gas_pod(
                bench_pod_name(self.created), self.templates[template]))
            self.created += 1

    def wait_ready(self, get) -> None:
        deadline = time.monotonic() + READY_LIMIT_S
        while time.monotonic() < deadline:
            status, payload = get(self.port, "/readyz")
            if status == 200 and self.settled():
                return
            time.sleep(0.05)
        raise RuntimeError(f"GAS not ready within {READY_LIMIT_S:.0f}s: {payload[:300]!r}")

    def settled(self) -> bool:
        return (len(self.extender.cache.work_queue) == 0
                and len(self.released) >= len(self.deletes))

    def wait_settled(self, limit_s: float = 60.0) -> bool:
        deadline = time.monotonic() + limit_s
        while time.monotonic() < deadline:
            if not self.kube.bound and self.settled():
                return True
            time.sleep(0.01)
        return False

    def start_churn(self) -> None:
        self._churn.start()

    def delete_warm(self, count: int) -> None:
        """The warm-up's pods go the way completed pods go, before the
        window: deleted through the API, released by the informer.  Only a
        pod that was bound holds a booking, so only its delete is logged."""
        bound = set(self.kube.bound)
        self.kube.bound.clear()
        for index in range(count):
            name = f"warm-{index:05d}"
            if name in bound:
                self.delete(name)
            else:
                self.kube.delete_pod("default", name)

    def delete(self, pod: str) -> None:
        """Delete a bound pod through the API, as a completed pod goes."""
        with self._flight_lock:
            self._in_flight.setdefault(self.kube.bound_to.pop(pod), []).append(pod)
        self.deletes.append([time.monotonic(), pod])
        self.kube.delete_pod("default", pod)

    def _churn_run(self) -> None:
        """Off the generator's path, as pod completions and creations are in
        a cluster: for every binding the API saw, keep the pool of pending
        pods ``pending_ahead`` deep, and delete the oldest live pod once
        more are live than the occupancy target holds."""
        kube = self.kube
        while not self._stop.is_set():
            if not kube.bound:
                kube.bound_event.wait(0.05)
                kube.bound_event.clear()
                continue
            pod = kube.bound.popleft()
            if not pod.startswith("bench-"):
                continue
            self.live.append(pod)
            self._create_pending(self.created + 1)
            while len(self.live) > self.target_live:
                self.delete(self.live.popleft())

    def delete_log(self) -> list:
        """[(issued, released or inf, pod)] for deletes since ``mark()``, in
        issue order; each release is stamped by its own pod's name."""
        return [(issued, self.released.get(pod, np.inf), pod)
                for issued, pod in self.deletes if issued >= self.log_from]


def assemble_system(config: dict, traffic: dict, seed: int, warm_pods: int):
    if config["assembler"] == "tas":
        return TasSystem(config, traffic, seed)
    if config["assembler"] == "gas":
        return GasSystem(config, traffic, seed, warm_pods)
    raise ValueError(f"unknown assembler {config['assembler']!r}")


# -- faults, for the tests and controls only --------------------------------------


def plant_fault(system: System, fault: str) -> None:
    """Break the timed path underneath the harness.

    ``answer-altered``  every 5th Prioritize (TAS) or Filter (GAS) answer is
                        altered where it is produced: the top two hosts
                        swapped / the first passing node dropped
    ``stale-round``     TAS: every third pass writes one metric's values of two
                        passes before in place of the new ones, so its rounds
                        go backwards on the wire
    ``unbooked-bind``   GAS: every 5th Bind is acknowledged and its pod known
                        as booked, but no card usage is booked for it
    """
    import json

    extender = system.extender
    count = {"n": 0}
    if fault == "answer-altered":
        verb = "prioritize" if system.kind == "tas" else "filter"
        inner = getattr(extender, verb)

        def altered(request):
            response = inner(request)
            count["n"] += 1
            if count["n"] % 5 or response.status != 200:
                return response
            answer = json.loads(response.body)
            if verb == "prioritize" and len(answer) > 1:
                answer[0]["Host"], answer[1]["Host"] = (
                    answer[1]["Host"], answer[0]["Host"])
            elif verb == "filter" and answer.get("NodeNames"):
                dropped = answer["NodeNames"].pop(0)
                answer["FailedNodes"][dropped] = "altered"
            response.body = json.dumps(answer).encode()
            if hasattr(response, "headers") and response.headers:
                response.headers.pop("Content-Length", None)
            return response

        setattr(extender, verb, altered)
    elif fault == "stale-round" and system.kind == "tas":
        cache = system.cache
        inner_write = cache.write_metric
        history = []

        def write(metric_name, data=None):
            if not data or metric_name != system.config["metrics"][1]:
                return inner_write(metric_name, data)
            count["n"] += 1
            if count["n"] % 3 == 0 and len(history) >= 2:
                data = history[-2]
            history.append(data)
            return inner_write(metric_name, data)

        cache.write_metric = write
    elif fault == "unbooked-bind" and system.kind == "gas":
        cache = extender.cache
        inner_adjust = cache.adjust_pod_resources

        skipped = set()

        def adjust(pod, adj, annotation, node_name):
            key = f"{pod.namespace}&{pod.name}"
            if adj and pod.name.startswith("bench-"):
                count["n"] += 1
                if count["n"] % 5 == 0:
                    # known as booked, so that the informer's update of the
                    # same pod does not book it after all
                    skipped.add(key)
                    cache.annotated_pods[key] = annotation
                    return
            elif not adj and key in skipped:
                # the release needs something to take back
                skipped.discard(key)
                inner_adjust(pod, True, annotation, node_name)
            inner_adjust(pod, adj, annotation, node_name)

        cache.adjust_pod_resources = adjust
    else:
        raise ValueError(f"no fault {fault!r} for a {system.kind} system")
