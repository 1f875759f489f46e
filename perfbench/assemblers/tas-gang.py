"""The ``tas-gang`` assembler: TAS as ``batch-10k`` runs it, without the
planner and with ``--gang=on`` (``cmd/tas.assemble(..., gang_tracker=...)``
over ``cmd/common.build_gang_tracker``), in front of a fleet of TPU hosts
labelled with their ICI domain and coordinate (``gang_world``).

Of ``assemblers/tas-planner.py`` it takes the played kube API's binding
endpoint (``PlannerSystem._listen``), loaded through ``plugins.load`` and not
copied; the endpoint also takes the driver's ``DELETE`` of a pod.  What it
logs for the comparison: every binding and every deletion as the API took
them, each refresh pass's end, and each release of a gang's slice by the
program (a wrap of ``GangTracker.release``, as the planner's assembler wraps
``planner.pod_observed``).  After the warm-up it waits until the program has
released every warm-up gang its pods' deletion gave back.

Its controls, planted by ``--fault``: ``domain-blind`` (the tracker's nodes
lose their domain label and the pods lie side by side in one global mesh, as
a program without domains would see them), ``bind-unheard`` (the
tracker's pod feed is dropped, as on a program that learns bindings only
from a Bind verb nobody sends) and ``release-unheard`` (the feed keeps its
bindings and drops its deletions, as on a program that releases a slice
only at a pod LIST long after the job went).

It needs a program whose tracker reads the ICI domain label and follows the
cluster's pods: a program without them is refused at once, before anything
is assembled.
"""

from __future__ import annotations

import argparse
import math
import time

import gang_world
import plugins
from generator import node_names, tas_policies

base = plugins.load("assemblers", "tas-planner")
built_in = base.built_in
SETTLE_LIMIT_S = 60.0


class GangSystem(base.PlannerSystem):
    kind = "tas_gang"

    def __init__(self, config: dict, traffic: dict, seed: int, warm_pods: int):
        from platform_aware_scheduling_tpu.gang import GangTracker
        from platform_aware_scheduling_tpu.utils import labels

        if (not hasattr(labels, "TPU_DOMAIN_LABEL")
                or not hasattr(GangTracker, "watch")):
            raise RuntimeError(
                "this program's gang tracker reads no ICI domain label (it lays "
                "every node in one mesh, so a slice may straddle two TPU pods) "
                "and learns bindings only from a Bind verb kube-scheduler never "
                "sends: the cell tpu-v5e-fleet-12k.gang-backlog-drain cannot "
                "run on it")
        built_in.System.__init__(self)
        from platform_aware_scheduling_tpu.cmd import common
        from platform_aware_scheduling_tpu.cmd.tas import assemble
        from platform_aware_scheduling_tpu.tas.metrics import CustomMetricsClient
        from platform_aware_scheduling_tpu.utils import health

        import played_api

        self.config, self.seed = config, seed
        self.names = node_names(config["node_prefix"], config["nodes"])
        self.kube = played_api.PlayedTas(config, seed)
        for index, name in enumerate(self.names):
            self.kube.add_node(gang_world.node_raw(config, index, name))
        warm_policy = {**config, "policies": [config["warm_policy"]]}
        for policy in tas_policies(config) + tas_policies(warm_policy):
            self.kube.create_taspolicy({
                "apiVersion": "telemetry.intel.com/v1alpha1", "kind": "TASPolicy",
                "metadata": {"name": policy["name"], "namespace": "default"},
                "spec": {"strategies": {
                    kind: {"policyName": policy["name"], "rules": [
                        {"metricname": m, "operator": op, "target": target}
                        for m, op, target in rules]}
                    for kind, rules in policy["strategies"].items()}},
            })
        # pods in creation order: the fleet's running jobs, the warm-up's
        # pods, then the backlog
        policies = gang_world.policy_names(config)
        for job in gang_world.history(config, seed):
            for pod, host in zip(job.pods, job.hosts):
                self.kube.add_pod(gang_world.pod_raw(
                    config, pod, job, policies[job.policy], self.names[int(host)]))
        for job in gang_world.warm_jobs(config) + gang_world.backlog(config, seed):
            for pod in job.pods:
                self.kube.add_pod(gang_world.pod_raw(
                    config, pod, job, policies[job.policy]))
        # cmd/tas.py main() with --gang=on, minus the kubeconfig
        common.prepare_device_runtime()
        self.tracker = common.build_gang_tracker(
            argparse.Namespace(gang="on"), self.kube)
        cache, mirror, extender, controller, _enforcer, stop = assemble(
            self.kube, CustomMetricsClient(self.kube),
            float(config["sync_period_s"]),
            node_cache_capable=traffic["wire"] == "names",
            gang_tracker=self.tracker,
        )
        common.start_device_watch(stop=stop)
        self.cache, self.mirror, self.extender = cache, mirror, extender
        self.stops.append(stop.set)
        self.passes = []  # when each refresh pass ended
        cache.on_refresh_pass.append(lambda: self.passes.append(time.monotonic()))
        self.bindings = []  # (taken at, pod, node): what the played API saw
        self.deletes = []  # (taken at, pod)
        self.releases = []  # (released at, gang id): the program's own
        self.unheard = self.deaf = False
        release = self.tracker.release

        def released(gang_id: str) -> bool:
            out = release(gang_id)
            self.releases.append((time.monotonic(), gang_id))
            return out

        self.tracker.release = released
        self._listen()
        built_in.serve(self, extender, config["serving"])
        if controller.informer is not None:
            self.server.probe.register(
                "policy_informer_synced",
                health.informer_synced(controller.informer, "taspolicy"))

    # -- the played kube API: bindings and deletions ----------------------------------

    def _bind(self, path: str, body: bytes) -> tuple:
        parts = path.split("/")
        if len(parts) != 7 or path != (
                f"/api/v1/namespaces/{gang_world.NAMESPACE}/pods/{parts[-1]}"):
            return super()._bind(path, body)
        try:
            self.kube.get_pod(gang_world.NAMESPACE, parts[-1])
        except Exception as exc:  # noqa: BLE001 — an answer, not a crash
            return 404, {"kind": "Status", "status": "Failure",
                         "message": repr(exc)}
        self.kube.delete_pod(gang_world.NAMESPACE, parts[-1])
        self.deletes.append((time.monotonic(), parts[-1]))
        return 200, {"kind": "Status", "status": "Success"}

    # -- what run.py asks ---------------------------------------------------------------

    def after_warm(self, warm_pods: int, limit_s: float = 30.0) -> None:
        """The warm-up's pods are deleted by the driver: wait until the program
        holds none of their hosts, then for the next refresh pass to end."""
        deadline = time.monotonic() + SETTLE_LIMIT_S
        while self.tracker.reserved_nodes():
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "the gang tracker still holds the warm-up's slices: "
                    f"{sorted(set(self.tracker.reserved_nodes().values()))}")
            time.sleep(0.01)
        if self.unheard:
            self.tracker._feed.stop()
            self.tracker._feed = None
        if self.deaf:
            self.tracker.observe_gone = lambda namespace, name: None
        super().after_warm(warm_pods, limit_s)

    def logical_sizes(self, config: dict, candidates: int) -> dict:
        sizes = built_in.System.logical_sizes(self, config, candidates)
        sizes["domains"] = config["domains"]
        return sizes

    def compare(self, config: dict, traffic: dict, seed: int, run: dict) -> dict:
        import gang_reference

        window = run["window"]

        def moved(name: str) -> int:
            return int(run["after"].get(name, 0.0) - run["before"].get(name, 0.0))

        compared = gang_reference.compare(
            config, seed, window, self.kube.fetches, self.bindings,
            self.deletes, self.releases,
            admitted=moved("pas_gang_admitted_total"))
        compared["lags"], compared["censored"] = [], 0
        compared["numbers"]["window_without_pass"] = 0 if self.pass_intervals(
            window["began"], window["ended"]) else 1
        return compared

    def plant_fault(self, fault: str) -> None:
        """``domain-blind``: the tracker's nodes carry no domain label and lie
        side by side, the pods in a square of pods, in one global mesh.
        ``bind-unheard``: the tracker's pod feed is stopped and dropped once
        the warm-up's gangs are released, before the window.
        ``release-unheard``: from then on the feed's deletions reach no
        one."""
        tracker = self.tracker
        if fault in ("bind-unheard", "release-unheard"):
            # once the warm-up's gangs are released
            self.unheard, self.deaf = fault == "bind-unheard", fault != "bind-unheard"
            return
        if fault != "domain-blind":
            return super().plant_fault(fault)
        domains, rows, cols = gang_world.grid(self.config)
        across = math.isqrt(domains - 1) + 1
        listed = tracker.nodes_provider

        def side_by_side():
            nodes = listed()
            for node in nodes:
                labels = node.raw["metadata"]["labels"]
                domain = int(labels.pop(gang_world.DOMAIN_LABEL)[4:])
                row, col = (int(x) for x in labels[gang_world.COORD_LABEL].split(","))
                labels[gang_world.COORD_LABEL] = (
                    f"{domain // across * rows + row},{domain % across * cols + col}")
            return nodes

        tracker.nodes_provider = side_by_side


def assemble(config: dict, traffic: dict, seed: int, warm_pods: int):
    return GangSystem(config, traffic, seed, warm_pods)
