"""The ``backlog-mixed`` driver: the ``backlog`` driver's kube-scheduler over
pods that ask for unlike amounts.

The loop, the two verbs, the short readers, the binding connection and the
records are ``drivers/backlog.py``'s, loaded through ``plugins.load`` and not
copied.  The one difference is kube-scheduler's own Fit: for pod *i* the
candidates are the nodes whose free pod slots, cpu AND memory each cover
*that pod's* requests, so a node leaves one class's candidates while it
stays in another's.  The candidates, their rendered list and the nodes that
have left are therefore kept per class, and the base driver's single set is
pointed at the pod's class for the length of its cycle.

Beyond the base driver's, a record holds ``klass`` (the pod's class; its
``gone`` counts the nodes that had left *its class's* candidates), and the
window gives ``left`` as one list a class, in the order the nodes left, and
beside it ``short``: for each of those, which resources no longer covered the
class's request (bit 0 pods, bit 1 cpu, bit 2 memory).
"""

from __future__ import annotations

import numpy as np

import generator
import mixed_world
import plugins

base = plugins.load("drivers", "backlog")
TRAFFIC_KEYS = base.TRAFFIC_KEYS


class Driver(base.Driver):

    def __init__(self, job: dict):
        config = job["config"]
        # the base driver sizes ONE fit from one request and never lets go
        # of a node through it here: the fit it is told is out of reach
        stand_in = {**config, "pod_requests": config["pod_classes"][0]["requests"]}
        super().__init__({**job, "config": stand_in})
        self.config = config
        self.fit = np.iinfo(np.int64).max
        self.demand = mixed_world.demands(config)  # [classes, 3]
        self.klass_of = mixed_world.pod_classes(config, self.seed)
        self.free = (mixed_world.allocatable(config)[None, :]
                     - mixed_world.initial_held(config, self.seed))
        # per class: who is still a candidate, who left (in order, and what
        # was short), the candidates as sent and their rendered names
        covers = (self.free[None, :, :] >= self.demand[:, None, :])
        self.feasible_of = [covers[c].all(axis=1) for c in range(len(self.demand))]
        self.left_of = [[int(i) for i in np.flatnonzero(~f)]
                        for f in self.feasible_of]
        self.short_of = [[self.short(c, node) for node in left]
                         for c, left in enumerate(self.left_of)]
        self.sent_of = [(None, None)] * len(self.demand)
        self.klass = 0

    def short(self, klass: int, node: int) -> int:
        lacking = self.free[node] < self.demand[klass]
        return int(lacking[0]) | int(lacking[1]) << 1 | int(lacking[2]) << 2

    def pod(self, index: int, warm: bool) -> tuple:
        name = f"warm-{index:05d}" if warm else generator.bench_pod_name(index)
        which = (index % len(self.policies) if warm
                 else int(self.policy_of[index]))
        klass = (mixed_world.warm_class(self.config, index) if warm
                 else int(self.klass_of[index]))
        raw = mixed_world.pod_raw(self.config, name, klass, self.policies[which])
        return name, generator.compact(raw), which

    def first_verb(self, index: int, warm: bool = False) -> tuple:
        """Filter over every node whose free pods, cpu and memory each cover
        this pod's requests: the base driver's Filter, on its class's set."""
        klass = self.klass = (mixed_world.warm_class(self.config, index) if warm
                              else int(self.klass_of[index]))
        self.feasible, self.left = self.feasible_of[klass], self.left_of[klass]
        self._candidates, self._rendered = self.sent_of[klass]
        try:
            record, name, pod_bytes = super().first_verb(index, warm)
        finally:
            self.sent_of[klass] = (self._candidates, self._rendered)
        record["klass"] = klass
        return record, name, pod_bytes

    def pick(self, record: dict, name: str, pod_bytes: bytes, keep: bool) -> bool:
        """The base driver's Prioritize and choice; the pod is then assumed
        on its node at its own requests, and the node leaves the candidates
        of every class it no longer covers."""
        if not super().pick(record, name, pod_bytes, keep):
            return False
        node = record["node"]
        self.free[node] -= self.demand[self.klass]
        for klass, feasible in enumerate(self.feasible_of):
            if feasible[node] and (self.free[node] < self.demand[klass]).any():
                feasible[node] = False
                self.left_of[klass].append(node)
                self.short_of[klass].append(self.short(klass, node))
                self.sent_of[klass] = (None, None)
        return True

    def window(self, seconds: float) -> dict:
        result = super().window(seconds)
        result["left"] = [list(left) for left in self.left_of]
        result["short"] = [list(short) for short in self.short_of]
        return result
