"""The ``tas-planner-mixed`` assembler: the ``tas-planner`` deployment over
pods that ask for unlike amounts (``alibaba-colo-4k``): TAS as
``tas-shipped-5k`` runs it plus ``--batchPlanner``, on its normal assembly —
no flag says that the pods differ; the planner sees it in its pending set.

Everything is ``assemblers/tas-planner.py``'s ``PlannerSystem``, loaded
through ``plugins.load`` and not copied: the played kube API's
``pods/binding`` endpoint, the replans' and the bindings' stamps, the faults
``plan-shifted`` and ``plan-dropped``.  That class makes every pod through
``batch_world.pod_raw`` with the configuration's one ``pod_requests`` and
holds the window to ``plan_reference``; for the length of its construction
and of its comparison this module stands ``mixed_world``'s per-class pod and
``mixed_plan_reference`` in for those two names, as ``tas-planner-mesh``
stands its own ``assemble`` in.

It brings the control ``room-by-largest``: the planner counts room as it did
before it knew demands — every pod as the largest request pending.

It needs a planner that books each pod's own requests and says so in its
counters.  A program without them is refused at once, before anything is
assembled: that is how the cell fails on a program from before this path.
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

import batch_world
import mixed_plan_reference
import mixed_world
import plugins

base = plugins.load("assemblers", "tas-planner")


@contextlib.contextmanager
def standing_in(holder, name: str, stand_in):
    """``holder[name]`` is ``stand_in`` inside the block (an attribute of a
    module, or an entry of ``sys.modules``)."""
    entries = holder if isinstance(holder, dict) else vars(holder)
    missing = object()
    was = entries.get(name, missing)
    entries[name] = stand_in
    try:
        yield
    finally:
        if was is missing:
            del entries[name]
        else:
            entries[name] = was


class MixedPlannerSystem(base.PlannerSystem):
    kind = "tas_planner_mixed"

    def __init__(self, config: dict, traffic: dict, seed: int, warm_pods: int):
        from platform_aware_scheduling_tpu.utils import trace

        if "pas_planner_demand_solves_total" not in trace.METRICS:
            raise RuntimeError(
                "this program's batch planner books one unit of room a pod, "
                "every pod counted as the largest request pending: it places "
                "pods of unlike requests against its own policy, and the cell "
                "alibaba-colo-4k.mixed-backlog-drain cannot run on it")
        tables = {}

        def pod_of_its_class(_config, name, policy="", node=""):
            klass = mixed_world.class_of(config, seed, name, tables)
            return mixed_world.pod_raw(config, name, klass, policy, node)

        with standing_in(batch_world, "pod_raw", pod_of_its_class):
            super().__init__(config, traffic, seed, warm_pods)

    def logical_sizes(self, config: dict, candidates: int) -> dict:
        sizes = super().logical_sizes(config, candidates)
        sizes["resources"] = len(mixed_world.RESOURCES)
        # the exact width of a room entry: one int32 limb where the rows'
        # quantities share a unit (whole Gi, whole half-cores), as here
        sizes["room_bytes"] = 4
        return sizes

    def compare(self, config: dict, traffic: dict, seed: int, run: dict) -> dict:
        with standing_in(sys.modules, "plan_reference", mixed_plan_reference):
            return super().compare(config, traffic, seed, run)

    def plant_fault(self, fault: str) -> None:
        """``room-by-largest``: the room is one count a node, of pods of the
        LARGEST request pending, and every pod takes one — what the planner
        did before it read each pod's own demand."""
        if fault != "room-by-largest":
            return super().plant_fault(fault)
        planner = self.planner

        def by_largest(free, asked, n_cap, size):
            count = planner._room(free, tuple(asked.max(axis=0)), n_cap)
            one_each = np.zeros((size, 1, 1), dtype=np.int32)
            one_each[: len(asked)] = 1
            return count.reshape(1, 1, n_cap), one_each

        planner._room_rows = by_largest


def assemble(config: dict, traffic: dict, seed: int, warm_pods: int):
    return MixedPlannerSystem(config, traffic, seed, warm_pods)
