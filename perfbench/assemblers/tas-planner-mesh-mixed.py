"""The ``tas-planner-mesh-mixed`` assembler: the ``tas-planner-mixed``
deployment (pods of unlike requests, ``alibaba-colo-40k``) with the planner's
solve spanning ``planner_devices`` devices
(``--batchPlanner --batchPlannerDevices=n``), on TAS's normal assembly: no flag
says that the pods differ, and none that they meet the mesh.

It copies neither parent.  The class is ``tas-planner-mesh``'s
``MeshPlannerSystem`` over ``tas-planner-mixed``'s ``MixedPlannerSystem``,
both loaded through ``plugins.load``; they share ``tas-planner``'s
``PlannerSystem`` (the loader keeps one module a name), so the method order
runs the mesh's wrap of ``cmd.tas.assemble`` around the mixed class's
per-class pods, and the mixed class's comparison (``mixed_plan_reference``),
logical sizes and control ``room-by-largest``.  That control stands in for
``planner._room_rows``, which the mesh's replan now reads as the one-device
replan does: it reaches the mesh path.

It needs a planner whose mesh books each pod's own requests and says so in
its counters.  A program without ``pas_planner_mesh_demand_solves_total`` is
refused at once, before anything is assembled and without waiting: that is
how the cell fails on a program from before this path, whose mesh counts
every unlike pod as the largest.
"""

from __future__ import annotations

import plugins

mesh = plugins.load("assemblers", "tas-planner-mesh")
mixed = plugins.load("assemblers", "tas-planner-mixed")


class MeshMixedPlannerSystem(mesh.MeshPlannerSystem, mixed.MixedPlannerSystem):
    kind = "tas_planner_mesh_mixed"

    def __init__(self, config: dict, traffic: dict, seed: int, warm_pods: int):
        from platform_aware_scheduling_tpu.utils import trace

        if "pas_planner_mesh_demand_solves_total" not in trace.METRICS:
            raise RuntimeError(
                "this program's batch planner counts every pod as the largest "
                "request pending when it solves over a mesh: it places pods of "
                "unlike requests against its own policy there, and the cell "
                "alibaba-colo-40k.mixed-backlog-drain cannot run on it")
        super().__init__(config, traffic, seed, warm_pods)

    def logical_sizes(self, config: dict, candidates: int) -> dict:
        """The mixed class's sizes, with ``pending_mean`` counted over the
        pods that were pending.  ``tas-planner``'s count takes every binding
        the planner's informer fed in off the pods pending at the start, the
        pods bound before the warm-up among them: here 80,000 of them, more
        than the backlog, which would make the mean negative.  Those are
        left out of ``observed`` for the length of the count."""
        import batch_world

        init = {batch_world.init_pod_name(i) for i in range(config["init_pods"])}
        observed = self.observed
        self.observed = {pod: at for pod, at in observed.items() if pod not in init}
        try:
            return super().logical_sizes(config, candidates)
        finally:
            self.observed = observed


def assemble(config: dict, traffic: dict, seed: int, warm_pods: int):
    return MeshMixedPlannerSystem(config, traffic, seed, warm_pods)
