"""The ``tas-planner-mesh`` assembler: the ``tas-planner`` deployment with the
planner's solve spanning ``planner_devices`` devices
(``cmd/tas.assemble(..., enable_batch_planner=True, planner_devices=n)``:
``--batchPlanner --batchPlannerDevices=n``).

Everything else is ``assemblers/tas-planner.py``'s ``PlannerSystem``, loaded
through ``plugins.load`` and not copied: the played kube API's ``pods/binding``
endpoint, the stamps the plan's reference needs, the comparison
(``plan_reference.py``) and the faults.  That class imports
``cmd.tas.assemble`` when it is constructed and calls it with
``enable_batch_planner=True``; this module hands it the number of devices by
wrapping that one function for the length of the construction.

It needs a program whose ``cmd.tas.assemble`` takes ``planner_devices`` and a
JAX with that many devices.  Either missing is refused at once, before
anything is assembled and without waiting: that is how the cell fails on a
program from before the mesh path.
"""

from __future__ import annotations

import inspect

import plugins

base = plugins.load("assemblers", "tas-planner")


class MeshPlannerSystem(base.PlannerSystem):
    kind = "tas_planner_mesh"

    def __init__(self, config: dict, traffic: dict, seed: int, warm_pods: int):
        import jax

        from platform_aware_scheduling_tpu.cmd import tas as tas_main

        devices = int(config["planner_devices"])
        inner = tas_main.assemble
        if "planner_devices" not in inspect.signature(inner).parameters:
            raise RuntimeError(
                "this program's cmd.tas.assemble takes no planner_devices: "
                "its batch planner solves on one device, and the cell "
                "tas-40k.backlog-drain cannot run on it")
        if len(jax.devices()) < devices:
            raise RuntimeError(
                f"the planner's mesh needs {devices} devices; JAX has "
                f"{len(jax.devices())}")

        def over_the_mesh(*args, **kwargs):
            return inner(*args, planner_devices=devices, **kwargs)

        tas_main.assemble = over_the_mesh
        try:
            super().__init__(config, traffic, seed, warm_pods)
        finally:
            tas_main.assemble = inner
        mesh = getattr(self.planner, "mesh", None)
        if mesh is None or mesh.devices.size != devices:
            raise RuntimeError(
                f"the planner does not span {devices} devices: {mesh}")


def assemble(config: dict, traffic: dict, seed: int, warm_pods: int):
    return MeshPlannerSystem(config, traffic, seed, warm_pods)
