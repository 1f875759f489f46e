"""The ``mesh_module_roofline`` reader kind: the share of the roofline of ALL
the chips a jitted program spans that its runs reach.

The built-in ``module_roofline`` sums a program's device time over the device
planes and counts a run a plane, so for a program that runs once on each of
four chips it divides one run's least time on ONE chip by the mean of the
planes' time: the share of one chip's peak.  This kind holds the same work
(``work`` names the function, as there: the cell's logical sizes, never a
padded or a per-shard shape) to ``chips`` times the peak, against the mean of
the planes' time a run:

    100 x (least seconds on one chip / chips) / (plane seconds / plane runs)

``chips`` is the number of device planes that ran anything in the traced
window (``trace["devices"]``).  No trace, no run of a matching program, or a
program from before the mesh path (whose trace names none): None, and the
metric is left out.
"""

from __future__ import annotations

import trace_reduce
import work


def read(spec: dict, ctx: dict):
    trace = ctx["trace"]
    if trace is None:
        return None
    runs, seconds = trace_reduce.module_time(trace, spec["pattern"])
    chips = trace.get("devices", 0)
    if not runs or seconds <= 0 or not chips:
        return None
    least, _bound = work.roofline_seconds(
        work.function(spec["work"])(ctx["sizes"]), work.peaks(ctx["device_kind"]))
    return 100.0 * (least / chips) / (seconds / runs)
