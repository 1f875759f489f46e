"""The ``stage_mean`` reader kind: mean milliseconds of one span stage over the
served verbs — over the whole window where the program counts the stage's
seconds as spans finish, else over the ring's end.

``stage`` names the stage, ``seconds`` and ``count`` the counter families of
/metrics that sum it.  Where the scrape has every ``count`` family and it
moved inside the window the value is ``1e3 x seconds' increase / count's``
(a ``counter_mean``: every span of the window that carried the stage).  A
program from before those counters, or a window in which they stood still,
gives the mean over the ``/debug/traces`` ring's last 256 requests, exactly as
the built-in ``trace_stage_mean`` computes it: the same quantity on the sample
the program offers.  Neither: None, and the metric is left out.
"""

from __future__ import annotations

import plugins
import readers


def read(spec: dict, ctx: dict):
    # a family the scrape lacks counts as one that stood still: nothing
    # counted, and the ring is read
    value = plugins.load("reader_kinds", "counter_mean").read(spec, ctx)
    return value if value is not None else readers.trace_stage_mean(spec, ctx)
