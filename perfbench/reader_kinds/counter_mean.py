"""The ``counter_mean`` reader kind: 1e3 x the window's increase of a seconds
counter over that of a count counter — the mean milliseconds of whatever the
pair counts, over the whole window (a ``trace_stage_mean`` is a mean over the
ring's last 256 requests).  ``seconds`` and ``count`` each name one or more
counters of /metrics.  Nothing counted: None, and the metric is left out.
"""

from __future__ import annotations


def read(spec: dict, ctx: dict):
    before, after = ctx["counters"]

    def delta(names) -> float:
        return sum(after.get(n, 0.0) - before.get(n, 0.0) for n in names)

    count = delta(spec["count"])
    return 1e3 * delta(spec["seconds"]) / count if count > 0 else None
