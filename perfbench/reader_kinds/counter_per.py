"""The ``counter_per`` reader kind: the window's increase of some counters over
that of others, with no factor — so many of one thing for each of another
(device calls a publish).  A ``counter_ratio`` multiplies by 100 and a
``counter_mean`` by 1e3; this one by nothing.  ``numerator`` and
``denominator`` each name one or more counters of /metrics.  A denominator
that did not move: None, and the metric is left out.
"""

from __future__ import annotations


def read(spec: dict, ctx: dict):
    before, after = ctx["counters"]

    def delta(names) -> float:
        return sum(after.get(n, 0.0) - before.get(n, 0.0) for n in names)

    below = delta(spec["denominator"])
    return delta(spec["numerator"]) / below if below > 0 else None
