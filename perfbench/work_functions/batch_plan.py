"""The least an exact greedy-in-order plan has to move, from the cell's
logical sizes alone: the score rows of the D distinct policies (per node an
int64 value and a presence flag: 9 bytes), the nodes' room (int32) and one
int32 result per pending pod; per pod a compare against its policy's current
best node and a decrement.  Never a [pods, nodes] tensor: that is one
implementation's choice, and a later PR that stops materialising it is held
to the same yardstick.  ``pending_mean`` is the mean number of pods pending
at the window's replans, as the harness counted them.
"""

from __future__ import annotations

import math


def work(sizes: dict) -> dict:
    n, d, p = sizes["nodes"], sizes["policies"], sizes["pending_mean"]
    return {
        "bytes": d * n * 9 + n * 4 + p * 4,
        # ranking D rows once (a comparison sort), then a step a pod
        "ops": d * n * math.log2(max(n, 2)) + 2 * p,
    }
