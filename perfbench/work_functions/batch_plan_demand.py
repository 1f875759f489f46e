"""The least an exact greedy-in-order plan over pods of UNLIKE requests has
to move, from the cell's logical sizes alone: the score rows of the D
distinct policies (per node an int64 value and a presence flag: 9 bytes),
the nodes' room as R rows of exact integers (``room_bytes`` a node: 4 where
one int32 limb holds a row, as the configuration's whole-unit quantities
do), and per pending pod its R demands read and one int32 result written;
per pod R compares against its policy's current best node and R
subtractions.  Never a [pods, nodes] tensor, and never a padded shape: a
later PR that stops materialising the keys, or widens the kernel, is held to
the same yardstick.  ``pending_mean`` is the mean number of pods pending at
the window's replans, as the harness counted them.
"""

from __future__ import annotations

import math


def work(sizes: dict) -> dict:
    n, d, p = sizes["nodes"], sizes["policies"], sizes["pending_mean"]
    r, width = sizes["resources"], sizes["room_bytes"]
    return {
        "bytes": d * n * 9 + r * n * width + p * (1 + r) * 4,
        # ranking D rows once (a comparison sort), then R compares and R
        # subtractions a pod
        "ops": d * n * math.log2(max(n, 2)) + 2 * r * p,
    }
