"""The work a device solve has to do, from the cell's logical sizes — never
from a kernel's padded shapes — so that a later PR which swaps the kernel
under the same jitted program is held to the same yardstick.

Each function returns ``{"bytes": ..., "ops": ...}`` for ONE run of the
solve.  ``roofline_seconds`` turns that into the least time the chip could
take; which of the two bounds applies is returned with it.
"""

from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def rank(sizes: dict) -> dict:
    """Ordinal ranking of every node on one metric row (what Prioritize's
    refresh-time solve produces): read an int64 value and a presence flag per
    node, write an int32 rank per node; a comparison sort's N log2 N compares."""
    n = sizes["nodes"]
    return {"bytes": n * (8 + 1 + 4), "ops": n * math.log2(max(n, 2))}


def binpack(sizes: dict) -> dict:
    """First fit of one pod on each candidate node: per candidate read the
    used amounts of each card for each resource and the per-card capacity
    (int64), write one verdict; per requested GPU share one compare-and-add
    per card and resource."""
    c, cards, r = sizes["candidates"], sizes["cards_mean"], sizes["resources"]
    return {
        "bytes": c * (cards * r * 8 + r * 8 + 1),
        "ops": c * sizes["shares_mean"] * cards * r * 2,
    }


WORK = {"rank": rank, "binpack": binpack}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as handle:
        table = json.load(handle)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in perfbench/peaks.json")
    return table[device_kind]


def roofline_seconds(work: dict, peak: dict) -> tuple:
    """(least seconds one run could take, which bound sets it)."""
    by_bytes = work["bytes"] / peak["bytes_per_s"]
    by_ops = work["ops"] / peak["flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")
