"""The least one run of the gang reservation's slice solve (one orientation,
every ICI domain) has to move, from the cell's logical sizes alone: the free
mask at one byte a host — ``nodes`` hosts, never the padded domains' grids —
and the best anchor it returns, four int32s.  Its operations are the window
sums' adds over the same cells: for the window and for the window one cell
wider, two prefix-sum adds a cell and the four-term window sum's three, then
the ring's subtraction and the fold to the best anchor, a cell each.
"""

from __future__ import annotations


def work(sizes: dict) -> dict:
    cells = sizes["nodes"]
    return {"bytes": cells + 4 * 4, "ops": cells * (2 * (2 + 3) + 2)}
