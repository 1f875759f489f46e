"""Drift detection with hysteresis over per-cycle violation sets.

One transiently hot scrape must not evict anything: a node becomes an
eviction candidate only after K CONSECUTIVE enforcement cycles in the
violation set (the deschedule strategy publishes its node -> [policies]
map every cycle, empty included).  A cycle in which the node is absent
resets its streak to zero — recovery is immediate, escalation is slow,
which is the asymmetry a safe eviction loop wants.

With forecasting on (docs/forecast.md), the loop additionally passes a
``hold`` set: nodes violating NOW whose violated metrics are all
trending back DOWN (a transient spike mid-resolution).  A held node's
streak neither advances (the spike is not evidence of drift) nor resets
(it is still violating) — so a spike that self-resolves never reaches
the eviction threshold, while a genuine trend keeps escalating at the
same speed as before.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, List

#: consecutive violating enforcement cycles before a node becomes an
#: eviction candidate
DEFAULT_HYSTERESIS_CYCLES = 3


class DriftDetector:
    """Streak counter over violation cycles.  Not thread-safe on its own;
    the rebalance loop calls :meth:`observe` from the single enforcement
    thread that publishes violations."""

    def __init__(self, k: int = DEFAULT_HYSTERESIS_CYCLES):
        self.k = k
        self._streaks: Dict[str, int] = {}

    @property
    def k(self) -> int:
        return self._k

    @k.setter
    def k(self, value: int) -> None:
        # mutated at runtime by the budget controller
        # (loop.set_aggressiveness); a bad write must never silently
        # disable hysteresis, so the invariant holds at every assignment
        if value < 1:
            raise ValueError(f"hysteresis cycles must be >= 1, got {value}")
        self._k = int(value)

    def observe(
        self,
        violations: Dict[str, List[str]],
        hold: FrozenSet[str] = frozenset(),
    ) -> Dict[str, List[str]]:
        """Fold one enforcement cycle in; returns the candidate map
        (node -> policies violated this cycle) for nodes whose streak has
        reached K.  Nodes in ``hold`` (violating but trending down) keep
        their prior streak instead of advancing, AND are never candidates
        this cycle regardless of streak — a node whose eviction was
        deferred at streak K and is now resolving on its own is exactly
        the useless eviction the hold exists to prevent."""
        streaks: Dict[str, int] = {}
        for node in violations:
            prior = self._streaks.get(node, 0)
            streaks[node] = prior if node in hold else prior + 1
        # nodes absent from this cycle's set simply drop out: streak reset
        self._streaks = streaks
        if hold:
            return {
                node: policies
                for node, policies in violations.items()
                if streaks[node] >= self.k and node not in hold
            }
        return {
            node: list(policies)
            for node, policies in violations.items()
            if streaks[node] >= self.k
        }

    def streaks(self) -> Dict[str, int]:
        """Current per-node consecutive-violation counts (for /debug)."""
        return dict(self._streaks)

    def reset(self) -> None:
        self._streaks = {}
