"""ShardPlane: the partition plane's single facade (docs/sharding.md).

One object behind ``MetricsExtender.shard`` (None by default — off path
constructs nothing and the wire stays byte-identical, pinned).  It owns
the four collaborators — :class:`PartitionMap` (pure math),
:class:`HandoffCoordinator` (journaled, fenced ownership),
:class:`DigestStore` + :class:`ShardGossip` (remote summaries) — and
exposes exactly three integration surfaces:

  * ``on_refresh_pass``: appended to the cache's refresh hooks, so every
    telemetry pass drives one coordination tick, one digest publish, and
    one gossip round — no new threads, fake-clock friendly;
  * ``refresh_filter`` / mirror partition scope: the ~1/P ingest cut —
    the cache fetches the metrics API result and drops non-owned nodes
    before they are written or interned;
  * ``review_filter`` / ``gather_prioritize``: scatter/gather serving —
    the local partition's solve merged with fresh remote digests, failing
    OPEN to local-only answers whenever a digest is missing, stale, or
    fenced (a degraded answer beats a wrong or absent one; the staleness
    event spine makes the degradation observable).

Gang slices that straddle partitions resolve through the owner of the
ANCHOR partition — the partition of the gang's first-listed node — which
serves the whole slice from its local view plus digests like any other
verb (no cross-owner two-phase anything; see docs/sharding.md
"Straddling gangs").
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from platform_aware_scheduling_tpu.shard.digest import (
    DEFAULT_STALE_S,
    DEFAULT_TOPK,
    DigestStore,
    ShardGossip,
    build_partition_digests,
)
from platform_aware_scheduling_tpu.shard.partition import (
    DEFAULT_CONFIGMAP,
    DEFAULT_MEMBER_TTL_S,
    HandoffCoordinator,
    PartitionMap,
)
from platform_aware_scheduling_tpu.utils.tracing import CounterSet


class ShardPlane:
    """Everything sharded serving needs, behind one attribute.

    Construction wires nothing into the extender — the cmd layer (or the
    HA harness) calls :meth:`attach` so tests can build a plane and
    inspect it without touching a live cache."""

    def __init__(
        self,
        identity: str,
        partitions: int,
        kube_client,
        namespace: str = "default",
        configmap: str = DEFAULT_CONFIGMAP,
        leadership=None,
        peers: Sequence = (),
        stale_after_s: float = DEFAULT_STALE_S,
        member_ttl_s: float = DEFAULT_MEMBER_TTL_S,
        gossip_timeout_s: float = 1.0,
        clock: Callable[[], float] = time.monotonic,
        static_owners: Optional[Dict[int, str]] = None,
    ):
        self.identity = identity
        self.clock = clock
        self.counters = CounterSet()
        self.pmap = PartitionMap(partitions)
        self.coordinator = HandoffCoordinator(
            kube_client,
            identity=identity,
            partitions=partitions,
            namespace=namespace,
            name=configmap,
            leadership=leadership,
            member_ttl_s=member_ttl_s,
            clock=clock,
            static_owners=static_owners,
        )
        self.store = DigestStore(
            epoch_of=self.coordinator.epoch,
            stale_after_s=stale_after_s,
            clock=clock,
            counters=self.counters,
        )
        self.gossip = ShardGossip(
            self.store, peers=peers, timeout_s=gossip_timeout_s
        )
        self._topk_lock = threading.Lock()
        #: per-partition top-k width — the controller's shed surface
        #: (attach_shard ladders these down under pressure)
        self._topk: Dict[int, int] = {}
        self.mirror = None
        self.cache = None
        #: count of gather attempts refused because the needed remote
        #: digest was missing/stale/fenced (the twin's fenced-verdict
        #: audit reads this: it must stay 0 for FENCED digests to have
        #: influenced any verdict — staleness fails open to local-only)
        self.gather_local_only = 0
        self._seeded = False

    # -- wiring ----------------------------------------------------------------

    def attach(self, cache, mirror) -> None:
        """Wire the ~1/P ingest cut and the per-pass driver.  The mirror
        keeps interning ONLY owned nodes; the cache drops non-owned nodes
        between fetch and write."""
        self.cache = cache
        self.mirror = mirror
        mirror.set_partition_scope(self.pmap, self.coordinator.owned)
        cache.refresh_filter = self._filter_refresh
        cache.on_refresh_pass.append(self.on_refresh_pass)
        # initial ownership before the first refresh pass, so a cold
        # replica doesn't ingest the full world for one period
        self.coordinator.tick()

    def _filter_refresh(self, info: Optional[Dict[str, object]]):
        """cache.refresh_filter hook: keep only owned nodes from one
        fetched metric map, counting both sides so the bench can report
        the measured per-replica refresh volume (~1/P of the world)."""
        if not info:
            return info
        owned = self.coordinator.owned()
        kept = {
            name: value
            for name, value in info.items()
            if self.pmap.partition_of(name) in owned
        }
        skipped = len(info) - len(kept)
        if kept:
            self.counters.inc(
                "pas_shard_refresh_nodes_total",
                by=len(kept),
                labels={"scope": "owned"},
            )
        if skipped:
            self.counters.inc(
                "pas_shard_refresh_nodes_total",
                by=skipped,
                labels={"scope": "skipped"},
            )
        return kept

    def on_refresh_pass(self) -> None:
        """The per-pass driver (cache.on_refresh_pass): coordination
        tick, digest publish for owned partitions, one gossip round.
        Rides the refresh thread — no new threads, and the fake clock
        that steps the cache steps this."""
        self.coordinator.tick()
        self.publish_digests()
        try:
            ingested = self.gossip.pull()
        except Exception:
            ingested = 0
        if ingested:
            self.counters.inc("pas_shard_gossip_ingested_total", by=ingested)
        self.counters.inc("pas_shard_ticks_total")

    def publish_digests(self) -> int:
        """Build + ingest this replica's own digests (local partitions
        answer from the same store remote ones land in — one lookup path
        for the gatherer)."""
        if self.mirror is None:
            return 0
        digests = build_partition_digests(
            self.mirror,
            self.pmap,
            self.coordinator.owned(),
            identity=self.identity,
            epoch_of=self.coordinator.epoch,
            topk_of=self.topk_for,
            clock=self.clock,
        )
        stored = 0
        for digest in digests:
            if self.store.put(digest):
                stored += 1
        if stored:
            self.counters.inc("pas_shard_digests_published_total", by=stored)
        return stored

    # -- controller surface ----------------------------------------------------

    def topk_for(self, partition: int) -> int:
        with self._topk_lock:
            return self._topk.get(int(partition), DEFAULT_TOPK)

    def set_topk(self, partition: int, k: int) -> None:
        with self._topk_lock:
            self._topk[int(partition)] = max(1, int(k))

    def default_topk(self) -> int:
        return DEFAULT_TOPK

    # -- scatter/gather serving ------------------------------------------------

    def review_filter(self, policy_name: str, node_names: Sequence[str]):
        """Filter gather: (held, consulted) — nodes among ``node_names``
        that REMOTE partitions' fresh digests list as violators of
        ``policy_name``, plus how many remote partitions answered.  A
        missing/stale/fenced digest contributes nothing (fail open): its
        nodes pass filter on remote facts and the local verdict stands.

        The loop runs over the P-|owned| remote PARTITIONS, not the
        candidate names: violator sets are sparse and a digest only ever
        carries its own partition's nodes, so intersecting each set
        against the request gives the identical held set without hashing
        every candidate on the verb path (at 10k candidates that walk
        alone costs more than the whole native filter).  Consequence:
        ``gather_local_only`` counts every remote partition missing a
        fresh digest per review — whether or not the request carried
        nodes of that partition (a scheduler's candidate list spans the
        universe, so in practice these coincide)."""
        owned = self.coordinator.owned()
        held: List[str] = []
        consulted = 0
        requested = None
        for partition in range(self.pmap.partitions):
            if partition in owned:
                continue  # local solve already judged these
            digest = self.store.fresh(partition)
            if digest is None:
                self.gather_local_only += 1
                self.counters.inc(
                    "pas_shard_gather_local_only_total",
                    labels={"verb": "filter"},
                )
                continue
            consulted += 1
            violators = digest.violations.get(policy_name, ())
            if not violators:
                continue
            if requested is None:
                requested = set(node_names)
            held.extend(n for n in violators if n in requested)
        if held:
            self.counters.inc(
                "pas_shard_gather_held_total", by=len(held)
            )
        return held, consulted

    def gather_metric(
        self, metric_name: str, node_names: Sequence[str]
    ) -> Optional[Dict[str, int]]:
        """Prioritize gather: {node: milli} for ``node_names`` merged
        from the local partitions' mirror values and remote digests'
        top-k summaries.  Returns None when the LOCAL view is unusable
        (caller falls through to the full-world host path).  Nodes a
        fresh remote digest doesn't carry in its top-k are simply absent
        — identical to the host path's treatment of nodes missing from
        metric data, so mid-pack nodes rank below every summarized one
        rather than wrongly."""
        if self.mirror is None:
            return None
        _policies, view, _host_only = self.mirror.policies_snapshot()
        if view.values_milli is None or view.metric_index is None:
            return None
        row = view.metric_index.get(metric_name)
        owned = self.coordinator.owned()
        merged: Dict[str, int] = {}
        # one host-side copy of the presence matrix: indexing the device
        # array would dispatch a jax op per access (and compile on the
        # first verb — a 40 ms tail the p99 SLO sees).  np.asarray is a
        # pure device->host transfer, no traced op, and the matrix is
        # bools at metrics x nodes — small next to the verb's own body.
        present_row = (
            np.asarray(view.present)[row] if row is not None else None
        )
        for partition, names in self.pmap.group(list(node_names)).items():
            if partition in owned:
                if row is None:
                    continue
                for name in names:
                    col = view.node_index.get(name)
                    if col is not None and bool(present_row[col]):
                        merged[name] = int(view.values_milli[row, col])
                continue
            digest = self.store.fresh(partition)
            if digest is None:
                self.gather_local_only += 1
                self.counters.inc(
                    "pas_shard_gather_local_only_total",
                    labels={"verb": "prioritize"},
                )
                continue
            summary = digest.topk.get(metric_name, {})
            for name in names:
                if name in summary:
                    merged[name] = summary[name]
        return merged

    def remote_holds_possible(self) -> bool:
        """False when NO remote partition's stored digest lists a single
        violator — then the merged Filter verdict provably equals the
        local one for every possible candidate set, and the verb may
        serve through the native fastpath (span cache + native miss
        encode) exactly as full-world mode does.  O(P) dict walk, no
        per-candidate work.  Own-partition digests are excluded: their
        violators are the local solve's own facts, already in the local
        verdict.  Conservative on every edge — a stale or fenced-since-
        ingest digest keeps this True (the reviewed path then fails open
        properly), and ownership changes surface here the same pass the
        coordinator ticks them."""
        return self.store.has_violations(exclude=self.coordinator.owned())

    def anchor_partition(self, node_names: Sequence[str]) -> Optional[int]:
        """A straddling gang's resolution partition: the partition of the
        slice's FIRST node (deterministic for a node list, so every
        front-end routes the same slice to the same owner)."""
        for name in node_names:
            return self.pmap.partition_of(name)
        return None

    def owns_anchor(self, node_names: Sequence[str]) -> bool:
        anchor = self.anchor_partition(node_names)
        return anchor is None or anchor in self.coordinator.owned()

    # -- observability ---------------------------------------------------------

    def status(self) -> Dict:
        return {
            "identity": self.identity,
            "partitions": self.pmap.partitions,
            "coordinator": self.coordinator.snapshot(),
            "gossip": self.gossip.snapshot(),
            "gather_local_only": self.gather_local_only,
            "topk": {
                "default": DEFAULT_TOPK,
                "overrides": dict(self._topk),
            },
            **self.store.snapshot(),
        }

    def to_json(self) -> bytes:
        import json

        return (json.dumps(self.status(), sort_keys=True) + "\n").encode()
