"""Partition digests: the compact remote-partition summaries
scatter/gather serving answers from (docs/sharding.md "Digest
staleness contract").

A :class:`PartitionDigest` is everything one partition owner needs to
publish for OTHER replicas to answer verbs about its nodes without
holding its telemetry:

  * per-metric TOP-K candidate summaries — the k lowest and k highest
    milli values with their node names (both ends, because the
    scheduleonmetric operator decides which end ranks best);
  * the per-policy dontschedule VIOLATOR set — violators are the only
    remote facts Filter needs, and they are sparse;
  * the partition's universe digest (FNV over the sorted member names)
    + node count, so a gatherer can tell how much of the partition the
    top-k actually covers;
  * mirror ``version``, ownership ``epoch``, and a clock ``stamp``.

The :class:`DigestStore` enforces the two safety rules at the edges:
INGEST rejects digests stamped under an older ownership epoch than the
coordinator's journal shows (a fenced-out owner's view must never reach
a verdict — the handoff invariant the twin audits), and LOOKUP refuses
digests older than the staleness bound (serving then fails open to
local-only answers and publishes ``digest_stale`` into the event spine).

Gossip is pull-based over the existing HTTP plane: each replica's
refresh pass GETs its peers' ``/debug/shard`` and ingests the digests
found there — one endpoint serves both the human and the fleet.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from platform_aware_scheduling_tpu.kube.retry import stable_hash
from platform_aware_scheduling_tpu.ops.rules import (
    OP_EQUALS,
    OP_GREATER_THAN,
    OP_LESS_THAN,
)
from platform_aware_scheduling_tpu.utils import events, klog

#: per-metric candidate summaries carried in each partition digest (k
#: lowest + k highest); the budget controller's per-partition shed knob
#: steps this down under freshness burn
DEFAULT_TOPK = 16
#: digest staleness bound, seconds: a remote digest older than this
#: stops serving and the gather fails open to local-only answers
DEFAULT_STALE_S = 30.0

#: digest schema version: what a gossip pull must find in ``format``
DIGEST_FORMAT = "pas-shard-digest/1"


class PartitionDigest:
    """One partition's published summary; a plain value object so it
    round-trips /debug/shard JSON losslessly."""

    def __init__(
        self,
        partition: int,
        owner: str,
        epoch: int,
        version: int,
        stamp: float,
        node_count: int,
        universe: int,
        topk: Dict[str, Dict[str, int]],
        violations: Dict[str, List[str]],
    ):
        self.partition = int(partition)
        self.owner = owner
        self.epoch = int(epoch)
        self.version = int(version)
        self.stamp = float(stamp)
        self.node_count = int(node_count)
        self.universe = int(universe)
        #: metric -> {node: milli} — the k lowest + k highest values
        self.topk = topk
        #: policy name -> violating node names (dontschedule, any rule)
        self.violations = violations

    def to_obj(self) -> Dict:
        return {
            "format": DIGEST_FORMAT,
            "partition": self.partition,
            "owner": self.owner,
            "epoch": self.epoch,
            "version": self.version,
            "stamp": self.stamp,
            "node_count": self.node_count,
            "universe": self.universe,
            "topk": self.topk,
            "violations": self.violations,
        }

    @classmethod
    def from_obj(cls, obj: Dict) -> Optional["PartitionDigest"]:
        if obj.get("format") != DIGEST_FORMAT:
            return None
        try:
            return cls(
                partition=int(obj["partition"]),
                owner=str(obj.get("owner", "")),
                epoch=int(obj.get("epoch", 0)),
                version=int(obj.get("version", 0)),
                stamp=float(obj.get("stamp", 0.0)),
                node_count=int(obj.get("node_count", 0)),
                universe=int(obj.get("universe", 0)),
                topk={
                    str(m): {str(n): int(v) for n, v in entries.items()}
                    for m, entries in (obj.get("topk") or {}).items()
                },
                violations={
                    str(p): [str(n) for n in names]
                    for p, names in (obj.get("violations") or {}).items()
                },
            )
        except Exception:
            return None


def universe_digest(names: Sequence[str]) -> int:
    """Order-independent FNV digest of a partition's member names —
    cheap change detection for a gatherer (the same stable_hash the
    partition math rides, folded over the sorted list)."""
    h = 2166136261
    for name in sorted(names):
        h = (h ^ stable_hash(name)) * 16777619 & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def _rule_violations(values: np.ndarray, present: np.ndarray, ruleset) -> np.ndarray:
    """Bool mask of columns violating ANY active rule of one compiled
    dontschedule ruleset (host-side twin of the device kernel's compare:
    same milli domain, same operators)."""
    out = np.zeros(values.shape[1], dtype=bool)
    for i in range(len(ruleset.active)):
        if not ruleset.active[i]:
            continue
        row = int(ruleset.metric_rows[i])
        op = int(ruleset.op_ids[i])
        target = int(ruleset.targets[i])
        if row < 0 or row >= values.shape[0]:
            continue
        vals = values[row]
        if op == OP_GREATER_THAN:
            hit = vals > target
        elif op == OP_LESS_THAN:
            hit = vals < target
        elif op == OP_EQUALS:
            hit = vals == target
        else:
            continue  # unknown operator: host-only policy, never digested
        out |= hit & present[row]
    return out


def build_partition_digests(
    mirror,
    pmap,
    owned,
    identity: str,
    epoch_of: Callable[[int], int],
    topk_of: Callable[[int], int] = lambda p: DEFAULT_TOPK,
    clock: Callable[[], float] = time.monotonic,
) -> List[PartitionDigest]:
    """One digest per OWNED partition from the mirror's current
    snapshot.  Runs on the refresh thread (the same cadence the fastpath
    warms on), so the per-pass cost is one policies_snapshot plus numpy
    over the owned columns — never on a request."""
    policies, view, host_only = mirror.policies_snapshot()
    if view.values_milli is None or view.metric_index is None:
        return []
    groups = pmap.group(view.node_names)
    values = view.values_milli
    present = np.asarray(view.present)
    # per-policy violator masks once, shared across partitions; host-only
    # policies are excluded — their exact-Quantity semantics never made
    # it into the milli matrix, so a digest would misjudge them (the
    # gatherer fails open to local-only answers for those pods)
    violation_masks: Dict[str, np.ndarray] = {}
    for (_ns, name), compiled in policies.items():
        ruleset = compiled.dontschedule
        if ruleset is None or ruleset.host_only:
            continue
        if any(m in host_only and host_only[m] for m in ruleset.metric_names):
            continue
        violation_masks[name] = _rule_violations(values, present, ruleset)
    digests: List[PartitionDigest] = []
    for p in sorted(owned):
        names = groups.get(p, [])
        cols = np.fromiter(
            (view.node_index[n] for n in names), dtype=np.int64,
            count=len(names),
        )
        topk: Dict[str, Dict[str, int]] = {}
        k = max(1, int(topk_of(p)))
        for metric, row in view.metric_index.items():
            if row >= values.shape[0] or len(cols) == 0:
                continue
            live = cols[present[row, cols]]
            if len(live) == 0:
                continue
            vals = values[row, live]
            order = np.argsort(vals, kind="stable")
            pick = (
                np.concatenate([order[:k], order[-k:]])
                if len(order) > 2 * k
                else order
            )
            topk[metric] = {
                view.node_names[int(live[i])]: int(vals[int(i)])
                for i in pick
            }
        violations = {
            policy: [
                view.node_names[int(c)] for c in cols if mask[int(c)]
            ]
            for policy, mask in violation_masks.items()
        }
        digests.append(
            PartitionDigest(
                partition=p,
                owner=identity,
                epoch=epoch_of(p),
                version=view.partition_version(p),
                stamp=clock(),
                node_count=len(names),
                universe=universe_digest(names),
                topk=topk,
                violations={
                    pol: nodes for pol, nodes in violations.items() if nodes
                },
            )
        )
    return digests


class DigestStore:
    """Fenced, staleness-bounded digest shelf: one slot per partition.

    ``put`` ingests local publishes and gossip pulls alike, rejecting
    anything stamped under an older epoch than the coordinator's
    journal shows for that partition (counted + published as
    ``digest_fenced``).  ``fresh`` answers serving lookups, returning
    None — fail open — past the staleness bound (counted + published
    edge-triggered as ``digest_stale``)."""

    def __init__(
        self,
        epoch_of: Callable[[int], int],
        stale_after_s: float = DEFAULT_STALE_S,
        clock: Callable[[], float] = time.monotonic,
        counters=None,
    ):
        self.epoch_of = epoch_of
        self.stale_after_s = float(stale_after_s)
        self.clock = clock
        self.counters = counters
        self._lock = threading.Lock()
        self._digests: Dict[int, PartitionDigest] = {}
        self._stale_flagged: Dict[int, bool] = {}
        self.fenced_rejects = 0

    def _count(self, name: str, labels: Optional[Dict[str, str]] = None) -> None:
        if self.counters is not None:
            self.counters.inc(name, labels=labels or {})

    def put(self, digest: PartitionDigest) -> bool:
        known_epoch = self.epoch_of(digest.partition)
        if digest.epoch < known_epoch:
            with self._lock:
                self.fenced_rejects += 1
            self._count(
                "pas_shard_digest_fenced_total",
                {"partition": str(digest.partition)},
            )
            events.JOURNAL.publish(
                "shard",
                "digest_fenced",
                data={
                    "partition": digest.partition,
                    "owner": digest.owner,
                    "epoch": digest.epoch,
                    "current_epoch": known_epoch,
                },
            )
            return False
        with self._lock:
            held = self._digests.get(digest.partition)
            if held is not None and (
                held.epoch > digest.epoch
                or (held.epoch == digest.epoch and held.stamp > digest.stamp)
            ):
                return False  # never replace newer with older
            self._digests[digest.partition] = digest
            self._stale_flagged[digest.partition] = False
        return True

    def fresh(self, partition: int) -> Optional[PartitionDigest]:
        """The partition's digest if it is live under BOTH safety rules
        (current epoch, inside the staleness bound); None fails open."""
        now = self.clock()
        with self._lock:
            digest = self._digests.get(int(partition))
        if digest is None:
            return None
        if digest.epoch < self.epoch_of(digest.partition):
            return None  # fenced since ingest (handoff mid-shelf-life)
        age = now - digest.stamp
        if age > self.stale_after_s:
            flag = False
            with self._lock:
                if not self._stale_flagged.get(digest.partition, False):
                    self._stale_flagged[digest.partition] = True
                    flag = True
            if flag:  # edge-triggered: one event per staleness episode
                self._count(
                    "pas_shard_digest_stale_total",
                    {"partition": str(digest.partition)},
                )
                events.JOURNAL.publish(
                    "shard",
                    "digest_stale",
                    data={
                        "partition": digest.partition,
                        "owner": digest.owner,
                        "age_s": round(age, 3),
                        "replica": digest.owner,
                    },
                )
            return None
        return digest

    def has_violations(self, exclude=frozenset()) -> bool:
        """True when any STORED digest outside ``exclude`` carries a
        non-empty violator set — the shard plane's gate for the native
        filter fastpath (plane.remote_holds_possible).  Deliberately
        ignores staleness and fencing: a digest those rules would refuse
        keeps this True, which only sends requests down the slower
        reviewed path (review_filter then fails open properly) — never
        the other way around."""
        with self._lock:
            return any(
                d.violations
                for p, d in self._digests.items()
                if p not in exclude
            )

    def ages(self) -> Dict[int, float]:
        now = self.clock()
        with self._lock:
            return {
                p: round(now - d.stamp, 3) for p, d in self._digests.items()
            }

    def snapshot(self) -> Dict:
        with self._lock:
            digests = dict(self._digests)
            fenced = self.fenced_rejects
        now = self.clock()
        return {
            "stale_after_s": self.stale_after_s,
            "fenced_rejects": fenced,
            "digests": {
                str(p): dict(d.to_obj(), age_s=round(now - d.stamp, 3))
                for p, d in sorted(digests.items())
            },
        }


class ShardGossip:
    """Pull-based digest exchange over the existing HTTP plane.

    Peers are either base URLs (``http://host:port`` — a real GET of
    ``/debug/shard`` with a short timeout, for the multi-process bench
    and production) or zero-arg callables returning the same JSON (the
    in-process harness/twin).  Each pull ingests every digest found —
    the store's epoch fencing and freshness rules decide what sticks.

    The gossip path is fault-injectable like every other verb: set
    ``fault_plan`` (a ``testing.faults.FaultPlan``) and the pull
    consults verb ``"shard_gossip"`` once per peer — an erroring fault
    is a failed pull (the peer went dark mid-exchange), a latency fault
    advances ``fault_clock`` before the fetch (a slow peer ages the
    digests it delivers), and a ``truncate`` fault keeps only the first
    N digests of the payload (a cut-off answer; whatever survives
    merges normally)."""

    #: the FaultPlan verb name the pull consumes, one entry per peer
    FAULT_VERB = "shard_gossip"

    def __init__(
        self,
        store: DigestStore,
        peers: Sequence = (),
        timeout_s: float = 1.0,
        fault_plan=None,
        fault_clock=None,
    ):
        self.store = store
        self.peers = list(peers)
        self.timeout_s = float(timeout_s)
        self.fault_plan = fault_plan
        self.fault_clock = fault_clock
        self.pulls_ok = 0
        self.pulls_failed = 0

    def _fetch(self, peer) -> Optional[Dict]:
        if callable(peer):
            payload = peer()
            if isinstance(payload, (bytes, str)):
                return json.loads(payload)
            return payload
        url = f"{str(peer).rstrip('/')}/debug/shard"
        with urllib.request.urlopen(url, timeout=self.timeout_s) as resp:
            return json.loads(resp.read())

    def pull(self) -> int:
        """One gossip round: returns how many digests were ingested.
        Never raises — a dead peer costs one failed-pull count."""
        ingested = 0
        for peer in self.peers:
            fault = None
            if self.fault_plan is not None:
                fault = self.fault_plan.next(self.FAULT_VERB)
            if fault is not None and fault.latency_s and (
                self.fault_clock is not None
            ):
                self.fault_clock.advance(fault.latency_s)
            if fault is not None and fault.exc_factory is not None:
                self.pulls_failed += 1
                klog.v(2).info_s(
                    "shard gossip pull failed: injected fault",
                    component="shard",
                )
                continue
            try:
                obj = self._fetch(peer)
            except Exception as exc:
                self.pulls_failed += 1
                klog.v(2).info_s(
                    f"shard gossip pull failed: {exc}", component="shard"
                )
                continue
            self.pulls_ok += 1
            digests = (obj or {}).get("digests") or {}
            items = list(digests.values())
            if fault is not None and fault.truncate is not None:
                # deterministic cut: partition order, first ``keep``
                items = sorted(
                    items, key=lambda raw: raw.get("partition", -1)
                )[: fault.truncate]
            for raw in items:
                digest = PartitionDigest.from_obj(raw)
                if digest is not None and self.store.put(digest):
                    ingested += 1
        return ingested

    def snapshot(self) -> Dict:
        return {
            "peers": len(self.peers),
            "pulls_ok": self.pulls_ok,
            "pulls_failed": self.pulls_failed,
        }
