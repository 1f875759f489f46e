"""Consistent-hash partition map + fenced ownership coordination
(docs/sharding.md "Partition math" and "Ownership & fencing").

The map is pure math: ``stable_hash(node) % P`` (kube/retry.py's FNV-1a
— process-independent, so every replica, the bench subprocesses, and the
twin all agree on which partition any node lives in without exchanging a
byte).

Ownership is state: the :class:`HandoffCoordinator` journals
``partition -> (replica, epoch)`` into one ConfigMap, the same machinery
the gang journal rides.  Replicas heartbeat their membership; the
DESIRED owner of each partition is the rendezvous (highest-random-weight)
winner among live members, so every replica computes the same assignment
from the same journal and concurrent writers converge instead of
fighting.  Every ownership change bumps the partition's EPOCH — the
per-partition fencing token: a digest stamped under an older epoch is
rejected at ingest (shard/digest.py), so a fenced-out owner's view can
never reach a verdict after handoff.  With a lease elector wired, only
the current leader REASSIGNS (followers just heartbeat) — handoff rides
the existing leader-election machinery and survives leader change like
every other singleton loop.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Callable, Dict, FrozenSet, List, Optional, Sequence

from platform_aware_scheduling_tpu.kube.retry import stable_hash
from platform_aware_scheduling_tpu.utils import events, klog

#: ownership journal schema version (the ConfigMap ``state`` key)
OWNERS_FORMAT = "pas-shard-owners/1"

DEFAULT_CONFIGMAP = "pas-shard-partitions"
#: membership heartbeat TTL, seconds: a replica silent for longer drops
#: from the rendezvous and its partitions hand off
DEFAULT_MEMBER_TTL_S = 15.0


class PartitionMap:
    """Pure consistent-hash node -> partition assignment: no state, no
    coordination — every holder of the same P computes the same map."""

    def __init__(self, partitions: int):
        if int(partitions) < 1:
            raise ValueError(f"partitions must be >= 1, got {partitions}")
        self.partitions = int(partitions)
        # per-name memo: partition_of is pure in (name, P) and group()
        # runs on the request path over every candidate name — at 10k
        # nodes the FNV walk alone costs milliseconds per verb, the memo
        # a dict probe.  Bounded by the node universe; a benign write
        # race re-stores the identical value.
        self._memo: Dict[str, int] = {}

    def partition_of(self, node_name: str) -> int:
        p = self._memo.get(node_name)
        if p is None:
            p = stable_hash(node_name) % self.partitions
            self._memo[node_name] = p
        return p

    def group(self, names: Sequence[str]) -> Dict[int, List[str]]:
        """names bucketed by partition (input order preserved)."""
        out: Dict[int, List[str]] = {}
        memo = self._memo
        for name in names:
            p = memo.get(name)
            if p is None:
                p = stable_hash(name) % self.partitions
                memo[name] = p
            out.setdefault(p, []).append(name)
        return out

    def nodes_in(self, names: Sequence[str], partition: int) -> List[str]:
        return [n for n in names if self.partition_of(n) == partition]


def rendezvous_owner(partition: int, members: Sequence[str]) -> Optional[str]:
    """Highest-random-weight winner for one partition among ``members``
    — deterministic for a member set, minimal churn when it changes (a
    leaving member redistributes ONLY its own partitions)."""
    best = None
    best_weight = -1
    for member in sorted(members):
        weight = stable_hash(f"{partition}|{member}")
        if weight > best_weight:
            best, best_weight = member, weight
    return best


class HandoffCoordinator:
    """Journaled, fenced partition-ownership over one ConfigMap.

    ``tick()`` (driven by the telemetry refresh pass) heartbeats this
    replica's membership, prunes members whose heartbeat aged past the
    TTL, and — on the replica allowed to reassign — moves each partition
    to its rendezvous winner, bumping the partition epoch and publishing
    ``partition_assign``/``partition_handoff`` into the event spine.
    All clock reads come through the injectable ``clock`` so the twin
    steps this on fake time."""

    def __init__(
        self,
        kube_client,
        identity: str,
        partitions: int,
        namespace: str = "default",
        name: str = DEFAULT_CONFIGMAP,
        leadership=None,
        member_ttl_s: float = DEFAULT_MEMBER_TTL_S,
        clock: Callable[[], float] = time.monotonic,
        static_owners: Optional[Dict[int, str]] = None,
    ):
        self.kube_client = kube_client
        self.identity = identity
        self.partitions = int(partitions)
        self.namespace = namespace
        self.name = name
        #: optional kube.lease.LeaseElector: when wired, only the leader
        #: reassigns ownership (followers heartbeat only), so handoff
        #: rides the existing election machinery
        self.leadership = leadership
        self.member_ttl_s = float(member_ttl_s)
        self.clock = clock
        #: optional utils.record.FlightRecorder: ownership changes land
        #: in the capture as anonymized shard events (partition ids and
        #: epochs only — never node names)
        self.flight = None
        self._lock = threading.Lock()
        # local view of the journal, refreshed every tick; owners maps
        # partition -> {"replica": str, "epoch": int}
        self._owners: Dict[int, Dict] = {}
        self._members: Dict[str, float] = {}
        self._handoffs = 0
        self._last_error = ""
        #: fixed partition -> replica assignment: no journal, no kube
        #: I/O, epoch pinned at 1.  For single-owner-per-process bench
        #: topologies where replicas share no API server — production
        #: assemblies leave this None and coordinate through the journal.
        self.static_owners = (
            {int(p): str(r) for p, r in static_owners.items()}
            if static_owners
            else None
        )
        if self.static_owners is not None:
            self._owners = {
                p: {"replica": r, "epoch": 1}
                for p, r in self.static_owners.items()
            }
            self._members = {self.identity: self.clock()}

    # -- journal I/O -----------------------------------------------------------

    def _read_state(self):
        """(state dict, resourceVersion or None when the ConfigMap does
        not exist yet).  The resourceVersion rides into the write-back —
        optimistic concurrency: a concurrent coordinator's write bumps
        it, our update 409s, and we simply re-read next tick (rendezvous
        determinism means the winner wrote what we would have)."""
        empty = {"format": OWNERS_FORMAT, "members": {}, "owners": {}}
        try:
            cm = self.kube_client.get_configmap(self.namespace, self.name)
        except Exception:
            return empty, None
        rv = (cm.get("metadata") or {}).get("resourceVersion")
        try:
            state = json.loads((cm.get("data") or {}).get("state", "{}"))
        except Exception:
            state = {}
        if state.get("format") != OWNERS_FORMAT:
            return empty, rv
        return state, rv

    def _write_state(self, state: Dict, resource_version) -> bool:
        metadata: Dict = {"namespace": self.namespace, "name": self.name}
        if resource_version is not None:
            metadata["resourceVersion"] = resource_version
        cm = {
            "metadata": metadata,
            "data": {"state": json.dumps(state, sort_keys=True)},
        }
        try:
            if resource_version is None:
                self.kube_client.create_configmap(cm)
            else:
                self.kube_client.update_configmap(cm)
            return True
        except Exception as exc:
            self._last_error = str(exc)
            klog.v(2).info_s(
                f"shard ownership journal write failed: {exc}",
                component="shard",
            )
            return False

    # -- the coordination pass -------------------------------------------------

    def _may_reassign(self) -> bool:
        """Reassignment gate: with an elector wired, only the current
        leader rewrites ownership (handoff-safe on leader change — the
        new leader continues from the journal); without one, any replica
        may (rendezvous determinism makes concurrent writers agree)."""
        if self.leadership is None:
            return True
        try:
            return bool(self.leadership.is_leader())
        except Exception:
            return False

    def tick(self) -> None:
        """One coordination pass; never raises (the refresh loop that
        drives this must keep ticking through journal trouble)."""
        if self.static_owners is not None:
            return
        try:
            self._tick()
        except Exception as exc:  # noqa: BLE001 — coordination is best-effort
            self._last_error = str(exc)
            klog.error("shard coordinator tick failed: %r", exc)

    def _tick(self) -> None:
        now = self.clock()
        state, resource_version = self._read_state()
        members = {
            str(m): float(stamp)
            for m, stamp in (state.get("members") or {}).items()
        }
        members[self.identity] = now
        live = sorted(
            m for m, stamp in members.items()
            if now - stamp <= self.member_ttl_s
        )
        journaled: Dict[int, Dict] = {}
        for key, rec in (state.get("owners") or {}).items():
            try:
                journaled[int(key)] = {
                    "replica": str(rec.get("replica", "")),
                    "epoch": int(rec.get("epoch", 0)),
                }
            except Exception:
                continue
        owners = {p: dict(rec) for p, rec in journaled.items()}
        changed_members = live != sorted(
            m for m in (state.get("members") or {}) if m in members
        )
        moves: List[Dict] = []
        if self._may_reassign() and live:
            for p in range(self.partitions):
                desired = rendezvous_owner(p, live)
                current = owners.get(p)
                holder = current["replica"] if current else ""
                if holder == desired:
                    continue
                # a dead holder's partitions move the moment its
                # heartbeat expires; a live-member change moves only the
                # partitions rendezvous actually redistributes
                epoch = (current["epoch"] if current else 0) + 1
                owners[p] = {"replica": desired, "epoch": epoch}
                moves.append(
                    {"partition": p, "from": holder, "to": desired,
                     "epoch": epoch}
                )
        # heartbeat renewal: re-journal our own stamp well before it
        # ages past the TTL (third of it, the lease elector's renew
        # cadence) — without this, a quiet fleet's stamps all freeze at
        # the last write and membership flaps every TTL
        journaled_self = (state.get("members") or {}).get(self.identity)
        needs_heartbeat = (
            journaled_self is None
            or now - float(journaled_self) >= self.member_ttl_s / 3.0
        )
        wrote = True
        if moves or changed_members or needs_heartbeat:
            wrote = self._write_state(
                {
                    "format": OWNERS_FORMAT,
                    "members": {m: members[m] for m in members},
                    "owners": {
                        str(p): rec for p, rec in sorted(owners.items())
                    },
                },
                resource_version,
            )
        if not wrote:
            # lost the write race (or journal trouble): our recomputed
            # assignment never happened — keep serving from the state we
            # READ, and retry against the fresh journal next tick
            # (rendezvous determinism means the race winner wrote the
            # same assignment we computed)
            owners = journaled
            moves = []
        with self._lock:
            self._members = members
            self._owners = owners
            if wrote:
                self._handoffs += len([m for m in moves if m["from"]])
        if wrote:
            for move in moves:
                event = "partition_handoff" if move["from"] else "partition_assign"
                events.JOURNAL.publish(
                    "shard",
                    event,
                    data={
                        "partition": move["partition"],
                        "from": move["from"],
                        "to": move["to"],
                        "epoch": move["epoch"],
                        "replica": move["to"],
                    },
                )
                flight = self.flight
                if flight is not None:
                    try:
                        flight.record_shard(
                            event, move["partition"], move["epoch"]
                        )
                    except Exception:
                        pass

    # -- the consumer surface --------------------------------------------------

    def owned(self) -> FrozenSet[int]:
        """Partitions this replica currently owns (per its last journal
        read — ownership is only as fresh as the last tick, which is the
        same staleness bound the lease elector's grant carries)."""
        with self._lock:
            return frozenset(
                p for p, rec in self._owners.items()
                if rec["replica"] == self.identity
            )

    def owner(self, partition: int) -> str:
        with self._lock:
            rec = self._owners.get(int(partition))
            return rec["replica"] if rec else ""

    def epoch(self, partition: int) -> int:
        """The partition's fencing epoch: strictly monotonic across
        ownership changes; a digest stamped under an older epoch is from
        a fenced-out owner and must not reach a verdict."""
        with self._lock:
            rec = self._owners.get(int(partition))
            return rec["epoch"] if rec else 0

    def handoffs(self) -> int:
        with self._lock:
            return self._handoffs

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "identity": self.identity,
                "partitions": self.partitions,
                "members": dict(self._members),
                "owners": {
                    str(p): dict(rec)
                    for p, rec in sorted(self._owners.items())
                },
                "owned": sorted(
                    p for p, rec in self._owners.items()
                    if rec["replica"] == self.identity
                ),
                "handoffs": self._handoffs,
                "last_error": self._last_error,
            }
