"""All-or-nothing gang co-scheduling with topology-constrained
reservations (docs/gang.md).

The stock Filter/Prioritize path admits pods one at a time — the
node-level version of the "sum fits but no single unit does" problem
PAPER.md's GAS solves per card.  Two multi-host jobs that each need a
contiguous ICI sub-slice of a shared mesh then deadlock half-placed:
each holds scattered nodes the other needs, and neither ever completes
a valid topology.

The :class:`GangTracker` makes co-scheduling atomic:

  * a pod carrying ``pas-workload-group`` + ``pas-gang-size`` (and
    optionally ``pas-gang-topology: "HxW"``) labels is a **gang
    member** (utils/labels.py);
  * the FIRST member's Filter runs the topology-feasibility kernel
    (ops/topology.py) over the free cells of every ICI domain's mesh —
    one program per orientation; a slice never spans two domains — and,
    all-or-nothing, either **reserves a whole feasible slice** (best
    anchor = fewest stranded free neighbors) or fails every candidate
    with a concrete ``gang ...: no feasible HxW slice`` reason;
  * while the reservation holds, members pass Filter ONLY on reserved
    nodes, other gangs' pods fail reserved nodes with
    ``gang: node reserved by gang ...``, and each member Filter
    refreshes the reservation TTL;
  * bindings promote members to bound — learned from the cluster's pod
    feed (:meth:`GangTracker.watch`: kube-scheduler sends Bind only to
    an extender configured with a ``bindVerb``), and from the Bind verb
    where one is sent; when every member has bound the gang is
    **admitted** (``pas_gang_admitted_total``, time to full gang
    recorded), and the deletion of its last bound member releases the
    slice;
  * a reservation whose TTL lapses before the gang fully binds is
    **reclaimed** (``pas_gang_reservation_expirations_total``) and the
    gang re-forms — so an abandoned half-gang can never pin mesh nodes
    forever, and no member of an incomplete gang binds after expiry.

Lifecycle: ``forming -> reserved -> bound -> released``, with a
``draining`` detour for preemption victims (admission/preempt.py): an
evicted-whole gang keeps holding its slice while its pods terminate so
the preemptor's overlapping reservation (``reserve_slice``) is never
observably free to third parties.  All state
transitions happen under one short lock; the feasibility solve runs on
device (host mirror as fallback/control — byte-identical wire behavior,
pinned by tests/test_gang.py).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Set, Tuple

import numpy as np

from platform_aware_scheduling_tpu.extender.types import HostPriority
from platform_aware_scheduling_tpu.kube.objects import Pod
from platform_aware_scheduling_tpu.ops import topology
from platform_aware_scheduling_tpu.utils import decisions, klog, trace
from platform_aware_scheduling_tpu.utils import labels as shared_labels
from platform_aware_scheduling_tpu.utils.tracing import (
    LatencyRecorder,
    histograms_text,
)

STATE_FORMING = "forming"
STATE_RESERVED = "reserved"
STATE_BOUND = "bound"
#: a preempted victim: its whole-gang eviction has been issued and its
#: pods are terminating.  The gang KEEPS holding its slice (no third pod
#: may slip into the hole) while the preemptor's overlapping reservation
#: is already in place (reserve_slice) — reservation-while-draining.
#: The dead-gang sweep releases it once every member is gone; a wedged
#: drain is idle-dropped like an abandoned forming gang.
STATE_DRAINING = "draining"
STATE_RELEASED = "released"
#: the states in which a gang holds its slice (bound gangs until released;
#: draining victims while their pods terminate)
HOLDING = (STATE_RESERVED, STATE_BOUND, STATE_DRAINING)

#: seconds a gang's slice reservation holds without bind progress before
#: it is reclaimed; each member Filter refreshes it
DEFAULT_TTL_S = 30.0
#: max age, seconds, of the cached node mesh-coordinate map
#: (pas-tpu-coord labels) before the tracker relists nodes
DEFAULT_MESH_MAX_AGE_S = 30.0

#: process-wide time-to-full-gang histogram (its own family —
#: pas_gang_time_to_full_seconds, label: topology), registered once into
#: the shared /metrics page via trace.EXTRA_PROVIDERS
FULL_GANG_LATENCY = LatencyRecorder()


def _gang_histogram_text() -> str:
    return histograms_text(
        [FULL_GANG_LATENCY],
        metric="pas_gang_time_to_full_seconds",
        help_texts=trace.help_texts(),
        label_name="topology",
    )


trace.EXTRA_PROVIDERS.append(_gang_histogram_text)


class GangSpec:
    """A pod's parsed gang demand."""

    __slots__ = ("gang_id", "size", "topology")

    def __init__(self, gang_id: str, size: int, topo: Optional[tuple]):
        self.gang_id = gang_id
        self.size = size
        self.topology = topo  # (rows, cols) or None (any k nodes)

    @property
    def topology_label(self) -> str:
        if self.topology is None:
            return "any"
        return f"{self.topology[0]}x{self.topology[1]}"

    @classmethod
    def from_pod(cls, pod: Pod) -> Optional["GangSpec"]:
        """None unless the pod carries a well-formed gang demand."""
        return cls.from_labels(pod.namespace, pod.name, pod.get_labels())

    @classmethod
    def from_labels(
        cls, namespace: str, name: str, pod_labels: Dict[str, str]
    ) -> Optional["GangSpec"]:
        """None unless the labels carry a well-formed gang demand.  The
        validation lives in ONE place — utils/labels.gang_id_for (group
        + size labels, size >= 1, topology cell count == size) — so the
        scheduler and the gang-aware rebalance actuator can never
        disagree about membership.  A malformed demand fails open to
        non-gang semantics (logged) — a typo must not wedge scheduling."""
        gang_id = shared_labels.gang_id_for(namespace, pod_labels)
        if gang_id is None:
            if (
                pod_labels.get(shared_labels.GROUP_LABEL)
                and shared_labels.GANG_SIZE_LABEL in pod_labels
            ):
                klog.v(2).info_s(
                    f"malformed gang labels on pod {namespace}/"
                    f"{name}; treating pod as non-gang",
                    component="gang",
                )
            return None
        size = int(pod_labels[shared_labels.GANG_SIZE_LABEL])
        topo = None
        raw_topo = pod_labels.get(shared_labels.GANG_TOPOLOGY_LABEL)
        if raw_topo:
            topo = shared_labels.parse_topology(raw_topo)
        return cls(gang_id, size, topo)


class _Gang:
    """One tracked gang's mutable state (all access under the tracker's
    lock)."""

    __slots__ = (
        "gang_id",
        "spec",
        "state",
        "members",
        "bound",
        "reserved_nodes",
        "cells",
        "anchor",
        "domain",
        "created_at",
        "last_seen",
        "expires_at",
    )

    def __init__(self, spec: GangSpec, now: float):
        self.gang_id = spec.gang_id
        self.spec = spec
        self.state = STATE_FORMING
        self.members: Set[str] = set()  # pod keys seen at Filter time
        self.bound: Dict[str, str] = {}  # pod key -> node
        self.reserved_nodes: List[str] = []  # row-major slice order
        # int32 flat cells of reserved_nodes on the tracker's counting mesh
        # while the gang holds them there (GangTracker._held_cells_locked)
        self.cells: Optional[np.ndarray] = None
        self.anchor: Optional[Tuple[int, int, int, int]] = None  # i, j, h, w
        self.domain = ""  # the ICI domain the anchor lies in
        self.created_at = now
        self.last_seen = now
        self.expires_at: Optional[float] = None

    def to_dict(self, now: float) -> Dict:
        out = {
            "gang": self.gang_id,
            "state": self.state,
            "size": self.spec.size,
            "topology": self.spec.topology_label,
            "members_seen": len(self.members),
            "bound": len(self.bound),
            "reserved_nodes": list(self.reserved_nodes),
        }
        if self.anchor is not None:
            i, j, h, w = self.anchor
            out["anchor"] = {"row": i, "col": j, "rows": h, "cols": w}
            if self.domain:
                out["anchor"]["domain"] = self.domain
        if self.state == STATE_RESERVED and self.expires_at is not None:
            out["ttl_remaining_s"] = round(max(0.0, self.expires_at - now), 3)
        return out


class HeldSet:
    """The hosts every live reservation holds at one reservation version:
    ``parts``, one ``(gang id, hosts)`` a holding gang in the tracker's
    order, and :attr:`map`, ``{host: holding gang}`` (a host two gangs
    hold maps to the later), made on its first read.  Built once a
    version and shared: read it, never write."""

    __slots__ = ("version", "parts", "_map")

    def __init__(self, version: int, parts: Tuple[Tuple[str, List[str]], ...]):
        self.version = version
        self.parts = parts
        self._map: Optional[Dict[str, str]] = None

    def __bool__(self) -> bool:
        return bool(self.parts)

    @property
    def map(self) -> Dict[str, str]:
        held = self._map
        if held is None:
            held = {}
            for gang_id, hosts in self.parts:
                held.update(dict.fromkeys(hosts, gang_id))
            self._map = held
        return held


class MemberVerdict(NamedTuple):
    """A gang member's Filter verdict in compact form: ``allowed`` (the
    slice, while the gang holds one) passes where telemetry-clean; every
    other clean candidate fails with the holder's reason where ``held``
    names one, else with ``reason``.  Without a slice every clean
    candidate fails with ``reason``.  ``held`` (every reservation's hosts
    at ``version``) and ``allowed`` are shared: read them, never write."""

    state: str
    allowed: List[str]
    reason: str
    version: int
    held: HeldSet

    @property
    def holds_slice(self) -> bool:
        return self.state in (STATE_RESERVED, STATE_BOUND)


class GangTracker:
    """The gang ledger the TAS verbs consult: reservations, member
    lifecycle, and the Filter/Prioritize overlays.

    ``nodes_provider`` supplies the cluster node list (kube
    ``list_nodes`` in production, the fake in tests) from which the mesh
    coordinate map is built and refreshed (``mesh_max_age_s``);
    ``clock`` is injectable so TTL behavior tests advance time instead
    of sleeping."""

    def __init__(
        self,
        nodes_provider: Callable[[], list],
        ttl_s: float = DEFAULT_TTL_S,
        mesh_max_age_s: float = DEFAULT_MESH_MAX_AGE_S,
        use_device: bool = True,
        clock: Callable[[], float] = time.monotonic,
        pods_provider: Optional[Callable[[], list]] = None,
    ):
        self.nodes_provider = nodes_provider
        # optional live-pod source (kube list_pods): bound gangs whose
        # members have ALL disappeared (job finished, pods deleted) are
        # released by the periodic dead-gang sweep, so a completed job's
        # slice cannot stay reserved until process restart
        self.pods_provider = pods_provider
        self.ttl_s = float(ttl_s)
        self.mesh_max_age_s = float(mesh_max_age_s)
        self.use_device = use_device
        self._clock = clock
        self._lock = threading.Lock()
        self._gangs: Dict[str, _Gang] = {}
        self._member_gang: Dict[str, str] = {}  # pod key -> gang id
        # bumped whenever the set of gang-held nodes can have changed
        # (reserve, TTL expiry, release/drop of a holding gang): the
        # Filter response cache keys non-gang entries on this, so a
        # cached verdict can never outlive the reservation state it
        # encoded (docs/gang.md)
        self._reservation_version = 0
        # the held set built once a reservation version, read by every
        # Filter (_held_locked)
        self._held_memo: Optional[HeldSet] = None
        # how many live reservations hold each flat cell of _cell_mesh:
        # kept as gangs reserve and release, under the lock, inside the
        # same version bumps (_held_cells_locked)
        self._cell_mesh: Optional[topology.MeshView] = None
        self._cell_count: Optional[np.ndarray] = None
        self._mesh: Optional[topology.MeshView] = None
        self._mesh_at: float = -float("inf")
        self._swept_at: float = -float("inf")
        self._sweeping = False
        # optional kube.lease.LeaseElector: the dead-gang sweep is a
        # singleton loop — cluster-wide pod LISTs from every replica
        # would multiply API load for an action only one replica's
        # release should perform (docs/robustness.md "HA & leader
        # election").  Verb overlays are NOT gated: every replica serves
        # Filter/Prioritize against its own reservation ledger.
        self.leadership = None
        # optional gang.journal.GangJournal: reservation/bind state is
        # journaled write-behind after every mutation (and recovered by
        # recover() at assembly) so a restart cannot lose live slices —
        # docs/gang.md "Crash-safe reservations"
        self.journal = None
        self._journal_gen = 0  # bumped under the lock on durable changes
        self._journal_saved_gen = 0
        # serializes flushes: two verbs flushing concurrently could
        # otherwise land an OLDER snapshot after a newer one while the
        # generation math marks the state clean
        self._journal_write_lock = threading.Lock()
        # the pod feed's informer once watch() has started it
        self._feed = None

    # -- mesh ------------------------------------------------------------------

    def _mesh_view(self, now: float) -> Optional[topology.MeshView]:
        """The (cached) coordinate map; a provider failure keeps serving
        the stale mesh rather than wedging the verb (same last-known-good
        stance as the telemetry cache)."""
        with self._lock:
            mesh = self._mesh
            fresh = (now - self._mesh_at) <= self.mesh_max_age_s
        if mesh is not None and fresh:
            return mesh
        try:
            nodes = self.nodes_provider()
        except Exception as exc:
            klog.error("gang mesh refresh failed: %s", exc)
            return mesh
        new = topology.MeshView(nodes)
        if new.same_as(mesh):
            # an unchanged cluster keeps its view: what is kept per view
            # (the held cells, the Filter's row->cell map) stays valid
            new = mesh
        with self._lock:
            self._mesh = new
            self._mesh_at = now
        trace.COUNTERS.set_gauge("pas_gang_domains", float(len(new.domains)))
        return new

    def _sweep_dead_gangs(self, now: float, wait: bool = False) -> None:
        """The pod feed's departures for a tracker that has no feed: a
        pod LIST at most once per ``mesh_max_age_s``, and every bound
        member that no longer runs (job finished / pods deleted) leaves
        through :meth:`observe_gone`, the feed's own rule.  ``cmd/tas``
        always starts the feed, so there this never runs; it serves a
        tracker built with ``pods_provider`` alone (the HA twin,
        testing/ha.py).  Unlike the feed, which every replica runs on
        its own ledger, it is leader-only: a cluster-wide LIST from
        every replica would multiply API load.

        The cluster pod LIST never runs on a verb's thread: a Filter
        that trips the interval hands the scan to a one-shot daemon
        thread (``wait=False``); :meth:`prune` runs it inline
        (``wait=True``) so tests and maintenance calls are
        deterministic."""
        if self.pods_provider is None or self._feed is not None:
            return  # no live view, or the pod feed releases dead gangs
        if self.leadership is not None and not self.leadership.is_leader():
            # singleton loop: only the leader scans the cluster and
            # releases dead gangs (module attr doc); _swept_at is left
            # alone so a freshly-promoted leader sweeps immediately
            return
        with self._lock:
            if self._sweeping or (now - self._swept_at) <= (
                self.mesh_max_age_s
            ):
                return
            self._swept_at = now
            bound_gangs = {
                gang.gang_id: set(gang.bound)
                for gang in self._gangs.values()
                # draining victims release here too: once every evicted
                # member is gone the slice belongs to the preemptor alone
                if gang.state in (STATE_BOUND, STATE_DRAINING)
            }
            if not bound_gangs:
                return
            self._sweeping = True

        def scan() -> None:
            try:
                pods = self.pods_provider()
                # a pod that Succeeded/Failed or is terminating no longer
                # RUNS on its slice — counting it as live would hold a
                # completed Job's reservation until its pods are GCed
                # (same liveness rule as the actuator's group floor)
                live = {
                    f"{pod.namespace}/{pod.name}"
                    for pod in pods
                    if pod.phase not in ("Succeeded", "Failed")
                    and pod.deletion_timestamp is None
                }
                for members in bound_gangs.values():
                    for key in sorted(members - live):
                        namespace, _, name = key.partition("/")
                        self.observe_gone(namespace, name)
            except Exception as exc:
                klog.error("gang dead-sweep pod list failed: %s", exc)
            finally:
                with self._lock:
                    self._sweeping = False

        if wait:
            scan()
        else:
            threading.Thread(
                target=scan, name="pas-gang-sweep", daemon=True
            ).start()

    # -- reservation bookkeeping (all under the lock) --------------------------

    def _reserved_map_locked(
        self, exclude: Optional[str] = None
    ) -> Dict[str, str]:
        """{node: holding gang id} across every live reservation
        (bound gangs keep holding their slice until released)."""
        held: Dict[str, str] = {}
        for gang in self._gangs.values():
            if gang.gang_id == exclude:
                continue
            # draining victims still hold: their pods are terminating on
            # the slice and the overlapping preemptor reservation relies
            # on nobody else slipping in (reservation-while-draining)
            if gang.state in HOLDING:
                for node in gang.reserved_nodes:
                    held[node] = gang.gang_id
        return held

    def _held_locked(self) -> HeldSet:
        """Every live reservation's hosts, built once a reservation
        version — one step a holding gang: every change of the held set
        bumps the version, so the memo can never outlive the state it
        maps.  Shared — callers only read it."""
        memo = self._held_memo
        if memo is None or memo.version != self._reservation_version:
            memo = HeldSet(
                self._reservation_version,
                tuple([
                    (gang.gang_id, gang.reserved_nodes)
                    for gang in self._gangs.values()
                    if gang.state in HOLDING and gang.reserved_nodes
                ]),
            )
            self._held_memo = memo
        return memo

    def _held_cells_locked(self, mesh: topology.MeshView) -> np.ndarray:
        """int32 [``mesh.n_cells``]: how many live reservations hold each flat
        cell of ``mesh``.  Kept as gangs reserve and release; counted anew
        from every holding gang's hosts when ``mesh`` is not the view it
        counts on (the first solve, a cluster whose nodes changed)."""
        if mesh is not self._cell_mesh:
            count = np.zeros(mesh.n_cells, dtype=np.int32)
            for gang in self._gangs.values():
                gang.cells = None
                if gang.state in HOLDING and gang.reserved_nodes:
                    cells = mesh.cells_of(gang.reserved_nodes)
                    gang.cells = cells[cells >= 0]
                    np.add.at(count, gang.cells, 1)
            self._cell_mesh = mesh
            self._cell_count = count
        return self._cell_count

    def _hold_cells_locked(
        self, gang: _Gang, cells: Optional[np.ndarray] = None
    ) -> None:
        """``gang`` now holds ``gang.reserved_nodes``: count its cells
        (``cells``, where the solve on the counting mesh knows them)."""
        mesh = self._cell_mesh
        gang.cells = None
        if mesh is None:
            return  # nothing is counted yet: the first solve counts all
        if cells is None:
            cells = mesh.cells_of(gang.reserved_nodes)
            cells = cells[cells >= 0]
        gang.cells = cells
        np.add.at(self._cell_count, cells, 1)

    def _unhold_cells_locked(self, gang: _Gang) -> None:
        """``gang`` stops holding its hosts: uncount its cells."""
        if gang.cells is not None:
            np.subtract.at(self._cell_count, gang.cells, 1)
            gang.cells = None

    def _taken_cells_locked(
        self, mesh: topology.MeshView, gang: _Gang
    ) -> np.ndarray:
        """bool [padded domains, rows, cols] of ``mesh``: the cells some
        live reservation other than ``gang``'s holds — the cells of
        ``_reserved_map_locked(exclude=gang)``'s hosts."""
        count = self._held_cells_locked(mesh)
        if gang.cells is not None:
            count = count.copy()
            np.subtract.at(count, gang.cells, 1)
        return (count > 0).reshape(mesh.padded_domains, mesh.rows, mesh.cols)

    def _prune_locked(self, now: float) -> int:
        """Reclaim expired reservations (gang re-forms) and drop gangs
        abandoned in forming for 10x the TTL.  Returns the number of
        expirations (counted by the caller outside the lock)."""
        expired = 0
        for gang in self._gangs.values():
            if (
                gang.state == STATE_RESERVED
                and gang.expires_at is not None
                and gang.expires_at <= now
            ):
                self._unhold_cells_locked(gang)
                gang.state = STATE_FORMING
                gang.reserved_nodes = []
                gang.anchor = None
                gang.domain = ""
                gang.expires_at = None
                # binds on the abandoned slice do not carry over: the
                # re-formed gang may reserve a DIFFERENT slice, and
                # admission must mean k binds on the CURRENT one — never
                # a gang straddling two slices
                gang.bound = {}
                expired += 1
        if expired:
            self._reservation_version += 1
            self._journal_gen += 1
        idle_bound = 10.0 * self.ttl_s
        for gang_id in [
            gid
            for gid, gang in self._gangs.items()
            # a DRAINING victim whose pods never finish terminating must
            # not pin its slice forever either — same idle bound as an
            # abandoned forming gang (the sweep handles the normal case)
            if gang.state in (STATE_FORMING, STATE_DRAINING)
            and (now - gang.last_seen) > idle_bound
        ]:
            self._drop_locked(gang_id)
        return expired

    def _drop_locked(self, gang_id: str) -> None:
        dropped = self._gangs.pop(gang_id, None)
        if dropped is not None:
            self._unhold_cells_locked(dropped)
            if dropped.reserved_nodes:
                self._reservation_version += 1  # its slice is free again
                self._journal_gen += 1
            # released = removed from tracking; the terminal state is
            # stamped on the object so any held reference reads true
            dropped.state = STATE_RELEASED
            dropped.reserved_nodes = []
        for key in [
            k for k, gid in self._member_gang.items() if gid == gang_id
        ]:
            del self._member_gang[key]

    def _publish_gauges_locked(self) -> Tuple[float, float]:
        active = sum(
            1
            for gang in self._gangs.values()
            if gang.state in (STATE_FORMING, STATE_RESERVED)
        )
        held = sum(
            len(gang.reserved_nodes)
            for gang in self._gangs.values()
            if gang.state in HOLDING
        )
        return float(active), float(held)

    def _set_gauges(self, gauges: Tuple[float, float]) -> None:
        trace.COUNTERS.set_gauge("pas_gang_active", gauges[0])
        trace.COUNTERS.set_gauge("pas_gang_reserved_nodes", gauges[1])

    # -- reservation solve -----------------------------------------------------

    def _try_reserve_locked(
        self,
        gang: _Gang,
        candidates: Optional[List[str]],
        mesh: Optional[topology.MeshView],
        now: float,
        clean: Optional[np.ndarray] = None,
        span=trace.NULL_SPAN,
    ) -> Optional[str]:
        """Attempt the all-or-nothing reservation for a forming gang over
        this request's candidates: their names, or — for a gang with a
        topology on a mesh — ``clean``, the bool mask of ``mesh``'s cells
        whose nodes are candidates (``candidates`` is then unused).  Both
        reserve the same slice.  The solve's steps are stages on ``span``
        (ops/topology.py ``best_domain_anchor``).  Returns None on success
        (the gang holds a slice) or the bounded rejection-reason label."""
        # gang.bound is always empty here: both paths into FORMING (new
        # gang, TTL expiry) clear it — abandoned-slice binds never leak
        # into a new solve (the straddling fix)
        spec = gang.spec
        cells = None
        if clean is None:
            held = self._reserved_map_locked(exclude=gang.gang_id)
            free = [name for name in candidates if name not in held]
        if spec.topology is None:
            # size-only gang: any k nodes, chosen in sorted-name order
            # for determinism (no adjacency constraint, no mesh needed)
            chosen = sorted(set(free))[: spec.size]
            if len(chosen) < spec.size:
                return "infeasible"
            gang.reserved_nodes = chosen
            gang.anchor = None
            gang.domain = ""
        else:
            if mesh is None or len(mesh) == 0:
                return "no_mesh"
            # one device program per orientation over every ICI domain:
            # a slice never spans two.  From cells, the free mask is the
            # candidates' cells less the cells other gangs hold: a step a
            # cell, never a step a host by name
            found = topology.best_slice_in(
                mesh,
                mesh.free_masks(free)
                if clean is None
                else clean & ~self._taken_cells_locked(mesh, gang),
                spec.topology,
                use_device=self.use_device,
                span=span,
            )
            if found is None:
                return "infeasible"
            gang.reserved_nodes, gang.anchor, gang.domain, cells = found
        self._hold_cells_locked(
            gang, cells if mesh is self._cell_mesh else None
        )
        gang.state = STATE_RESERVED
        gang.expires_at = now + self.ttl_s
        self._reservation_version += 1
        self._journal_gen += 1
        return None

    # -- verb overlays ---------------------------------------------------------

    def filter_overlay(
        self, pod: Pod, candidates: List[str], span=trace.NULL_SPAN
    ) -> Tuple[Dict[str, str], Dict[str, int]]:
        """The gang verdict for one Filter request: ``(failed, codes)``
        merged over the telemetry violation map by the caller
        (tas/telemetryscheduler._filter_nodes).

        Non-gang pod: candidates held by gang reservations fail with a
        concrete ``gang: node reserved by gang <id>`` reason
        (CODE_GANG_RESERVED).  Gang member: only the gang's reserved
        slice passes; with no reservable slice EVERY candidate fails
        (CODE_GANG_INFEASIBLE) — the all-or-nothing invariant.  A
        member's verdict is :meth:`member_verdict`'s, spelled out over
        the candidates: the exact path, and the reference the native
        encoder's answer is held to (tas/telemetryscheduler.py)."""
        spec = GangSpec.from_pod(pod)
        failed: Dict[str, str] = {}
        codes: Dict[str, int] = {}
        if spec is None:
            now = self._clock()
            self._sweep_dead_gangs(now)
            with self._lock:
                expired = self._prune_locked(now)
                held = self._held_locked().map
                gauges = self._publish_gauges_locked()
            for name in candidates:
                holder = held.get(name)
                if holder is not None:
                    failed[name] = shared_labels.gang_reserved_reason(holder)
                    codes[name] = decisions.CODE_GANG_RESERVED
            self._count_expired(expired)
            self._set_gauges(gauges)
            self._journal_flush()  # no-op unless durable state moved
            return failed, codes
        verdict = self._member_verdict(
            spec, f"{pod.namespace}/{pod.name}", lambda: candidates, span
        )
        if verdict.holds_slice:
            allowed = set(verdict.allowed)
            held = verdict.held.map
            for name in candidates:
                if name in allowed:
                    continue
                holder = held.get(name)
                if holder is not None:
                    failed[name] = shared_labels.gang_reserved_reason(holder)
                    codes[name] = decisions.CODE_GANG_RESERVED
                else:
                    failed[name] = verdict.reason
                    codes[name] = decisions.CODE_GANG_INFEASIBLE
        else:
            for name in candidates:
                failed[name] = verdict.reason
                codes[name] = decisions.CODE_GANG_INFEASIBLE
        return failed, codes

    def member_verdict(
        self,
        namespace: str,
        name: str,
        pod_labels: Dict[str, str],
        clean_names: Callable[[], List[str]],
        span=trace.NULL_SPAN,
        clean_cells: Optional[
            Callable[[topology.MeshView], np.ndarray]
        ] = None,
    ) -> Optional[MemberVerdict]:
        """A gang member's Filter verdict in compact form, or None for a
        pod whose labels make it no member.  ``clean_names`` gives the
        request's telemetry-clean candidates; it is called only when the
        gang must reserve (the solve's free mask sees clean candidates
        alone, so a gang can never reserve a slice it cannot bind).
        ``clean_cells``, where given, gives the same candidates as a bool
        mask of a mesh's cells, and a gang with a topology reserves from
        it (``pas_gang_reserve_rows_total``) without their names."""
        spec = GangSpec.from_labels(namespace, name, pod_labels)
        if spec is None:
            return None
        return self._member_verdict(
            spec, f"{namespace}/{name}", clean_names, span, clean_cells
        )

    def _member_verdict(
        self, spec: GangSpec, key: str, clean_names, span, clean_cells=None
    ) -> MemberVerdict:
        """Every side effect of a member's Filter — membership, the TTL
        refresh of a held slice, the reservation of a forming gang (the
        stage ``gang_reserve`` on ``span``: the held cells or map, the
        free mask, the solve — its stages ``gang_upload``,
        ``gang_launch``, ``gang_fetch`` — the anchor and its names;
        the candidates' names or cells are made before it), the counters,
        gauges and journal — and its verdict, with no per-candidate
        work."""
        now = self._clock()
        self._sweep_dead_gangs(now)
        mesh = self._mesh_view(now) if spec.topology is not None else None
        from_cells = (
            clean_cells is not None and mesh is not None and len(mesh) > 0
        )
        reserved = False
        rejected_reason = None
        with self._lock:
            tracked = len(self._gangs)
            expired = self._prune_locked(now)
            gang = self._gangs.get(spec.gang_id)
            created = gang is None
            if created:
                gang = _Gang(spec, now)
                self._gangs[spec.gang_id] = gang
            gang.last_seen = now
            gang.members.add(key)
            self._member_gang[key] = spec.gang_id
            if gang.state == STATE_FORMING:
                candidates = None if from_cells else clean_names()
                clean = clean_cells(mesh) if from_cells else None
                with span.stage("gang_reserve", leaf=False):
                    rejected_reason = self._try_reserve_locked(
                        gang, candidates, mesh, now, clean=clean, span=span
                    )
                reserved = rejected_reason is None
            state = gang.state
            if state in (STATE_RESERVED, STATE_BOUND):
                if state == STATE_RESERVED:
                    # an actively scheduling gang keeps its hold
                    gang.expires_at = now + self.ttl_s
                allowed = gang.reserved_nodes
                reason = (
                    f"gang {spec.gang_id}: node outside reserved "
                    f"{spec.topology_label} slice"
                )
            else:
                allowed = []
                reason = f"gang {spec.gang_id}: " + (
                    "no mesh coordinates available"
                    if rejected_reason == "no_mesh"
                    else f"no feasible {spec.topology_label} slice"
                )
            verdict = MemberVerdict(
                state, allowed, reason, self._reservation_version,
                self._held_locked(),
            )
            # the gauges count gangs by state and the hosts they hold: only
            # a new gang, a reservation, an expiry or a drop moves them,
            # and the walk over every gang is most of a verdict's cost
            moved = (
                created
                or reserved
                or expired
                or len(self._gangs) != tracked
            )
            gauges = self._publish_gauges_locked() if moved else None
        self._count_expired(expired)
        if reserved:
            trace.COUNTERS.inc("pas_gang_reservations_total")
            if from_cells:
                trace.COUNTERS.inc("pas_gang_reserve_rows_total")
        if rejected_reason is not None:
            trace.COUNTERS.inc(
                "pas_gang_rejected_total", labels={"reason": rejected_reason}
            )
        if gauges is not None:
            self._set_gauges(gauges)
        self._journal_flush()  # no-op unless durable state moved
        return verdict

    @staticmethod
    def _count_expired(expired: int) -> None:
        if expired:
            trace.COUNTERS.inc(
                "pas_gang_reservation_expirations_total", expired
            )

    def prioritize_overlay(
        self, pod: Pod, candidates: List[str], span=trace.NULL_SPAN
    ) -> Optional[List[HostPriority]]:
        """Gang-member Prioritize: the reserved slice's nodes in
        row-major slice order (the topology kernel already chose the
        anchor stranding the fewest free neighbors), ordinal scores like
        the host path.  None for non-gang pods (the normal ranking
        serves); an unreservable gang gets an empty list — no node is a
        good home for a gang that cannot fully place."""
        spec = GangSpec.from_pod(pod)
        if spec is None:
            return None
        # Filter normally runs first and holds the reservation; this
        # degenerates to a lookup.  A Prioritize-first arrival drives the
        # same reservation path so the verbs cannot disagree.
        verdict = self._member_verdict(
            spec, f"{pod.namespace}/{pod.name}", lambda: candidates, span
        )
        reserved = verdict.allowed if verdict.holds_slice else []
        in_request = set(candidates)
        ordered = [name for name in reserved if name in in_request]
        return [
            HostPriority(host=name, score=10 - i)
            for i, name in enumerate(ordered)
        ]

    # -- outcome feedback ------------------------------------------------------

    def observe_bind(self, namespace: str, name: str, node: str) -> None:
        """A member landed: promote it within its gang; the gang is
        admitted when every member has bound onto the reserved slice.
        Fed by the pod feed (:meth:`watch`: a pod update that carries
        ``spec.nodeName``) and by the Bind verb where a kube-scheduler
        sends one; the same binding seen twice counts once."""
        key = f"{namespace}/{name}"
        admitted: Optional[_Gang] = None
        learned = False
        now = self._clock()
        with self._lock:
            gang_id = self._member_gang.get(key)
            if gang_id is None:
                return
            gang = self._gangs.get(gang_id)
            if gang is None or gang.state not in (
                STATE_RESERVED,
                STATE_BOUND,
            ):
                return
            if node not in gang.reserved_nodes:
                klog.v(2).info_s(
                    f"gang {gang_id}: member {key} bound OFF-slice to "
                    f"{node}",
                    component="gang",
                )
                return
            if gang.bound.get(key) == node:
                return  # seen already: the Bind verb and the feed both say it
            learned = True
            gang.bound[key] = node
            self._journal_gen += 1  # binds are durable: recovery replays them
            if (
                gang.state == STATE_RESERVED
                and len(gang.bound) >= gang.spec.size
            ):
                gang.state = STATE_BOUND
                gang.expires_at = None
                admitted = gang
            gauges = self._publish_gauges_locked()
        if learned:
            trace.COUNTERS.inc("pas_gang_member_binds_total")
        if admitted is not None:
            trace.COUNTERS.inc("pas_gang_admitted_total")
            FULL_GANG_LATENCY.observe(
                admitted.spec.topology_label, max(0.0, now - admitted.created_at)
            )
            klog.v(1).info_s(
                f"gang {admitted.gang_id} fully bound "
                f"({admitted.spec.size} pods, "
                f"{admitted.spec.topology_label})",
                component="gang",
            )
        self._set_gauges(gauges)
        self._journal_flush()

    def observe_gone(self, namespace: str, name: str) -> None:
        """A pod was deleted, or no longer runs (Succeeded, Failed,
        terminating): a bound member of a bound or draining gang leaves
        it, and the departure of the last one releases the slice at once.
        Fed by the pod feed (:meth:`watch`) or, without one, by the
        dead-gang sweep's pod LIST."""
        key = f"{namespace}/{name}"
        with self._lock:
            gang_id = self._member_gang.get(key)
            gang = self._gangs.get(gang_id) if gang_id is not None else None
            if (
                gang is None
                or gang.state not in (STATE_BOUND, STATE_DRAINING)
                or gang.bound.pop(key, None) is None
            ):
                return
            self._journal_gen += 1
            gauges = self._publish_gauges_locked() if gang.bound else None
        if gauges is not None:  # others remain: the gang keeps its slice
            self._set_gauges(gauges)
            self._journal_flush()
            return
        klog.v(1).info_s(
            f"gang {gang_id}: every bound member gone; releasing its slice",
            component="gang",
        )
        self.release(gang_id)

    def watch(self, kube_client):
        """The cluster's pod feed, as kube-scheduler learns its own
        bindings: a pod update carrying ``spec.nodeName`` is a member's
        binding (:meth:`observe_bind`), and a deletion — or a pod that
        no longer runs — is a departure (:meth:`observe_gone`).  A
        kube-scheduler sends Bind only to an extender configured with a
        ``bindVerb``; without one this feed is how the tracker hears of
        bindings at all.  While it runs, the periodic pod LIST of the
        dead-gang sweep is skipped.  Every replica runs its own feed, as
        every replica keeps its own ledger (a follower's slices are
        released as promptly as the leader's).  Returns the informer
        (``.stop()``)."""
        from platform_aware_scheduling_tpu.kube.informer import (
            DeletedFinalStateUnknown,
            Informer,
            ListWatch,
        )
        from platform_aware_scheduling_tpu.kube.objects import object_key

        def on_event(pod: Pod) -> None:
            if (
                pod.phase in ("Succeeded", "Failed")
                or pod.deletion_timestamp is not None
            ):
                self.observe_gone(pod.namespace, pod.name)
            elif pod.spec_node_name:
                self.observe_bind(pod.namespace, pod.name, pod.spec_node_name)

        def on_delete(obj) -> None:
            if isinstance(obj, DeletedFinalStateUnknown):
                obj = obj.obj
            if isinstance(obj, Pod):
                self.observe_gone(obj.namespace, obj.name)

        informer = Informer(
            ListWatch(
                lambda: (kube_client.list_pods(), ""),
                lambda rv: (
                    (etype, Pod(raw)) for etype, raw in kube_client.watch_pods()
                ),
                object_key,
            ),
            on_add=on_event,
            on_update=lambda _old, new: on_event(new),
            on_delete=on_delete,
            name="gang-pods",
        )
        informer.start()
        self._feed = informer
        return informer

    def release(self, gang_id: str) -> bool:
        """Drop a gang and free its slice (job finished or evicted whole
        by the gang-aware actuator)."""
        with self._lock:
            existed = gang_id in self._gangs
            self._drop_locked(gang_id)
            gauges = self._publish_gauges_locked()
        self._set_gauges(gauges)
        self._journal_flush()
        return existed

    # -- preemption support (admission/preempt.py; docs/admission.md) ----------

    def mark_draining(self, gang_id: str) -> bool:
        """Flip a preemption victim to DRAINING after its whole-gang
        eviction was issued: the gang keeps holding its slice while its
        pods terminate (nobody else may slip into the hole), but the
        planner's census no longer offers it and its members re-enter
        scheduling as a fresh gang once the sweep releases it."""
        with self._lock:
            gang = self._gangs.get(gang_id)
            if gang is None or gang.state not in (
                STATE_RESERVED,
                STATE_BOUND,
            ):
                return False
            gang.state = STATE_DRAINING
            gang.expires_at = None
            gang.last_seen = self._clock()
            # held nodes did not change, but cached Filter verdicts may
            # encode this gang as schedulable-on — not true anymore
            self._reservation_version += 1
            self._journal_gen += 1
            gauges = self._publish_gauges_locked()
        self._set_gauges(gauges)
        self._journal_flush()
        return True

    def reserve_slice(
        self,
        pod: Pod,
        nodes: List[str],
        anchor: Optional[Tuple[int, int, int, int]] = None,
    ) -> bool:
        """Reservation-while-draining, the preemptor's half: hold the
        planned slice for ``pod``'s gang BEFORE the victims finish
        draining.  The preemptor's reservation may overlap DRAINING
        victims' holds — its own members pass Filter on the slice (the
        allowed-set check precedes the held map), every other pod keeps
        failing those nodes, and when the sweep releases the last victim
        the slice transfers without ever being observably free.  The
        normal TTL applies from now, so an abandoned preemption still
        expires instead of pinning the mesh."""
        spec = GangSpec.from_pod(pod)
        if spec is None or not nodes:
            return False
        now = self._clock()
        with self._lock:
            gang = self._gangs.get(spec.gang_id)
            if gang is None:
                gang = _Gang(spec, now)
                self._gangs[spec.gang_id] = gang
            if gang.state in (STATE_BOUND, STATE_DRAINING):
                return False  # already placed, or itself a victim
            key = f"{pod.namespace}/{pod.name}"
            gang.members.add(key)
            self._member_gang[key] = spec.gang_id
            gang.last_seen = now
            self._unhold_cells_locked(gang)  # a reserved gang's old slice
            gang.state = STATE_RESERVED
            gang.reserved_nodes = list(nodes)
            self._hold_cells_locked(gang)
            gang.anchor = tuple(anchor) if anchor is not None else None
            gang.bound = {}
            gang.expires_at = now + self.ttl_s
            self._reservation_version += 1
            self._journal_gen += 1
            gauges = self._publish_gauges_locked()
        trace.COUNTERS.inc("pas_gang_reservations_total")
        self._set_gauges(gauges)
        self._journal_flush()
        return True

    def preemption_census(self) -> List[Dict]:
        """The victim-candidate view the preemption planner scores:
        every gang currently holding nodes and not already committed to
        a prior preemption (RESERVED or BOUND; DRAINING gangs are spoken
        for, FORMING gangs hold nothing worth taking)."""
        with self._lock:
            out = []
            for gang in self._gangs.values():
                if gang.state not in (STATE_RESERVED, STATE_BOUND):
                    continue
                out.append(
                    {
                        "gang": gang.gang_id,
                        "state": gang.state,
                        "size": gang.spec.size,
                        "nodes": list(gang.reserved_nodes),
                        "members": sorted(gang.members | set(gang.bound)),
                        "bound": dict(gang.bound),
                    }
                )
            return out

    def mesh(self) -> Optional[topology.MeshView]:
        """The (cached) mesh coordinate map, for the preemption
        planner's feasibility what-ifs."""
        return self._mesh_view(self._clock())

    # -- crash-safe journal (gang/journal.py; docs/gang.md) --------------------

    def _journal_snapshot_locked(self) -> Dict:
        """The full durable state: every RESERVED/BOUND gang's slice and
        binds.  Forming gangs hold nothing and are not journaled; TTL
        deadlines are not journaled either — recovery re-arms a fresh
        TTL so an abandoned reservation still expires on schedule."""
        gangs = []
        for gang in sorted(
            self._gangs.values(), key=lambda g: (g.created_at, g.gang_id)
        ):
            # DRAINING journals too (its slice is still held); recovery's
            # non-bound branch restores any non-BOUND state as RESERVED
            # with a fresh TTL, which is exactly the containment we want
            # after a crash mid-preemption
            if gang.state not in (
                STATE_RESERVED,
                STATE_BOUND,
                STATE_DRAINING,
            ):
                continue
            gangs.append(
                {
                    "gang": gang.gang_id,
                    "state": gang.state,
                    "size": gang.spec.size,
                    "topology": (
                        list(gang.spec.topology)
                        if gang.spec.topology is not None
                        else None
                    ),
                    "reserved_nodes": list(gang.reserved_nodes),
                    "anchor": (
                        list(gang.anchor) if gang.anchor is not None else None
                    ),
                    "domain": gang.domain,
                    "bound": dict(gang.bound),
                    "members": sorted(gang.members),
                }
            )
        return {"gangs": gangs}

    def _journal_flush(self) -> None:
        """Write-behind: persist the snapshot iff durable state moved
        since the last committed write.  A failed/skipped write leaves
        the saved generation behind, so the NEXT durable mutation (or
        maintenance call) retries — in-memory-only degradation heals
        itself once the kube circuit closes."""
        journal = self.journal
        if journal is None:
            return
        # one flush at a time, and the snapshot is taken AFTER the write
        # lock is held — so whichever flush runs last always persists
        # the newest state (a concurrent mutation's own flush either
        # waits here or finds the generation already saved)
        with self._journal_write_lock:
            with self._lock:
                if self._journal_gen == self._journal_saved_gen:
                    return
                gen = self._journal_gen
                snapshot = self._journal_snapshot_locked()
            if journal.save(snapshot):
                with self._lock:
                    self._journal_saved_gen = max(
                        self._journal_saved_gen, gen
                    )

    def recover(self) -> int:
        """Restore journaled reservations at startup, reconciled against
        live pods; returns the number of gangs restored.

        Reconciliation is the safety half: a bind whose pod is gone is
        simply dropped (the slice stays reserved for the re-forming
        gang), but a bind CONTRADICTED by the live cluster — the pod
        runs on a different node, or on a node outside the journaled
        slice — discards the whole entry.  Replaying a contradicted
        reservation is exactly how a recovered extender would admit a
        gang straddling two slices; the journal is evidence, the
        cluster is truth."""
        journal = self.journal
        if journal is None:
            return 0
        data = journal.load()
        entries = (data or {}).get("gangs") or []
        if not entries:
            return 0
        if self.pods_provider is None:
            # no live view, no validation, no replay — same stance as a
            # failing pod list below: restoring unreconciled state is
            # the straddling hazard (docs/robustness.md recovery matrix)
            klog.error(
                "gang journal recovery: no pods_provider to reconcile "
                "against; discarding %d journaled gangs",
                len(entries),
            )
            trace.COUNTERS.inc(
                "pas_gang_journal_discarded_total", len(entries)
            )
            return 0
        live: Dict[str, str] = {}
        try:
            for pod in self.pods_provider():
                if (
                    pod.phase in ("Succeeded", "Failed")
                    or pod.deletion_timestamp is not None
                ):
                    continue
                live[f"{pod.namespace}/{pod.name}"] = (
                    pod.spec_node_name or ""
                )
        except Exception as exc:
            # no live view, no validation, no replay: restoring
            # unreconciled state is the straddling hazard
            klog.error(
                "gang journal recovery: cannot list pods (%s); "
                "discarding %d journaled gangs",
                exc,
                len(entries),
            )
            trace.COUNTERS.inc(
                "pas_gang_journal_discarded_total", len(entries)
            )
            return 0
        now = self._clock()
        restored = 0
        discarded = 0
        with self._lock:
            for entry in entries:
                gang_id = entry.get("gang")
                try:
                    size = int(entry.get("size"))
                    raw_topo = entry.get("topology")
                    topo = tuple(raw_topo) if raw_topo else None
                    reserved = [str(n) for n in entry.get("reserved_nodes")]
                except (TypeError, ValueError):
                    discarded += 1
                    continue
                if not gang_id or size < 1 or not reserved:
                    discarded += 1
                    continue
                if gang_id in self._gangs:
                    continue  # live state outranks the journal
                slice_set = set(reserved)
                members = set(entry.get("members") or []) | set(
                    entry.get("bound") or {}
                )
                # the cluster is truth: a recovered bind is a live member
                # RUNNING ON the journaled slice (even one whose bind
                # observation the crash swallowed); a gone-or-unbound
                # member just drops its bind; a live member bound OFF the
                # slice contradicts the whole entry
                contradicted = False
                bound: Dict[str, str] = {}
                for key in sorted(members):
                    node_now = live.get(key)
                    if not node_now:
                        continue  # pod gone, or never actually bound
                    if node_now not in slice_set:
                        contradicted = True
                        break
                    bound[key] = node_now
                if contradicted:
                    discarded += 1
                    klog.v(1).info_s(
                        f"gang {gang_id}: journal contradicted by live "
                        f"pods; discarding its reservation",
                        component="gang",
                    )
                    continue
                gang = _Gang(GangSpec(gang_id, size, topo), now)
                gang.reserved_nodes = reserved
                anchor = entry.get("anchor")
                gang.anchor = tuple(anchor) if anchor else None
                gang.domain = str(entry.get("domain") or "")
                gang.bound = bound
                gang.members = members | set(bound)
                if entry.get("state") == STATE_BOUND and len(bound) >= size:
                    gang.state = STATE_BOUND
                    gang.expires_at = None
                else:
                    # fresh TTL: the recovered reservation holds exactly
                    # one grace window for the gang to resume binding
                    gang.state = STATE_RESERVED
                    gang.expires_at = now + self.ttl_s
                self._gangs[gang_id] = gang
                self._hold_cells_locked(gang)
                for key in gang.members:
                    self._member_gang[key] = gang_id
                restored += 1
            if restored:
                self._reservation_version += 1
            gauges = self._publish_gauges_locked()
        if restored:
            trace.COUNTERS.inc("pas_gang_journal_recovered_total", restored)
            klog.v(1).info_s(
                f"gang journal recovery: {restored} reservation(s) "
                f"restored, {discarded} discarded",
                component="gang",
            )
        if discarded:
            trace.COUNTERS.inc("pas_gang_journal_discarded_total", discarded)
        self._set_gauges(gauges)
        return restored

    # -- introspection ---------------------------------------------------------

    def cache_token(self) -> Tuple[int, HeldSet]:
        """(reservation version, the held set at it) for the Filter
        response cache (tas/telemetryscheduler._gang_cache_token): every
        reservation change bumps the version, so a cached response keyed
        on it can never outlive the state it encoded.  Prunes expired
        reservations first — a cache-hit steady state must still observe
        TTL expiry (the expiry itself bumps the version and misses the
        stale entries)."""
        now = self._clock()
        self._sweep_dead_gangs(now)
        with self._lock:
            expired = self._prune_locked(now)
            version = self._reservation_version
            held = self._held_locked()
            # gauges only when something actually expired — this runs on
            # every non-gang Filter request, and the common no-expiry
            # case must not pay two all-gang walks under the lock
            gauges = self._publish_gauges_locked() if expired else None
        if expired:
            self._count_expired(expired)
            self._set_gauges(gauges)
            self._journal_flush()
        return version, held

    def reserved_nodes(self) -> Dict[str, str]:
        with self._lock:
            return self._reserved_map_locked()

    def gang_state(self, gang_id: str) -> Optional[str]:
        with self._lock:
            gang = self._gangs.get(gang_id)
            return gang.state if gang is not None else None

    def prune(self) -> int:
        now = self._clock()
        self._sweep_dead_gangs(now, wait=True)
        with self._lock:
            expired = self._prune_locked(now)
            gauges = self._publish_gauges_locked()
        self._count_expired(expired)
        self._set_gauges(gauges)
        self._journal_flush()
        return expired

    def snapshot(self) -> Dict:
        now = self._clock()
        with self._lock:
            gangs = sorted(
                self._gangs.values(), key=lambda g: (g.created_at, g.gang_id)
            )
            out = {
                "enabled": True,
                "ttl_s": self.ttl_s,
                "mesh": {
                    "rows": self._mesh.rows if self._mesh else 0,
                    "cols": self._mesh.cols if self._mesh else 0,
                    "nodes": len(self._mesh) if self._mesh else 0,
                },
                "gangs": [gang.to_dict(now) for gang in gangs],
                "reserved_nodes": len(self._reserved_map_locked()),
            }
        return out

    def to_json(self) -> bytes:
        import json

        return json.dumps(self.snapshot()).encode() + b"\n"
