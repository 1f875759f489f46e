"""Gang-aware preemption: the admission plane's sharp edge.

When a higher-priority gang is infeasible for capacity reasons, the
planner selects the **cheapest set of lower-priority victims** — whole
gangs only, never equal-or-higher class — whose release makes the
target's demand feasible, evicts them all-or-nothing through the
``SafeActuator``'s atomic gang path (fencing-token re-verification per
eviction, breaker-gated kube client, token-bucket rate limit), and
**reserves the freed slice for the target before the victims finish
draining** (GangTracker.reserve_slice over the DRAINING holds), so the
hole can never be observed free by third parties.

Safety argument, in gate order:

  1. **leader-only** — only the replica holding the lease plans or
     actuates (a standby planning against its own ledger could pick
     different victims);
  2. **never equal-or-higher** — the victim pool is strictly
     lower-ranked gangs; two same-class gangs can never preempt each
     other into a livelock;
  3. **whole gangs only** — victims come from the tracker's census and
     are evicted via the atomic gang verb; a partial refusal (pdb,
     fencing, rate) aborts the rest of the plan and, critically,
     **creates no reservation**: nothing is ever admitted on the back
     of a half-executed plan (fenced-refusal containment);
  4. **bounded appetite** — at most ``max_victims`` pods per plan, the
     BudgetController's preemption-aggressiveness knob
     (utils/control.attach_preemption): sustained availability burn in
     the victim classes steps the ceiling down.

Every executed preemption lands a provenance record
(DecisionLog.record_preemption) naming target, victims, and the
reserved slice.  All ``pas_preemption_*`` families live in the
admission plane's CounterSet — the off path registers nothing.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from platform_aware_scheduling_tpu.gang.group import GangSpec
from platform_aware_scheduling_tpu.kube.objects import Pod
from platform_aware_scheduling_tpu.ops import topology
from platform_aware_scheduling_tpu.utils import events, klog

DEFAULT_MAX_VICTIMS = 8
#: minimum seconds between plans for the SAME target gang — the retry
#: loop re-consults every Filter; replanning each time would hammer the
#: census and the actuator gates for a target that just got refused
DEFAULT_RETRY_S = 5.0
#: preemption eviction burst of the planner's dedicated SafeActuator; a
#: victim gang larger than this can never be evicted atomically
ACTUATOR_BURST = 8


class PreemptionPlanner:
    """Victim selection + atomic execution for one admission plane.

    ``plane`` supplies class ranks (its single classifier) and the
    CounterSet; ``tracker`` is the gang ledger (census, feasibility
    what-ifs, reservation-while-draining); ``actuator`` the SafeActuator
    whose ``preempt_gang`` verb does the evicting."""

    def __init__(
        self,
        plane,
        tracker,
        actuator,
        max_victims: int = DEFAULT_MAX_VICTIMS,
        retry_s: float = DEFAULT_RETRY_S,
        leadership=None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.plane = plane
        self.tracker = tracker
        self.actuator = actuator
        self.max_victims = max(1, int(max_victims))
        self.retry_s = float(retry_s)
        self.leadership = leadership
        self._clock = clock
        self._lock = threading.Lock()
        self._last_attempt: Dict[str, float] = {}  # target gang -> stamp
        self._plans = 0
        self._last_plan: Optional[Dict] = None

    @property
    def counters(self):
        return self.plane.counters

    # -- trigger ---------------------------------------------------------------

    def maybe_preempt(
        self, pod: Pod, klass: str, rank: int, request_id: str = ""
    ) -> bool:
        """Plan-and-execute for one starving gang pod; True when a
        preemption fully executed and the slice is reserved.
        ``request_id`` is the triggering Filter span's id, carried into
        the provenance record and causal-spine events."""
        spec = GangSpec.from_pod(pod)
        if spec is None:
            return False
        now = self._clock()
        with self._lock:
            last = self._last_attempt.get(spec.gang_id)
            if last is not None and (now - last) < self.retry_s:
                return False
            self._last_attempt[spec.gang_id] = now
            if len(self._last_attempt) > 4096:
                self._last_attempt = {spec.gang_id: now}
        if self.leadership is not None and not self.leadership.is_leader():
            self._outcome("not_leader")
            return False
        target_state = self.tracker.gang_state(spec.gang_id)
        if target_state in ("reserved", "bound", "draining"):
            # already holds (or is itself being preempted): nothing to do
            return False
        plan = self._plan(spec, rank)
        if plan is None:
            self._outcome("infeasible")
            return False
        victims, nodes, anchor = plan
        return self._execute(
            pod, spec, klass, victims, nodes, anchor, request_id
        )

    # -- victim selection ------------------------------------------------------

    def _plan(
        self, spec: GangSpec, rank: int
    ) -> Optional[Tuple[List[Dict], List[str], Optional[tuple]]]:
        """The cheapest strictly-lower-class victim set that makes
        ``spec`` feasible, or None.  Greedy add (lowest class first,
        fewest pods) to feasibility, then reverse-prune — small, exact
        enough, and O(victims^2) over a census that is already tiny."""
        census = self.tracker.preemption_census()
        pool = [
            c
            for c in census
            if c["gang"] != spec.gang_id
            and self.plane.rank_of_gang(c["gang"]) > rank
        ]
        if not pool:
            return None
        pool.sort(
            key=lambda c: (
                -self.plane.rank_of_gang(c["gang"]),
                len(c["members"]) or c["size"],
                c["gang"],
            )
        )
        mesh = self.tracker.mesh()
        held = self.tracker.reserved_nodes()
        chosen: List[Dict] = []
        freed: set = set()
        feasible = None
        for candidate in pool:
            chosen.append(candidate)
            freed.update(candidate["nodes"])
            feasible = self._feasible(spec, mesh, held, freed)
            if feasible is not None:
                break
        if feasible is None:
            return None
        # reverse-prune: drop any victim whose nodes turn out unneeded
        # (greedy may have added a cheap gang that the final anchor
        # doesn't touch)
        for candidate in list(reversed(chosen[:-1])):
            trial = freed - set(candidate["nodes"])
            result = self._feasible(spec, mesh, held, trial)
            if result is not None:
                chosen.remove(candidate)
                freed = trial
                feasible = result
        victim_pods = sum(
            len(c["members"]) or c["size"] for c in chosen
        )
        if victim_pods > self.max_victims:
            self._outcome("over_budget")
            return None
        nodes, anchor = feasible
        return chosen, nodes, anchor

    def _feasible(
        self,
        spec: GangSpec,
        mesh,
        held: Dict[str, str],
        freed: set,
    ) -> Optional[Tuple[List[str], Optional[tuple]]]:
        """Would ``spec`` place if ``freed`` nodes returned to the pool?
        Returns (slice nodes, anchor) or None — the same solve shape as
        GangTracker._try_reserve_locked, run as a what-if."""
        if spec.topology is None:
            try:
                names = {n.name for n in self.tracker.nodes_provider()}
            except Exception:
                return None
            free = sorted(
                name
                for name in names
                if name not in held or name in freed
            )
            if len(free) < spec.size:
                return None
            return free[: spec.size], None
        if mesh is None or len(mesh) == 0:
            return None
        free_names = [
            name
            for name in mesh.coord_of
            if name not in held or name in freed
        ]
        found = topology.best_slice(
            mesh, free_names, spec.topology,
            use_device=self.tracker.use_device,
        )
        if found is None:
            return None
        names, anchor, _domain = found
        return names, anchor

    # -- execution -------------------------------------------------------------

    def _execute(
        self,
        pod: Pod,
        spec: GangSpec,
        klass: str,
        victims: List[Dict],
        nodes: List[str],
        anchor: Optional[tuple],
        request_id: str = "",
    ) -> bool:
        pods_by_key = self._live_pods()
        if pods_by_key is None:
            self._outcome("no_pod_view")
            return False
        executed: List[Dict] = []
        for victim in victims:
            members = victim["members"]
            pods = [
                pods_by_key[key] for key in members if key in pods_by_key
            ]
            if not pods:
                # every member already gone: the sweep will release it;
                # treat as drained and move on
                self.tracker.mark_draining(victim["gang"])
                executed.append(victim)
                continue
            fully, _result = self.actuator.preempt_gang(
                victim["gang"], pods, counters=self.counters
            )
            if not fully:
                # containment: a refused victim (fencing moved, pdb,
                # rate, dry-run) aborts the remaining plan and creates
                # NO reservation — already-drained victims free up
                # capacity the normal retry loop will use, but nothing
                # is admitted on the back of a half-executed plan
                self._outcome("actuation_refused")
                klog.v(1).info_s(
                    f"preemption for gang {spec.gang_id} aborted at "
                    f"victim {victim['gang']} (refused); no reservation "
                    f"created",
                    component="admission",
                )
                return False
            self.tracker.mark_draining(victim["gang"])
            executed.append(victim)
        if not self.tracker.reserve_slice(pod, nodes, anchor):
            self._outcome("reserve_failed")
            return False
        self.counters.inc("pas_preemption_reservations_total")
        self.counters.inc(
            "pas_preemption_victim_gangs_total", len(executed)
        )
        target = f"{pod.namespace}/{pod.name}"
        detail = {
            "target": target,
            "target_gang": spec.gang_id,
            "class": klass,
            "outcome": "planned",
            "request_id": request_id,
            "victims": [
                {
                    "gang": v["gang"],
                    "class": self.plane.class_of_gang(v["gang"]),
                    "pods": len(v["members"]) or v["size"],
                }
                for v in executed
            ],
            "reserved_nodes": list(nodes),
            "anchor": list(anchor) if anchor is not None else None,
        }
        self._outcome("planned", detail)
        if self.plane.decision_log is not None:
            self.plane.decision_log.record_preemption(detail)
        events.JOURNAL.publish(
            "preemption",
            "planned",
            request_id=request_id,
            pod=target,
            gang=spec.gang_id or "",
            data={
                "class": klass,
                "victims": [v["gang"] for v in detail["victims"]],
            },
        )
        for victim in detail["victims"]:
            events.JOURNAL.publish(
                "preemption",
                "victim evicted",
                request_id=request_id,
                pod=target,
                gang=victim["gang"],
                data={"class": victim["class"], "pods": victim["pods"]},
            )
        events.JOURNAL.publish(
            "preemption",
            "slice reserved",
            request_id=request_id,
            pod=target,
            gang=spec.gang_id or "",
            data={"nodes": len(nodes)},
        )
        klog.v(1).info_s(
            f"preempted {len(executed)} gang(s) for {spec.gang_id} "
            f"(class={klass}); slice reserved while victims drain",
            component="admission",
        )
        return True

    def _live_pods(self) -> Optional[Dict[str, Pod]]:
        provider = getattr(self.tracker, "pods_provider", None)
        if provider is None:
            return None
        try:
            return {
                f"{p.namespace}/{p.name}": p
                for p in provider()
                if p.phase not in ("Succeeded", "Failed")
                and p.deletion_timestamp is None
            }
        except Exception as exc:
            klog.error("preemption pod list failed: %s", exc)
            return None

    def _outcome(self, outcome: str, detail: Optional[Dict] = None) -> None:
        self.counters.inc(
            "pas_preemption_plans_total", labels={"outcome": outcome}
        )
        with self._lock:
            self._plans += 1
            self._last_plan = detail if detail is not None else {
                "outcome": outcome
            }

    # -- the debug surface -----------------------------------------------------

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "enabled": True,
                "max_victims": self.max_victims,
                "retry_s": self.retry_s,
                "plans": self._plans,
                "last_plan": self._last_plan,
            }
