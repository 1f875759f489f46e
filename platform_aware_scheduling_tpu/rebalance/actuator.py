"""Safe eviction actuation: the only component that touches the cluster.

Every planned move passes four gates before the pods/eviction
subresource is called, in order:

  1. per-pod cooldown — a pod EVICTED recently is left alone, so a
     workload cannot be bounced every cycle (skipped moves do not start
     a cooldown: a pdb- or rate-blocked pod stays eligible and is simply
     re-gated next cycle);
  2. per-workload-group min-available — evicting must not drop the
     group's running count below the floor (the in-tree analogue of a
     PodDisruptionBudget, enforced BEFORE the API server gets a say);
  3. token-bucket rate limit — cluster-wide evictions per second with a
     small burst, so even a pathological plan drains slowly;
  4. mode — ``dry-run`` stops here (the move is recorded as skipped with
     reason ``dry_run``), ``active`` evicts.

A 409 from the API server (a real PodDisruptionBudget) is recorded as a
skipped move with reason ``pdb`` and never retried within the cycle.
Every outcome increments ``pas_rebalance_moves_{executed,skipped}_total``.

Gang atomicity (docs/gang.md): a pod that is a gang member (carries
``pas-workload-group`` + ``pas-gang-size``) is never evicted as a
subset — a plan naming only part of a gang skips those moves with
reason ``gang_partial``; a plan naming the WHOLE gang gates the gang as
one unit (any member in cooldown, a group floor breach, or missing rate
tokens skips the entire gang) and then evicts its members together.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from platform_aware_scheduling_tpu.kube.client import KubeError
from platform_aware_scheduling_tpu.kube.objects import Pod, object_key
from platform_aware_scheduling_tpu.rebalance.replan import Move
from platform_aware_scheduling_tpu.utils import klog, trace
from platform_aware_scheduling_tpu.utils import labels as shared_labels

MODE_OFF = "off"
MODE_DRY_RUN = "dry-run"
MODE_ACTIVE = "active"
MODES = (MODE_OFF, MODE_DRY_RUN, MODE_ACTIVE)

#: token-bucket eviction rate (evictions/s) and burst
DEFAULT_RATE_PER_S = 0.5
DEFAULT_BURST = 3
#: per-pod eviction cooldown, seconds
DEFAULT_COOLDOWN_S = 300.0
#: per-workload-group running-pod floor the actuator must not evict below
DEFAULT_MIN_AVAILABLE = 1
#: back-compat alias — the definition moved to utils/labels.py so
#: gang/, rebalance/, and the decision records share one constant
GROUP_LABEL = shared_labels.GROUP_LABEL


class TokenBucket:
    """Classic token bucket; ``clock`` injectable for hermetic tests."""

    def __init__(
        self,
        rate_per_s: float = DEFAULT_RATE_PER_S,
        burst: int = DEFAULT_BURST,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.rate_per_s = float(rate_per_s)
        self.burst = max(1, int(burst))
        self._clock = clock
        self._tokens = float(self.burst)
        self._last = clock()
        self._lock = threading.Lock()

    def try_take(self) -> bool:
        return self.try_take_n(1)

    def try_take_n(self, n: int) -> bool:
        """Take ``n`` tokens atomically or none at all — the gang-atomic
        eviction gate (a gang larger than ``burst`` can never pass; the
        operator sizes the burst to the largest gang they will evict)."""
        with self._lock:
            now = self._clock()
            self._tokens = min(
                float(self.burst),
                self._tokens + (now - self._last) * self.rate_per_s,
            )
            self._last = now
            if self._tokens >= float(n):
                self._tokens -= float(n)
                return True
            return False


@dataclass
class ActuationResult:
    executed: List[Move] = field(default_factory=list)
    skipped: Dict[str, List[Move]] = field(default_factory=dict)

    def skip(self, reason: str, move: Move) -> None:
        self.skipped.setdefault(reason, []).append(move)

    def skip_counts(self) -> Dict[str, int]:
        return {reason: len(moves) for reason, moves in self.skipped.items()}


def workload_group(pod: Pod) -> str:
    """The min-available accounting unit: the explicit group label, else
    the first ownerReference's name (ReplicaSet/Job/StatefulSet), else
    the pod's own name (a bare pod is its own group of one)."""
    label = pod.get_labels().get(GROUP_LABEL)
    if label:
        return f"label/{pod.namespace}/{label}"
    owners = pod.metadata.get("ownerReferences") or []
    if owners and owners[0].get("name"):
        return f"owner/{pod.namespace}/{owners[0]['name']}"
    return f"pod/{pod.namespace}/{pod.name}"


class SafeActuator:
    """Executes a plan's moves through the eviction subresource, behind
    the cooldown / min-available / rate-limit gates."""

    def __init__(
        self,
        kube_client,
        mode: str = MODE_DRY_RUN,
        rate_per_s: float = DEFAULT_RATE_PER_S,
        burst: int = DEFAULT_BURST,
        cooldown_s: float = DEFAULT_COOLDOWN_S,
        min_available: int = DEFAULT_MIN_AVAILABLE,
        clock: Callable[[], float] = time.monotonic,
    ):
        if mode not in MODES:
            raise ValueError(f"unknown rebalance mode {mode!r}")
        self.kube_client = kube_client
        self.mode = mode
        self.cooldown_s = float(cooldown_s)
        self.min_available = int(min_available)
        self._clock = clock
        self._bucket = TokenBucket(rate_per_s, burst, clock)
        self._lock = threading.Lock()
        self._last_evicted: Dict[str, float] = {}  # pod key -> stamp
        # optional gang.GangTracker (set by assembly when --gang=on): a
        # fully-evicted gang's slice reservation is released so the mesh
        # nodes return to the pool instead of being held by a dead gang
        self.gang_tracker = None
        # optional kube.lease.LeaseElector: with --leaderElect, EVERY
        # eviction re-verifies the fencing token against the live lease
        # first.  A leader deposed mid-cycle (its plan already computed,
        # a standby already promoted) fails this check and the move is
        # skipped with reason ``fenced`` — the new leader owns that pod
        # now, and acting anyway is the double-eviction split-brain
        # (docs/robustness.md "HA & leader election")
        self.leadership = None

    # -- gates -----------------------------------------------------------------

    def _in_cooldown(self, pod_key: str) -> bool:
        with self._lock:
            stamp = self._last_evicted.get(pod_key)
        return stamp is not None and (self._clock() - stamp) < self.cooldown_s

    def _stamp(self, pod_key: str) -> None:
        with self._lock:
            self._last_evicted[pod_key] = self._clock()

    # -- actuation -------------------------------------------------------------

    def actuate(
        self,
        moves: List[Move],
        pods_by_key: Dict[str, Pod],
        all_pods: Optional[List[Pod]] = None,
    ) -> ActuationResult:
        """Apply the plan.  ``pods_by_key`` maps move.pod_key to the live
        Pod object; ``all_pods`` is the cluster pod list used for group
        min-available accounting (group members evicted earlier in this
        same call count against the floor) AND for gang-membership
        completeness — without it a gang's planned moves are taken as
        the full membership (nothing to verify against).

        Gang members are never evicted as a subset: partial-gang moves
        skip with reason ``gang_partial``; whole-gang moves gate and
        evict atomically (module doc)."""
        result = ActuationResult()
        group_running: Dict[str, int] = {}
        gang_members: Dict[str, set] = {}  # gang id -> live member keys
        if all_pods is not None:
            for pod in all_pods:
                # terminating pods (deletionTimestamp set) are already on
                # their way out — counting them as available would let an
                # eviction drop the group below the floor
                if (
                    pod.phase in ("Succeeded", "Failed")
                    or pod.deletion_timestamp is not None
                ):
                    continue
                group = workload_group(pod)
                group_running[group] = group_running.get(group, 0) + 1
                gang = shared_labels.gang_id_for(
                    pod.namespace, pod.get_labels()
                )
                if gang is not None:
                    # membership is compared via object_key on the Pod
                    # objects THEMSELVES — Move.pod_key's format
                    # (object_key in production, free-form in tests) is
                    # never assumed
                    gang_members.setdefault(gang, set()).add(
                        object_key(pod)
                    )
        singles: List[Move] = []
        gang_moves: Dict[str, List[Move]] = {}
        for move in moves:
            pod = pods_by_key.get(move.pod_key)
            gang = (
                shared_labels.gang_id_for(pod.namespace, pod.get_labels())
                if pod is not None
                else None
            )
            if gang is not None:
                gang_moves.setdefault(gang, []).append(move)
            else:
                singles.append(move)
        for gang, gmoves in gang_moves.items():
            planned = {
                object_key(pods_by_key[m.pod_key])
                for m in gmoves
                if m.pod_key in pods_by_key
            }
            members = gang_members.get(gang, planned)
            if planned != members:
                # evicting a subset would leave a half-dead gang holding
                # its slice: whole gangs or nothing
                for move in gmoves:
                    result.skip("gang_partial", move)
                continue
            self._actuate_gang(gang, gmoves, pods_by_key, group_running,
                               all_pods, result)
        for move in singles:
            pod = pods_by_key.get(move.pod_key)
            if pod is None:
                result.skip("error", move)
                continue
            if self._in_cooldown(move.pod_key):
                result.skip("cooldown", move)
                continue
            group = workload_group(pod)
            if all_pods is not None:
                if group_running.get(group, 0) - 1 < self.min_available:
                    result.skip("min_available", move)
                    continue
            if not self._bucket.try_take():
                result.skip("rate_limit", move)
                continue
            if self.mode != MODE_ACTIVE:
                result.skip("dry_run", move)
                continue
            if not self._evict(move, pod, result):
                continue
            if group in group_running:
                group_running[group] -= 1
        if result.executed:
            trace.COUNTERS.inc(
                "pas_rebalance_moves_executed_total", len(result.executed)
            )
        for reason, skipped in result.skipped.items():
            trace.COUNTERS.inc(
                "pas_rebalance_moves_skipped_total",
                len(skipped),
                labels={"reason": reason},
            )
        return result

    def _evict(self, move: Move, pod: Pod, result: ActuationResult) -> bool:
        """One eviction through the subresource; False records the skip
        (409 -> ``pdb``, fencing refusal -> ``fenced``, anything else ->
        ``error``).  The fencing check runs before EACH eviction, not
        once per cycle: leadership can move between the first and last
        move of one plan."""
        if self.leadership is not None and not self.leadership.check_fencing():
            klog.v(1).info_s(
                f"eviction of {move.pod_key} refused: fencing token no "
                f"longer valid (leadership moved)",
                component="rebalance",
            )
            result.skip("fenced", move)
            return False
        try:
            self.kube_client.evict_pod(pod.namespace, pod.name)
        except KubeError as exc:
            reason = "pdb" if exc.status == 409 else "error"
            klog.v(2).info_s(
                f"eviction of {move.pod_key} refused ({reason}): {exc}",
                component="rebalance",
            )
            result.skip(reason, move)
            return False
        self._stamp(move.pod_key)
        result.executed.append(move)
        klog.v(2).info_s(
            f"evicted {move.pod_key}: {move.from_node} -> "
            f"{move.to_node} (gain {move.gain})",
            component="rebalance",
        )
        return True

    def _actuate_gang(
        self,
        gang: str,
        gmoves: List[Move],
        pods_by_key: Dict[str, Pod],
        group_running: Dict[str, int],
        all_pods: Optional[List[Pod]],
        result: ActuationResult,
    ) -> None:
        """Whole-gang atomic actuation: every gate is evaluated for the
        gang as one unit BEFORE any eviction, so a mid-gang gate trip can
        never strand a half-evicted gang.  (An API-server refusal on one
        member mid-flight is recorded per pod — the server, not this
        actuator, broke atomicity there.)"""
        pods = []
        for move in gmoves:
            pod = pods_by_key.get(move.pod_key)
            if pod is None:
                for m in gmoves:
                    result.skip("error", m)
                return
            pods.append(pod)
        if any(self._in_cooldown(m.pod_key) for m in gmoves):
            for m in gmoves:
                result.skip("cooldown", m)
            return
        if all_pods is not None:
            floor_breach: Dict[str, int] = {}
            for pod in pods:
                group = workload_group(pod)
                floor_breach[group] = floor_breach.get(group, 0) + 1
            for group, n in floor_breach.items():
                if group_running.get(group, 0) - n < self.min_available:
                    for m in gmoves:
                        result.skip("min_available", m)
                    return
        if not self._bucket.try_take_n(len(gmoves)):
            for m in gmoves:
                result.skip("rate_limit", m)
            return
        if self.mode != MODE_ACTIVE:
            for m in gmoves:
                result.skip("dry_run", m)
            return
        klog.v(2).info_s(
            f"evicting gang {gang} atomically ({len(gmoves)} pods)",
            component="rebalance",
        )
        evicted = 0
        for move, pod in zip(gmoves, pods):
            if self._evict(move, pod, result):
                evicted += 1
                group = workload_group(pod)
                if group in group_running:
                    group_running[group] -= 1
        if evicted == len(gmoves) and self.gang_tracker is not None:
            # the whole gang is gone: free its slice reservation (a
            # partially-refused gang keeps its hold; the tracker's
            # dead-gang sweep reclaims it once every member disappears)
            self.gang_tracker.release(gang)

    # -- preemption (admission/preempt.py; docs/admission.md) ------------------

    def preempt_gang(
        self,
        gang_id: str,
        pods: List[Pod],
        counters=None,
    ) -> Tuple[bool, ActuationResult]:
        """The preemption verb — deliberate whole-gang displacement for
        the admission plane, distinct from drift eviction in three ways:

          * **no min-available floor**: preemption removes the victim
            group entirely by design; the floor exists to stop a drift
            plan from accidentally gutting a group, and here the planner
            chose the whole gang deliberately (whole-gang atomicity is
            the safety property, not the floor);
          * **no slice release on success**: the victim flips to
            DRAINING (caller) and keeps holding its nodes until its pods
            are actually gone — reservation-while-draining;
          * **its own accounting**: outcomes land in the admission
            plane's ``pas_preemption_*`` families via ``counters``, not
            in ``pas_rebalance_moves_*`` (the off path registers
            nothing).

        The shared gates stay: any member in eviction cooldown, missing
        rate tokens (taken atomically for the whole gang), or a
        non-active mode refuses the WHOLE preemption before any API
        call, and every eviction re-verifies the fencing token.  Returns
        ``(fully_evicted, result)``."""
        result = ActuationResult()
        moves = [
            Move(
                pod_key=object_key(pod),
                namespace=pod.namespace,
                name=pod.name,
                from_node=pod.spec_node_name or "",
                to_node="",
                gain=0.0,
            )
            for pod in pods
        ]

        def refuse(reason: str) -> Tuple[bool, ActuationResult]:
            for m in moves:
                result.skip(reason, m)
            if counters is not None and moves:
                counters.inc(
                    "pas_preemption_skipped_total",
                    len(moves),
                    labels={"reason": reason},
                )
            return False, result

        if not moves:
            return False, result
        if any(self._in_cooldown(m.pod_key) for m in moves):
            return refuse("cooldown")
        if not self._bucket.try_take_n(len(moves)):
            return refuse("rate_limit")
        if self.mode != MODE_ACTIVE:
            return refuse("dry_run")
        klog.v(1).info_s(
            f"preempting gang {gang_id} atomically ({len(moves)} pods)",
            component="rebalance",
        )
        evicted = 0
        for move, pod in zip(moves, pods):
            if self._evict(move, pod, result):
                evicted += 1
        if counters is not None:
            if evicted:
                counters.inc("pas_preemption_evictions_total", evicted)
            for reason, skipped in result.skipped.items():
                counters.inc(
                    "pas_preemption_skipped_total",
                    len(skipped),
                    labels={"reason": reason},
                )
        return evicted == len(moves), result
