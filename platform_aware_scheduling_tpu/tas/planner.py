"""Batch planner: the whole-pending-set solve wired into the service.

SURVEY §7 step 4's product form.  kube-scheduler's protocol is one pod
per round-trip; the planner watches pending pods carrying the
``telemetry-policy`` label, solves the ENTIRE set after each telemetry
refresh pass with ``models/batch_scheduler.scheduling_step``, and lets the
per-pod verbs be answered from the precomputed solution: when Prioritize
arrives for a planned pod, its batch-assigned node gets the top score,
steering the sequential scheduler onto the coordinated plan
(capacity-aware placement the per-pod ordinal scores alone cannot
express).

The plan is what the sequential system would decide: pods in the order
they were created, each on the best node — by its own policy's
``scheduleonmetric`` rule — that reports the metric, does not violate the
pod's OWN policy's ``dontschedule`` rules, and has room left by
kube-scheduler's ``NodeResourcesFit`` (pods, cpu, memory) after the bound
and the already-planned pods.

Room, and in which representation it is exact.  A replan reads the nodes'
room as exact integers over :data:`FIT_RESOURCES` (pods, cpu, memory:
allocatable − what the bound pods hold, in milli-units) and each pending
pod's own demand.  Which form the solve runs follows from the pending set:

* pods that all ask for the same amounts: the room is ONE int32 count a
  node, the pods of that request it still takes, and a pod books one unit
  (:meth:`BatchPlanner._room`) — exact for such a set;
* pods of unlike requests: the room is ``[L, R, n_cap]`` int32 rows and
  the demand ``[size, L, R]`` columns, padded like the other columns
  (:meth:`BatchPlanner._room_rows`); pod i is feasible on node j iff every
  resource's room covers ITS request, and books its own vector.  Each
  resource's row is divided by the gcd of its rooms and demands (512Gi
  nodes and pods of whole Gi are counts under a thousand), a room no
  pending set can exhaust is cut to the set's total, and where a quantity
  still passes 31 bits the row rides as two limbs of 31
  (ops/i64.split31_np, ``L`` = 2).  No demand is rounded up and no room
  down: the comparisons are kube-scheduler's NodeResourcesFit's own, on
  int64 quantities.

The mesh form (``--batchPlannerDevices`` > 1) runs both forms, as one
device does: unlike pods book their own vectors there too, and such a
replan counts itself in ``pas_planner_mesh_demand_solves_total``.  Only
``--batchSolver sinkhorn`` takes a count alone: a replan of unlike pods
there counts every pod as the LARGEST request of the set (never an
overcommit, not exact; such a plan names a node the pod's own rule ranks
lower once the true room would still take it) and counts itself in
``pas_planner_conservative_room_total``.

Shapes: the pending set is padded to a few fixed sizes (powers of two
from :data:`PAD_FLOOR`), so a draining backlog runs a handful of compiled
programs and never retraces; the first time a size is seen every smaller
one is compiled with it, in the refresh thread.

Trigger: one — the end of a refresh pass (``cache.on_refresh_pass``), so
the plan for the version a pass published exists as soon as that version
serves.  A plan whose version is not the mirror's is never served.

More than one device (``--batchPlannerDevices=n``, n > 1): the solve runs
node-sharded over a mesh of the first n devices.  A replan then places the
mirror's ``[M, N]`` view and the room (a count or ``[L, R, N]`` rows) split
over the nodes, the demand columns on every device (stage
``plan.place``), makes the candidate mask and every other ``[P, N]`` array
on the mesh so that none is ever whole on one device, and reads back
``node_for_pod`` alone.  The plan is the one-device plan, pod for pod.

OPT-IN (``--batchPlanner`` on cmd/tas.py): with the planner off the verbs
behave exactly like the reference.  Planner answers degrade gracefully:
unknown pod / stale plan / no assignment -> the ordinary per-request path.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from platform_aware_scheduling_tpu.kube.objects import Pod, object_key
from platform_aware_scheduling_tpu.models.batch_scheduler import (
    ClusterState,
    PendingPods,
    mesh_scheduling_step,
    observed_scheduling_step,
    score_and_filter,
)
from platform_aware_scheduling_tpu.ops import i64, solveobs
from platform_aware_scheduling_tpu.ops.assign import LIMB_MASK
from platform_aware_scheduling_tpu.ops.rules import RuleSet
from platform_aware_scheduling_tpu.ops.state import RULE_PAD, TensorStateMirror
from platform_aware_scheduling_tpu.parallel.mesh import (
    NODE_AXIS,
    make_mesh,
    node_sharded,
    replicated,
)
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.utils import klog, trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity

TAS_POLICY_LABEL = "telemetry-policy"
DEFAULT_NODE_CAPACITY = 110  # kubelet's default max pods per node
PAD_FLOOR = 1024  # the smallest padded pending set
POLICY_PAD = 8  # distinct policies of a pending set, padded to multiples
#: kube-scheduler's NodeResourcesFit resources the planner counts, in
#: milli-units; one pod asks for 1000 milli of ``pods``
FIT_RESOURCES = ("pods", "cpu", "memory")
_UNBOUNDED = 1 << 60  # a resource the node does not report: no limit
_ROOM_LIMIT = 1 << 62  # what two limbs of 31 bits hold


def padded_size(pending: int) -> int:
    """The fixed size a pending set of ``pending`` pods is solved at."""
    size = PAD_FLOOR
    while size < pending:
        size *= 2
    return size


@functools.lru_cache(maxsize=4096)
def _milli(text: str) -> int:
    """A resource quantity in milli-units (0 when unreadable)."""
    try:
        return int(Quantity(text).milli_value_exact()[0])
    except Exception:
        return 0


def _pod_requests(pod: Pod) -> Tuple[int, int]:
    """(cpu, memory) the pod's containers request, in milli-units (an
    amount no node could report is cut to :data:`_UNBOUNDED`)."""
    cpu = mem = 0
    for requests in pod.container_resource_requests():
        if "cpu" in requests:
            cpu += _milli(str(requests["cpu"]))
        if "memory" in requests:
            mem += _milli(str(requests["memory"]))
    return min(cpu, _UNBOUNDED), min(mem, _UNBOUNDED)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _candidate_mask(pods: int, nodes: int, pending, known) -> jax.Array:
    """bool [pods, nodes], made on the device: row i is a pending pod
    (``i < pending``, the rest is padding and never assigned) and column j
    an interned node (``j < known``)."""
    live = jnp.arange(pods, dtype=jnp.int32)[:, None] < pending
    return live & (jnp.arange(nodes, dtype=jnp.int32)[None, :] < known)


class MeshRefused(ValueError):
    """The planner cannot span the devices it was told to use; cmd/tas.py
    turns it into a usage error at start-up."""


class _InformerGroup:
    """Stop-handle over the planner's pod + node informers."""

    def __init__(self, *informers):
        self._informers = informers

    def stop(self) -> None:
        for informer in self._informers:
            informer.stop()

    def wait_for_cache_sync(self, timeout: float = 30.0) -> bool:
        return all(i.wait_for_cache_sync(timeout) for i in self._informers)


class BatchPlanner:
    """Maintains the batch solution over the current pending set."""

    def __init__(
        self,
        cache: AutoUpdatingCache,
        mirror: TensorStateMirror,
        node_capacity: int = DEFAULT_NODE_CAPACITY,
        solver: str = "greedy",
        devices: int = 1,
    ):
        """``devices``: how many of JAX's devices the solve spans; 1 is the
        one-device solve (the Pallas assigner on a TPU), more a mesh over
        the first ``devices`` of them with every ``[P, N]`` array split
        over the nodes.  :class:`MeshRefused` when JAX has fewer, when the
        mirror's node capacity does not divide, or with ``solver``
        "sinkhorn", which has no mesh form on this path.

        ``solver``: "greedy" reproduces what the sequential scheduler
        would do; "sinkhorn" globally coordinates the batch
        (ops/sinkhorn.py) — strictly an enhancement over the reference.

        ``node_capacity`` is only the fallback for nodes whose allocatable
        hasn't been observed (``node_capacity`` − bound pods); observed
        nodes have the room kube-scheduler's NodeResourcesFit leaves
        (:meth:`_room`, :meth:`_room_rows`), fed by :meth:`node_changed` /
        :meth:`pod_observed` (wired to informers by :meth:`watch`)."""
        self.cache = cache
        self.mirror = mirror
        self.node_capacity = node_capacity
        self.solver = solver
        # ONE lock over the pending set and the bound set: a replan's
        # snapshot sees a pod either pending or bound, never both
        self._lock = threading.RLock()
        # pod key -> ((namespace, policy name), cpu, memory), in the order
        # the pods were created (dict order: the informer lists them so)
        self._pending: Dict[str, Tuple[Tuple[str, str], int, int]] = {}
        # ({pod key: assigned node name}, mirror version it was solved
        # at), replaced whole by a replan; read without the lock
        self._published: Tuple[Dict[str, str], int] = ({}, -1)
        # (pods, cpu, memory) in milli-units throughout: node -> allocatable;
        # bound pod key -> (node, what the pod holds); node -> what its
        # bound pods hold together
        self._node_alloc: Dict[str, Tuple[int, int, int]] = {}
        self._bound_pods: Dict[str, Tuple[str, Tuple[int, int, int]]] = {}
        self._bound_used: Dict[str, Tuple[int, int, int]] = {}
        # shapes every padded size below them has been compiled for
        self._warmed: set = set()
        # more than one device: the mesh, the mask born split over it, and
        # where a replan's operands go — what has a node axis split over the
        # mesh, the per-pod columns and the rules on every device
        self.mesh = None
        self._mask = _candidate_mask
        if devices != 1:
            self.mesh = mesh = self._mesh_over(devices)
            by_node, everywhere = node_sharded(mesh), replicated(mesh)
            self._mask = jax.jit(
                _candidate_mask.__wrapped__, static_argnums=(0, 1),
                out_shardings=by_node,
            )
            # keyed by the room's form: a count a node, or [L, R, N] rows
            # with each pod's [L, R] demand on every device
            room_spec = {False: PartitionSpec(NODE_AXIS),
                         True: PartitionSpec(None, None, NODE_AXIS)}
            self._placement = {
                vectors: (
                    ClusterState(
                        metric_values=by_node, metric_present=by_node,
                        dontschedule=everywhere,
                        capacity=NamedSharding(mesh, room_spec[vectors]),
                    ),
                    PendingPods(
                        metric_row=everywhere, op_id=everywhere,
                        candidates=by_node, policy=everywhere,
                        demand=everywhere if vectors else None,
                    ),
                )
                for vectors in (False, True)
            }
            trace.COUNTERS.set_gauge("pas_planner_mesh_devices", devices)

    def _mesh_over(self, devices: int):
        have = jax.devices()
        n_cap = self.mirror.device_view().node_capacity
        if devices < 1:
            refusal = f"{devices} devices is not a number of devices"
        elif self.solver == "sinkhorn":
            refusal = "the sinkhorn solver runs on one device"
        elif len(have) < devices:
            refusal = f"JAX has {len(have)} device(s)"
        elif n_cap % devices:
            # the capacity only ever doubles: what divides it now always will
            refusal = f"the mirror's node capacity ({n_cap}) does not divide"
        else:
            return make_mesh(n_node_shards=devices, devices=have[:devices])
        raise MeshRefused(
            f"the batch planner cannot span {devices} devices: {refusal}"
        )

    # -- pending-set maintenance ----------------------------------------------

    def pod_added(self, pod: Pod) -> None:
        labels = pod.get_labels()
        if pod.spec_node_name or TAS_POLICY_LABEL not in labels:
            return
        entry = ((pod.namespace, labels[TAS_POLICY_LABEL]), *_pod_requests(pod))
        with self._lock:
            self._pending[object_key(pod)] = entry

    def pod_removed(self, pod: Pod) -> None:
        key = object_key(pod)
        with self._lock:
            self._pending.pop(key, None)
        self._published[0].pop(key, None)

    def pod_bound(self, pod: Pod) -> None:
        self.pod_removed(pod)

    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    # -- cluster capacity feed ---------------------------------------------------

    def node_changed(self, node, deleted: bool = False) -> None:
        """Track what the node can hold: ``status.allocatable`` pods, cpu
        and memory.  A node that reports no ``pods`` keeps the fallback;
        one that reports no cpu or memory is not limited by it."""
        alloc = node.allocatable
        with self._lock:
            if deleted or alloc.get("pods") is None:
                self._node_alloc.pop(node.name, None)
                return
            self._node_alloc[node.name] = tuple(
                _milli(str(alloc[r])) if alloc.get(r) is not None else _UNBOUNDED
                for r in FIT_RESOURCES
            )

    def pod_observed(self, pod: Pod, deleted: bool = False) -> None:
        """Track every pod's binding and requests so a node's room is
        allocatable − what its bound pods hold (terminated pods free
        theirs)."""
        key = object_key(pod)
        node = pod.spec_node_name
        active = (
            not deleted and node and pod.phase not in ("Succeeded", "Failed")
        )
        held = (1000, *_pod_requests(pod)) if active else None
        with self._lock:
            prev = self._bound_pods.pop(key, None)
            if prev is not None:
                was_on, freed = prev
                left = tuple(
                    u - h for u, h in zip(self._bound_used[was_on], freed)
                )
                if left[0] > 0:
                    self._bound_used[was_on] = left
                else:
                    self._bound_used.pop(was_on, None)
            if active:
                self._bound_pods[key] = (node, held)
                used = self._bound_used.get(node, (0, 0, 0))
                self._bound_used[node] = tuple(
                    u + h for u, h in zip(used, held)
                )

    @staticmethod
    def _free(alloc: Dict, used: Dict, names: List[str],
              fallback: int) -> np.ndarray:
        """int64 [known, 3]: what each interned node has free of pods, cpu
        and memory in milli-units — allocatable − what the bound pods
        request, never under 0 (an overcommitted node has room for
        nothing that asks).  A node whose allocatable was never seen has
        ``fallback`` − bound pods and no other limit."""
        unseen = (fallback * 1000, _UNBOUNDED, _UNBOUNDED)
        have = np.array([alloc.get(n, unseen) for n in names], dtype=np.int64)
        held = np.array([used.get(n, (0, 0, 0)) for n in names], dtype=np.int64)
        return np.maximum(have.reshape(-1, 3) - held.reshape(-1, 3), 0)

    @staticmethod
    def _room(free: np.ndarray, need: Tuple[int, int, int],
              n_cap: int) -> np.ndarray:
        """int32 [n_cap]: how many pods asking for ``need`` each interned
        node can still take, as kube-scheduler's NodeResourcesFit counts
        it — the least, over pods, cpu and memory, of ``free`` over the
        request, floored.  EXACT when the pending pods all ask for
        ``need``, which is when a greedy replan uses it; with
        ``need`` the largest request of a set of unlike pods it is the
        conservative room of the sinkhorn form: never an overcommit, not
        exact."""
        room = np.zeros(n_cap, dtype=np.int64)
        if len(free):
            asked = np.array(need, dtype=np.int64)
            counted = asked > 0
            room[: len(free)] = (free[:, counted] // asked[counted]).min(axis=1)
        return np.clip(room, 0, np.iinfo(np.int32).max).astype(np.int32)

    @staticmethod
    def _room_rows(free: np.ndarray, asked: np.ndarray, n_cap: int,
                   size: int) -> Tuple[np.ndarray, np.ndarray]:
        """(room int32 [L, R, n_cap], demand int32 [size, L, R]) of pods
        that ask for unlike amounts: ``free`` [known, R] and ``asked``
        [p, R] in milli-units, exactly.  Per resource the row and the
        column are divided by the gcd of every bounded room and every
        demand in them, so each stays a whole number; a room at or past
        the pending set's total demand — a resource the node does not
        report, at :data:`_UNBOUNDED` — is cut to that total, which no
        plan can exhaust.  L is 1 where every quantity then fits 31 bits
        (the common case: nodes and pods in whole Mi and millicores), else
        2: limbs of 31 bits, low first (ops/i64.split31_np).  Padding
        lanes have no room and padding rows ask for nothing; both are
        masked as in the count form."""
        known, p = len(free), len(asked)
        resources = asked.shape[1]
        have = np.empty((resources, known), dtype=np.int64)
        want = np.empty((p, resources), dtype=np.int64)
        for r in range(resources):
            rooms, demands = free[:, r], asked[:, r]
            bounded = rooms[rooms < _UNBOUNDED // 2]
            unit = int(np.gcd(np.gcd.reduce(demands), np.gcd.reduce(bounded))) or 1
            demands = demands // unit
            most = (
                int(demands.sum())
                if float(demands.max(initial=0)) * p < _ROOM_LIMIT
                else _ROOM_LIMIT - 1
            )
            have[r] = np.minimum(rooms // unit, most)
            want[:, r] = demands
        wide = max(have.max(initial=0), want.max(initial=0)) > LIMB_MASK
        limbs = 2 if wide else 1
        room = np.zeros((limbs, resources, n_cap), dtype=np.int32)
        demand = np.zeros((size, limbs, resources), dtype=np.int32)
        if wide:
            room[0, :, :known], room[1, :, :known] = i64.split31_np(have)
            demand[:p, 0], demand[:p, 1] = i64.split31_np(want)
        else:
            room[0, :, :known] = have
            demand[:p, 0] = want
        return room, demand

    # -- solve ----------------------------------------------------------------

    def replan(self) -> int:
        """Solve the current pending set; returns the number of planned
        pods.  Called at the end of every refresh pass
        (``cache.on_refresh_pass``, wired by cmd/tas.assemble) and on
        demand in tests."""
        began = time.perf_counter()
        with trace.stage("plan.snap", "pas_planner_snapshot_seconds_total"):
            snapshot = self._snapshot()
        if snapshot is None:
            self._published = ({}, self.mirror.version)
            return 0
        state, batch, keys, view, timer = snapshot
        p = len(keys)
        if self.mesh is not None:
            with trace.stage("plan.place", "pas_planner_place_seconds_total"):
                state, batch = self._place(state, batch)
        with trace.stage("plan.solve", "pas_planner_solve_seconds_total"):
            if self.mesh is not None:
                assigned = np.asarray(self._mesh_step(state, batch, timer))
                trace.COUNTERS.inc("pas_planner_mesh_solves_total")
            elif self.solver == "sinkhorn":
                from platform_aware_scheduling_tpu.ops.sinkhorn import (
                    sinkhorn_assign_kernel,
                )

                _violating, score, eligible = score_and_filter(state, batch)
                sink = sinkhorn_assign_kernel(score, eligible, state.capacity)
                if timer is not None:
                    timer.mark("execute")
                assigned = np.asarray(sink.assignment.node_for_pod)
            else:
                out = observed_scheduling_step(state, batch, timer=timer)
                assigned = np.asarray(out.assignment.node_for_pod)
            if timer is not None:
                timer.mark("readback")
        with trace.stage("plan.publish", "pas_planner_publish_seconds_total"):
            names = view.node_names
            known = len(names)
            plan = {
                key: names[node]
                for key, node in zip(keys, assigned[:p].tolist())
                if 0 <= node < known
            }
            self._published = (plan, view.version)
        counters = trace.COUNTERS
        counters.inc("pas_planner_replans_total")
        took = time.perf_counter() - began
        if batch.demand is not None:
            counters.inc("pas_planner_demand_solves_total")
            if self.mesh is not None:
                counters.inc("pas_planner_mesh_demand_solves_total")
                counters.inc("pas_planner_mesh_demand_seconds_total", took)
        counters.inc("pas_planner_replan_seconds_total", took)
        counters.set_gauge("pas_planner_pending_pods", p)
        if timer is not None:
            timer.mark("encode")
            timer.done(pods=p, nodes=known)
        klog.v(4).info_s(
            f"batch plan: {len(plan)}/{p} pods assigned", component="planner"
        )
        if self.solver != "sinkhorn":
            self._warm_smaller(state, batch, known)
        return len(plan)

    def _place(self, state: ClusterState, batch: PendingPods):
        """The replan's operands on the mesh (``self._placement``): the
        view's ``[M, N]`` values and presence go device to device, the room
        and the per-pod columns up; ``candidates`` was born split
        (``self._mask``)."""
        placement = self._placement[batch.demand is not None]
        return jax.device_put((state, batch), placement)

    def _mesh_step(self, state: ClusterState, batch: PendingPods, timer=None):
        """``node_for_pod`` of the mesh solve, ready on the devices."""
        assigned = mesh_scheduling_step(self.mesh, state, batch)
        assigned.block_until_ready()
        if timer is not None:
            timer.mark("execute")
        return assigned

    def _snapshot(self):
        """(state, batch, pod keys in order, view, timer) of one replan, or
        None when nothing can be planned.  The pending set, the node
        allocatables and the bound pods are read at ONE instant, under
        the lock; the compiled policies and the view at one other, under
        the mirror's (a per-pod lookup could straddle a metric delete +
        row reuse — ADVICE r1)."""
        with self._lock:
            entries = list(self._pending.items())
            alloc = dict(self._node_alloc)
            used = dict(self._bound_used)
        if not entries:
            return None
        keys, details = zip(*entries)
        policy_of, cpus, mems = zip(*details)
        distinct = list(dict.fromkeys(policy_of))
        policies, view, host_only = self.mirror.policies_with_view(distinct)
        # the distinct policies a plan can serve, each with a row of its own
        usable = [
            key for key in distinct
            if policies.get(key) is not None
            and policies[key].scheduleonmetric_row >= 0
            and policies[key].scheduleonmetric_metric not in host_only
        ]
        if not usable:
            return None
        obs = solveobs.ACTIVE
        timer = obs.begin("replan") if obs is not None else None
        d_of = {key: d for d, key in enumerate(usable)}
        policy = np.fromiter(
            (d_of.get(key, -1) for key in policy_of), np.int32, len(keys)
        )
        kept = None
        if len(usable) < len(distinct):
            kept = policy >= 0
            keys = tuple(itertools.compress(keys, kept.tolist()))
            policy = policy[kept]
        p = len(keys)
        size = padded_size(p)
        n_cap = view.node_capacity
        known = len(view.node_names)
        compiled = [policies[key] for key in usable]

        def padded(column: np.ndarray) -> jax.Array:
            out = np.zeros(size, dtype=np.int32)
            out[:p] = column
            return jnp.asarray(out)

        rows = np.array([c.scheduleonmetric_row for c in compiled], np.int32)
        ops = np.array([c.scheduleonmetric_op for c in compiled], np.int32)
        with trace.stage("plan.room", "pas_planner_room_seconds_total"):
            free = self._free(alloc, used, view.node_names, self.node_capacity)
            classes = len(set(zip(cpus, mems)))
            trace.COUNTERS.set_gauge("pas_planner_demand_classes", classes)
            demand = None
            if classes == 1 or self.solver == "sinkhorn":
                # alike pods: the count is exact.  Unlike pods under
                # sinkhorn: every pod counted as the largest
                if classes > 1:
                    trace.COUNTERS.inc("pas_planner_conservative_room_total")
                room = self._room(free, (1000, max(cpus), max(mems)), n_cap)
            else:
                # what each planned pod asks of FIT_RESOURCES, milli-units
                asked = np.empty((len(cpus), 3), dtype=np.int64)
                asked[:, 0] = 1000
                asked[:, 1] = cpus
                asked[:, 2] = mems
                if kept is not None:
                    asked = asked[kept]
                room, demand = self._room_rows(free, asked, n_cap, size)
                demand = jnp.asarray(demand)
        batch = PendingPods(
            metric_row=padded(rows[policy]),
            op_id=padded(ops[policy]),
            candidates=self._mask(size, n_cap, p, known),
            policy=padded(policy),
            demand=demand,
        )
        if timer is not None:
            timer.mark("snapshot")
        state = ClusterState(
            metric_values=view.values,
            metric_present=view.present,
            dontschedule=self._policy_rules(compiled),
            capacity=jnp.asarray(room),
        )
        if timer is not None:
            timer.mark("transfer")
        return state, batch, keys, view, timer

    @staticmethod
    def _policy_rules(compiled) -> RuleSet:
        """The pending set's dontschedule rules, one padded row per
        distinct policy ([D, R]): pod i is held to row ``policy[i]``, its
        own policy's, and to no other's.  A policy without device rules
        has an all-inactive row."""
        rule_sets = [
            c.dontschedule
            if c.dontschedule is not None and not c.dontschedule.host_only
            else None
            for c in compiled
        ]
        width = max(
            [RULE_PAD] + [len(rs.active) for rs in rule_sets if rs is not None]
        )
        depth = -(-len(compiled) // POLICY_PAD) * POLICY_PAD
        metric_rows = np.zeros((depth, width), dtype=np.int32)
        op_ids = np.zeros((depth, width), dtype=np.int32)
        targets = np.zeros((depth, width), dtype=np.int64)
        active = np.zeros((depth, width), dtype=bool)
        for d, rs in enumerate(rule_sets):
            if rs is not None:
                r = len(rs.active)
                metric_rows[d, :r] = rs.metric_rows
                op_ids[d, :r] = rs.op_ids
                targets[d, :r] = rs.targets
                active[d, :r] = rs.active
        t_hi, t_lo = i64.split_int64_np(targets)
        return RuleSet(
            metric_row=jnp.asarray(metric_rows),
            op_id=jnp.asarray(op_ids),
            target=i64.I64(hi=jnp.asarray(t_hi), lo=jnp.asarray(t_lo)),
            active=jnp.asarray(active),
        )

    def _warm_smaller(self, state: ClusterState, batch: PendingPods,
                      known: int) -> None:
        """The first time a padded size is solved, compile every smaller
        one with it (an all-padding batch, nothing assigned): a backlog
        seen at one size drains through all of those below it, and a
        compile must never land between two of its replans."""
        size, n_cap = batch.candidates.shape
        rules = state.dontschedule.active.shape
        # the form of the room is a part of the program: () for a count
        form = () if batch.demand is None else batch.demand.shape[1:]
        shape = (size, n_cap, state.metric_present.shape[0], *rules, form)
        if shape in self._warmed:
            return
        smaller = size // 2
        while smaller >= PAD_FLOOR and (smaller, *shape[1:]) not in self._warmed:
            empty = jnp.zeros(smaller, dtype=jnp.int32)
            nothing = PendingPods(
                metric_row=empty, op_id=empty,
                candidates=self._mask(smaller, n_cap, 0, known),
                policy=empty,
                demand=None if batch.demand is None else jnp.zeros(
                    (smaller, *form), dtype=jnp.int32
                ),
            )
            if self.mesh is not None:
                # placed as a replan places it: the same program is found
                self._mesh_step(*self._place(state, nothing))
            else:
                observed_scheduling_step(
                    state, nothing
                ).assignment.node_for_pod.block_until_ready()
            self._warmed.add((smaller, *shape[1:]))
            smaller //= 2
        self._warmed.add(shape)

    # -- serving --------------------------------------------------------------

    def planned_node(self, pod: Pod) -> Optional[str]:
        """The batch-assigned node for this pod, if the plan is current
        against the mirror (otherwise None -> per-request path)."""
        plan, version = self._published
        node = plan.get(object_key(pod))
        if node is None:
            trace.COUNTERS.inc("pas_planner_unplanned_total")
            return None
        if version != self.mirror.version:
            # cluster state moved since the solve
            trace.COUNTERS.inc("pas_planner_stale_total")
            return None
        return node

    # -- pending-pod feed -------------------------------------------------------

    def watch(self, kube_client):
        """Informers over pods (pending set + per-node bound counts) and
        nodes (allocatable pod slots); returns a handle with ``.stop()``."""
        from platform_aware_scheduling_tpu.kube.informer import (
            DeletedFinalStateUnknown,
            Informer,
            ListWatch,
        )
        from platform_aware_scheduling_tpu.kube.objects import Node

        def on_event(pod: Pod, deleted: bool = False) -> None:
            with self._lock:  # the bound and the pending set move as one
                self.pod_observed(pod, deleted=deleted)
                if (
                    deleted
                    # the label may have been removed while the pod was pending
                    or TAS_POLICY_LABEL not in pod.get_labels()
                    or pod.spec_node_name
                    or pod.phase in ("Succeeded", "Failed")
                ):
                    self.pod_removed(pod)
                else:
                    self.pod_added(pod)

        def on_delete(obj) -> None:
            if isinstance(obj, DeletedFinalStateUnknown):
                obj = obj.obj
            if isinstance(obj, Pod):
                on_event(obj, deleted=True)

        pod_informer = Informer(
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
        )

        def on_node_delete(obj) -> None:
            if isinstance(obj, DeletedFinalStateUnknown):
                obj = obj.obj
            if isinstance(obj, Node):
                self.node_changed(obj, deleted=True)

        node_informer = Informer(
            ListWatch(
                lambda: (kube_client.list_nodes(), ""),
                lambda rv: (
                    (etype, Node(raw)) for etype, raw in kube_client.watch_nodes()
                ),
                lambda node: node.name,
            ),
            on_add=self.node_changed,
            on_update=lambda _old, new: self.node_changed(new),
            on_delete=on_node_delete,
        )
        pod_informer.start()
        node_informer.start()
        return _InformerGroup(pod_informer, node_informer)
