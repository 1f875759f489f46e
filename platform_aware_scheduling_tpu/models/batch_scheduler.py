"""The batched scheduling solve: filter + score + assign in one program.

This is the capability the reference cannot express (SURVEY §7 step 4):
kube-scheduler drives one pod per extender round-trip
(telemetryscheduler.go:39-59 per request); here the WHOLE pending set is
solved at once over dense tensors:

  1. dontschedule violations over the metric matrix  (ops/rules.py),
     per policy: a pod is kept off the nodes its OWN policy forbids
  2. per-pod score keys from each pod's scheduleonmetric rule
  3. greedy capacity-constrained assignment           (ops/assign.py)

Greedy-in-pod-order reproduces what the sequential system would decide, so
answers to individual /scheduler verbs can be served from this solution.

Room has two forms (ops/assign.py), and which one a solve runs follows from
its operands — no flag.  ``PendingPods.demand`` absent: ``capacity`` counts
pods, one unit a pod (a pending set of alike pods).  ``demand`` given
(``[P, L, R]``): ``capacity`` is the nodes' room ``[L, R, N]`` over R
resources in L limbs of 31 bits, and every pod books its own vector.

Multi-chip (:func:`mesh_scheduling_step`, the planner's
``--batchPlannerDevices`` path): the operands arrive node-sharded over a
mesh, rules and score keys run under GSPMD (elementwise over nodes: no
collective), and the assignment is parallel/sharded.py's hand-written
collective form, for either form of the room; only ``node_for_pod`` comes
back.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from platform_aware_scheduling_tpu.ops import i64, solveobs
from platform_aware_scheduling_tpu.ops.assign import (
    AssignResult,
    auction_assign_kernel,
    greedy_assign_kernel,
)
from platform_aware_scheduling_tpu.ops.pallas_assign import greedy_assign_pallas
from platform_aware_scheduling_tpu.ops.rules import (
    OP_GREATER_THAN,
    OP_LESS_THAN,
    RuleSet,
    violated_nodes,
)
from platform_aware_scheduling_tpu.parallel.mesh import node_sharded
from platform_aware_scheduling_tpu.parallel.sharded import sharded_greedy_assign
from platform_aware_scheduling_tpu.utils import trace


class ClusterState(NamedTuple):
    """Dense device form of the cluster, maintained by the state mirror."""

    metric_values: i64.I64  # [M, N] milli-units
    metric_present: jax.Array  # bool [M, N]
    # violation rules, ``[D, R]``: one (padded) list per distinct policy of
    # the pending set, picked per pod by ``PendingPods.policy``
    dontschedule: RuleSet
    # int32 [N] — pods each node may still accept; with
    # ``PendingPods.demand`` int32 [L, R, N] — the room left of R resources
    capacity: jax.Array


class PendingPods(NamedTuple):
    """The pending set: one scheduleonmetric rule + candidate mask per pod."""

    metric_row: jax.Array  # int32 [P]
    op_id: jax.Array  # int32 [P]
    candidates: jax.Array  # bool [P, N]
    policy: jax.Array  # int32 [P] — the pod's row of ``dontschedule``
    # int32 [P, L, R] — each pod's own requests, in the units and limbs of
    # ``ClusterState.capacity``; None: alike pods, one unit of room a pod
    demand: Optional[jax.Array] = None


class ScheduleOutput(NamedTuple):
    assignment: AssignResult
    violating: jax.Array  # bool [D, N] — per policy of the pending set
    score: i64.I64  # [P, N] keys used (larger = better)
    eligible: jax.Array  # bool [P, N] — candidates ∩ present ∩ ¬violating


def _score_keys(values: i64.I64, present, metric_row, op_id) -> i64.I64:
    """Per-pod score keys where larger is better: GreaterThan keeps the
    metric value, LessThan flips it, anything else prefers low node index
    (the deterministic stand-in for the reference's map-order walk)."""
    v = i64.I64(hi=values.hi[metric_row], lo=values.lo[metric_row])  # [P, N]
    flipped = i64.flip(v)
    by_value = i64.select((op_id == OP_GREATER_THAN)[:, None], v, flipped)
    n = v.hi.shape[-1]
    idx = jnp.arange(n, dtype=jnp.uint32)
    index_key = i64.flip(
        i64.I64(hi=jnp.zeros_like(v.hi), lo=jnp.broadcast_to(idx, v.lo.shape))
    )
    sorts = ((op_id == OP_LESS_THAN) | (op_id == OP_GREATER_THAN))[:, None]
    return i64.select(sorts, by_value, index_key)


@jax.jit
def score_and_filter(state: ClusterState, pods: PendingPods):
    """The non-assignment half of the solve: (violating, score, eligible).
    Separable so alternative assignment solvers (ops/sinkhorn.py) don't pay
    for a greedy solve they discard."""
    violating = jax.vmap(violated_nodes, in_axes=(None, None, 0))(
        state.metric_values, state.metric_present, state.dontschedule
    )  # [D, N]
    forbidden = violating[pods.policy]  # [P, N]: each pod's own policy
    score = _score_keys(
        state.metric_values, state.metric_present, pods.metric_row, pods.op_id
    )
    present = state.metric_present[pods.metric_row]  # [P, N]
    eligible = pods.candidates & present & ~forbidden
    return violating, score, eligible


ASSIGNER_PALLAS = "pallas"
ASSIGNER_SCAN = "scan"
#: the one platform the hand-written Pallas (Mosaic) kernel lowers on
PALLAS_PLATFORM = "tpu"


def choose_assigner(*operands) -> str:
    """Which exact greedy assigner a solve over ``operands`` runs, decided
    from the operands themselves: the Pallas kernel when every array sits
    on ONE TPU device, else the XLA scan.  A hand-written pallas_call
    lowers only on TPU and does not auto-partition under GSPMD, so
    mesh-sharded operands take the scan (or parallel/sharded.py) — and an
    unsharded solve keeps the Pallas kernel however many chips the host
    exposes.  Operands seen as tracers (a caller's own jit) carry no
    placement: the scan runs unless the caller passes the choice it made
    from its concrete inputs."""
    devices = set()
    for leaf in jax.tree.leaves(operands):
        if isinstance(leaf, jax.core.Tracer):
            return ASSIGNER_SCAN
        if isinstance(leaf, jax.Array):
            devices |= leaf.devices()
    if not devices:  # host operands land on the default device
        devices = {jax.devices()[0]}
    if len(devices) == 1 and next(iter(devices)).platform == PALLAS_PLATFORM:
        return ASSIGNER_PALLAS
    return ASSIGNER_SCAN


def _room_operands(state: ClusterState, pods: PendingPods) -> tuple:
    """(room, the demand's keyword arguments) as every assigner takes them:
    the count alone, or the limbs as rows — ``[L * R, N]`` and
    ``[P, L * R]``."""
    if pods.demand is None:
        return state.capacity, {}
    p, limbs, resources = pods.demand.shape
    return state.capacity.reshape(limbs * resources, -1), dict(
        demand=pods.demand.reshape(p, limbs * resources), limbs=limbs
    )


@partial(jax.jit, static_argnames=("assigner",))
def _scheduling_step(
    state: ClusterState, pods: PendingPods, assigner: str
) -> ScheduleOutput:
    violating, score, eligible = score_and_filter(state, pods)
    # Both assigners are exact greedy-in-order and return identical
    # results: the Pallas kernel keeps capacity resident in VMEM for one
    # launch, the scan pays P dispatch-bound steps.
    assign = (
        greedy_assign_pallas if assigner == ASSIGNER_PALLAS
        else greedy_assign_kernel
    )
    room, form = _room_operands(state, pods)
    assignment = assign(score, eligible, room, **form)
    return ScheduleOutput(
        assignment=assignment, violating=violating, score=score, eligible=eligible
    )


# the planner pads its pending set to a few fixed sizes (tas/planner.py):
# a lowering past those is a retrace the compile counters must show
_scheduling_step = trace.watch_jit("scheduling_step", _scheduling_step)


def scheduling_step(
    state: ClusterState, pods: PendingPods, assigner: Optional[str] = None
) -> ScheduleOutput:
    """One full solve over the pending set.  ``assigner`` defaults to
    :func:`choose_assigner` over the operands; a caller that traces this
    inside its own program passes the value it chose outside the trace."""
    if assigner is None:
        assigner = choose_assigner(state, pods)
    return _scheduling_step(state, pods, assigner=assigner)


@partial(jax.jit, static_argnames=("mesh",))
def _mesh_scheduling_step(state: ClusterState, pods: PendingPods, mesh) -> jax.Array:
    _violating, score, eligible = score_and_filter(state, pods)
    # every [P, N] array stays split over the nodes: none is ever whole on
    # one device
    by_node = node_sharded(mesh)
    score = i64.I64(
        hi=jax.lax.with_sharding_constraint(score.hi, by_node),
        lo=jax.lax.with_sharding_constraint(score.lo, by_node),
    )
    eligible = jax.lax.with_sharding_constraint(eligible, by_node)
    room, form = _room_operands(state, pods)
    node_for_pod, _left = sharded_greedy_assign(mesh, score, eligible, room, **form)
    return node_for_pod


_mesh_scheduling_step = trace.watch_jit(
    "mesh_scheduling_step", _mesh_scheduling_step
)


def mesh_scheduling_step(mesh, state: ClusterState, pods: PendingPods) -> jax.Array:
    """The solve over operands placed node-sharded on ``mesh``
    (tas/planner.py places them): the same plan as :func:`scheduling_step`
    — greedy in pod order, first index on a tie — as ``node_for_pod``
    alone, int32 [P], replicated.  ``score`` and ``eligible`` never leave
    the mesh.  The room's form follows ``pods.demand`` as in
    :func:`scheduling_step`: a count, or each pod's own vector.  Assigned
    by ``sharded_greedy_assign``, one all_gather a block of 32 pods:
    0.43 s at 32,768 x 65,536 on four v5e chips, where
    ``greedy_assign_kernel`` under GSPMD (four all-reduces a pod) took
    0.76 s for the same plan (PERF.md §6, PR 33)."""
    return _mesh_scheduling_step(state, pods, mesh=mesh)


def observed_scheduling_step(
    state: ClusterState, pods: PendingPods, timer=None
) -> ScheduleOutput:
    """``scheduling_step`` with solve-observatory stage attribution.

    When no observatory is enabled (and no caller-owned timer is
    passed) this is exactly one extra ``is None`` check around the
    plain call — the planner routes through here unconditionally so the
    off path stays byte-identical.  With a timer the call is bracketed
    with ``compile``/``execute`` marks: compile when the jit cache grew
    during the dispatch, execute timed across ``block_until_ready`` so
    XLA's async dispatch cannot launder device time into the caller's
    readback.  The caller keeps ownership of the timer — its readback
    and encode happen on its side of the fence."""
    own = timer is None
    if own:
        obs = solveobs.ACTIVE
        if obs is None:
            return scheduling_step(state, pods)
        timer = obs.begin("batch_solve")
    before = _scheduling_step.cache_size()
    out = scheduling_step(state, pods)
    timer.mark(
        "compile" if _scheduling_step.cache_size() > before else "execute"
    )
    jax.block_until_ready(out.assignment.node_for_pod)
    timer.mark("execute")
    if own:
        timer.done(
            pods=int(pods.metric_row.shape[0]),
            nodes=int(state.capacity.shape[-1]),
        )
    return out


def example_inputs(
    num_metrics: int = 4,
    num_nodes: int = 64,
    num_pods: int = 16,
    seed: int = 0,
    resources: int = 0,
):
    """Small synthetic (state, pods) pair for compile checks and benches:
    one policy (D = 1) over every pod.  ``resources`` > 0 gives the demand
    form: room ``[1, resources, N]`` and a request vector a pod."""
    import numpy as np

    rng = np.random.default_rng(seed)
    values = rng.integers(0, 1_000_000, size=(num_metrics, num_nodes)).astype(
        np.int64
    )
    hi, lo = i64.split_int64_np(values)
    t_hi, t_lo = i64.split_int64_np(np.array([500_000, 900_000], dtype=np.int64))
    state = ClusterState(
        metric_values=i64.I64(hi=jnp.asarray(hi), lo=jnp.asarray(lo)),
        metric_present=jnp.asarray(rng.random((num_metrics, num_nodes)) > 0.05),
        dontschedule=RuleSet(
            metric_row=jnp.asarray(np.array([[0, 1]], dtype=np.int32)),
            op_id=jnp.asarray(
                np.array([[OP_GREATER_THAN, OP_GREATER_THAN]], dtype=np.int32)
            ),
            target=i64.I64(hi=jnp.asarray(t_hi[None]), lo=jnp.asarray(t_lo[None])),
            active=jnp.asarray(np.array([[True, True]])),
        ),
        capacity=jnp.asarray(
            rng.integers(1, 4, size=num_nodes).astype(np.int32)
        ),
    )
    pods = PendingPods(
        metric_row=jnp.asarray(
            rng.integers(0, num_metrics, size=num_pods).astype(np.int32)
        ),
        op_id=jnp.asarray(
            rng.choice([OP_LESS_THAN, OP_GREATER_THAN], size=num_pods).astype(
                np.int32
            )
        ),
        candidates=jnp.asarray(rng.random((num_pods, num_nodes)) > 0.1),
        policy=jnp.zeros(num_pods, dtype=jnp.int32),
    )
    if resources:
        state = state._replace(capacity=jnp.asarray(
            rng.integers(0, 12, size=(1, resources, num_nodes)).astype(np.int32)
        ))
        pods = pods._replace(demand=jnp.asarray(
            rng.integers(0, 5, size=(num_pods, 1, resources)).astype(np.int32)
        ))
    return state, pods
