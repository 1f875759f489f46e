"""Incremental replan: the penalized batched solve behind the rebalancer.

Each cycle the evictable pods on candidate nodes plus the current
telemetry matrix become one bounded assignment problem, solved on-device
through the SAME kernels the batch planner uses (``_score_keys`` from
models/batch_scheduler, greedy/sinkhorn rounding from ops/) with two
penalty terms layered on the normalized utilities:

  * ``violation_penalty`` pushes every currently-violating node's lanes
    far below any clean node — the whole point of the move;
  * ``migration_cost`` is a bonus on each pod's CURRENT node — a pod
    moves only when the destination's utility beats staying put by more
    than the cost of the migration, so the plan converges to "no moves"
    instead of oscillating.

The solve is incremental in the scheduling sense: pods not on candidate
nodes never enter the problem, every pod's stay-put option is always
feasible (its own slot is added back to its node's remaining capacity),
and the host-side churn budget truncates the move list to the
highest-gain ``max_moves`` per cycle so actuation is always bounded.

Shapes are padded (pods to 8, nodes to the mirror's capacity buckets) so
XLA recompiles per bucket, never per pod.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from platform_aware_scheduling_tpu.kube.objects import Pod, object_key
from platform_aware_scheduling_tpu.models.batch_scheduler import _score_keys
from platform_aware_scheduling_tpu.ops import i64
from platform_aware_scheduling_tpu.ops.assign import greedy_assign_kernel
from platform_aware_scheduling_tpu.ops.sinkhorn import (
    _normalize_scores,
    sinkhorn_assign_kernel,
)
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas.planner import (
    DEFAULT_NODE_CAPACITY,
    TAS_POLICY_LABEL,
)
from platform_aware_scheduling_tpu.utils import klog

POD_PAD = 8
#: utility drop applied to every violating node's lanes; utilities are
#: normalized into [0, 1], so anything > 1 + migration bonus guarantees a
#: clean node with capacity always beats staying on a violating one
DEFAULT_VIOLATION_PENALTY = 4.0
#: stay-put bonus in normalized-utility units: a move must buy at least
#: this much headroom over the pod's current node
DEFAULT_MIGRATION_COST = 0.1
#: churn budget: max evictions planned per cycle
DEFAULT_MAX_MOVES = 5
#: incoming moves any one destination accepts per cycle.  Telemetry
#: utilities rank nodes globally, so every evictee prefers the SAME
#: least-loaded node; slot capacity alone lets the whole herd land
#: there, which overshoots the very threshold the move was curing and
#: ping-pongs the same pods between destinations every hysteresis
#: window (found by the scenario fuzzer: tests/scenarios/
#: rebalance_herd.json).  One-in-per-cycle spreads the herd across
#: distinct destinations; the next cycle replans against fresh
#: telemetry that already includes the landed pods.
DEFAULT_MAX_INFLOW = 1


class Move(NamedTuple):
    pod_key: str
    namespace: str
    name: str
    from_node: str
    to_node: str
    gain: float  # adjusted-utility headroom the move buys


class PlanResult(NamedTuple):
    moves: List[Move]
    considered: int  # pods that entered the solve
    skipped_pods: int  # evictable pods the solve could not score
    truncated: int  # moves dropped by the churn budget
    latency_s: float
    view_version: int
    deferred: int = 0  # moves held back by the per-destination inflow cap


@partial(jax.jit, static_argnames=("solver",))
def penalized_assign_kernel(
    values_hi,  # int32 [M, N]
    values_lo,  # uint32 [M, N]
    present,  # bool [M, N]
    metric_row,  # int32 [P]
    op_id,  # int32 [P]
    violating,  # bool [N]
    current,  # int32 [P] — each pod's current node index
    capacity,  # int32 [N] — remaining slots incl. the pods' own
    active,  # bool [P] — real pod vs shape padding
    migration_bonus,  # f32 scalar
    violation_penalty,  # f32 scalar
    solver: str = "greedy",
):
    """(node_for_pod [P], adjusted utility [P, N]).  Padding rows are
    inactive (no eligible lane) and come back UNASSIGNED."""
    values = i64.I64(hi=values_hi, lo=values_lo)
    score = _score_keys(values, present, metric_row, op_id)  # [P, N]
    present_rows = present[metric_row]  # [P, N]
    n = present.shape[1]
    is_current = (
        jnp.arange(n, dtype=jnp.int32)[None, :] == current[:, None]
    )  # [P, N]; padding rows carry current = -1 -> no current lane
    utility = _normalize_scores(score, present_rows)
    adj = (
        utility
        - violation_penalty * violating[None, :].astype(jnp.float32)
        + migration_bonus * is_current.astype(jnp.float32)
    )
    # stay-put must always be representable, even when the pod's metric
    # is absent on its own node
    eligible = (present_rows | is_current) & active[:, None]
    # quantize the adjusted utilities to exact keys (micro-units) for the
    # deterministic i64 comparators, sign-extended into the limbs —
    # exactly the sinkhorn module's rounding trick
    q = jnp.clip(adj * jnp.float32(1e6), -2.0e9, 2.0e9).astype(jnp.int32)
    keys = i64.I64(
        hi=jnp.where(q < 0, jnp.int32(-1), jnp.int32(0)),
        lo=jax.lax.bitcast_convert_type(q, jnp.uint32),
    )
    if solver == "sinkhorn":
        assignment = sinkhorn_assign_kernel(keys, eligible, capacity).assignment
    else:
        assignment = greedy_assign_kernel(keys, eligible, capacity)
    return assignment.node_for_pod, adj


class IncrementalReplanner:
    """Builds and solves the per-cycle reassignment problem against the
    mirror's current device view."""

    def __init__(
        self,
        mirror: TensorStateMirror,
        solver: str = "greedy",
        migration_cost: float = DEFAULT_MIGRATION_COST,
        violation_penalty: float = DEFAULT_VIOLATION_PENALTY,
        max_moves: int = DEFAULT_MAX_MOVES,
        default_node_capacity: int = DEFAULT_NODE_CAPACITY,
        max_inflow: Optional[int] = DEFAULT_MAX_INFLOW,
    ):
        if solver not in ("greedy", "sinkhorn"):
            raise ValueError(f"unknown rebalance solver {solver!r}")
        self.mirror = mirror
        self.solver = solver
        self.migration_cost = float(migration_cost)
        self.violation_penalty = float(violation_penalty)
        self.max_moves = int(max_moves)
        self.default_node_capacity = int(default_node_capacity)
        self.max_inflow = None if max_inflow is None else max(1, int(max_inflow))

    def plan(
        self,
        pods: List[Pod],
        violations: Dict[str, List[str]],
        remaining_capacity: Optional[Dict[str, int]] = None,
    ) -> PlanResult:
        """Solve the reassignment for ``pods`` (the evictable set on
        candidate nodes) against the full current ``violations`` map.
        ``remaining_capacity``: node -> free pod slots EXCLUDING the
        pods being replanned (their own slots are added back here so
        stay-put is always feasible)."""
        t0 = time.perf_counter()
        empty = PlanResult([], 0, len(pods), 0, 0.0, self.mirror.version)
        if not pods:
            return empty._replace(latency_s=time.perf_counter() - t0)
        policy_keys = {
            (pod.namespace, pod.get_labels().get(TAS_POLICY_LABEL))
            for pod in pods
        }
        policies, view, host_only = self.mirror.policies_with_view(
            [key for key in policy_keys if key[1]]
        )
        rows: List[Tuple[Pod, int, int, int]] = []  # pod, row, op, current
        skipped = 0
        for pod in pods:
            compiled = policies.get(
                (pod.namespace, pod.get_labels().get(TAS_POLICY_LABEL))
            )
            current_idx = view.node_index.get(pod.spec_node_name)
            if (
                compiled is None
                or compiled.scheduleonmetric_row < 0
                or compiled.scheduleonmetric_metric in host_only
                or current_idx is None
            ):
                skipped += 1
                continue
            rows.append(
                (
                    pod,
                    compiled.scheduleonmetric_row,
                    compiled.scheduleonmetric_op,
                    current_idx,
                )
            )
        if not rows:
            return PlanResult(
                [], 0, skipped, 0, time.perf_counter() - t0, view.version
            )
        n_cap = view.node_capacity
        p = len(rows)
        p_pad = max(POD_PAD, -(-p // POD_PAD) * POD_PAD)
        metric_row = np.zeros(p_pad, dtype=np.int32)
        op_id = np.zeros(p_pad, dtype=np.int32)
        current = np.full(p_pad, -1, dtype=np.int32)
        active = np.zeros(p_pad, dtype=bool)
        for idx, (_pod, row, op, cur) in enumerate(rows):
            metric_row[idx], op_id[idx], current[idx] = row, op, cur
            active[idx] = True
        violating = np.zeros(n_cap, dtype=bool)
        for node in violations:
            node_idx = view.node_index.get(node)
            if node_idx is not None:
                violating[node_idx] = True
        capacity = self._capacity_vector(view, remaining_capacity, current, p)
        node_for_pod, adj = penalized_assign_kernel(
            view.values.hi,
            view.values.lo,
            view.present,
            jnp.asarray(metric_row),
            jnp.asarray(op_id),
            jnp.asarray(violating),
            jnp.asarray(current),
            jnp.asarray(capacity),
            jnp.asarray(active),
            jnp.float32(self.migration_cost),
            jnp.float32(self.violation_penalty),
            solver=self.solver,
        )
        assigned = np.asarray(node_for_pod)
        adj_np = np.asarray(adj)
        moves: List[Move] = []
        for idx, (pod, _row, _op, cur) in enumerate(rows):
            target = int(assigned[idx])
            if target < 0 or target == cur or target >= len(view.node_names):
                continue
            gain = float(adj_np[idx, target] - adj_np[idx, cur])
            if gain <= 0.0:
                continue  # solver contention artifact: staying is better
            moves.append(
                Move(
                    pod_key=object_key(pod),
                    namespace=pod.namespace,
                    name=pod.name,
                    from_node=pod.spec_node_name,
                    to_node=view.node_names[target],
                    gain=round(gain, 6),
                )
            )
        moves.sort(key=lambda m: (-m.gain, m.pod_key))
        deferred = 0
        if self.max_inflow is not None:
            # anti-herding (DEFAULT_MAX_INFLOW): keep only the
            # highest-gain ``max_inflow`` moves per destination; the
            # rest stay put this cycle and replan next cycle against
            # telemetry that already includes the landed pods.  Applied
            # host-side so the solvers' capacity semantics (sinkhorn's
            # column scaling in particular) are untouched.
            inflow: Dict[str, int] = {}
            spread: List[Move] = []
            for move in moves:
                landed = inflow.get(move.to_node, 0)
                if landed >= self.max_inflow:
                    deferred += 1
                    continue
                inflow[move.to_node] = landed + 1
                spread.append(move)
            if deferred:
                klog.v(4).info_s(
                    f"inflow cap: {deferred} moves deferred "
                    f"(max {self.max_inflow} per destination/cycle)",
                    component="rebalance",
                )
            moves = spread
        truncated = max(0, len(moves) - self.max_moves)
        if truncated:
            klog.v(4).info_s(
                f"churn budget: {truncated} moves dropped "
                f"(cap {self.max_moves})",
                component="rebalance",
            )
        moves = moves[: self.max_moves]
        return PlanResult(
            moves=moves,
            considered=p,
            skipped_pods=skipped,
            truncated=truncated,
            latency_s=time.perf_counter() - t0,
            view_version=view.version,
            deferred=deferred,
        )

    def _capacity_vector(
        self, view, remaining_capacity, current: np.ndarray, p: int
    ) -> np.ndarray:
        """int32 [N_cap] slots per interned node: caller-observed remaining
        capacity (or the kubelet default), plus each replanned pod's own
        slot at its current node so the stay-put assignment is feasible."""
        cap = np.full(view.node_capacity, self.default_node_capacity, dtype=np.int64)
        if remaining_capacity is not None:
            for name, idx in view.node_index.items():
                if idx < cap.shape[0]:
                    cap[idx] = remaining_capacity.get(
                        name, self.default_node_capacity
                    )
        cap = np.clip(cap, 0, None)
        for idx in current[:p]:
            if idx >= 0:
                cap[idx] += 1
        return np.clip(cap, 0, np.iinfo(np.int32).max).astype(np.int32)
