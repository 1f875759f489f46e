"""The fused TAS+GAS solve: telemetry scoring AND per-card bin-packing
feasibility in ONE program (BASELINE config #4 as written).

The reference ships this composition as two chained extenders — the
combined scheduler config registers TAS and GAS on the same verb chain
(telemetry-aware-scheduling/deploy/extender-configuration/
tas+gas-extender-configmap.yaml), so a pod is first filtered/scored by
telemetry rules (telemetryscheduler.go:128-149) and then GAS prunes nodes
where no card fits the request and books cards at bind
(gpuscheduler/scheduler.go:200-257, 341-383).  One pod per round trip,
each extender paying its own HTTP + cache walk, GAS under a global lock.

Here the whole pending set is solved in one jitted program over dense
tensors:

  1. TAS half: dontschedule violations + per-pod score keys + candidate
     eligibility (models/batch_scheduler.score_and_filter);
  2. GAS half: per-card first-fit feasibility of each pod's request
     class against EVERY node at once — ``binpack_kernel`` over the
     ``[nodes, cards, resources]`` usage tensor, vmapped over request
     classes -> ``fits[T, N]``;
  3. fused greedy scan in pod order: each pod takes its best-scoring
     node among (eligible ∩ capacity>0 ∩ fits[class]); booking a pod
     updates the chosen node's card usage exactly as GAS bind does
     (first-fit card picks, gpuscheduler/scheduler.go:216-247) and
     re-evaluates feasibility for THAT node only — fits of untouched
     nodes cannot change, so the per-step work is O(N) for the argmax
     plus O(T·C·R·G) for the one-node re-pack, not O(N·C·R).

Pods are grouped into **request classes** (pending bursts share pod
templates; the class axis T is static and small).  The scan reproduces
the sequential reference composition decision-for-decision: pod i gets
its best feasible node given pods 0..i-1's bookings — pinned against a
host TAS-then-GAS control in tests/test_fused.py and benchmarks/
configs.py config4_fused.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from platform_aware_scheduling_tpu.models.batch_scheduler import (
    ClusterState,
    PendingPods,
    score_and_filter,
)
from platform_aware_scheduling_tpu.ops import i64, solveobs
from platform_aware_scheduling_tpu.ops.assign import lex_argmin
from platform_aware_scheduling_tpu.ops.binpack import (
    BinpackNodeState,
    BinpackRequest,
    _fit_one_node,
)


class FusedRequests(NamedTuple):
    """T request classes, each a stacked :class:`BinpackRequest`."""

    need: i64.I64  # [T, Tc, R] per-GPU share per container
    need_active: jax.Array  # bool [T, Tc, R]
    num_gpus: jax.Array  # int32 [T, Tc]
    container_active: jax.Array  # bool [T, Tc]

    def request(self, t) -> BinpackRequest:
        return BinpackRequest(
            need=i64.I64(hi=self.need.hi[t], lo=self.need.lo[t]),
            need_active=self.need_active[t],
            num_gpus=self.num_gpus[t],
            container_active=self.container_active[t],
        )


class FusedOutput(NamedTuple):
    node_for_pod: jax.Array  # int32 [P] — node index or -1
    capacity_left: jax.Array  # int32 [N]
    used: i64.I64  # [N, C, R] card usage after all bookings
    fits: jax.Array  # bool [T, N] feasibility AFTER all bookings
    violating: jax.Array  # bool [D, N] — TAS dontschedule mask per policy


def _stacked(requests: FusedRequests):
    """The vmap-able leaves of the request-class axis."""
    return (
        i64.I64(hi=requests.need.hi, lo=requests.need.lo),
        requests.need_active,
        requests.num_gpus,
        requests.container_active,
    )


def _all_fits(gas: BinpackNodeState, requests: FusedRequests, max_gpus: int):
    """fits[T, N]: every request class against every node (the batched
    GAS Filter, step 2 of the module doc)."""
    card_ok = gas.card_valid & gas.card_real

    def per_class(req_t):
        req = BinpackRequest(*req_t)

        def per_node(used_hi, used_lo, cap_hi, cap_lo, cap_p, ok, order):
            fits, _, _ = _fit_one_node(
                i64.I64(hi=used_hi, lo=used_lo),
                i64.I64(hi=cap_hi, lo=cap_lo),
                cap_p,
                ok,
                order,
                req,
                max_gpus,
            )
            return fits

        return jax.vmap(per_node)(
            gas.used.hi,
            gas.used.lo,
            gas.capacity.hi,
            gas.capacity.lo,
            gas.cap_present,
            card_ok,
            gas.card_order,
        )

    return jax.vmap(per_class)(_stacked(requests))


def shard_fused_inputs(mesh, state, pods, req_class, gas, requests):
    """Place a fused problem on a node-sharded mesh: every node-axis leaf
    (metric matrix dim 1, candidates dim 1, capacity dim 0, the whole GAS
    usage tensor dim 0) gets a NamedSharding over ``NODE_AXIS``; rule
    tensors, request classes, and per-pod vectors replicate.  The single
    sharding recipe used by both the multi-chip dryrun and the GSPMD
    parity test — ``fused_schedule`` then runs unchanged and GSPMD
    inserts the collectives."""
    from jax.sharding import NamedSharding, PartitionSpec

    from platform_aware_scheduling_tpu.parallel.mesh import (
        NODE_AXIS,
        replicated,
    )

    rep = replicated(mesh)

    def node_shard(x, axis):
        spec = [None] * x.ndim
        spec[axis] = NODE_AXIS
        return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*spec)))

    state_s = state._replace(
        metric_values=jax.tree.map(
            lambda x: node_shard(x, 1), state.metric_values
        ),
        metric_present=node_shard(state.metric_present, 1),
        dontschedule=jax.tree.map(
            lambda x: jax.device_put(x, rep), state.dontschedule
        ),
        capacity=node_shard(state.capacity, 0),
    )
    pods_s = pods._replace(
        candidates=node_shard(pods.candidates, 1),
        metric_row=jax.device_put(pods.metric_row, rep),
        op_id=jax.device_put(pods.op_id, rep),
        policy=jax.device_put(pods.policy, rep),
    )
    gas_s = jax.tree.map(lambda x: node_shard(x, 0), gas)
    requests_s = jax.tree.map(lambda x: jax.device_put(x, rep), requests)
    req_class_s = jax.device_put(req_class, rep)
    return state_s, pods_s, req_class_s, gas_s, requests_s


@partial(jax.jit, static_argnames=("max_gpus",))
def fused_schedule(
    state: ClusterState,
    pods: PendingPods,
    req_class: jax.Array,  # int32 [P] — request class per pod
    gas: BinpackNodeState,
    requests: FusedRequests,
    max_gpus: int,
) -> FusedOutput:
    """One fused TAS+GAS solve over the pending set (module doc)."""
    violating, score, eligible = score_and_filter(state, pods)
    fits0 = _all_fits(gas, requests, max_gpus)  # [T, N]
    card_ok = gas.card_valid & gas.card_real  # [N, C]
    n_nodes = eligible.shape[1]

    def step(carry, pod):
        used, fits, cap = carry
        s_hi, s_lo, elig, cls = pod
        ok = elig & (cap > 0) & fits[cls]
        flipped = i64.flip(i64.I64(hi=s_hi, lo=s_lo))
        best, found = lex_argmin(flipped, ok)
        node = jnp.maximum(best, 0)  # safe index when unassigned

        # re-pack the chosen node with the pod's class: _fit_one_node's
        # final carry IS the booked usage (GAS bind's card walk,
        # scheduler.go:216-247); the fits gate guarantees the request
        # fully fits, so applying it wholesale is exact
        used_n = i64.I64(hi=used.hi[node], lo=used.lo[node])  # [C, R]
        cap_n = i64.I64(hi=gas.capacity.hi[node], lo=gas.capacity.lo[node])
        _, _, new_used_n = _fit_one_node(
            used_n,
            cap_n,
            gas.cap_present[node],
            card_ok[node],
            gas.card_order[node],
            requests.request(cls),
            max_gpus,
        )
        booked = found
        used = i64.I64(
            hi=jnp.where(booked, used.hi.at[node].set(new_used_n.hi), used.hi),
            lo=jnp.where(booked, used.lo.at[node].set(new_used_n.lo), used.lo),
        )
        # only the booked node's feasibility can change — re-evaluate that
        # one node for every class and scatter the [T] column
        def refit(req_t):
            fit_n, _, _ = _fit_one_node(
                new_used_n,
                cap_n,
                gas.cap_present[node],
                card_ok[node],
                gas.card_order[node],
                BinpackRequest(*req_t),
                max_gpus,
            )
            return fit_n

        col = jax.vmap(refit)(_stacked(requests))  # [T]
        fits = jnp.where(booked, fits.at[:, node].set(col), fits)
        take = jnp.where(
            booked,
            jax.nn.one_hot(node, n_nodes, dtype=cap.dtype),
            jnp.zeros_like(cap),
        )
        return (used, fits, cap - take), best

    (used, fits, cap_left), node_for_pod = jax.lax.scan(
        step,
        (gas.used, fits0, state.capacity),
        (score.hi, score.lo, eligible, req_class),
    )
    return FusedOutput(
        node_for_pod=node_for_pod,
        capacity_left=cap_left,
        used=used,
        fits=fits,
        violating=violating,
    )


def observed_fused_schedule(
    state: ClusterState,
    pods: PendingPods,
    req_class: jax.Array,
    gas: BinpackNodeState,
    requests: FusedRequests,
    max_gpus: int,
    timer=None,
) -> FusedOutput:
    """``fused_schedule`` with solve-observatory stage attribution — the
    same caller-owned-timer contract as
    ``models.batch_scheduler.observed_scheduling_step``: compile when
    the jit cache grew during the dispatch, execute across
    ``block_until_ready``; readback/encode belong to the caller."""
    own = timer is None
    if own:
        obs = solveobs.ACTIVE
        if obs is None:
            return fused_schedule(
                state, pods, req_class, gas, requests, max_gpus
            )
        timer = obs.begin("fused_solve")
    before = fused_schedule._cache_size()
    out = fused_schedule(state, pods, req_class, gas, requests, max_gpus)
    timer.mark(
        "compile" if fused_schedule._cache_size() > before else "execute"
    )
    jax.block_until_ready(out.node_for_pod)
    timer.mark("execute")
    if own:
        timer.done(
            pods=int(pods.metric_row.shape[0]),
            nodes=int(state.capacity.shape[0]),
        )
    return out
