"""BASELINE.md config benches #1-#5 plus the solver surface.

Each config reports a measured device number with a measured host control
beside it (no extrapolation):

  * **config #1** — the reference's own e2e scale (3 nodes, single
    metric) through the live socket: the honest lower anchor where the
    batched design has nothing to win;
  * **config #2** — TAS multi-metric Prioritize, 1k synthetic nodes x
    100 pods: the batched scheduling solve (per-pod scheduleonmetric rows
    over a 4-metric matrix) vs the reference's per-pod loop
    (telemetryscheduler.go:128-149) in exact host semantics.
  * **config #3** — GAS card bin-packing, 256 nodes x 8 GPUs: the
    vectorized constraint-mask kernel (ops/binpack.py) evaluating every
    node at once vs the reference's sequential per-node first-fit
    (gpuscheduler/scheduler.go:200-257, 341-383), with a device/host
    parity assertion on the fits.
  * **config #4** — the fused TAS+GAS joint solve at 10k nodes x 1k
    pods (models/fused.py) vs the sequential host TAS-then-GAS
    composition, decision parity reported.
  * **config #5** — streaming deschedule + Sinkhorn reassignment, 10k
    nodes under continuous churn: per tick, re-evaluate the dontschedule
    violation set on churned metrics and re-solve the pending set with
    the Sinkhorn-guided assignment (ops/sinkhorn.py) vs the host loop
    re-running the reference's violation scan + per-pod sort
    (deschedule/enforce.go:57-151 cadence).
  * **solver surface** — greedy scan vs auction fixpoint vs Sinkhorn at
    1k pods x 10k nodes on the current backend (plus the Pallas kernel on
    TPU), and the all_gather vs ppermute-ring sharded Prioritize on an
    8-device virtual CPU mesh (subprocess).

On-device timings use K solves chained inside ONE compiled program, one
readback, so the per-dispatch host round trip is amortized over K
(chip_smoke.py prints the round trip itself).

Process model (benchmarks/children.py): the configs that compute on the
device (:data:`DEVICE_CONFIGS`) and the ones that launch services or
CPU-mesh children (:data:`LAUNCHED_CONFIGS`) run in separate processes —
``run_all`` launches one child for each group and never touches JAX.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict

import numpy as np

from benchmarks import children

# -- shared helpers ---------------------------------------------------------


def _timed_chain(make_jit, reps: int) -> float:
    """Seconds per solve for `reps` solves chained in one program."""
    fn = make_jit(reps)
    np.asarray(fn())  # compile + run once
    t0 = time.perf_counter()
    np.asarray(fn())
    return (time.perf_counter() - t0) / reps


def _i64_np(values: "np.ndarray"):
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.ops import i64

    hi, lo = i64.split_int64_np(values.astype(np.int64))
    return i64.I64(hi=jnp.asarray(hi), lo=jnp.asarray(lo))


# -- config #1: single-metric policy at the reference e2e scale -------------


def config1_single_metric(num_nodes: int = 3, platform: str = "tpu") -> Dict:
    """BASELINE config #1: the reference's own e2e scale — 3 worker nodes,
    a single-metric scheduleonmetric policy — through the live HTTP
    socket, device fastpath vs host control.  At 3 nodes the control's
    sort is trivial, so this config is the honest LOWER anchor of the
    scaling story: the batched design neither wins nor loses at the scale
    the reference was actually exercised at (functional parity is pinned
    by tests/test_e2e.py's kind-shaped scenarios); the win grows with
    cluster size (configs #2-#5, the north-star A/B)."""
    from benchmarks import http_load

    out = http_load.run(
        num_nodes=num_nodes,
        device_requests=104,
        control_requests=104,
        concurrency_sweep=(1,),
        warmup=5,
        repeats=1,
        platform=platform,
    )
    return {
        "scale": f"{num_nodes} nodes (reference e2e scale), single metric",
        "platform": out["platform"],
        "device_p99_ms": out["p99_prioritize_ms_device"],
        "control_p99_ms": out["p99_prioritize_ms_control"],
        "speedup_p99": out["speedup_p99"],
    }


# -- config #2: multi-metric Prioritize, 1k nodes x 100 pods ----------------


def _host_prioritize_control(state, pods, num_nodes: int, n_pods: int) -> float:
    """The reference per-pod loop (violation set once, then per pod:
    intersect -> sort -> take best free node), exact host semantics."""
    m_hi = np.asarray(state.metric_values.hi).astype(np.int64)
    m_lo = np.asarray(state.metric_values.lo).astype(np.int64)
    matrix = (m_hi << 32) | m_lo
    present = np.asarray(state.metric_present)
    rules_row = np.asarray(state.dontschedule.metric_row)[0]
    rules_op = np.asarray(state.dontschedule.op_id)[0]
    t_hi = np.asarray(state.dontschedule.target.hi)[0].astype(np.int64)
    t_lo = np.asarray(state.dontschedule.target.lo)[0].astype(np.int64)
    rules_target = (t_hi << 32) | t_lo
    rules_active = np.asarray(state.dontschedule.active)[0]
    capacity = list(np.asarray(state.capacity))
    pod_rows = np.asarray(pods.metric_row)
    pod_ops = np.asarray(pods.op_id)
    candidates = np.asarray(pods.candidates)

    start = time.perf_counter()
    violating = set()
    for r in range(len(rules_row)):
        if not rules_active[r]:
            continue
        row = rules_row[r]
        for n in range(num_nodes):
            if not present[row, n]:
                continue
            v = int(matrix[row, n])
            t = int(rules_target[r])
            op = int(rules_op[r])
            if (op == 0 and v < t) or (op == 1 and v > t) or (op == 2 and v == t):
                violating.add(n)
    for p in range(n_pods):
        row = pod_rows[p]
        op = int(pod_ops[p])
        cand = [
            n
            for n in range(num_nodes)
            if candidates[p, n] and present[row, n] and n not in violating
        ]
        cand.sort(key=lambda n: int(matrix[row, n]), reverse=(op == 1))
        for n in cand:
            if capacity[n] > 0:
                capacity[n] -= 1
                break
    return time.perf_counter() - start


def config2_multi_metric(num_nodes: int = 1000, num_pods: int = 100) -> Dict:
    import jax
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.models.batch_scheduler import (
        choose_assigner,
        example_inputs,
        scheduling_step,
    )

    state, pods = example_inputs(
        num_metrics=4, num_nodes=num_nodes, num_pods=num_pods, seed=5
    )
    # chosen from the concrete operands: inside the loop's trace they are
    # tracers and carry no placement
    assigner = choose_assigner(state, pods)

    def make_jit(reps):
        def loop_body(i, carry):
            checksum, cap = carry
            rolled = pods._replace(
                candidates=jnp.roll(pods.candidates, i, axis=1)
            )
            out = scheduling_step(
                state._replace(capacity=cap), rolled, assigner=assigner
            )
            return (
                checksum + jnp.sum(out.assignment.node_for_pod),
                out.assignment.capacity_left + jnp.int32(1),
            )

        @jax.jit
        def run():
            return jax.lax.fori_loop(
                0, reps, loop_body, (jnp.int32(0), state.capacity)
            )[0]

        return run

    device_s = _timed_chain(make_jit, reps=100)
    control_s = _host_prioritize_control(state, pods, num_nodes, num_pods)
    return {
        "scale": f"{num_nodes} nodes x {num_pods} pods, 4 metrics",
        "assigner": assigner,
        "device_ms_per_solve": round(device_s * 1e3, 3),
        "control_ms_per_solve": round(control_s * 1e3, 3),
        "speedup": round(control_s / device_s, 1),
    }


# -- config #3: GAS card bin-packing, 256 nodes x 8 GPUs --------------------


def _binpack_problem(num_nodes=256, num_cards=8, num_res=3, seed=9):
    """(BinpackNodeState, BinpackRequest, max_gpus, numpy mirrors)."""
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.ops import i64
    from platform_aware_scheduling_tpu.ops.binpack import (
        BinpackNodeState,
        BinpackRequest,
    )

    rng = np.random.default_rng(seed)
    cap = rng.integers(400, 1000, size=(num_nodes, num_res)).astype(np.int64)
    used = rng.integers(0, 500, size=(num_nodes, num_cards, num_res)).astype(
        np.int64
    )
    used = np.minimum(used, cap[:, None, :])
    # two containers: one asks 2 GPUs, one asks 1; per-GPU shares
    need = np.array(
        [[120, 90, 40], [200, 150, 0]], dtype=np.int64
    )
    need_active = np.array([[True, True, True], [True, True, False]])
    num_gpus = np.array([2, 1], dtype=np.int32)
    container_active = np.array([True, True])
    max_gpus = 2

    state = BinpackNodeState(
        used=_i64_np(used),
        capacity=_i64_np(cap),
        cap_present=jnp.ones((num_nodes, num_res), dtype=bool),
        card_valid=jnp.ones((num_nodes, num_cards), dtype=bool),
        card_real=jnp.ones((num_nodes, num_cards), dtype=bool),
        card_order=jnp.broadcast_to(
            jnp.arange(num_cards, dtype=jnp.int32), (num_nodes, num_cards)
        ),
    )
    request = BinpackRequest(
        need=_i64_np(need),
        need_active=jnp.asarray(need_active),
        num_gpus=jnp.asarray(num_gpus),
        container_active=jnp.asarray(container_active),
    )
    hosts = {
        "cap": cap,
        "used": used,
        "need": need,
        "need_active": need_active,
        "num_gpus": num_gpus,
    }
    return state, request, max_gpus, hosts


def _host_fit_node(used_n, cap_n, need, need_active, num_gpus):
    """(ok, booked used) for ONE node — the reference's per-node first-fit
    card walk (scheduler.go:200-257, 341-383), card_order == identity."""
    used = used_n.copy()
    n_cards, n_res = used.shape
    ok = True
    for t in range(len(num_gpus)):
        for _g in range(int(num_gpus[t])):
            placed = False
            for c in range(n_cards):
                fit = True
                for r in range(n_res):
                    if not need_active[t, r]:
                        continue
                    if used[c, r] + need[t, r] > cap_n[r]:
                        fit = False
                        break
                if fit:
                    for r in range(n_res):
                        if need_active[t, r]:
                            used[c, r] += need[t, r]
                    placed = True
                    break
            if not placed:
                ok = False
    return ok, used


def _host_first_fit(hosts) -> np.ndarray:
    """The reference's sequential first-fit over every node: fits bool [N]."""
    cap = hosts["cap"]
    base_used = hosts["used"]
    n_nodes = base_used.shape[0]
    fits = np.zeros(n_nodes, dtype=bool)
    for n in range(n_nodes):
        fits[n], _ = _host_fit_node(
            base_used[n],
            cap[n],
            hosts["need"],
            hosts["need_active"],
            hosts["num_gpus"],
        )
    return fits


def config3_gas_binpack(num_nodes: int = 256, num_cards: int = 8) -> Dict:
    import jax
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.ops import i64
    from platform_aware_scheduling_tpu.ops.binpack import binpack_kernel

    state, request, max_gpus, hosts = _binpack_problem(num_nodes, num_cards)

    # parity first: device fits must equal the host first-fit exactly
    result = binpack_kernel(state, request, max_gpus)
    device_fits = np.asarray(result.fits)
    host_fits = _host_first_fit(hosts)
    parity = bool((device_fits == host_fits).all())

    def make_jit(reps):
        def loop_body(i, checksum):
            rolled = state._replace(
                used=i64.I64(
                    hi=jnp.roll(state.used.hi, i, axis=0),
                    lo=jnp.roll(state.used.lo, i, axis=0),
                )
            )
            out = binpack_kernel(rolled, request, max_gpus)
            return checksum + jnp.sum(out.fits.astype(jnp.int32))

        @jax.jit
        def run():
            return jax.lax.fori_loop(0, reps, loop_body, jnp.int32(0))

        return run

    device_s = _timed_chain(make_jit, reps=100)

    t0 = time.perf_counter()
    host_reps = 5
    for _ in range(host_reps):
        _host_first_fit(hosts)
    control_s = (time.perf_counter() - t0) / host_reps
    return {
        "scale": f"{num_nodes} nodes x {num_cards} GPUs, 2 containers",
        "device_ms_per_batch_fit": round(device_s * 1e3, 3),
        "control_ms_per_batch_fit": round(control_s * 1e3, 3),
        "speedup": round(control_s / device_s, 1),
        "parity": parity,
        "nodes_fitting": int(host_fits.sum()),
    }


def config3_gas_binpack_large(num_nodes: int = 4096) -> Dict:
    """The BASELINE shape is 256 x 8; at that size the batched kernel is
    dispatch/overhead-bound.  This second scale point shows where the
    vectorized form pulls away (per-node host cost is linear; the batched
    evaluation is one program either way)."""
    return config3_gas_binpack(num_nodes=num_nodes)


# -- config #4: fused TAS+GAS joint solve, 10k nodes x 1k pods --------------


def _fused_problem(
    num_nodes=10_000,
    num_pods=1000,
    num_cards=8,
    num_res=3,
    num_classes=3,
    seed=21,
):
    """(tas_state, pods, req_class, gas_state, requests, max_gpus, hosts):
    a joint problem — TAS metric state + per-pod scheduleonmetric rules
    AND a per-card GAS usage tensor + T pod request classes."""
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.models.batch_scheduler import (
        example_inputs,
    )
    from platform_aware_scheduling_tpu.models.fused import FusedRequests
    from platform_aware_scheduling_tpu.ops.binpack import BinpackNodeState

    rng = np.random.default_rng(seed)
    state, pods = example_inputs(
        num_metrics=4, num_nodes=num_nodes, num_pods=num_pods, seed=seed
    )
    cap = rng.integers(600, 1200, size=(num_nodes, num_res)).astype(np.int64)
    used = rng.integers(0, 400, size=(num_nodes, num_cards, num_res)).astype(
        np.int64
    )
    used = np.minimum(used, cap[:, None, :])
    need = rng.integers(40, 260, size=(num_classes, 2, num_res)).astype(
        np.int64
    )
    need_active = rng.random((num_classes, 2, num_res)) > 0.2
    num_gpus = rng.integers(1, 3, size=(num_classes, 2)).astype(np.int32)
    container_active = np.ones((num_classes, 2), dtype=bool)
    req_class = rng.integers(0, num_classes, size=num_pods).astype(np.int32)
    max_gpus = int(num_gpus.max())

    gas = BinpackNodeState(
        used=_i64_np(used),
        capacity=_i64_np(cap),
        cap_present=jnp.ones((num_nodes, num_res), dtype=bool),
        card_valid=jnp.ones((num_nodes, num_cards), dtype=bool),
        card_real=jnp.ones((num_nodes, num_cards), dtype=bool),
        card_order=jnp.broadcast_to(
            jnp.arange(num_cards, dtype=jnp.int32), (num_nodes, num_cards)
        ),
    )
    requests = FusedRequests(
        need=_i64_np(need),
        need_active=jnp.asarray(need_active),
        num_gpus=jnp.asarray(num_gpus),
        container_active=jnp.asarray(container_active),
    )
    hosts = {
        "cap": cap,
        "used": used,
        "need": need,
        "need_active": need_active,
        "num_gpus": num_gpus,
    }
    return state, pods, jnp.asarray(req_class), gas, requests, max_gpus, hosts


def _host_fused_control(
    state, pods, req_class, hosts, num_nodes: int, n_pods: int
):
    """The sequential TAS-then-GAS composition the reference deploys
    (tas+gas-extender-configmap.yaml): per pod, TAS violation filter +
    sort (telemetryscheduler.go:128-149), then walk nodes best-first and
    take the first with pod capacity AND a first-fit card packing
    (scheduler.go:200-257); book the cards.  Returns (assignment [P],
    seconds)."""
    m_hi = np.asarray(state.metric_values.hi).astype(np.int64)
    m_lo = np.asarray(state.metric_values.lo).astype(np.int64)
    matrix = (m_hi << 32) | m_lo
    present = np.asarray(state.metric_present)
    rules_row = np.asarray(state.dontschedule.metric_row)[0]
    rules_op = np.asarray(state.dontschedule.op_id)[0]
    t_hi = np.asarray(state.dontschedule.target.hi)[0].astype(np.int64)
    t_lo = np.asarray(state.dontschedule.target.lo)[0].astype(np.int64)
    rules_target = (t_hi << 32) | t_lo
    rules_active = np.asarray(state.dontschedule.active)[0]
    capacity = list(np.asarray(state.capacity))
    pod_rows = np.asarray(pods.metric_row)
    pod_ops = np.asarray(pods.op_id)
    candidates = np.asarray(pods.candidates)
    classes = np.asarray(req_class)
    cap = hosts["cap"]
    used = hosts["used"].copy()
    need = hosts["need"]
    need_active = hosts["need_active"]
    num_gpus = hosts["num_gpus"]

    start = time.perf_counter()
    violating = set()
    for r in range(len(rules_row)):
        if not rules_active[r]:
            continue
        row = rules_row[r]
        for n in range(num_nodes):
            if not present[row, n]:
                continue
            v = int(matrix[row, n])
            t = int(rules_target[r])
            op = int(rules_op[r])
            if (op == 0 and v < t) or (op == 1 and v > t) or (op == 2 and v == t):
                violating.add(n)
    assignment = np.full(n_pods, -1, dtype=np.int64)
    for p in range(n_pods):
        row = pod_rows[p]
        op = int(pod_ops[p])
        cand = [
            n
            for n in range(num_nodes)
            if candidates[p, n] and present[row, n] and n not in violating
        ]
        cand.sort(key=lambda n: int(matrix[row, n]), reverse=(op == 1))
        t = int(classes[p])
        for n in cand:
            if capacity[n] <= 0:
                continue
            ok, new_used = _host_fit_node(
                used[n], cap[n], need[t], need_active[t], num_gpus[t]
            )
            if ok:
                used[n] = new_used
                capacity[n] -= 1
                assignment[p] = n
                break
    return assignment, time.perf_counter() - start


def config4_fused(num_nodes: int = 10_000, num_pods: int = 1000) -> Dict:
    """BASELINE config #4: the joint TAS+GAS fused solve at 10k x 1k,
    device vs the sequential host composition; the device/host parity bit
    is REPORTED in the result (exactness itself is pinned at multiple
    shapes by tests/test_fused.py — a bench run never hides a divergence
    behind an exception, it surfaces parity: false)."""
    import jax
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.models.fused import fused_schedule

    state, pods, req_class, gas, requests, max_gpus, hosts = _fused_problem(
        num_nodes=num_nodes, num_pods=num_pods
    )

    # parity first: device assignment == sequential host TAS-then-GAS
    out = fused_schedule(state, pods, req_class, gas, requests, max_gpus)
    device_assign = np.asarray(out.node_for_pod).astype(np.int64)
    host_assign, control_s = _host_fused_control(
        state, pods, req_class, hosts, num_nodes, num_pods
    )
    parity = bool((device_assign == host_assign).all())

    def make_jit(reps):
        def loop_body(i, checksum):
            rolled = pods._replace(
                candidates=jnp.roll(pods.candidates, i, axis=1)
            )
            out = fused_schedule(
                state, rolled, req_class, gas, requests, max_gpus
            )
            return checksum + jnp.sum(out.node_for_pod)

        @jax.jit
        def run():
            return jax.lax.fori_loop(0, reps, loop_body, jnp.int32(0))

        return run

    device_s = _timed_chain(make_jit, reps=20)
    return {
        "scale": f"{num_nodes} nodes x {num_pods} pods, "
        f"{hosts['used'].shape[1]} cards x {hosts['used'].shape[2]} res, "
        f"{hosts['num_gpus'].shape[0]} request classes",
        "device_ms_per_solve": round(device_s * 1e3, 3),
        "control_ms_per_solve": round(control_s * 1e3, 3),
        "speedup": round(control_s / device_s, 1),
        "parity": parity,
        "pods_assigned": int((host_assign >= 0).sum()),
    }


# -- config #5: streaming deschedule + Sinkhorn churn, 10k nodes ------------


def config5_churn(
    num_nodes: int = 10_000, num_pods: int = 256, ticks: int = 8
) -> Dict:
    import jax
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.models.batch_scheduler import (
        example_inputs,
        score_and_filter,
    )
    from platform_aware_scheduling_tpu.ops import i64
    from platform_aware_scheduling_tpu.ops.sinkhorn import sinkhorn_assign_kernel

    state, pods = example_inputs(
        num_metrics=4, num_nodes=num_nodes, num_pods=num_pods, seed=13
    )

    def make_jit(reps):
        def tick(checksum, t):
            # churn: the metric matrix shifts every tick (node values move)
            churned = state._replace(
                metric_values=i64.I64(
                    hi=jnp.roll(state.metric_values.hi, t, axis=1),
                    lo=jnp.roll(state.metric_values.lo, t, axis=1),
                )
            )
            violating, score, eligible = score_and_filter(churned, pods)
            out = sinkhorn_assign_kernel(
                score, eligible, churned.capacity, iterations=20
            )
            checksum = (
                checksum
                + jnp.sum(out.assignment.node_for_pod)
                + jnp.sum(violating.astype(jnp.int32))
            )
            return checksum, None

        @jax.jit
        def run():
            return jax.lax.scan(
                tick, jnp.int32(0), jnp.arange(reps, dtype=jnp.int32)
            )[0]

        return run

    device_s = _timed_chain(make_jit, reps=ticks)

    # host control: per tick the reference re-runs the violation scan
    # (deschedule enforcement cadence) and re-sorts each pending pod
    host_ticks = 2
    t0 = time.perf_counter()
    for _ in range(host_ticks):
        _host_prioritize_control(state, pods, num_nodes, num_pods)
    control_s = (time.perf_counter() - t0) / host_ticks
    return {
        "scale": f"{num_nodes} nodes, {num_pods} pods/tick, sinkhorn-20",
        "device_ms_per_tick": round(device_s * 1e3, 3),
        "control_ms_per_tick": round(control_s * 1e3, 3),
        "speedup": round(control_s / device_s, 1),
        # the two sides run DIFFERENT algorithms by design: the device tick
        # is the Sinkhorn-guided global re-solve (the churn engine this
        # framework adds), the control is the reference's own per-tick work
        # (violation scan + per-pod sort greedy) — so the speedup includes
        # algorithm substitution, not pure acceleration (advisor r4)
        "device_algorithm": "sinkhorn-20-guided batch assignment",
        "control_algorithm": "reference per-pod sort greedy "
        "(deschedule enforcement cadence)",
    }


# -- solver surface ---------------------------------------------------------


def solver_surface(num_nodes: int = 10_000, num_pods: int = 1000) -> Dict:
    import jax
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.models.batch_scheduler import (
        ASSIGNER_PALLAS,
        choose_assigner,
        example_inputs,
        score_and_filter,
    )
    from platform_aware_scheduling_tpu.ops.assign import (
        auction_assign_kernel,
        greedy_assign_kernel,
    )
    from platform_aware_scheduling_tpu.ops.pallas_assign import (
        greedy_assign_pallas,
    )
    from platform_aware_scheduling_tpu.ops.sinkhorn import sinkhorn_assign_kernel

    state, pods = example_inputs(
        num_metrics=4, num_nodes=num_nodes, num_pods=num_pods, seed=3
    )
    violating, score, eligible = score_and_filter(state, pods)
    solvers = {
        "greedy_scan": lambda s, e, c: greedy_assign_kernel(s, e, c).node_for_pod,
        "auction": lambda s, e, c: auction_assign_kernel(s, e, c).node_for_pod,
        "sinkhorn20_guided": lambda s, e, c: sinkhorn_assign_kernel(
            s, e, c, iterations=20
        ).assignment.node_for_pod,
    }
    if choose_assigner(score, eligible, state.capacity) == ASSIGNER_PALLAS:
        solvers["greedy_pallas"] = (
            lambda s, e, c: greedy_assign_pallas(s, e, c).node_for_pod
        )

    out: Dict = {"scale": f"{num_pods} pods x {num_nodes} nodes"}
    for name, solver in solvers.items():

        def make_jit(reps, solver=solver):
            def loop_body(i, checksum):
                elig = jnp.roll(eligible, i, axis=1)
                assigned = solver(score, elig, state.capacity)
                return checksum + jnp.sum(assigned)

            @jax.jit
            def run():
                return jax.lax.fori_loop(0, reps, loop_body, jnp.int32(0))

            return run

        out[f"{name}_ms"] = round(_timed_chain(make_jit, reps=20) * 1e3, 3)
    return out


# -- sharded ring vs all_gather Prioritize (8-device virtual CPU mesh) ------


def _cpu_mesh(n_shards: int) -> None:
    """The two CPU-mesh children are structural checks on a virtual
    ``n_shards``-device CPU mesh by construction — never a chip."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_shards)


def _ring_main(nodes_per_shard: int, n_shards: int) -> None:
    _cpu_mesh(n_shards)
    import jax
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.ops import i64
    from platform_aware_scheduling_tpu.ops.rules import OP_GREATER_THAN
    from platform_aware_scheduling_tpu.parallel.mesh import make_mesh
    from platform_aware_scheduling_tpu.parallel.sharded import (
        sharded_prioritize,
        sharded_prioritize_ring,
    )

    num_nodes = nodes_per_shard * n_shards
    rng = np.random.default_rng(2)
    values = rng.integers(0, 10**9, size=num_nodes).astype(np.int64)
    hi, lo = i64.split_int64_np(values)
    row = i64.I64(hi=jnp.asarray(hi), lo=jnp.asarray(lo))
    valid = jnp.asarray(rng.random(num_nodes) > 0.05)
    mesh = make_mesh(n_node_shards=n_shards, n_pod_shards=1)
    op = jnp.int32(OP_GREATER_THAN)

    results = {}
    for name, fn in (
        ("allgather", sharded_prioritize),
        ("ring", sharded_prioritize_ring),
    ):
        scores, _ = fn(mesh, row, valid, op)  # compile + run
        ref = np.asarray(scores)
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            scores, _ = fn(mesh, row, valid, op)
            np.asarray(scores)
        results[f"{name}_ms"] = round(
            (time.perf_counter() - t0) / reps * 1e3, 3
        )
        results[f"{name}_checksum"] = int(ref.astype(np.int64).sum())
    results["parity"] = (
        results["allgather_checksum"] == results["ring_checksum"]
    )
    results["scale"] = f"{n_shards} shards x {nodes_per_shard} nodes (cpu mesh)"
    print(json.dumps(results))


def _subprocess_bench(mode: str, *args: int, timeout: int = 600) -> Dict:
    """Run one of this module's ``--<mode>`` CPU-mesh entries as a child
    (they configure their own virtual mesh); the LAST int arg is the
    shard count."""
    return children.run_child(
        ["-m", "benchmarks.configs", f"--{mode}"] + [str(a) for a in args],
        timeout=timeout,
    )


def ring_cpu_mesh(nodes_per_shard: int = 512, n_shards: int = 8) -> Dict:
    """Ring-vs-gather comparison on a virtual 8-device CPU mesh."""
    return _subprocess_bench("ring", nodes_per_shard, n_shards)


def _churn_mesh_main(nodes_per_shard: int, n_shards: int) -> None:
    """config #5 on the mesh (VERDICT r4 #5): per tick, score/filter the
    churned metric state and re-solve the pending set with the SHARDED
    Sinkhorn engine (parallel/sharded.sharded_sinkhorn_assign), vs the
    single-chip kernel on the same problem; objective parity asserted."""
    _cpu_mesh(n_shards)
    import jax
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.models.batch_scheduler import (
        example_inputs,
        score_and_filter,
    )
    from platform_aware_scheduling_tpu.ops import i64
    from platform_aware_scheduling_tpu.ops.sinkhorn import (
        sinkhorn_assign_kernel,
        total_utility,
    )
    from platform_aware_scheduling_tpu.parallel.mesh import make_mesh
    from platform_aware_scheduling_tpu.parallel.sharded import (
        sharded_sinkhorn_assign,
    )

    num_nodes = nodes_per_shard * n_shards
    num_pods = 256
    ticks = 4
    state, pods = example_inputs(
        num_metrics=4, num_nodes=num_nodes, num_pods=num_pods, seed=13
    )
    mesh = make_mesh(n_node_shards=n_shards, n_pod_shards=1)

    def churned(t):
        return state._replace(
            metric_values=i64.I64(
                hi=jnp.roll(state.metric_values.hi, t, axis=1),
                lo=jnp.roll(state.metric_values.lo, t, axis=1),
            )
        )

    def mesh_tick(t):
        _, score, eligible = score_and_filter(churned(t), pods)
        assigned, _ = sharded_sinkhorn_assign(
            mesh, score, eligible, state.capacity, iterations=20
        )
        return assigned

    def single_tick(t):
        _, score, eligible = score_and_filter(churned(t), pods)
        out = sinkhorn_assign_kernel(
            score, eligible, state.capacity, iterations=20
        )
        return out.assignment.node_for_pod

    results: Dict = {}
    for name, fn in (("mesh", mesh_tick), ("single", single_tick)):
        np.asarray(fn(0))  # compile
        t0 = time.perf_counter()
        last = None
        for t in range(ticks):
            last = fn(t)
            np.asarray(last)
        results[f"{name}_ms_per_tick"] = round(
            (time.perf_counter() - t0) / ticks * 1e3, 3
        )
        _, score, eligible = score_and_filter(churned(ticks - 1), pods)
        results[f"{name}_objective"] = round(
            float(total_utility(score, last)), 3
        )
        results[f"{name}_assigned"] = int((np.asarray(last) >= 0).sum())
    results["objective_parity"] = (
        abs(results["mesh_objective"] - results["single_objective"])
        <= max(0.02 * abs(results["single_objective"]), 0.1)
    )
    results["scale"] = (
        f"{n_shards} shards x {nodes_per_shard} nodes, {num_pods} pods/tick, "
        f"sinkhorn-20 (cpu mesh)"
    )
    results["notes"] = (
        "structural check (collective pattern + objective parity) on the "
        "virtual CPU mesh; not a TPU performance claim — CPU-mesh "
        "collectives are orders slower than ICI"
    )
    print(json.dumps(results))


def churn_mesh_cpu8(nodes_per_shard: int = 256, n_shards: int = 8) -> Dict:
    """config #5's churn engine on a virtual 8-device CPU mesh.  Like
    ring_prioritize_cpu8 this is a structural check (collective pattern +
    objective parity), not a TPU performance claim — virtual CPU-mesh
    collectives are orders slower than ICI."""
    return _subprocess_bench("churn-mesh", nodes_per_shard, n_shards)


# -- entry ------------------------------------------------------------------


def filter_floor() -> Dict:
    """Per-stage filter-floor decomposition (benchmarks/http_load.py)."""
    from benchmarks import http_load

    return http_load.filter_floor_breakdown()


def http_floor() -> Dict:
    """The transport floor under it, from a launched service."""
    from benchmarks import http_load

    return http_load.http_floor()


#: computed in THIS process on the device (the child holds the chip)
DEVICE_CONFIGS = (
    ("config2_multi_metric_1k_100", config2_multi_metric),
    ("config3_gas_binpack_256x8", config3_gas_binpack),
    ("config3_gas_binpack_4096x8", config3_gas_binpack_large),
    ("config4_fused_10k_1k", config4_fused),
    ("config5_churn_10k", config5_churn),
    ("solvers_1k_pods_10k_nodes", solver_surface),
    ("filter_floor_breakdown", filter_floor),
)
#: each launches its own service or CPU-mesh child (this process stays
#: off JAX)
LAUNCHED_CONFIGS = (
    ("config1_single_metric_3node", config1_single_metric),
    ("ring_prioritize_cpu8", ring_cpu_mesh),
    ("config5_churn_mesh_cpu8", churn_mesh_cpu8),
    ("http_floor", http_floor),
)


def _run_group(group) -> Dict:
    out: Dict = {}
    for name, fn in group:
        try:
            out[name] = fn()
        except Exception as exc:  # one config must not sink the others;
            # the error stays in the result and fails the run
            # (children.nested_errors)
            out[name] = {"error": str(exc)[:300]}
    return out


def run_all() -> Dict:
    """Every config under the keys BENCH consumers match on.  Launches
    one child for each process kind; never computes itself."""
    device = children.run_child(["-m", "benchmarks.configs", "--device"])
    launched = children.run_child(["-m", "benchmarks.configs", "--launched"])
    # the transport floor belongs in the breakdown it sits under
    floor = launched.pop("http_floor")
    floor.pop("platform", None)
    for key, value in floor.items():
        device["filter_floor_breakdown"].setdefault(key, value)
    return {**launched, **device}


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else ""
    if mode == "--ring":
        _ring_main(int(sys.argv[2]), int(sys.argv[3]))
    elif mode == "--churn-mesh":
        _churn_mesh_main(int(sys.argv[2]), int(sys.argv[3]))
    elif mode == "--device":
        identity = children.hold_chip("benchmarks.configs --device")
        result = _run_group(DEVICE_CONFIGS)
        result["platform"] = identity["platform"]
        print(json.dumps(result))
    else:
        result = (
            _run_group(LAUNCHED_CONFIGS) if mode == "--launched" else run_all()
        )
        children.assert_launcher(f"benchmarks.configs {mode}".strip())
        print(json.dumps(result, indent=None if mode else 2))
        if not mode and children.nested_errors(result):
            raise SystemExit(
                f"configs failed: {children.nested_errors(result)}"
            )
