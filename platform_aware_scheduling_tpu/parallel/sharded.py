"""Sharded scheduling kernels: shard_map over the ``nodes`` mesh axis.

Four building blocks, each the multi-chip form of an ops/ kernel:

  * :func:`sharded_violations` — rule evaluation is elementwise over nodes,
    so the sharded form needs NO collectives at all: each chip filters its
    node shard independently (the embarrassingly-parallel half);
  * :func:`sharded_prioritize` — exact global ordinal ranks without a
    global sort: all_gather the (tiny) score keys over ICI, then each chip
    rank-by-counting its local lanes against the global key set —
    rank_i = |{j : key_j < key_i or (key_j = key_i and j < i)}|,
    identical to the single-chip sort's ranks;
  * :func:`sharded_greedy_assign` — the sequential-in-pods greedy solve,
    a block of pods at a time: each shard extracts its best candidates a pod
    among the nodes whose room covers that pod's demand, ONE all_gather
    carries them with their room, every chip deterministically replays the
    block's decisions alike, and only the owning shard books the room;
  * :func:`sharded_sinkhorn_assign` — the mesh form of the Sinkhorn churn
    engine (ops/sinkhorn.py, BASELINE config #5): the [P, N] logit matrix
    stays node-sharded end to end; row normalizers are global
    log-sum-exps built from one ``pmax`` (stability shift) + one ``psum``
    (exp-sum) per iteration, column normalizers are purely local to each
    shard's nodes, and the soft plan is rounded by the exact
    :func:`sharded_greedy_assign` — so feasibility and determinism are
    inherited from the exact solver while only guidance quality rides on
    f32 collectives.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from platform_aware_scheduling_tpu.ops import i64
from platform_aware_scheduling_tpu.ops.assign import (
    UNASSIGNED,
    room_covers,
    room_less,
)
from platform_aware_scheduling_tpu.ops.rules import (
    OP_GREATER_THAN,
    OP_LESS_THAN,
    RuleSet,
    violated_nodes,
)
from platform_aware_scheduling_tpu.parallel.mesh import NODE_AXIS, POD_AXIS



def sharded_violations(mesh: Mesh, metric_values: i64.I64, metric_present, rules: RuleSet):
    """dontschedule violation mask with the node axis sharded; pure local
    compute (rule tensors replicated, metric matrix sharded on nodes)."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            i64.I64(hi=P(None, NODE_AXIS), lo=P(None, NODE_AXIS)),
            P(None, NODE_AXIS),
            RuleSet(metric_row=P(), op_id=P(),
                    target=i64.I64(hi=P(), lo=P()), active=P()),
        ),
        out_specs=P(NODE_AXIS),
    )
    def _impl(values, present, ruleset):
        return violated_nodes(values, present, ruleset)

    return _impl(metric_values, metric_present, rules)


def _rank_key(value: i64.I64, valid, op_id, index):
    """Sort key for ranking (same construction as ops/scoring._rank_keys);
    ``index`` must be the GLOBAL node index of each lane."""
    flipped = i64.flip(value)
    by_value = i64.select(op_id == OP_GREATER_THAN, flipped, value)
    index_key = i64.I64(hi=jnp.zeros_like(value.hi), lo=index.astype(jnp.uint32))
    sorts = (op_id == OP_LESS_THAN) | (op_id == OP_GREATER_THAN)
    key = i64.select(sorts, by_value, index_key)
    return i64.select(valid, key, i64.full_like(key, i64.INT64_MAX))


def sharded_prioritize(mesh: Mesh, value: i64.I64, valid, op_id):
    """Exact ordinal scores (10 - global rank) for a node-sharded metric
    row.  One all_gather of the key limbs; ranks by counting."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            i64.I64(hi=P(NODE_AXIS), lo=P(NODE_AXIS)),
            P(NODE_AXIS),
            P(),
        ),
        out_specs=(P(NODE_AXIS), P(NODE_AXIS)),
    )
    def _impl(value_loc, valid_loc, op):
        n_loc = value_loc.hi.shape[-1]
        shard = jax.lax.axis_index(NODE_AXIS)
        offset = (shard * n_loc).astype(jnp.int32)
        local_idx = jnp.arange(n_loc, dtype=jnp.int32) + offset
        key_loc = _rank_key(value_loc, valid_loc, op, local_idx)
        # invalid lanes sort after valid ones on key collision: index + N
        # axis_size is a newer jax API; psum(1) is its portable spelling
        if hasattr(jax.lax, "axis_size"):
            n_total = n_loc * jax.lax.axis_size(NODE_AXIS)
        else:
            n_total = n_loc * jax.lax.psum(1, NODE_AXIS)
        tie_loc = jnp.where(valid_loc, local_idx, local_idx + n_total)

        g_hi = jax.lax.all_gather(key_loc.hi, NODE_AXIS, tiled=True)
        g_lo = jax.lax.all_gather(key_loc.lo, NODE_AXIS, tiled=True)
        g_tie = jax.lax.all_gather(tie_loc, NODE_AXIS, tiled=True)

        gk = i64.I64(hi=g_hi[None, :], lo=g_lo[None, :])
        lk = i64.I64(hi=key_loc.hi[:, None], lo=key_loc.lo[:, None])
        cmp = i64.cmp(gk, lk)  # [n_loc, N]
        before = (cmp == -1) | ((cmp == 0) & (g_tie[None, :] < tie_loc[:, None]))
        ranks = jnp.sum(before, axis=-1, dtype=jnp.int32)
        return jnp.int32(10) - ranks, valid_loc

    return _impl(value, valid, op_id)


def sharded_prioritize_ring(mesh: Mesh, value: i64.I64, valid, op_id):
    """Ring-pass form of :func:`sharded_prioritize` — identical results.

    Instead of all_gathering the full key set (O(N) memory per chip), each
    chip's key block circulates the ring via ``ppermute`` while every chip
    accumulates how many circulating keys rank before each of its local
    lanes; after D hops the counts are exact global ranks.  This is the
    ring-attention/sequence-parallel communication pattern (blockwise
    compute overlapped with neighbor exchange over ICI) applied to the
    node axis — the memory-scalable path for very large clusters.
    """
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[NODE_AXIS]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            i64.I64(hi=P(NODE_AXIS), lo=P(NODE_AXIS)),
            P(NODE_AXIS),
            P(),
        ),
        out_specs=(P(NODE_AXIS), P(NODE_AXIS)),
    )
    def _impl(value_loc, valid_loc, op):
        n_loc = value_loc.hi.shape[-1]
        shard = jax.lax.axis_index(NODE_AXIS)
        offset = (shard * n_loc).astype(jnp.int32)
        local_idx = jnp.arange(n_loc, dtype=jnp.int32) + offset
        key_loc = _rank_key(value_loc, valid_loc, op, local_idx)
        n_total = n_loc * n_shards
        tie_loc = jnp.where(valid_loc, local_idx, local_idx + n_total)
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

        def hop(carry, _):
            blk_hi, blk_lo, blk_tie, counts = carry
            gk = i64.I64(hi=blk_hi[None, :], lo=blk_lo[None, :])
            lk = i64.I64(hi=key_loc.hi[:, None], lo=key_loc.lo[:, None])
            cmp = i64.cmp(gk, lk)  # [n_loc, n_loc]
            before = (cmp == -1) | (
                (cmp == 0) & (blk_tie[None, :] < tie_loc[:, None])
            )
            counts = counts + jnp.sum(before, axis=-1, dtype=jnp.int32)
            blk_hi = jax.lax.ppermute(blk_hi, NODE_AXIS, perm)
            blk_lo = jax.lax.ppermute(blk_lo, NODE_AXIS, perm)
            blk_tie = jax.lax.ppermute(blk_tie, NODE_AXIS, perm)
            return (blk_hi, blk_lo, blk_tie, counts), None

        # node-varying zeros derived from a sharded value (tie_loc) so the
        # scan carry's varying axes match what the body produces
        zero_counts = tie_loc * jnp.int32(0)
        init = (key_loc.hi, key_loc.lo, tie_loc, zero_counts)
        (_, _, _, ranks), _ = jax.lax.scan(hop, init, None, length=n_shards)
        return jnp.int32(10) - ranks, valid_loc

    return _impl(value, valid, op_id)


def greedy_assign_collective_count(num_pods: int, block_size: int = 32) -> int:
    """all_gathers :func:`sharded_greedy_assign` issues for ``num_pods``."""
    padded = -(-num_pods // block_size) * block_size
    return padded // block_size


def sharded_greedy_assign(
    mesh: Mesh,
    score: i64.I64,
    eligible,
    room,
    demand=None,
    limbs: int = 1,
    block_size: int = 32,
):
    """Greedy batch assignment with the node axis sharded, chunked into
    pod blocks: ONE all_gather per ``block_size`` pods instead of the
    per-pod gather the round-2/3 verdicts flagged (1k sequential
    collectives at target scale -> ~32).

    Room takes the forms of ops/assign.greedy_assign_kernel, and they are
    one form here.  ``demand`` given: ``room`` is the nodes' room
    ``[limbs * R, N]`` (split over the nodes) and ``demand`` each pod's own
    request vector ``[P, limbs * R]`` (on every chip); pod i is feasible on
    node j iff ``room_covers`` and books its vector with ``room_less``.
    ``demand`` None: ``room`` is a count ``[N]``, solved as a demand of one
    on one resource, and the room left comes back as ``[N]``.

    Per block of B pods, each shard extracts its top-B local candidates
    per pod: nodes where every resource covers THAT pod's demand at block
    start, in score order, each with its block-start room attached.  The
    ``[B, B, 4 + limbs * R]`` payload (hi, lo, index, found, room) is
    gathered once, and every chip deterministically REPLAYS the block's
    greedy decisions from the merged candidate lists: a pod's booking is
    taken off every candidate entry of the node it chose, so each later
    pod sees the room its turn would see, and the plan is the sequential
    solve's.  After the block each shard's lanes take the room left of the
    nodes its pods chose.

    Top-B per shard suffices for exactness, with vectors as with a count:
    a booking on node j lowers only j's room, so it can make only j
    infeasible for a later pod, whatever that pod asks.  A pod's
    sequential best node on shard s is feasible at its turn, hence at block
    start, and sits in s's list unless every node above it was made
    infeasible, one booking each: that takes B bookings, and a block books
    at most B-1 times before any pod's turn (equality with the single-chip
    kernels is pinned by tests/test_parallel.py and
    tests/test_planner_demand.py).  Padding pods ask for nothing and are
    eligible nowhere.
    """
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[NODE_AXIS]
    num_pods = score.hi.shape[0]
    counted = demand is None
    if counted:
        # a count is a demand of one on one resource
        room = room.reshape(1, -1)
        demand = jnp.ones((num_pods, 1), dtype=room.dtype)
        limbs = 1
    held = room.shape[0]  # limbs * R
    padded = -(-num_pods // block_size) * block_size
    pad = padded - num_pods
    if pad:
        # padding pods are ineligible everywhere -> UNASSIGNED, no effect
        score = i64.I64(
            hi=jnp.pad(score.hi, ((0, pad), (0, 0))),
            lo=jnp.pad(score.lo, ((0, pad), (0, 0))),
        )
        eligible = jnp.pad(eligible, ((0, pad), (0, 0)))
        demand = jnp.pad(demand, ((0, pad), (0, 0)))

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            i64.I64(hi=P(None, NODE_AXIS), lo=P(None, NODE_AXIS)),
            P(None, NODE_AXIS),
            P(None, NODE_AXIS),
            P(),
        ),
        out_specs=(P(), P(None, NODE_AXIS)),
        # `assigned` is replicated by construction (every chip replays the
        # same decision from the same gathered candidates); the static
        # varying-axes check can't see that
        check_vma=False,
    )
    def _impl(s, elig, room_loc, asked):
        n_loc = room_loc.shape[-1]
        b_top = min(block_size, n_loc)
        width = b_top * n_shards  # candidate entries a pod
        shard = jax.lax.axis_index(NODE_AXIS)
        offset = (shard * n_loc).astype(jnp.int32)
        big_hi = jnp.int32(2**31 - 1)
        big_lo = jnp.uint32(2**32 - 1)
        big_idx = jnp.int32(2**30)
        iota_loc = jnp.arange(n_loc, dtype=jnp.int32)
        row = jnp.arange(block_size, dtype=jnp.int32)
        num_blocks = padded // block_size
        s_hi = s.hi.reshape(num_blocks, block_size, n_loc)
        s_lo = s.lo.reshape(num_blocks, block_size, n_loc)
        elig_b = elig.reshape(num_blocks, block_size, n_loc)
        asked_b = asked.reshape(num_blocks, block_size, held)

        def block_step(room_loc, blk):
            b_hi, b_lo, b_elig, b_asked = blk
            flipped = i64.flip(i64.I64(hi=b_hi, lo=b_lo))  # lex-min = best
            # [B, n_loc]: every resource covers that pod's own demand
            avail = b_elig & room_covers(
                room_loc[:, None, :], b_asked.T[:, :, None], limbs
            )

            def extract(taken, _):
                ok = avail & ~taken
                hi = jnp.where(ok, flipped.hi, big_hi)
                m_hi = jnp.min(hi, axis=-1, keepdims=True)
                on_hi = ok & (flipped.hi == m_hi)
                lo = jnp.where(on_hi, flipped.lo, big_lo)
                m_lo = jnp.min(lo, axis=-1, keepdims=True)
                on_lo = on_hi & (flipped.lo == m_lo)
                pick = jnp.min(
                    jnp.where(on_lo, iota_loc[None, :], jnp.int32(n_loc)),
                    axis=-1,
                )  # [B] local index (n_loc when none)
                found = jnp.any(ok, axis=-1)  # [B]
                safe = jnp.minimum(pick, jnp.int32(n_loc - 1))
                cand = jnp.concatenate(
                    [
                        jnp.stack(
                            [
                                jnp.where(found, flipped.hi[row, safe], big_hi),
                                jnp.where(
                                    found, flipped.lo[row, safe], big_lo
                                ).astype(jnp.int32),
                                jnp.where(found, safe + offset, big_idx),
                                found.astype(jnp.int32),
                            ],
                            axis=-1,
                        ),
                        jnp.where(found[:, None], room_loc[:, safe].T, 0),
                    ],
                    axis=-1,
                )  # [B, 4 + held]
                taken = taken | (
                    found[:, None] & (iota_loc[None, :] == safe[:, None])
                )
                return taken, cand

            _, cands = jax.lax.scan(
                extract,
                jnp.zeros_like(avail),
                None,
                length=b_top,
            )  # [b_top, B, 4 + held]
            payload = jnp.transpose(cands, (1, 0, 2))  # [B, b_top, 4 + held]
            # [D, B, b_top, 4 + held]
            gathered = jax.lax.all_gather(payload, NODE_AXIS)
            merged = jnp.transpose(gathered, (1, 0, 2, 3)).reshape(
                block_size, width, 4 + held
            )
            c_hi = merged[..., 0]
            c_lo = merged[..., 1].astype(jnp.uint32)
            c_idx = merged[..., 2]
            c_valid = merged[..., 3] > 0
            # [held, B * width]: the room of every entry's node as the
            # replay goes; entries of one node always hold one value
            c_room = jnp.transpose(merged[..., 4:], (2, 0, 1)).reshape(
                held, block_size * width
            )
            flat_idx = c_idx.reshape(-1)

            def replay(c_room, pod):
                step_i, f_hi, f_lo, idx, valid, need = pod
                mine = jax.lax.dynamic_slice_in_dim(
                    c_room, step_i * width, width, axis=1
                )
                feas = valid & room_covers(mine, need[:, None], limbs)
                hi = jnp.where(feas, f_hi, big_hi)
                m_hi = jnp.min(hi)
                on_hi = feas & (f_hi == m_hi)
                lo = jnp.where(on_hi, f_lo, big_lo)
                m_lo = jnp.min(lo)
                on_lo = on_hi & (f_lo == m_lo)
                winner = jnp.min(jnp.where(on_lo, idx, big_idx))
                choice = jnp.where(jnp.any(feas), winner, UNASSIGNED)
                c_room = room_less(
                    c_room, need[:, None], flat_idx == choice, limbs
                )
                return c_room, choice

            c_room, choices = jax.lax.scan(
                replay,
                c_room,
                (row, c_hi, c_lo, c_idx, c_valid, b_asked),
            )
            # each pod's chosen node's room after the whole block, from the
            # first entry of its own list that names it
            c_room = c_room.reshape(held, block_size, width)
            at = jnp.argmax(c_idx == choices[:, None], axis=-1)  # [B]
            left = c_room[:, row, at]  # [held, B]
            mine = (choices >= offset) & (choices < offset + n_loc)
            hit = mine[:, None] & (
                iota_loc[None, :] == (choices - offset)[:, None]
            )  # [B, n_loc]
            chosen_room = jnp.max(
                jnp.where(hit[None], left[:, :, None], jnp.int32(-1)), axis=1
            )  # [held, n_loc]
            room_loc = jnp.where(jnp.any(hit, axis=0), chosen_room, room_loc)
            return room_loc, choices

        room_left, chosen = jax.lax.scan(
            block_step, room_loc, (s_hi, s_lo, elig_b, asked_b)
        )
        return chosen.reshape(padded), room_left

    assigned, room_left = _impl(score, eligible, room, demand)
    if counted:
        room_left = room_left[0]
    return assigned[:num_pods], room_left


def sharded_auction_assign(
    mesh: Mesh,
    score: i64.I64,  # [P, N] node-sharded — larger is better
    eligible,  # bool [P, N] node-sharded
    capacity,  # int32 [N] node-sharded
):
    """Mesh form of ``auction_assign_kernel`` — EXACTLY the single-chip
    (and therefore the sequential greedy) result.

    Per fixpoint round every shard computes each pod's best local lane
    (three masked reductions), the per-shard candidates — key limbs,
    global index, found — cross the mesh in one small all_gather, and
    every chip deterministically reduces the same global winner per pod.
    Capacity pressure ("room") is evaluated shard-locally: the exclusive
    per-pod count of holds on each node only needs the replicated choice
    vector mapped into the shard's own lane range.  Collectives per
    round: ONE all_gather of 4x[P] scalars, vs gathering the full [P, N]
    score matrix."""
    num_pods = score.hi.shape[0]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            i64.I64(hi=P(None, NODE_AXIS), lo=P(None, NODE_AXIS)),
            P(None, NODE_AXIS),
            P(NODE_AXIS),
        ),
        out_specs=(P(), P(NODE_AXIS)),
        # choice is replicated by construction (every chip reduces the
        # same gathered candidates); the static check can't see that
        check_vma=False,
    )
    def _impl(s, elig, cap):
        n_loc = cap.shape[-1]
        shard = jax.lax.axis_index(NODE_AXIS)
        offset = (shard * n_loc).astype(jnp.int32)
        iota_loc = jnp.arange(n_loc, dtype=jnp.int32)
        neg_hi = jnp.int32(-(2**31))
        big_idx = jnp.int32(2**30)

        def count_below_local(choice):
            """Exclusive count of holds by lower-index pods on THIS
            shard's lanes (auction_assign_kernel.count_below, local);
            one_hot maps out-of-shard/unassigned choices to all-zero
            rows, same as the single-chip kernel."""
            onehot = jax.nn.one_hot(choice - offset, n_loc, dtype=jnp.int32)
            csum = jnp.cumsum(onehot, axis=0)
            return csum - onehot  # [P, n_loc]

        def body(state):
            choice, _changed = state
            room = count_below_local(choice) < cap[None, :]
            ok = elig & room
            hi = jnp.where(ok, s.hi, neg_hi)
            m_hi = jnp.max(hi, axis=-1)
            on_hi = ok & (s.hi == m_hi[:, None])
            lo = jnp.where(on_hi, s.lo, jnp.uint32(0))
            m_lo = jnp.max(lo, axis=-1)
            on_lo = on_hi & (s.lo == m_lo[:, None])
            idx = jnp.min(
                jnp.where(on_lo, iota_loc[None, :] + offset, big_idx),
                axis=-1,
            )
            found = jnp.any(ok, axis=-1)
            # ONE gather of the stacked per-shard candidates ([P, 4])
            payload = jnp.stack(
                [
                    jnp.where(found, m_hi, neg_hi),
                    jax.lax.bitcast_convert_type(
                        jnp.where(found, m_lo, jnp.uint32(0)), jnp.int32
                    ),
                    jnp.where(found, idx, big_idx),
                    found.astype(jnp.int32),
                ],
                axis=-1,
            )
            gathered = jax.lax.all_gather(payload, NODE_AXIS)  # [D, P, 4]
            g_hi = gathered[..., 0]
            g_lo = jax.lax.bitcast_convert_type(
                gathered[..., 1], jnp.uint32
            )
            g_idx = gathered[..., 2]
            g_found = gathered[..., 3] > 0
            w_hi = jnp.max(g_hi, axis=0)  # [P]
            on_whi = g_found & (g_hi == w_hi[None, :])
            w_lo = jnp.max(jnp.where(on_whi, g_lo, jnp.uint32(0)), axis=0)
            on_wlo = on_whi & (g_lo == w_lo[None, :])
            winner = jnp.min(jnp.where(on_wlo, g_idx, big_idx), axis=0)
            any_found = jnp.any(g_found, axis=0)
            new_choice = jnp.where(any_found, winner, UNASSIGNED)
            return new_choice, jnp.any(new_choice != choice)

        init = (jnp.full(num_pods, UNASSIGNED, dtype=jnp.int32),
                jnp.array(True))
        # the first body evaluation IS the single-chip init (all-UNASSIGNED
        # choices put zero pressure on capacity, so room == cap > 0); the
        # fixpoint sequence is then identical round for round
        choice, _ = jax.lax.while_loop(lambda st: st[1], body, init)
        taken = jnp.sum(
            jax.nn.one_hot(choice - offset, n_loc, dtype=cap.dtype), axis=0
        )  # out-of-shard/unassigned rows are all-zero
        return choice, cap - taken

    return _impl(score, eligible, capacity)


def sharded_sinkhorn_assign(
    mesh: Mesh,
    score: i64.I64,  # [P, N] node-sharded — larger is better
    eligible,  # bool [P, N] node-sharded
    capacity,  # int32 [N] node-sharded
    iterations: int = None,  # defaults to ops.sinkhorn.DEFAULT_ITERATIONS
    tau: float = 0.05,
    block_size: int = 32,
):
    """Mesh Sinkhorn-guided assignment (module doc): returns
    (assigned [P] replicated, capacity_left [N] sharded).

    Numerics note: the plan is the same entropic iteration as the
    single-chip ``sinkhorn_assign_kernel`` — per-row utilities from
    global pmin/pmax, row log-sum-exp via a pmax shift + psum of local
    exp-sums, column scaling local per shard — but cross-shard f32
    summation orders differ from the single-chip reduction, so guide
    log-probabilities can differ in the last ulps.  The exact greedy
    rounding re-masks eligibility and capacity, so the sharded result is
    always feasible and deterministic; tests assert objective parity
    with the single-chip kernel rather than bitwise equality
    (tests/test_parallel.py)."""
    from platform_aware_scheduling_tpu.ops.sinkhorn import (
        DEFAULT_ITERATIONS,
        NEG,
    )

    if iterations is None:
        # single source of truth with the single-chip kernel (ADVICE r5
        # #2): both forms anneal the same number of steps by default
        iterations = DEFAULT_ITERATIONS

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            i64.I64(hi=P(None, NODE_AXIS), lo=P(None, NODE_AXIS)),
            P(None, NODE_AXIS),
            P(NODE_AXIS),
        ),
        out_specs=(
            i64.I64(hi=P(None, NODE_AXIS), lo=P(None, NODE_AXIS))
        ),
    )
    def _guide(s, elig, cap):
        # per-pod [0,1] utilities over the GLOBAL node axis (the sharded
        # form of ops/sinkhorn._normalize_scores)
        value = s.hi.astype(jnp.float32) * jnp.float32(2.0**32) + s.lo.astype(
            jnp.float32
        )
        lo_v = jax.lax.pmin(
            jnp.min(jnp.where(elig, value, jnp.inf), axis=1), NODE_AXIS
        )[:, None]
        hi_v = jax.lax.pmax(
            jnp.max(jnp.where(elig, value, -jnp.inf), axis=1), NODE_AXIS
        )[:, None]
        span = jnp.maximum(hi_v - lo_v, jnp.float32(1.0))
        utility = jnp.where(elig, (value - lo_v) / span, 0.0)
        logits = jnp.where(elig, utility / jnp.float32(tau), NEG)
        cap_f = cap.astype(jnp.float32)
        any_local = jnp.any(elig, axis=1).astype(jnp.int32)
        has_eligible = jax.lax.psum(any_local, NODE_AXIS) > 0  # [P]

        def step(carry, _):
            log_u, log_v = carry
            # rows: global log-sum-exp = pmax shift + psum of exp-sums
            x = logits + log_v[None, :]
            m = jax.lax.pmax(jnp.max(x, axis=1), NODE_AXIS)  # [P]
            expsum = jax.lax.psum(
                jnp.sum(jnp.exp(x - m[:, None]), axis=1), NODE_AXIS
            )
            row_lse = m + jnp.log(expsum)
            log_u = jnp.where(has_eligible, -row_lse, NEG)
            # cols: each node's scaling is local to its shard
            col_lse = jax.nn.logsumexp(logits + log_u[:, None], axis=0)
            log_v = jnp.minimum(
                jnp.log(jnp.maximum(cap_f, 1e-9)) - col_lse, 0.0
            )
            log_v = jnp.where(cap_f > 0, log_v, NEG)
            return (log_u, log_v), None

        # log_v is per-node (varying over the shard axis); log_u is built
        # from psums and stays replicated
        # derive both zero carries from already-collective values so the
        # varying-axes tracker assigns them what the scan body produces:
        # log_u from the psum-built has_eligible (replicated over both
        # axes, like -row_lse), log_v from the node-sharded capacity
        # (node-varying, like col_lse).  Bare jnp.zeros carries would trip
        # the scan carry check on either side.
        init = (
            has_eligible.astype(jnp.float32) * jnp.float32(0.0),
            cap_f * jnp.float32(0.0),
        )
        (log_u, log_v), _ = jax.lax.scan(step, init, None, length=iterations)
        log_plan = logits + log_u[:, None] + log_v[None, :]
        # identical quantization to the single-chip kernel: micro-nats in
        # int32, sign-extended into the i64 limbs
        guide = jnp.where(elig, log_plan, jnp.float32(NEG))
        g_scaled = jnp.clip(guide * jnp.float32(1e6), -2.0e9, 2.0e9).astype(
            jnp.int32
        )
        g_hi = jnp.where(g_scaled < 0, jnp.int32(-1), jnp.int32(0))
        g_lo = jax.lax.bitcast_convert_type(g_scaled, jnp.uint32)
        return i64.I64(hi=g_hi, lo=g_lo)

    guide_scores = _guide(score, eligible, capacity)
    return sharded_greedy_assign(
        mesh, guide_scores, eligible, capacity, block_size=block_size
    )
