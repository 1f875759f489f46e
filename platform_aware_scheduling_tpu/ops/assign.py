"""Batched pods x nodes assignment solve.

The stock kube-scheduler schedules one pod at a time, paying one extender
round-trip per pod (SURVEY §3.2: the quadratic-in-practice loop).  This
module solves the whole pending set in one XLA program: greedy assignment
in pod-priority order with per-node capacity constraints, with exact int64
score keys.  The per-pod HTTP verbs can then be answered from the
precomputed solution (SURVEY §7 step 4).

Greedy-in-order matches what the sequential kube-scheduler+extender system
would produce: pod i gets its best feasible node given pods 0..i-1's
placements — so the batch solve is semantics-preserving, just ~P times
fewer round trips.

Room comes in two forms, and both are exact.  The count form: ``capacity``
int32 [N], how many pods a node still takes, one unit booked a pod — what a
pending set of ALIKE pods needs, and what ``auction_assign_kernel``,
ops/sinkhorn.py, rebalance/ and gang/ speak (a count is a demand of 1 on
one resource).  The demand form (``demand`` given): ``capacity`` is the
nodes' room as integer rows ``[limbs * R, N]`` over R resources and
``demand`` ``[P, limbs * R]`` each pod's own request vector; pod i is
feasible on node j iff ``room[r, j] >= demand[i, r]`` for every r
(kube-scheduler's NodeResourcesFit), and the chosen node's column loses
``demand[i, :]``.  Quantities are non-negative int32; one that does not fit
31 bits rides as two limbs of 31 (``limbs=2``: rows ``0..R-1`` the low
limbs, rows ``R..2R-1`` the high ones, ops/i64.split31_np), compared and
subtracted with a borrow, so no demand is ever rounded up and no room down.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from platform_aware_scheduling_tpu.ops import i64

# a host scalar: a jnp value here would initialize the JAX backend — and
# take the chip — in every process that merely imports this module
UNASSIGNED = np.int32(-1)


class AssignResult(NamedTuple):
    node_for_pod: jax.Array  # int32 [P] — node index or -1
    capacity_left: jax.Array  # int32 [N]


def lex_argmin(key: i64.I64, valid: jax.Array) -> tuple:
    """Index of the smallest key among valid lanes, ties to the lowest
    index; returns (idx, found).  Three cheap reductions instead of a sort."""
    big_hi = jnp.int32(2**31 - 1)
    big_lo = jnp.uint32(2**32 - 1)
    hi = jnp.where(valid, key.hi, big_hi)
    m_hi = jnp.min(hi)
    on_hi = valid & (key.hi == m_hi)
    lo = jnp.where(on_hi, key.lo, big_lo)
    m_lo = jnp.min(lo)
    on_lo = on_hi & (key.lo == m_lo)
    n = key.hi.shape[-1]
    idx = jnp.min(jnp.where(on_lo, jnp.arange(n, dtype=jnp.int32), jnp.int32(n)))
    found = jnp.any(valid)
    return jnp.where(found, idx, UNASSIGNED), found


LIMB_BITS = 31  # a two-limb quantity is hi * 2**31 + lo, both int32 >= 0
LIMB_MASK = (1 << LIMB_BITS) - 1


def room_covers(room, demand, limbs: int):
    """bool [N]: every resource's room covers the demand.  ``room`` is
    ``[limbs * R, N]``, ``demand`` ``[limbs * R, 1]``.  (The Pallas kernel
    does the same compares row by row on scalars it reads from SMEM.)"""
    r = room.shape[0] // limbs
    if limbs == 1:
        return jnp.all(room >= demand, axis=0)
    lo, hi, d_lo, d_hi = room[:r], room[r:], demand[:r], demand[r:]
    return jnp.all((hi > d_hi) | ((hi == d_hi) & (lo >= d_lo)), axis=0)


def room_less(room, demand, take, limbs: int):
    """``room`` with ``demand`` taken off the lanes of ``take`` (bool [N]):
    a plain subtraction, with a borrow from the high limb when there are
    two.  Only ever applied where :func:`room_covers` held."""
    r = room.shape[0] // limbs
    taken = jnp.where(take, demand, 0)  # [limbs * R, N]
    if limbs == 1:
        return room - taken
    lo = room[:r] - taken[:r]
    borrow = (lo < 0).astype(room.dtype)
    return jnp.concatenate(
        [lo & jnp.int32(LIMB_MASK), room[r:] - taken[r:] - borrow], axis=0
    )


@partial(jax.jit, static_argnames=("limbs",))
def greedy_assign_kernel(
    score: i64.I64,  # [P, N] — larger is better
    eligible: jax.Array,  # bool [P, N] — pod may land on node (post-filter)
    capacity: jax.Array,  # int32 [N] pods a node still takes | [limbs*R, N] room
    demand: jax.Array = None,  # int32 [P, limbs*R] — each pod's own requests
    limbs: int = 1,
) -> AssignResult:
    """Assign every pending pod its best feasible node, in order; with
    ``demand`` each pod books its own vector (module docstring) and
    ``capacity_left`` is the room left, ``[limbs * R, N]``."""
    n = eligible.shape[-1]
    lanes = jnp.arange(n, dtype=jnp.int32)

    def step(cap, pod):
        s_hi, s_lo, elig, asked = pod
        if asked is None:
            ok = elig & (cap > 0)
        else:
            ok = elig & room_covers(cap, asked[:, None], limbs)
        # maximize score == minimize flipped score
        flipped = i64.flip(i64.I64(hi=s_hi, lo=s_lo))
        best, found = lex_argmin(flipped, ok)
        if asked is None:
            take = jnp.where(
                found,
                jax.nn.one_hot(best, n, dtype=cap.dtype),
                jnp.zeros_like(cap),
            )
            return cap - take, best
        return room_less(cap, asked[:, None], lanes == best, limbs), best

    capacity_left, node_for_pod = jax.lax.scan(
        step, capacity, (score.hi, score.lo, eligible, demand)
    )
    return AssignResult(node_for_pod=node_for_pod, capacity_left=capacity_left)


def _row_lex_argmax(score: i64.I64, ok: jax.Array) -> jax.Array:
    """Per-row argmax of exact-i64 scores over masked lanes, ties to the
    lowest index; -1 where no lane is ok.  [P, N] -> [P]."""
    neg_hi = jnp.int32(-(2**31))
    hi = jnp.where(ok, score.hi, neg_hi)
    m_hi = jnp.max(hi, axis=-1, keepdims=True)
    on_hi = ok & (score.hi == m_hi)
    lo = jnp.where(on_hi, score.lo, jnp.uint32(0))
    m_lo = jnp.max(lo, axis=-1, keepdims=True)
    on_lo = on_hi & (score.lo == m_lo)
    n = score.hi.shape[-1]
    idx = jnp.min(
        jnp.where(on_lo, jnp.arange(n, dtype=jnp.int32), jnp.int32(n)), axis=-1
    )
    found = jnp.any(ok, axis=-1)
    return jnp.where(found, idx, UNASSIGNED)


@jax.jit
def auction_assign_kernel(
    score: i64.I64,  # [P, N] — larger is better
    eligible: jax.Array,  # bool [P, N]
    capacity: jax.Array,  # int32 [N]
) -> AssignResult:
    """Fixpoint form of :func:`greedy_assign_kernel` — EXACTLY the same
    result, massively fewer sequential steps.

    Iterate: every pod simultaneously picks its best eligible node among
    those where the number of holds by HIGHER-priority (lower-index) pods
    is below capacity (an exclusive cumsum of the one-hot choice matrix
    down the pod axis).  At the fixpoint each pod holds its best node
    given pods 0..p-1's holds — the definition of greedy-in-order.  Pod p
    is provably stable after p rounds (pod 0 after one), and in practice
    rounds ~ contention depth, so the while_loop replaces a P-step scan
    with a handful of [P, N] vector passes."""
    p, n = eligible.shape

    def count_below(choice):
        onehot = jax.nn.one_hot(choice, n, dtype=jnp.int32)  # [-1] -> zeros
        csum = jnp.cumsum(onehot, axis=0)
        return csum - onehot  # exclusive: holds by strictly-lower indices

    def body(state):
        choice, _changed = state
        room = count_below(choice) < capacity[None, :]
        new_choice = _row_lex_argmax(score, eligible & room)
        return new_choice, jnp.any(new_choice != choice)

    def cond(state):
        return state[1]

    init = _row_lex_argmax(score, eligible & (capacity[None, :] > 0))
    choice, _ = jax.lax.while_loop(cond, body, (init, jnp.array(True)))
    taken = jnp.sum(
        jax.nn.one_hot(choice, n, dtype=capacity.dtype), axis=0
    )
    return AssignResult(node_for_pod=choice, capacity_left=capacity - taken)
