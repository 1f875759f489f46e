"""Greedy batch assignment as a single Pallas TPU kernel.

The XLA form (ops/assign.greedy_assign_kernel) is a ``lax.scan`` of P
steps, each a cheap [N] reduction — dominated by per-step overhead.  Here
the whole solve is ONE kernel: a grid over pods streams each pod's score
row HBM -> VMEM while the [N] capacity vector lives in VMEM scratch for
the entire launch (TPU grid steps run sequentially on a core, so scratch
carries the running capacity between steps).  Per step the VPU does the
masked lexicographic argmax and a full-row capacity decrement — no
host round-trips, no per-step dispatch.

Room has the two forms of ops/assign.py.  The count form keeps a ``[1, n]``
scratch and takes one unit a pod.  The demand form keeps the nodes' room as
a ``[limbs * R, n]`` scratch for the launch and reads each row's
``limbs * R`` demands as scalars from an SMEM block beside the score rows:
R compares (``room[r] >= demand[r]``) ahead of the argmax and R
subtractions on the chosen lane after it, with the 31-bit limbs' borrow
when there are two — the same integers as the scan, so the same plan.

Exactness: int64 scores arrive as the (hi: i32, lo: u32) split of
ops/i64.py with ``lo`` pre-biased by 2^31 into an order-preserving i32
(u32 and i32 disagree on ordering; XOR with the sign bit fixes it), so
every compare matches the reference's int64 semantics bit-for-bit.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from platform_aware_scheduling_tpu.ops import i64
from platform_aware_scheduling_tpu.ops.assign import LIMB_MASK, AssignResult

LANE = 128
NEG_INF_I32 = -(2**31)  # python int: jnp constants may not be captured by kernels


BLOCK_P = 8  # pods per grid step — the minimum i32 sublane tile


def _kernel(score_hi_ref, score_lo_ref, elig_ref, cap_in_ref,
            out_ref, cap_out_ref, cap_ref):
    step = pl.program_id(0)
    n = cap_ref.shape[1]

    @pl.when(step == 0)
    def _init():
        cap_ref[:] = cap_in_ref[:]

    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def row(r, carry):
        cap = cap_ref[0, :]
        ok_row = elig_ref[pl.ds(r, 1), :][0, :]
        hi = score_hi_ref[pl.ds(r, 1), :][0, :]
        lo = score_lo_ref[pl.ds(r, 1), :][0, :]
        ok = (ok_row != 0) & (cap > 0)
        m_hi = jnp.max(jnp.where(ok, hi, jnp.int32(NEG_INF_I32)))
        on_hi = ok & (hi == m_hi)
        m_lo = jnp.max(jnp.where(on_hi, lo, jnp.int32(NEG_INF_I32)))
        on_lo = on_hi & (lo == m_lo)
        chosen = jnp.min(jnp.where(on_lo, iota[0, :], jnp.int32(n)))
        found = chosen < n
        take = (iota[0, :] == chosen) & found
        cap_ref[0, :] = cap - take.astype(jnp.int32)
        out_ref[pl.ds(r, 1), :] = jnp.where(
            found, chosen, jnp.int32(-1)
        ).reshape(1, 1)
        return carry

    jax.lax.fori_loop(0, BLOCK_P, row, 0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _flush():
        cap_out_ref[:] = cap_ref[:]


def _demand_kernel(limbs, dem_ref, score_hi_ref, score_lo_ref, elig_ref,
                   room_in_ref, out_ref, room_out_ref, room_ref):
    step = pl.program_id(0)
    k, n = room_ref.shape
    r_count = k // limbs

    @pl.when(step == 0)
    def _init():
        room_ref[:] = room_in_ref[:]

    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def row(r, carry):
        hi = score_hi_ref[pl.ds(r, 1), :][0, :]
        lo = score_lo_ref[pl.ds(r, 1), :][0, :]
        ok = elig_ref[pl.ds(r, 1), :][0, :] != 0
        for res in range(r_count):
            have, asked = room_ref[res, :], dem_ref[r, res]
            if limbs == 1:
                ok &= have >= asked
            else:
                have_hi = room_ref[r_count + res, :]
                asked_hi = dem_ref[r, r_count + res]
                ok &= (have_hi > asked_hi) | (
                    (have_hi == asked_hi) & (have >= asked))
        m_hi = jnp.max(jnp.where(ok, hi, jnp.int32(NEG_INF_I32)))
        on_hi = ok & (hi == m_hi)
        m_lo = jnp.max(jnp.where(on_hi, lo, jnp.int32(NEG_INF_I32)))
        on_lo = on_hi & (lo == m_lo)
        chosen = jnp.min(jnp.where(on_lo, iota[0, :], jnp.int32(n)))
        found = chosen < n
        take = (iota[0, :] == chosen) & found
        for res in range(r_count):
            left = room_ref[res, :] - jnp.where(take, dem_ref[r, res], 0)
            if limbs == 2:
                borrow = (left < 0).astype(jnp.int32)
                left = left & jnp.int32(LIMB_MASK)
                room_ref[r_count + res, :] = (
                    room_ref[r_count + res, :]
                    - jnp.where(take, dem_ref[r, r_count + res], 0) - borrow)
            room_ref[res, :] = left
        out_ref[pl.ds(r, 1), :] = jnp.where(
            found, chosen, jnp.int32(-1)
        ).reshape(1, 1)
        return carry

    jax.lax.fori_loop(0, BLOCK_P, row, 0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _flush():
        room_out_ref[:] = room_ref[:]


def _build_call(p: int, n: int, interpret: bool, rows: int = 0, limbs: int = 1):
    """The count form's call (``rows`` 0), or the demand form's over room
    of ``rows`` = limbs * R rows."""
    demand = [] if not rows else [
        pl.BlockSpec((BLOCK_P, rows), lambda i: (i, 0), memory_space=pltpu.SMEM)]
    held = rows or 1
    return pl.pallas_call(
        partial(_demand_kernel, limbs) if rows else _kernel,
        grid=(p // BLOCK_P,),
        in_specs=demand + [
            pl.BlockSpec((BLOCK_P, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK_P, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK_P, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((held, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((held, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, 1), jnp.int32),
            jax.ShapeDtypeStruct((held, n), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((held, n), jnp.int32)],
        interpret=interpret,
    )


@partial(jax.jit, static_argnames=("interpret", "limbs"))
def greedy_assign_pallas(
    score: i64.I64,  # [P, N] — larger is better
    eligible: jax.Array,  # bool [P, N]
    capacity: jax.Array,  # int32 [N] | [limbs*R, N] with ``demand``
    interpret: bool = False,
    demand: jax.Array = None,  # int32 [P, limbs*R]
    limbs: int = 1,
) -> AssignResult:
    """Drop-in replacement for greedy_assign_kernel (identical results),
    in either form of room."""
    p, n = eligible.shape
    n_pad = ((n + LANE - 1) // LANE) * LANE
    p_pad = ((p + BLOCK_P - 1) // BLOCK_P) * BLOCK_P
    pad_n = n_pad - n
    pad_p = p_pad - p
    hi = jnp.pad(score.hi, ((0, pad_p), (0, pad_n)))
    # bias u32 -> order-preserving i32 (bit reinterpret, not value convert)
    lo_biased = jax.lax.bitcast_convert_type(
        score.lo ^ jnp.uint32(0x80000000), jnp.int32
    )
    lo = jnp.pad(lo_biased, ((0, pad_p), (0, pad_n)))
    elig = jnp.pad(eligible, ((0, pad_p), (0, pad_n))).astype(jnp.int32)
    if demand is not None:
        # padding lanes are never eligible, padding rows ask for nothing
        room = jnp.pad(capacity, ((0, 0), (0, pad_n))).astype(jnp.int32)
        asked = jnp.pad(demand, ((0, pad_p), (0, 0))).astype(jnp.int32)
        out, room_left = _build_call(
            p_pad, n_pad, interpret, rows=room.shape[0], limbs=limbs
        )(asked, hi, lo, elig, room)
        return AssignResult(node_for_pod=out[:p, 0], capacity_left=room_left[:, :n])
    cap = jnp.pad(capacity, (0, pad_n)).reshape(1, n_pad).astype(jnp.int32)
    out, cap_left = _build_call(p_pad, n_pad, interpret)(hi, lo, elig, cap)
    return AssignResult(
        node_for_pod=out[:p, 0], capacity_left=cap_left[0, :n]
    )
