"""Greedy batch assignment as a single Pallas TPU kernel.

The XLA form (ops/assign.greedy_assign_kernel) is a ``lax.scan`` of P
steps, each a cheap [N] reduction — dominated by per-step overhead.  Here
the whole solve is ONE kernel: a grid over pods streams each pod's score
row HBM -> VMEM while the [N] capacity vector lives in VMEM scratch for
the entire launch (TPU grid steps run sequentially on a core, so scratch
carries the running capacity between steps).  Per step the VPU does the
masked lexicographic argmax and a full-row capacity decrement — no
host round-trips, no per-step dispatch.

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
from platform_aware_scheduling_tpu.ops.assign import AssignResult

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


def _build_call(p: int, n: int, interpret: bool):
    return pl.pallas_call(
        _kernel,
        grid=(p // BLOCK_P,),
        in_specs=[
            pl.BlockSpec((BLOCK_P, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK_P, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK_P, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, n), jnp.int32)],
        interpret=interpret,
    )


@partial(jax.jit, static_argnames=("interpret",))
def greedy_assign_pallas(
    score: i64.I64,  # [P, N] — larger is better
    eligible: jax.Array,  # bool [P, N]
    capacity: jax.Array,  # int32 [N]
    interpret: bool = False,
) -> AssignResult:
    """Drop-in replacement for greedy_assign_kernel (identical results)."""
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
    cap = jnp.pad(capacity, (0, pad_n)).reshape(1, n_pad).astype(jnp.int32)
    out, cap_left = _build_call(p_pad, n_pad, interpret)(hi, lo, elig, cap)
    return AssignResult(
        node_for_pod=out[:p, 0], capacity_left=cap_left[0, :n]
    )
