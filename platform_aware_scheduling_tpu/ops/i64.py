"""Exact int64 arithmetic/ordering on TPU via (hi: int32, lo: uint32) pairs.

Why: rule evaluation in the reference compares ``resource.Quantity`` values
against int64 targets with exact integer semantics
(reference pkg/strategies/core/operator.go:13-26 via ``Quantity.CmpInt64``).
Metric values in milli-units span the full int64 range (byte-valued memory
metrics overflow int32), but TPUs have no fast native s64 — XLA emulates it.
Instead we keep the split representation explicit: a 64-bit value ``v`` is
``(hi, lo)`` with ``hi = v >> 32`` (arithmetic, signed) and
``lo = v & 0xffffffff`` (unsigned).  Ordering of ``v`` equals lexicographic
ordering of ``(hi signed, lo unsigned)``, which maps directly onto
``lax.sort`` multi-key sorting and pairwise compares — all in fast 32-bit
TPU ops.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

INT64_MIN = -(2**63)
INT64_MAX = 2**63 - 1


class I64(NamedTuple):
    """A tensor of int64 values in split (hi, lo) form.  A pytree, so it
    passes transparently through jit/vmap/shard_map."""

    hi: jax.Array  # int32
    lo: jax.Array  # uint32

    @property
    def shape(self):
        return self.hi.shape


def from_int64(values: Union[np.ndarray, Sequence[int], int]) -> I64:
    """Host-side: numpy int64 array -> split representation."""
    arr = np.asarray(values, dtype=np.int64)
    hi = (arr >> np.int64(32)).astype(np.int32)
    lo = (arr & np.int64(0xFFFFFFFF)).astype(np.uint32)
    return I64(hi=jnp.asarray(hi), lo=jnp.asarray(lo))


def split_int64_np(values: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Numpy-only split (for host mirrors that stage into device buffers)."""
    arr = np.asarray(values, dtype=np.int64)
    return (arr >> np.int64(32)).astype(np.int32), (
        arr & np.int64(0xFFFFFFFF)
    ).astype(np.uint32)


def split31_np(values: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(lo, hi) limbs of 31 bits, both int32 >= 0, of non-negative values
    under 2**62: ``v == hi * 2**31 + lo``.  The form room and demand ride
    in where a quantity overflows int32 (ops/assign.py): signed 32-bit
    compares order both limbs, and a subtraction borrows with one mask —
    no unsigned type, which a Pallas TPU kernel would have to bias."""
    arr = np.asarray(values, dtype=np.int64)
    return (arr & np.int64(0x7FFFFFFF)).astype(np.int32), (
        arr >> np.int64(31)
    ).astype(np.int32)


def to_int64_np(value: I64) -> np.ndarray:
    """Device -> host: reassemble numpy int64 (for wire encoding/tests)."""
    hi = np.asarray(value.hi).astype(np.int64)
    lo = np.asarray(value.lo).astype(np.int64)
    return (hi << np.int64(32)) | lo


def full_like(template: I64, value: int) -> I64:
    hi = np.int32(np.int64(value) >> np.int64(32))
    lo = np.uint32(np.int64(value) & np.int64(0xFFFFFFFF))
    return I64(
        hi=jnp.full_like(template.hi, hi), lo=jnp.full_like(template.lo, lo)
    )


def cmp(a: I64, b: I64) -> jax.Array:
    """Elementwise sign(a - b) in {-1, 0, 1} as int32 — the device analog of
    ``Quantity.CmpInt64`` (reference operator.go:13-26)."""
    hi_lt = a.hi < b.hi
    hi_gt = a.hi > b.hi
    lo_lt = a.lo < b.lo  # unsigned compare
    lo_gt = a.lo > b.lo
    lt = hi_lt | (~hi_gt & lo_lt)
    gt = hi_gt | (~hi_lt & lo_gt)
    return jnp.where(lt, jnp.int32(-1), jnp.where(gt, jnp.int32(1), jnp.int32(0)))


def lt(a: I64, b: I64) -> jax.Array:
    return cmp(a, b) == -1


def eq(a: I64, b: I64) -> jax.Array:
    return (a.hi == b.hi) & (a.lo == b.lo)


def flip(a: I64) -> I64:
    """Bitwise complement: an order-*reversing* bijection on int64, so an
    ascending sort of ``flip(x)`` is a descending sort of ``x`` (used for
    the GreaterThan branch of OrderedList, reference operator.go:33-35)."""
    return I64(hi=~a.hi, lo=~a.lo)


def select(pred: jax.Array, on_true: I64, on_false: I64) -> I64:
    return I64(
        hi=jnp.where(pred, on_true.hi, on_false.hi),
        lo=jnp.where(pred, on_true.lo, on_false.lo),
    )


def add(a: I64, b: I64) -> I64:
    """Wrapping 64-bit add built from 32-bit limbs (carry via unsigned
    overflow detection)."""
    lo_sum = a.lo + b.lo
    carry = (lo_sum < a.lo).astype(jnp.int32)
    hi_sum = a.hi + b.hi + carry
    return I64(hi=hi_sum, lo=lo_sum)


def neg(a: I64) -> I64:
    """Two's-complement negate: ~a + 1."""
    lo = (~a.lo) + jnp.uint32(1)
    carry = (lo == 0).astype(jnp.int32)
    return I64(hi=(~a.hi) + carry, lo=lo)


def sub(a: I64, b: I64) -> I64:
    return add(a, neg(b))


def sort_by_key(
    key: I64, *values: jax.Array, tiebreak: jax.Array = None
) -> Tuple[jax.Array, ...]:
    """Sort ``values`` ascending by exact int64 ``key`` using lexicographic
    multi-key ``lax.sort`` over the 32-bit limbs.  ``tiebreak`` (int32) is an
    optional third key making the order total/deterministic (the reference's
    Go ``sort.Slice`` is unstable; we fix ties by node index)."""
    operands = [key.hi, key.lo]
    num_keys = 2
    if tiebreak is not None:
        operands.append(tiebreak)
        num_keys = 3
    operands.extend(values)
    out = jax.lax.sort(tuple(operands), num_keys=num_keys, dimension=-1)
    return out[num_keys:] if values else out
