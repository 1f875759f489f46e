"""GAS first-fit card bin-packing as a batched XLA program.

Reference semantics (gpu-aware-scheduling/pkg/gpuscheduler/scheduler.go:
200-257, 341-383): per container, the per-GPU share of the request is
placed on the first card (sorted name order) whose ``used + need <= cap``
for every requested resource; a card can be picked repeatedly for one
container when it has room for several shares; capacity missing or <= 0
for any requested resource fails; int64 overflow of used+need fails.

The reference runs this per node, sequentially, under a global lock
(scheduler.go:463-473).  Here one jitted program evaluates EVERY candidate
node at once: ``vmap`` over the node axis of a ``[nodes, cards, resources]``
usage tensor, ``lax.scan`` over the (small, static) container and GPU-count
axes.  Values are exact int64 in split (hi, lo) form (ops/i64.py).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from platform_aware_scheduling_tpu.ops import i64

# a host scalar: a jnp value here would initialize the JAX backend — and
# take the chip — in every process that merely imports this module
NO_CARD = np.int32(-1)


class BinpackRequest(NamedTuple):
    """Per-container per-GPU shares, padded to T containers x R resources."""

    need: i64.I64  # [T, R] per-GPU request share (host-divided, exact)
    need_active: jax.Array  # bool [T, R] — resource present in the request
    num_gpus: jax.Array  # int32 [T] — the container's i915 count
    container_active: jax.Array  # bool [T] — real (non-padding) container


class BinpackNodeState(NamedTuple):
    """Per-node card state, padded to N nodes x C cards x R resources."""

    used: i64.I64  # [N, C, R] booked usage
    capacity: i64.I64  # [N, R] per-GPU capacity (homogeneous cards)
    cap_present: jax.Array  # bool [N, R] — resource exists in node capacity
    card_valid: jax.Array  # bool [N, C] — card still in the node's GPU label
    card_real: jax.Array  # bool [N, C] — non-padding lane
    # first-fit priority of each card lane (lower = earlier).  The
    # reference iterates cards in sorted-name order (scheduler.go:216-224);
    # a persistent mirror interns card lanes append-only, so name order is
    # carried explicitly instead of assuming lane order.
    card_order: jax.Array  # int32 [N, C]


class BinpackResult(NamedTuple):
    fits: jax.Array  # bool [N]
    cards: jax.Array  # int32 [N, T, K] chosen card index per GPU, -1 = none
    # the usage the fit ran against, update block applied: only for a
    # packed request (its caller keeps it on the device as the next base)
    used: Optional[i64.I64] = None  # [N, C, R]


# -- the packed request ------------------------------------------------------
# One int32 host buffer carries a Filter's whole operand: the request's
# five tensors, then an update block of UPDATE_SLOTS usage rows (row index
# + that row's [C, R] hi/lo words) that the kernel scatters into ``used``
# before fitting.  A host<->device operation costs the same whatever its
# size (PERF.md), so what the layout saves is their number; packers and
# unpacker stand together here so that no caller knows it.  uint32 words
# travel as their int32 bit patterns, bools as 0/1.

UPDATE_SLOTS = 8


def pack_rows(used: np.ndarray, rows: Sequence[int] = ()) -> np.ndarray:
    """The update block: rows ``rows`` (at most UPDATE_SLOTS) of the int64
    host usage array ``used`` [N, C, R].  Unused slots carry the
    out-of-range row N, which the scatter drops."""
    slots = np.full(UPDATE_SLOTS, used.shape[0], dtype=np.int32)
    slots[: len(rows)] = rows
    block = np.zeros((UPDATE_SLOTS,) + used.shape[1:], dtype=np.int64)
    block[: len(rows)] = used[list(rows)]
    hi, lo = i64.split_int64_np(block)
    return np.concatenate([slots, hi.ravel(), lo.view(np.int32).ravel()])


def pack_request(
    shares,
    resources_index: Dict[str, int],
    t_pad: int,
    r_pad: int,
    update: np.ndarray,
) -> np.ndarray:
    """The packed operand of :func:`binpack_kernel`: ``shares`` (per
    container: per-GPU resource map, GPU count) padded to ``t_pad``
    containers x ``r_pad`` resources, then the block of :func:`pack_rows`."""
    need = np.zeros((t_pad, r_pad), dtype=np.int64)
    need_active = np.zeros((t_pad, r_pad), dtype=np.int32)
    num_gpus = np.zeros(t_pad, dtype=np.int32)
    container_active = np.zeros(t_pad, dtype=np.int32)
    for t, (per_gpu, k) in enumerate(shares):
        container_active[t] = 1
        num_gpus[t] = k
        for name, value in per_gpu.items():
            idx = resources_index[name]
            need[t, idx] = value
            need_active[t, idx] = 1
    hi, lo = i64.split_int64_np(need)
    return np.concatenate([
        hi.ravel(), lo.view(np.int32).ravel(), need_active.ravel(),
        num_gpus, container_active, update,
    ])


def _unpack_request(packed: jax.Array, used: i64.I64) -> tuple:
    """(BinpackRequest, ``used`` with the update block's rows set) from the
    buffer of :func:`pack_request`, by static slices."""
    _, c_pad, r_pad = used.hi.shape
    block = UPDATE_SLOTS * (1 + 2 * c_pad * r_pad)
    t_pad = (packed.shape[0] - block) // (3 * r_pad + 2)
    request_shapes = [(t_pad, r_pad)] * 3 + [(t_pad,)] * 2
    block_shapes = [(UPDATE_SLOTS,)] + [(UPDATE_SLOTS, c_pad, r_pad)] * 2
    parts, offset = [], 0
    for shape in request_shapes + block_shapes:
        size = int(np.prod(shape))
        parts.append(packed[offset:offset + size].reshape(shape))
        offset += size
    need_hi, need_lo, need_active, num_gpus, container_active = parts[:5]
    rows, used_hi, used_lo = parts[5:]

    def as_u32(words):
        return jax.lax.bitcast_convert_type(words, jnp.uint32)

    request = BinpackRequest(
        need=i64.I64(hi=need_hi, lo=as_u32(need_lo)),
        need_active=need_active != 0,
        num_gpus=num_gpus,
        container_active=container_active != 0,
    )
    return request, i64.I64(
        hi=used.hi.at[rows].set(used_hi, mode="drop"),
        lo=used.lo.at[rows].set(as_u32(used_lo), mode="drop"),
    )


def _card_fits(
    used: i64.I64,  # [C, R]
    need: i64.I64,  # [R]
    need_active: jax.Array,  # [R]
    capacity: i64.I64,  # [R]
    cap_present: jax.Array,  # [R]
    card_ok: jax.Array,  # [C]
) -> jax.Array:
    """checkResourceCapacity (scheduler.go:341-383) for every card at once.
    Returns bool [C]."""
    zero = i64.I64(
        hi=jnp.zeros_like(capacity.hi), lo=jnp.zeros_like(capacity.lo)
    )
    need_b = i64.I64(hi=need.hi[None, :], lo=need.lo[None, :])  # [1, R]
    cap_b = i64.I64(hi=capacity.hi[None, :], lo=capacity.lo[None, :])
    total = i64.add(used, need_b)  # [C, R]
    need_neg = need.hi < 0  # [R]
    cap_ok = cap_present & (i64.cmp(capacity, zero) == 1)  # [R]
    used_neg = used.hi < 0  # [C, R]
    # need >= 0 and used >= 0 here, so overflow <=> sum sign flipped negative
    overflow = (~used_neg) & (total.hi < 0)
    enough = i64.cmp(total, cap_b) <= 0
    per_resource = (
        (~need_neg[None, :])
        & cap_ok[None, :]
        & (~used_neg)
        & (~overflow)
        & enough
    )
    resource_ok = jnp.all(per_resource | ~need_active[None, :], axis=-1)  # [C]
    return card_ok & resource_ok


def _fit_one_node(
    used: i64.I64,  # [C, R]
    capacity: i64.I64,  # [R]
    cap_present: jax.Array,  # [R]
    card_ok: jax.Array,  # [C]
    card_order: jax.Array,  # int32 [C]
    request: BinpackRequest,
    max_gpus: int,
) -> tuple:
    """runSchedulingLogic's card selection for one node
    (scheduler.go:313-338 + 200-257): scan containers, scan GPU picks."""
    num_cards = card_ok.shape[0]
    card_iota = jnp.arange(num_cards, dtype=jnp.int32)
    big_order = jnp.int32(2**30)

    def per_container(carry, request_t):
        used, ok = carry
        need, need_active, num_gpus, active = request_t
        # only resources PRESENT in the request are booked — the reference
        # walks the request map (addRM over its keys, resource_map.go:38-55);
        # an inactive lane must neither gate (handled in _card_fits) nor
        # consume capacity here
        booked_need = i64.I64(
            hi=jnp.where(need_active, need.hi, jnp.int32(0)),
            lo=jnp.where(need_active, need.lo, jnp.uint32(0)),
        )

        def per_gpu(carry2, step):
            used2, ok2 = carry2
            fits = _card_fits(used2, need, need_active, capacity, cap_present, card_ok)
            # first-fit = smallest card_order among fitting lanes
            best_order = jnp.min(jnp.where(fits, card_order, big_order))
            on_best = fits & (card_order == best_order)
            chosen = jnp.min(jnp.where(on_best, card_iota, jnp.int32(num_cards)))
            fitted = chosen < num_cards
            wanted = active & (step < num_gpus)
            book = wanted & fitted
            sel = (card_iota == chosen) & book  # [C]
            total = i64.add(
                used2,
                i64.I64(hi=booked_need.hi[None, :], lo=booked_need.lo[None, :]),
            )
            used2 = i64.select(sel[:, None], total, used2)
            ok2 = ok2 & (fitted | ~wanted)
            picked = jnp.where(book, chosen, NO_CARD)
            return (used2, ok2), picked

        (used, ok_inner), picks = jax.lax.scan(
            per_gpu, (used, ok), jnp.arange(max_gpus, dtype=jnp.int32)
        )
        return (used, ok_inner), picks

    (used_out, ok), all_picks = jax.lax.scan(
        per_container,
        (used, jnp.array(True)),
        (request.need, request.need_active, request.num_gpus,
         request.container_active),
    )
    # used_out carries every booked share; meaningful when ok (the
    # reference discards the scratch copy on failure, scheduler.go:247) —
    # the fused solve gates on fits before applying it
    return ok, all_picks, used_out  # [T, K], [C, R]


@partial(jax.jit, static_argnames=("max_gpus",))
def binpack_kernel(
    state: BinpackNodeState,
    request: Union[BinpackRequest, jax.Array],
    max_gpus: int,
) -> BinpackResult:
    """Fit ``request`` against every node at once (the batched Filter).
    Given the packed buffer of :func:`pack_request` instead of a
    BinpackRequest, its update block is applied to ``state.used`` first
    and the result carries the updated ``used``."""
    used = None
    if not isinstance(request, BinpackRequest):
        request, used = _unpack_request(request, state.used)
        state = state._replace(used=used)
    fits, cards, _ = jax.vmap(
        lambda used, cap, cap_p, ok, order: _fit_one_node(
            used, cap, cap_p, ok, order, request, max_gpus
        )
    )(
        state.used,
        state.capacity,
        state.cap_present,
        state.card_valid & state.card_real,
        state.card_order,
    )
    return BinpackResult(fits=fits, cards=cards, used=used)
