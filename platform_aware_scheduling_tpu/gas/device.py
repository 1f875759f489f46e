"""Device side of GAS: persistent usage mirror + request staging for the
batched binpack kernel (ops/binpack.py).

:class:`GASUsageMirror` is the GAS analog of the TAS TensorStateMirror
(SURVEY §7 step 5): it subscribes to the cluster cache's booking hook and
the node informer events and keeps ``[nodes, cards, resources]`` usage /
capacity tensors current incrementally — so a Filter request only stages
its (tiny) per-container request tensors and gathers candidate rows on
device, instead of re-walking every node's resource maps in Python.

Lanes are interned append-only; the first-fit name order the reference
iterates in (scheduler.go:216-224) is carried as an explicit
``card_order`` rank tensor.  All values are exact int64 (split hi/lo).

:class:`DeviceBinpacker` answers one pod's fit across many nodes in one
XLA pass, through the mirror when one is attached (the hot path) or by
per-request staging otherwise (also the correctness control in tests).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from platform_aware_scheduling_tpu.gas import scheduler as gas_logic
from platform_aware_scheduling_tpu.gas.utils import container_requests
from platform_aware_scheduling_tpu.kube.objects import Node, Pod
from platform_aware_scheduling_tpu.ops import i64
from platform_aware_scheduling_tpu.ops.binpack import (
    BinpackNodeState,
    BinpackRequest,
    binpack_kernel,
)
from platform_aware_scheduling_tpu.utils import decisions, trace

import jax.numpy as jnp

MIN_NODES = 16
MIN_CARDS = 4
MIN_RESOURCES = 4
MIN_CONTAINERS = 2
MIN_GPUS = 2


def _bucket(n: int, minimum: int) -> int:
    size = minimum
    while size < n:
        size *= 2
    return size


class GASUsageMirror:
    """Incrementally-synced device tensors of per-card usage + capacity."""

    def __init__(self, cache):
        self.cache = cache
        self._lock = threading.RLock()
        self._node_index: Dict[str, int] = {}
        self._res_index: Dict[str, int] = {}
        self._card_index: List[Dict[str, int]] = []  # per node row
        n, c, r = MIN_NODES, MIN_CARDS, MIN_RESOURCES
        self._used = np.zeros((n, c, r), dtype=np.int64)
        self._cap = np.zeros((n, r), dtype=np.int64)
        self._cap_present = np.zeros((n, r), dtype=bool)
        self._card_valid = np.zeros((n, c), dtype=bool)
        self._card_real = np.zeros((n, c), dtype=bool)
        self._card_order = np.full((n, c), 2**30, dtype=np.int32)
        self._has_gpus = np.zeros(n, dtype=bool)
        self._known = np.zeros(n, dtype=bool)
        self._version = 0
        self._device: Optional[Tuple[int, BinpackNodeState]] = None
        cache.on_node_change(self.on_node_change)  # replays cached nodes
        # replays booked nodes + registers atomically under the cache lock,
        # preserving cache→mirror lock order (no ABBA window against the
        # cache worker firing the hook mid-construction)
        cache.on_booking_change(self.on_booking_change)

    # -- interning -------------------------------------------------------------

    def _grow(self, n=None, c=None, r=None) -> None:
        cur_n, cur_c, cur_r = self._used.shape
        new_n = _bucket(n or cur_n, cur_n)
        new_c = _bucket(c or cur_c, cur_c)
        new_r = _bucket(r or cur_r, cur_r)
        if (new_n, new_c, new_r) == (cur_n, cur_c, cur_r):
            return
        pad3 = ((0, new_n - cur_n), (0, new_c - cur_c), (0, new_r - cur_r))
        self._used = np.pad(self._used, pad3)
        self._cap = np.pad(self._cap, (pad3[0], pad3[2]))
        self._cap_present = np.pad(self._cap_present, (pad3[0], pad3[2]))
        self._card_valid = np.pad(self._card_valid, (pad3[0], pad3[1]))
        self._card_real = np.pad(self._card_real, (pad3[0], pad3[1]))
        self._card_order = np.pad(
            self._card_order, (pad3[0], pad3[1]), constant_values=2**30
        )
        self._has_gpus = np.pad(self._has_gpus, pad3[0])
        self._known = np.pad(self._known, pad3[0])

    def _intern_node(self, name: str) -> int:
        row = self._node_index.get(name)
        if row is None:
            row = len(self._node_index)
            self._grow(n=row + 1)
            self._node_index[name] = row
            self._card_index.append({})
        return row

    def _intern_resource(self, name: str) -> int:
        idx = self._res_index.get(name)
        if idx is None:
            idx = len(self._res_index)
            self._grow(r=idx + 1)
            self._res_index[name] = idx
            # growing the resource axis invalidates the memoized snapshot:
            # a request interning a never-seen resource between cluster
            # events would otherwise get a state whose r_pad is too small
            # for the index this just handed out (IndexError in
            # stage_request until the next event bumped the version)
            self._version += 1
        return idx

    def _intern_card(self, row: int, card: str) -> int:
        cards = self._card_index[row]
        lane = cards.get(card)
        if lane is None:
            lane = len(cards)
            self._grow(c=lane + 1)
            cards[card] = lane
            self._card_real[row, lane] = True
            # first-fit order = rank among sorted names of this node's lanes
            for rank, name in enumerate(sorted(cards)):
                self._card_order[row, cards[name]] = rank
        return lane

    # -- event hooks -----------------------------------------------------------

    def on_node_change(self, node, deleted: bool = False) -> None:
        """Node added/updated/deleted: restage capacity + card set."""
        with self._lock:
            row = self._intern_node(node.name)
            if deleted:
                self._known[row] = False
                self._version += 1
                return
            self._known[row] = True
            gpus = gas_logic.get_node_gpu_list(node)
            self._has_gpus[row] = bool(gpus)
            capacity = gas_logic.get_per_gpu_resource_capacity(node, len(gpus))
            self._cap[row, :] = 0
            self._cap_present[row, :] = False
            for name, value in capacity.items():
                idx = self._intern_resource(name)
                self._cap[row, idx] = value
                self._cap_present[row, idx] = True
            gpu_set = set(gpus)
            for card in gpus:
                self._intern_card(row, card)
            for card, lane in self._card_index[row].items():
                self._card_valid[row, lane] = card in gpu_set
            self._version += 1

    def on_booking_change(self, node_name: str) -> None:
        """Booking changed on one node: restage its used tensor row.
        Called with the cache lock held, so reads are consistent."""
        with self._lock:
            row = self._intern_node(node_name)
            used = self.cache.get_node_resource_status(node_name)
            self._used[row, :, :] = 0
            for card, rm in used.items():
                lane = self._intern_card(row, card)
                for name, value in rm.items():
                    idx = self._intern_resource(name)
                    self._used[row, lane, idx] = value
            self._version += 1

    # -- reads -----------------------------------------------------------------

    def resource_index(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._res_index)

    def snapshot(self, span=trace.NULL_SPAN):
        """(device state over ALL interned rows, node_index, flags) — device
        arrays memoized per version; a restage is the span's
        ``state_upload`` stage (the host copies and the eight uploads)."""
        with self._lock:
            if self._device is None or self._device[0] != self._version:
                with span.stage("state_upload"):
                    used_hi, used_lo = i64.split_int64_np(self._used)
                    cap_hi, cap_lo = i64.split_int64_np(self._cap)
                    state = BinpackNodeState(
                        used=i64.I64(
                            hi=jnp.asarray(used_hi), lo=jnp.asarray(used_lo)
                        ),
                        capacity=i64.I64(
                            hi=jnp.asarray(cap_hi), lo=jnp.asarray(cap_lo)
                        ),
                        cap_present=jnp.asarray(self._cap_present.copy()),
                        card_valid=jnp.asarray(self._card_valid.copy()),
                        card_real=jnp.asarray(self._card_real.copy()),
                        card_order=jnp.asarray(self._card_order.copy()),
                    )
                    self._device = (self._version, state)
            return (
                self._device[1],
                dict(self._node_index),
                self._known.copy(),
                self._has_gpus.copy(),
                dict(self._res_index),
            )


def stage_request(
    requests, shares, resources_index: Dict[str, int], r_pad: int
) -> Tuple[BinpackRequest, int]:
    """Build the padded per-container request tensors."""
    t_pad = _bucket(len(requests), MIN_CONTAINERS)
    max_gpus = max((k for _, k in shares), default=0)
    k_pad = _bucket(max(max_gpus, 1), MIN_GPUS)
    need = np.zeros((t_pad, r_pad), dtype=np.int64)
    need_active = np.zeros((t_pad, r_pad), dtype=bool)
    num_gpus = np.zeros(t_pad, dtype=np.int32)
    container_active = np.zeros(t_pad, dtype=bool)
    for t, (per_gpu, k) in enumerate(shares):
        container_active[t] = True
        num_gpus[t] = k
        for name, value in per_gpu.items():
            idx = resources_index[name]
            need[t, idx] = value
            need_active[t, idx] = True
    need_hi, need_lo = i64.split_int64_np(need)
    return (
        BinpackRequest(
            need=i64.I64(hi=jnp.asarray(need_hi), lo=jnp.asarray(need_lo)),
            need_active=jnp.asarray(need_active),
            num_gpus=jnp.asarray(num_gpus),
            container_active=jnp.asarray(container_active),
        ),
        k_pad,
    )


class DeviceBinpacker:
    """Evaluates one pod's fit against many nodes in one XLA pass.

    The mirror path amortizes the device dispatch across a scheduling
    burst: kube-scheduler filters one pod per request, but the pods of a
    deployment share a template, and the mirror state only changes when
    a booking/node event lands — so fits are cached per (state version,
    request signature) over ALL interned rows, and a burst of filter
    calls costs ONE kernel dispatch plus row lookups (the GAS analog of
    the TAS fastpath's precomputed rankings; the reference instead walks
    every node per request under its global lock, scheduler.go:463-473).
    """

    FITS_CACHE_SIZE = 8

    def __init__(self, cache, use_mirror: bool = True):
        self.cache = cache
        self.mirror = GASUsageMirror(cache) if use_mirror else None
        self._fits_lock = threading.Lock()
        # MRU [state, signature, fits-over-all-rows]; keyed by the state
        # OBJECT identity (snapshot memoizes one state per mirror version,
        # so identity == version) and the pod's request signature
        self._fits_cache: List[list] = []

    def batch_fit(
        self,
        pod: Pod,
        node_names: Sequence[str],
        with_reasons: bool = False,
        span=trace.NULL_SPAN,
    ) -> Optional[List[bool]]:
        """Per-node fit verdicts, or None when the pod has no per-card
        demand (the host loop decides cheaply).  With ``with_reasons``
        the return is ``(fits, codes)`` where codes carry the compact
        decision taxonomy per node (utils/decisions.py): 0 fit,
        gas_unknown_node / gas_no_gpus for the pre-failed lanes, and
        gas_capacity when the binpack kernel said no — the classes the
        host loop's typed exceptions produce identically."""
        requests = container_requests(pod)
        shares = [gas_logic.get_per_gpu_resource_request(req) for req in requests]
        max_gpus = max((k for _, k in shares), default=0)
        resources = sorted({name for req in requests for name in req})
        if not resources or max_gpus == 0:
            # no per-card demand: every readable node with GPUs fits, which
            # the host loop decides cheaply — no point shipping tensors
            return None
        if self.mirror is not None:
            fits, codes = self._fit_mirror(
                requests, shares, resources, node_names, span
            )
        else:
            fits, codes = self._fit_staged(requests, shares, resources, node_names)
        return (fits, codes) if with_reasons else fits

    # -- persistent-mirror path ------------------------------------------------

    def _all_rows_fits(self, state, signature, compute) -> np.ndarray:
        """fits over ALL interned rows for this (state, request template),
        served from the MRU cache when the burst repeats the template;
        ``compute`` runs only on a miss (a hit skips request staging and
        the kernel entirely)."""
        with self._fits_lock:
            for idx, entry in enumerate(self._fits_cache):
                if entry[0] is state and entry[1] == signature:
                    if idx:
                        self._fits_cache.insert(0, self._fits_cache.pop(idx))
                    return entry[2]
        fits = compute()
        # purge relative to the mirror's CURRENT memoized state, not this
        # call's: a straggler that snapshotted a superseded state must not
        # evict fresh entries or insert one that can never hit again
        # (superseded-state entries would only pin full-cluster device
        # arrays; snapshot returns ONE state object per mirror version)
        with self.mirror._lock:
            dev = self.mirror._device
            current = dev[1] if dev is not None else state
        with self._fits_lock:
            self._fits_cache = [
                entry for entry in self._fits_cache if entry[0] is current
            ]
            if state is current:
                self._fits_cache.insert(0, [state, signature, fits])
                del self._fits_cache[self.FITS_CACHE_SIZE:]
        return fits

    def _fit_mirror(
        self, requests, shares, resources, node_names, span=trace.NULL_SPAN
    ):
        mirror = self.mirror
        # informer deliveries restage rows under this lock: the wait is
        # what a release or a resync costs this request
        with span.stage("mirror_wait"):
            mirror._lock.acquire()
        try:
            for name in resources:  # unknown request resources: intern (all-absent)
                mirror._intern_resource(name)
            state, node_index, known, has_gpus, res_index = mirror.snapshot(
                span
            )
        finally:
            mirror._lock.release()
        max_gpus = max((k for _, k in shares), default=0)
        k_pad = _bucket(max(max_gpus, 1), MIN_GPUS)
        signature = (
            tuple(
                (tuple(sorted(per_gpu.items())), k) for per_gpu, k in shares
            ),
            k_pad,
        )

        def compute() -> np.ndarray:
            r_pad = state.capacity.hi.shape[-1]
            with span.stage("req_upload"):
                request, staged_k_pad = stage_request(
                    requests, shares, res_index, r_pad
                )
            # dispatch to readback: np.asarray is what waits for the device
            with span.stage("solve"):
                return np.asarray(
                    binpack_kernel(state, request, staged_k_pad).fits
                )

        fits_all = self._all_rows_fits(state, signature, compute)
        with span.stage("rows"):
            out = [False] * len(node_names)
            codes = [decisions.CODE_GAS_CAPACITY] * len(node_names)
            for pos, name in enumerate(node_names):
                row = node_index.get(name)
                if row is None or not known[row]:
                    codes[pos] = decisions.CODE_GAS_UNKNOWN_NODE
                    continue  # pre-failed
                if not has_gpus[row]:
                    codes[pos] = decisions.CODE_GAS_NO_GPUS
                    continue
                out[pos] = bool(fits_all[row])
                if out[pos]:
                    codes[pos] = decisions.CODE_ELIGIBLE
        return out, codes

    # -- per-request staging path (control) ------------------------------------

    def _fit_staged(self, requests, shares, resources, node_names):
        r_pad = _bucket(len(resources), MIN_RESOURCES)
        res_index = {name: i for i, name in enumerate(resources)}
        request, k_pad = stage_request(requests, shares, res_index, r_pad)

        staged = []
        out = [False] * len(node_names)
        codes = [decisions.CODE_GAS_CAPACITY] * len(node_names)
        max_cards = 1
        for pos, name in enumerate(node_names):
            try:
                node = self.cache.fetch_node(name)
            except Exception:
                codes[pos] = decisions.CODE_GAS_UNKNOWN_NODE
                continue
            gpus = gas_logic.get_node_gpu_list(node)
            if not gpus:
                codes[pos] = decisions.CODE_GAS_NO_GPUS
                continue
            capacity = gas_logic.get_per_gpu_resource_capacity(node, len(gpus))
            used = self.cache.get_node_resource_status(name)
            cards = sorted(set(gpus) | set(used))
            max_cards = max(max_cards, len(cards))
            staged.append((pos, cards, capacity, used, set(gpus)))
        if not staged:
            return out, codes

        n = len(staged)
        c_pad = _bucket(max_cards, MIN_CARDS)
        used_np = np.zeros((n, c_pad, r_pad), dtype=np.int64)
        cap_np = np.zeros((n, r_pad), dtype=np.int64)
        cap_present = np.zeros((n, r_pad), dtype=bool)
        card_valid = np.zeros((n, c_pad), dtype=bool)
        card_real = np.zeros((n, c_pad), dtype=bool)
        card_order = np.full((n, c_pad), 2**30, dtype=np.int32)
        for row, (_pos, cards, capacity, used, gpu_set) in enumerate(staged):
            for name, value in capacity.items():
                idx = res_index.get(name)
                if idx is not None:
                    cap_np[row, idx] = value
                    cap_present[row, idx] = True
            for ci, card in enumerate(cards):  # already name-sorted
                card_real[row, ci] = True
                card_valid[row, ci] = card in gpu_set
                card_order[row, ci] = ci
                for name, value in used.get(card, {}).items():
                    idx = res_index.get(name)
                    if idx is not None:
                        used_np[row, ci, idx] = value

        used_hi, used_lo = i64.split_int64_np(used_np)
        cap_hi, cap_lo = i64.split_int64_np(cap_np)
        state = BinpackNodeState(
            used=i64.I64(hi=jnp.asarray(used_hi), lo=jnp.asarray(used_lo)),
            capacity=i64.I64(hi=jnp.asarray(cap_hi), lo=jnp.asarray(cap_lo)),
            cap_present=jnp.asarray(cap_present),
            card_valid=jnp.asarray(card_valid),
            card_real=jnp.asarray(card_real),
            card_order=jnp.asarray(card_order),
        )
        result = binpack_kernel(state, request, k_pad)
        fits_np = np.asarray(result.fits)
        for row, (pos, *_rest) in enumerate(staged):
            out[pos] = bool(fits_np[row])
            if out[pos]:
                codes[pos] = decisions.CODE_ELIGIBLE
        return out, codes
