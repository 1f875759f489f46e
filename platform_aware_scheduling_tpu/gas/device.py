"""Device side of GAS: the resident usage mirror + request packing for the
batched binpack kernel (ops/binpack.py).

:class:`GASUsageMirror` is the GAS analog of the TAS TensorStateMirror
(SURVEY §7 step 5): it subscribes to the cluster cache's booking hook and
the node informer events and keeps ``[nodes, cards, resources]`` usage /
capacity tensors current incrementally, in NumPy and ON THE DEVICE.  What
changes only with the cluster's structure (capacity, the card lanes,
their first-fit order) is uploaded when a node event, a never-seen card
or resource, or a padded axis moves it; ``used`` stays resident, and a
booking travels to it as its changed rows inside the next Filter's own
call.  So a Filter crosses the host<->device boundary three times —
one packed buffer in (request + update block), one dispatch, one readback
of ``fits`` — instead of re-uploading every tensor for one changed row.

Lanes are interned append-only; the first-fit name order the reference
iterates in (scheduler.go:216-224) is carried as an explicit
``card_order`` rank tensor.  All values are exact int64 (split hi/lo).

:class:`DeviceBinpacker` answers one pod's fit across many nodes in one
XLA pass, through the mirror when one is attached (the hot path) or by
per-request staging otherwise (also the correctness control in tests).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from platform_aware_scheduling_tpu.gas import scheduler as gas_logic
from platform_aware_scheduling_tpu.gas.utils import container_requests
from platform_aware_scheduling_tpu.kube.objects import Node, Pod
from platform_aware_scheduling_tpu.ops import i64
from platform_aware_scheduling_tpu.ops.binpack import (
    UPDATE_SLOTS,
    BinpackNodeState,
    binpack_kernel,
    pack_request,
    pack_rows,
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


def _pads(shares) -> Tuple[int, int]:
    """(t_pad, k_pad): the container and GPU-pick axes a request compiles
    for."""
    max_gpus = max((k for _, k in shares), default=0)
    return (
        _bucket(len(shares), MIN_CONTAINERS),
        _bucket(max(max_gpus, 1), MIN_GPUS),
    )


def _to_device(array: np.ndarray):
    """Every explicit host->device copy of this module: each is one
    operation of ~0.3 ms on the chip whatever its size, so tests count
    them here."""
    return jnp.asarray(array)


def _to_device_i64(values: np.ndarray) -> i64.I64:
    hi, lo = i64.split_int64_np(values)
    return i64.I64(hi=_to_device(hi), lo=_to_device(lo))


def _node_state(used, cap, cap_present, card_valid, card_real, card_order):
    """Upload NumPy card state (``used``/``cap`` int64) whole: 8 copies."""
    return BinpackNodeState(
        used=_to_device_i64(used),
        capacity=_to_device_i64(cap),
        cap_present=_to_device(cap_present),
        card_valid=_to_device(card_valid),
        card_real=_to_device(card_real),
        card_order=_to_device(card_order),
    )


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
        # _version moves with every change (one device state object per
        # version: the fits cache's key); _structure only with what is not
        # ``used`` — node events, a never-seen node/card/resource, growth
        self._version = 0
        self._structure = 0
        # rows of ``used`` changed since the resident state was installed
        self._dirty: Set[int] = set()
        # (version, structure version, state) resident on the device
        self._device: Optional[Tuple[int, int, BinpackNodeState]] = None
        # (structure version, node_index, known, has_gpus, res_index)
        self._views: Optional[tuple] = None
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
            self._structure += 1
        return row

    def _intern_resource(self, name: str) -> int:
        idx = self._res_index.get(name)
        if idx is None:
            idx = len(self._res_index)
            self._grow(r=idx + 1)
            self._res_index[name] = idx
            # growing the resource axis invalidates the resident state:
            # a request interning a never-seen resource between cluster
            # events would otherwise get a state whose r_pad is too small
            # for the index this just handed out (IndexError in
            # pack_request until the next event bumped the version)
            self._version += 1
            self._structure += 1
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
            self._structure += 1
        return lane

    # -- event hooks -----------------------------------------------------------

    def on_node_change(self, node, deleted: bool = False) -> None:
        """Node added/updated/deleted: restage capacity + card set."""
        with self._lock:
            row = self._intern_node(node.name)
            self._version += 1
            self._structure += 1
            if deleted:
                self._known[row] = False
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

    def on_booking_change(self, node_name: str) -> None:
        """Booking changed on one node: rewrite its row of the NumPy
        mirror and mark it dirty — no device call from here (Bind's
        ``book`` stage and the informer thread run this); the next Filter
        carries the row.  Called with the cache lock held, so reads are
        consistent."""
        with self._lock:
            row = self._intern_node(node_name)
            used = self.cache.get_node_resource_status(node_name)
            self._used[row, :, :] = 0
            for card, rm in used.items():
                lane = self._intern_card(row, card)
                for name, value in rm.items():
                    idx = self._intern_resource(name)
                    self._used[row, lane, idx] = value
            self._dirty.add(row)
            self._version += 1

    # -- reads -----------------------------------------------------------------

    # the four below run under ``_lock``, taken by the Filter that calls them

    def views(self):
        """(node_index, known, has_gpus, res_index): the host copies a
        Filter reads after it has let go of the lock (the two row vectors
        as lists: it reads them a candidate name at a time), memoized per
        structure version."""
        if self._views is None or self._views[0] != self._structure:
            self._views = (
                self._structure,
                dict(self._node_index),
                self._known.tolist(),
                self._has_gpus.tolist(),
                dict(self._res_index),
            )
        return self._views[1:]

    def resident(self) -> Optional[BinpackNodeState]:
        """The device state over ALL interned rows, if it is this
        version's."""
        dev = self._device
        return dev[2] if dev is not None and dev[0] == self._version else None

    def stage(self) -> Tuple[BinpackNodeState, np.ndarray]:
        """(base state, update block): what the next solve needs to run on
        this version's usage.  Up to UPDATE_SLOTS dirty rows ride in the
        block on the resident state; more, or a moved structure, is a
        full restage of ``used`` (of everything) under an empty block —
        the same compiled program either way."""
        dev = self._device
        structure_held = dev is not None and dev[1] == self._structure
        if structure_held and len(self._dirty) <= UPDATE_SLOTS:
            rows = sorted(self._dirty)
            trace.COUNTERS.inc("pas_gas_state_incremental_total")
            trace.COUNTERS.inc("pas_gas_state_rows_applied_total", len(rows))
            return dev[2], pack_rows(self._used, rows)
        trace.COUNTERS.inc("pas_gas_state_full_restage_total")
        if structure_held:
            base = dev[2]._replace(used=_to_device_i64(self._used))
        else:
            # the copies: the CPU backend may alias a NumPy buffer it is handed
            base = _node_state(
                self._used,
                self._cap,
                self._cap_present.copy(),
                self._card_valid.copy(),
                self._card_real.copy(),
                self._card_order.copy(),
            )
        return base, pack_rows(self._used)

    def install(self, base: BinpackNodeState, used: i64.I64) -> BinpackNodeState:
        """This version's state: ``base`` with the ``used`` its solve
        returned — or the one already resident (a solve for a second
        template on an unchanged version must not replace the object the
        fits cache is keyed by)."""
        state = self.resident()
        if state is None:
            state = base._replace(used=used)
            self._device = (self._version, self._structure, state)
            self._dirty.clear()
        return state

    def forget(self) -> None:
        """Drop the resident state: the next Filter restages from NumPy."""
        with self._lock:
            self._device = None


class DeviceBinpacker:
    """Evaluates one pod's fit against many nodes in one XLA pass.

    The mirror path keeps the cluster's card state resident on the
    device (:class:`GASUsageMirror`): a Filter hands the jitted solve ONE
    packed host buffer — its request and the usage rows that bookings
    and releases changed since the last solve — and reads ``fits`` back;
    the solve returns the updated usage, which stays on the device as
    the next Filter's base.

    It also amortizes the dispatch across a scheduling burst:
    kube-scheduler filters one pod per request, but the pods of a
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
        # OBJECT identity (the mirror installs one state per version, so
        # identity == version) and the pod's request signature
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
            fits, codes = self._fit_mirror(shares, resources, node_names, span)
        else:
            fits, codes = self._fit_staged(shares, resources, node_names)
        return (fits, codes) if with_reasons else fits

    # -- persistent-mirror path ------------------------------------------------

    def _cached_fits(self, state, signature) -> Optional[np.ndarray]:
        """fits over ALL interned rows for this (state, request template)
        from the MRU cache, when the burst repeats the template: a hit
        skips request packing and the kernel entirely."""
        with self._fits_lock:
            for idx, entry in enumerate(self._fits_cache):
                if entry[0] is state and entry[1] == signature:
                    if idx:
                        self._fits_cache.insert(0, self._fits_cache.pop(idx))
                    return entry[2]
        return None

    def _remember_fits(self, state, signature, fits: np.ndarray) -> None:
        # purge relative to the mirror's CURRENT resident state, not this
        # call's: a straggler that solved a superseded state must not
        # evict fresh entries or insert one that can never hit again
        # (superseded-state entries would only pin full-cluster device
        # arrays; the mirror keeps ONE state object per version)
        with self.mirror._lock:
            dev = self.mirror._device
            current = dev[2] if dev is not None else state
        with self._fits_lock:
            self._fits_cache = [
                entry for entry in self._fits_cache if entry[0] is current
            ]
            if state is current:
                self._fits_cache.insert(0, [state, signature, fits])
                del self._fits_cache[self.FITS_CACHE_SIZE:]

    def _fit_mirror(self, shares, resources, node_names, span=trace.NULL_SPAN):
        mirror = self.mirror
        t_pad, k_pad = _pads(shares)
        signature = (
            tuple(
                (tuple(sorted(per_gpu.items())), k) for per_gpu, k in shares
            ),
            k_pad,
        )
        with contextlib.ExitStack() as held:
            # informer deliveries rewrite rows under this lock: the wait is
            # what a release or a resync costs this request
            with span.stage("mirror_wait"):
                mirror._lock.acquire()
            held.callback(mirror._lock.release)
            for name in resources:  # unknown request resources: intern (all-absent)
                mirror._intern_resource(name)
            node_index, known, has_gpus, res_index = mirror.views()
            state = mirror.resident()
            fits_all = (
                None if state is None else self._cached_fits(state, signature)
            )
            if fits_all is None:
                # stage, dispatch and install under the lock: each version's
                # ``used`` derives from the one before it, so no solve may
                # overtake another between taking its base and installing
                with span.stage("state_upload"):
                    base, update = mirror.stage()
                with span.stage("req_upload"):
                    r_pad = base.capacity.hi.shape[-1]
                    packed = pack_request(
                        shares, res_index, t_pad, r_pad, update
                    )
                # dispatch to readback: np.asarray is what waits for the device
                with span.stage("solve"):
                    result = binpack_kernel(base, packed, k_pad)
                    state = mirror.install(base, result.used)
                    held.close()  # the wait itself needs no lock
                    try:
                        fits_all = np.asarray(result.fits)
                    except Exception:
                        # a failed execution poisons the ``used`` every
                        # later update would build on
                        mirror.forget()
                        raise
                self._remember_fits(state, signature, fits_all)
        with span.stage("rows"):
            # plain lists, one pass: indexing a NumPy array a name costs
            # three times a list's, and whole-vector NumPy lets go of the
            # GIL at every operation — beside a Bind each is a hand-off
            fits_row = fits_all.tolist()
            codes = []
            for row in map(node_index.get, node_names):
                if row is None or not known[row]:
                    code = decisions.CODE_GAS_UNKNOWN_NODE  # pre-failed
                elif not has_gpus[row]:
                    code = decisions.CODE_GAS_NO_GPUS
                elif fits_row[row]:
                    code = decisions.CODE_ELIGIBLE
                else:
                    code = decisions.CODE_GAS_CAPACITY
                codes.append(code)
            out = [code == decisions.CODE_ELIGIBLE for code in codes]
        return out, codes

    # -- per-request staging path (control) ------------------------------------

    def _fit_staged(self, shares, resources, node_names):
        r_pad = _bucket(len(resources), MIN_RESOURCES)
        res_index = {name: i for i, name in enumerate(resources)}
        t_pad, k_pad = _pads(shares)

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

        state = _node_state(
            used_np, cap_np, cap_present, card_valid, card_real, card_order
        )
        packed = pack_request(shares, res_index, t_pad, r_pad, pack_rows(used_np))
        result = binpack_kernel(state, packed, k_pad)
        fits_np = np.asarray(result.fits)
        for row, (pos, *_rest) in enumerate(staged):
            out[pos] = bool(fits_np[row])
            if out[pos]:
                codes[pos] = decisions.CODE_ELIGIBLE
        return out, codes
