"""GAS extender tests: filter fit-checks, bind booking/rollback, cache
ingestion/replay, device-vs-host binpack equivalence."""

import json
import sys
import threading
import time

import jax
import numpy as np
import pytest

from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.extender.types import Args
from platform_aware_scheduling_tpu.gas import device as gas_device
from platform_aware_scheduling_tpu.gas.cache import Cache, get_key
from platform_aware_scheduling_tpu.gas.resource_map import ResourceMap
from platform_aware_scheduling_tpu.gas.scheduler import (
    GASExtender,
    check_resource_capacity,
    get_node_gpu_list,
    get_per_gpu_resource_capacity,
    get_per_gpu_resource_request,
)
from platform_aware_scheduling_tpu.gas.utils import (
    CARD_ANNOTATION,
    container_requests,
    has_gpu_resources,
    is_completed_pod,
)
from platform_aware_scheduling_tpu.ops import i64
from platform_aware_scheduling_tpu.ops.binpack import UPDATE_SLOTS, binpack_kernel
from platform_aware_scheduling_tpu.testing.builders import make_node, make_pod
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.utils import trace


def post(obj) -> HTTPRequest:
    return HTTPRequest(
        method="POST",
        path="/scheduler/filter",
        headers={"Content-Type": "application/json"},
        body=json.dumps(obj).encode(),
    )


def request_args(request: HTTPRequest) -> Args:
    return Args.from_json(request.body)


def gpu_node(name, cards=2, i915=2, millicores=2000, memory=4000):
    return make_node(
        name,
        labels={"gpu.intel.com/cards": ".".join(f"card{i}" for i in range(cards))},
        allocatable={
            "gpu.intel.com/i915": str(i915),
            "gpu.intel.com/millicores": str(millicores),
            "gpu.intel.com/memory.max": str(memory),
        },
    )


def gpu_pod(name, i915="1", millicores="500", node_name="", annotations=None,
            phase="Pending", containers=1):
    reqs = [{
        "gpu.intel.com/i915": i915,
        "gpu.intel.com/millicores": millicores,
    }] * containers
    return make_pod(
        name,
        container_requests=reqs,
        node_name=node_name,
        annotations=annotations,
        phase=phase,
    )


def wait_until(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


@pytest.fixture(params=["host", "staged", "mirror"])
def setup(request):
    kube = FakeKubeClient()
    cache = Cache(kube, start=False)
    ext = GASExtender(
        kube,
        cache=cache,
        use_device=request.param != "host",
        use_mirror=request.param == "mirror",
    )
    yield kube, cache, ext
    cache.stop()


def start(cache):
    cache.start()


class TestUtils:
    def test_container_requests_prefix_only(self):
        pod = make_pod("p", container_requests=[
            {"cpu": "2", "gpu.intel.com/i915": "1", "gpu.intel.com/millicores": "100"}
        ])
        reqs = container_requests(pod)
        assert reqs == [{"gpu.intel.com/i915": 1, "gpu.intel.com/millicores": 100}]

    def test_fractional_quantity_reads_zero(self):
        # AsInt64 of a fractional quantity: value 0 (reference ignores ok)
        pod = make_pod("p", container_requests=[{"gpu.intel.com/tiles": "500m"}])
        assert container_requests(pod) == [{"gpu.intel.com/tiles": 0}]

    def test_has_gpu_resources(self):
        assert has_gpu_resources(gpu_pod("p"))
        assert not has_gpu_resources(make_pod("p", container_requests=[{"cpu": "1"}]))
        assert not has_gpu_resources(None)

    def test_is_completed_pod(self):
        assert is_completed_pod(make_pod("p", phase="Succeeded"))
        assert is_completed_pod(make_pod("p", phase="Failed"))
        assert not is_completed_pod(make_pod("p", phase="Running"))
        pod = make_pod("p", phase="Running")
        pod.metadata["deletionTimestamp"] = "2026-01-01T00:00:00Z"
        assert is_completed_pod(pod)


class TestHelpers:
    def test_gpu_list_and_capacity(self):
        node = gpu_node("n1", cards=2, i915=2, millicores=2000)
        assert get_node_gpu_list(node) == ["card0", "card1"]
        per_gpu = get_per_gpu_resource_capacity(node, 2)
        assert per_gpu["gpu.intel.com/i915"] == 1
        assert per_gpu["gpu.intel.com/millicores"] == 1000

    def test_no_label_gives_empty(self):
        assert get_node_gpu_list(make_node("n")) == []

    def test_per_gpu_request_division(self):
        rm = ResourceMap({"gpu.intel.com/i915": 2, "gpu.intel.com/millicores": 900})
        per_gpu, k = get_per_gpu_resource_request(rm)
        assert k == 2
        assert per_gpu["gpu.intel.com/millicores"] == 450
        assert per_gpu["gpu.intel.com/i915"] == 1

    def test_check_resource_capacity(self):
        cap = ResourceMap(a=10)
        assert check_resource_capacity(ResourceMap(a=5), cap, ResourceMap(a=5))
        assert not check_resource_capacity(ResourceMap(a=6), cap, ResourceMap(a=5))
        assert not check_resource_capacity(ResourceMap(b=0), cap, ResourceMap())
        assert not check_resource_capacity(ResourceMap(a=0), ResourceMap(a=0),
                                           ResourceMap())


class TestFilter:
    def test_fit_and_reject(self, setup):
        kube, cache, ext = setup
        kube.add_node(gpu_node("empty-node"))
        kube.add_node(gpu_node("small-node", cards=1, i915=1, millicores=100))
        start(cache)
        resp = ext.filter(post({
            "Pod": gpu_pod("p", millicores="500").raw,
            "NodeNames": ["empty-node", "small-node"],
        }))
        assert resp.status == 200
        out = json.loads(resp.body)
        assert out["NodeNames"] == ["empty-node"]
        assert out["FailedNodes"] == {
            "small-node": "gas: no card fits request "
            "(gpu.intel.com/i915=1, gpu.intel.com/millicores=500)"
        }

    def test_missing_node_names_is_error_404(self, setup):
        _, cache, ext = setup
        start(cache)
        resp = ext.filter(post({"Pod": gpu_pod("p").raw, "Nodes": {"items": []}}))
        assert resp.status == 404
        assert "NodeCacheCapable" in json.loads(resp.body)["Error"]

    def test_unknown_node_fails(self, setup):
        _, cache, ext = setup
        start(cache)
        resp = ext.filter(post({
            "Pod": gpu_pod("p").raw, "NodeNames": ["ghost"],
        }))
        out = json.loads(resp.body)
        assert out["NodeNames"] is None or out["NodeNames"] == []
        assert "ghost" in out["FailedNodes"]

    def test_used_resources_counted(self, setup):
        kube, cache, ext = setup
        kube.add_node(gpu_node("n1", cards=1, i915=2, millicores=1000))
        start(cache)
        # book 800 of 1000 millicores on the single card
        booked = gpu_pod("booked", millicores="800", node_name="n1")
        cache.adjust_pod_resources_locked(booked, True, "card0", "n1")
        resp = ext.filter(post({
            "Pod": gpu_pod("p", millicores="300").raw, "NodeNames": ["n1"],
        }))
        out = json.loads(resp.body)
        assert out["FailedNodes"] == {
            "n1": "gas: no card fits request "
            "(gpu.intel.com/i915=1, gpu.intel.com/millicores=300)"
        }
        resp = ext.filter(post({
            "Pod": gpu_pod("p2", millicores="200").raw, "NodeNames": ["n1"],
        }))
        assert json.loads(resp.body)["NodeNames"] == ["n1"]

    def test_multi_gpu_spread(self, setup):
        kube, cache, ext = setup
        # 2 cards, 1000 each; i915=2 request of 1600 -> 800 per card: fits
        kube.add_node(gpu_node("n1", cards=2, i915=2, millicores=2000))
        start(cache)
        resp = ext.filter(post({
            "Pod": gpu_pod("p", i915="2", millicores="1600").raw,
            "NodeNames": ["n1"],
        }))
        assert json.loads(resp.body)["NodeNames"] == ["n1"]

    def test_prioritize_404(self, setup):
        _, cache, ext = setup
        resp = ext.prioritize(post({}))
        assert resp.status == 404


class TestBind:
    def test_bind_annotates_and_books(self, setup):
        kube, cache, ext = setup
        kube.add_node(gpu_node("n1"))
        pod = gpu_pod("p", millicores="500")
        kube.add_pod(pod)
        start(cache)
        resp = ext.bind(post({
            "PodName": "p", "PodNamespace": "default",
            "PodUID": pod.uid, "Node": "n1",
        }))
        assert resp.status == 200
        assert json.loads(resp.body) == {"Error": ""}
        bound = kube.get_pod("default", "p")
        assert bound.get_annotations()[CARD_ANNOTATION] == "card0"
        assert "gas-ts" in bound.get_annotations()
        assert bound.spec_node_name == "n1"
        used = cache.get_node_resource_status("n1")
        assert used["card0"]["gpu.intel.com/millicores"] == 500

    def test_bind_unknown_pod_errors(self, setup):
        _, cache, ext = setup
        start(cache)
        resp = ext.bind(post({
            "PodName": "ghost", "PodNamespace": "default",
            "PodUID": "u", "Node": "n1",
        }))
        assert resp.status == 404
        assert json.loads(resp.body)["Error"] != ""

    def test_bind_wont_fit_rolls_back(self, setup):
        kube, cache, ext = setup
        kube.add_node(gpu_node("n1", cards=1, i915=1, millicores=100))
        pod = gpu_pod("p", millicores="500")
        kube.add_pod(pod)
        start(cache)
        resp = ext.bind(post({
            "PodName": "p", "PodNamespace": "default",
            "PodUID": pod.uid, "Node": "n1",
        }))
        assert resp.status == 404
        assert cache.get_node_resource_status("n1") == {}
        assert get_key(pod) not in cache.annotated_pods


class TestCacheIngestion:
    def test_annotated_pod_replayed_on_start(self):
        """Restart reconstruction: informer ADD events replay annotated pods
        (SURVEY §3.7 / §5.4)."""
        kube = FakeKubeClient()
        kube.add_node(gpu_node("n1"))
        kube.add_pod(gpu_pod("p", millicores="600", node_name="n1",
                             annotations={CARD_ANNOTATION: "card0"}))
        cache = Cache(kube, start=False)
        cache.start()
        try:
            assert wait_until(
                lambda: cache.get_node_resource_status("n1")
                .get("card0", {})
                .get("gpu.intel.com/millicores") == 600
            )
        finally:
            cache.stop()

    def test_completed_pod_releases_resources(self):
        kube = FakeKubeClient()
        kube.add_node(gpu_node("n1"))
        pod = gpu_pod("p", millicores="600", node_name="n1",
                      annotations={CARD_ANNOTATION: "card0"})
        kube.add_pod(pod)
        cache = Cache(kube, start=False)
        cache.start()
        try:
            assert wait_until(
                lambda: get_key(pod) in cache.annotated_pods
            )
            done = gpu_pod("p", millicores="600", node_name="n1",
                           annotations={CARD_ANNOTATION: "card0"},
                           phase="Succeeded")
            done.metadata["uid"] = pod.uid
            done.metadata["resourceVersion"] = "99"
            kube.update_pod(done)
            assert wait_until(
                lambda: get_key(pod) not in cache.annotated_pods
            )
            used = cache.get_node_resource_status("n1")
            assert used["card0"]["gpu.intel.com/millicores"] == 0
        finally:
            cache.stop()

    def test_deleted_pod_releases_resources(self):
        kube = FakeKubeClient()
        kube.add_node(gpu_node("n1"))
        pod = gpu_pod("p", millicores="600", node_name="n1",
                      annotations={CARD_ANNOTATION: "card0"})
        kube.add_pod(pod)
        cache = Cache(kube, start=False)
        cache.start()
        try:
            assert wait_until(lambda: get_key(pod) in cache.annotated_pods)
            kube.delete_pod("default", "p")
            assert wait_until(lambda: get_key(pod) not in cache.annotated_pods)
            used = cache.get_node_resource_status("n1")
            assert used["card0"]["gpu.intel.com/millicores"] == 0
        finally:
            cache.stop()


class TestDeviceHostEquivalence:
    """Randomized cluster state: the batched kernel's verdicts must match
    the host first-fit on every node."""

    def test_random_fit_equivalence(self):
        rng = np.random.default_rng(7)
        kube = FakeKubeClient()
        names = []
        for i in range(24):
            name = f"n{i}"
            names.append(name)
            kube.add_node(gpu_node(
                name,
                cards=int(rng.integers(1, 5)),
                i915=int(rng.integers(1, 9)),
                millicores=int(rng.integers(100, 4000)),
                memory=int(rng.integers(100, 8000)),
            ))
        cache = Cache(kube, start=False)
        ext_host = GASExtender(kube, cache=cache, use_device=False)
        ext_dev = GASExtender(kube, cache=cache, use_device=True,
                              use_mirror=False)
        ext_mir = GASExtender(kube, cache=cache, use_device=True,
                              use_mirror=True)
        cache.start()
        try:
            # seed random bookings
            for i in range(10):
                node = f"n{int(rng.integers(0, 24))}"
                pod = gpu_pod(f"seed{i}",
                              millicores=str(int(rng.integers(0, 1500))),
                              node_name=node)
                card = f"card{int(rng.integers(0, 4))}"
                try:
                    cache.adjust_pod_resources_locked(pod, True, card, node)
                except Exception:
                    pass
            for trial in range(8):
                pod = gpu_pod(
                    f"trial{trial}",
                    i915=str(int(rng.integers(1, 4))),
                    millicores=str(int(rng.integers(0, 3000))),
                    containers=int(rng.integers(1, 3)),
                )
                req = post({"Pod": pod.raw, "NodeNames": names})
                host_out = json.loads(ext_host.filter(req).body)
                dev_out = json.loads(ext_dev.filter(req).body)
                mir_out = json.loads(ext_mir.filter(req).body)
                assert host_out == dev_out, f"trial {trial} staged diverged"
                assert host_out == mir_out, f"trial {trial} mirror diverged"
        finally:
            cache.stop()


class TestUsageMirrorSync:
    """The persistent mirror must track node events and bookings live."""

    def _filter_names(self, ext, names, millicores="500"):
        req = post({"Pod": gpu_pod("probe", millicores=millicores).raw,
                    "NodeNames": names})
        return json.loads(ext.filter(req).body)

    def test_node_update_changes_verdict(self):
        kube = FakeKubeClient()
        kube.add_node(gpu_node("n1", cards=1, i915=1, millicores=100))
        cache = Cache(kube, start=False)
        ext = GASExtender(kube, cache=cache, use_device=True, use_mirror=True)
        cache.start()
        try:
            out = self._filter_names(ext, ["n1"])
            assert "n1" in out["FailedNodes"]
            # capacity grows: update the node object
            bigger = gpu_node("n1", cards=1, i915=2, millicores=2000)
            bigger.metadata["resourceVersion"] = "7"
            kube.add_node(bigger)
            assert wait_until(
                lambda: self._filter_names(ext, ["n1"])["NodeNames"] == ["n1"]
            )
        finally:
            cache.stop()

    def test_fits_cache_invalidated_by_booking(self):
        """The per-(state version, template) fits cache must never serve
        stale fits: a booking bumps the mirror version, so a repeated
        identical request re-evaluates and sees the node full."""
        kube = FakeKubeClient()
        kube.add_node(gpu_node("n1", cards=1, i915=1, millicores=1000))
        cache = Cache(kube, start=False)
        ext = GASExtender(kube, cache=cache, use_device=True, use_mirror=True)
        cache.start()
        try:
            # two identical requests: second is a cache hit, same verdict
            assert wait_until(
                lambda: self._filter_names(ext, ["n1"], millicores="800")[
                    "NodeNames"
                ] == ["n1"]
            )
            assert self._filter_names(ext, ["n1"], millicores="800")[
                "NodeNames"
            ] == ["n1"]
            packer = ext._device
            assert len(packer._fits_cache) == 1
            # book 800 of 1000 millicores -> the same template no longer fits
            booked = gpu_pod("booked", millicores="800", node_name="n1")
            kube.add_pod(booked)
            cache.adjust_pod_resources_locked(booked, True, "card0", "n1")
            out = self._filter_names(ext, ["n1"], millicores="800")
            assert "n1" in out["FailedNodes"]
        finally:
            cache.stop()

    def test_unknown_request_resource_after_snapshot(self):
        """Interning a never-seen request resource must invalidate the
        memoized snapshot: before the fix the old state (too-small r_pad)
        made pack_request index out of bounds until the next cluster
        event, forcing host fallback on every such request."""
        kube = FakeKubeClient()
        kube.add_node(gpu_node("n1"))
        cache = Cache(kube, start=False)
        ext = GASExtender(kube, cache=cache, use_device=True, use_mirror=True)
        cache.start()
        try:
            # memoize the snapshot at the current version
            assert wait_until(
                lambda: self._filter_names(ext, ["n1"])["NodeNames"] == ["n1"]
            )
            pod = gpu_pod("probe2").raw
            pod["spec"]["containers"][0]["resources"]["requests"][
                "gpu.intel.com/never-seen"
            ] = "1"
            from platform_aware_scheduling_tpu.kube.objects import Pod

            fits = ext._device.batch_fit(Pod(pod), ["n1"])
            # no node carries the resource -> no fit; the point is the
            # device path answered (no IndexError -> host fallback)
            assert fits == [False]
        finally:
            cache.stop()

    def test_fits_cache_distinguishes_templates(self):
        """Different pod templates under one state version get separate
        cache entries with different verdicts."""
        kube = FakeKubeClient()
        kube.add_node(gpu_node("n1", cards=1, i915=1, millicores=1000))
        cache = Cache(kube, start=False)
        ext = GASExtender(kube, cache=cache, use_device=True, use_mirror=True)
        cache.start()
        try:
            assert wait_until(
                lambda: self._filter_names(ext, ["n1"], millicores="500")[
                    "NodeNames"
                ] == ["n1"]
            )
            out = self._filter_names(ext, ["n1"], millicores="5000")
            assert "n1" in out["FailedNodes"]
            assert len(ext._device._fits_cache) == 2
        finally:
            cache.stop()

    def test_node_delete_prefails(self):
        kube = FakeKubeClient()
        kube.add_node(gpu_node("n1"))
        cache = Cache(kube, start=False)
        ext = GASExtender(kube, cache=cache, use_device=True, use_mirror=True)
        cache.start()
        try:
            assert wait_until(
                lambda: self._filter_names(ext, ["n1"])["NodeNames"] == ["n1"]
            )
            kube.delete_node("n1")
            assert wait_until(
                lambda: "n1" in self._filter_names(ext, ["n1"])["FailedNodes"]
            )
        finally:
            cache.stop()

    def test_vanished_card_booking_tracked(self):
        """Usage booked on a card missing from the label: lane interned,
        marked invalid, skipped by first-fit — but label cards still fit."""
        kube = FakeKubeClient()
        kube.add_node(gpu_node("n1", cards=2, i915=4, millicores=2000))
        cache = Cache(kube, start=False)
        ext = GASExtender(kube, cache=cache, use_device=True, use_mirror=True)
        cache.start()
        try:
            ghost = gpu_pod("ghost", millicores="100", node_name="n1")
            cache.adjust_pod_resources_locked(ghost, True, "card9", "n1")
            out = self._filter_names(ext, ["n1"])
            assert out["NodeNames"] == ["n1"]
        finally:
            cache.stop()


    @pytest.mark.parametrize("ghost_at", ["first", "last", "both"])
    def test_every_reason_class_in_one_candidate_list(self, ghost_at):
        """The row lookups, one pass over lists: a fitting node, a full
        one, one without GPUs, a deleted one and a name the mirror never
        saw (wherever it stands) in one request get the host loop's
        verdicts, reasons and codes."""
        kube = FakeKubeClient()
        kube.add_node(gpu_node("fits"))
        kube.add_node(gpu_node("full", cards=1, i915=1, millicores=100))
        kube.add_node(make_node("bare"))
        kube.add_node(gpu_node("gone"))
        cache = Cache(kube, start=False)
        ext = GASExtender(kube, cache=cache, use_device=True, use_mirror=True)
        host = GASExtender(kube, cache=cache, use_device=False)
        cache.start()
        try:
            mirror = ext._device.mirror
            assert wait_until(lambda: int(mirror._known.sum()) == 4)
            kube.delete_node("gone")
            assert wait_until(lambda: int(mirror._known.sum()) == 3)
            names = ["full", "bare", "fits", "gone"]
            if ghost_at in ("first", "both"):
                names.insert(0, "ghost-a")
            if ghost_at in ("last", "both"):
                names.append("ghost-z")
            pod = gpu_pod("probe", millicores="500")
            request = post({"Pod": pod.raw, "NodeNames": names})
            got = json.loads(ext.filter(request).body)
            assert got["NodeNames"] == ["fits"]
            assert list(got["FailedNodes"]) == [n for n in names if n != "fits"]
            assert got == json.loads(host.filter(request).body)
            device_codes, host_codes = {}, {}
            ext._filter_nodes(request_args(request), codes_out=device_codes)
            host._filter_nodes(request_args(request), codes_out=host_codes)
            assert device_codes == host_codes
            assert len(set(device_codes.values())) == 3
        finally:
            cache.stop()


class _Readback:
    """Stands in for one array of a solve's result: counts the copies to
    the host (``np.asarray`` calls ``__array__``)."""

    def __init__(self, array, name, log):
        self._array, self._name, self._log = array, name, log

    def __array__(self, dtype=None, copy=None):
        self._log.append(self._name)
        return np.asarray(self._array)


class TestResidentState:
    """PR 27: ``used`` stays on the device and a booking travels as its
    changed rows inside the Filter's own buffer (gas/device.py)."""

    NODES = 12

    @pytest.fixture
    def cluster(self):
        rng = np.random.default_rng(27)
        kube = FakeKubeClient()
        names = [f"n{i}" for i in range(self.NODES)]
        for name in names:
            kube.add_node(gpu_node(
                name,
                cards=int(rng.integers(1, 5)),
                i915=int(rng.integers(2, 9)),
                millicores=int(rng.integers(500, 4000)),
                memory=int(rng.integers(500, 8000)),
            ))
        cache = Cache(kube, start=False)
        host = GASExtender(kube, cache=cache, use_device=False)
        staged = GASExtender(kube, cache=cache, use_device=True,
                             use_mirror=False)
        mirrored = GASExtender(kube, cache=cache, use_device=True,
                               use_mirror=True)
        cache.start()
        mirror = mirrored._device.mirror
        assert wait_until(lambda: int(mirror._known.sum()) == self.NODES)
        yield kube, cache, names, host, staged, mirrored
        cache.stop()

    @staticmethod
    def _counters():
        return {
            short: trace.COUNTERS.get(f"pas_gas_state_{short}_total")
            for short in ("incremental", "full_restage", "rows_applied")
        }

    def _moved(self, before):
        after = self._counters()
        return {name: after[name] - before[name] for name in after}

    @staticmethod
    def _device_used(mirror):
        version, _structure, state = mirror._device
        assert version == mirror._version
        return i64.to_int64_np(state.used)

    def _agree(self, cluster, pod, tag):
        """The mirror path against the host loop and the staged control,
        verdicts and reasons; the device's ``used`` against NumPy's."""
        _kube, _cache, names, host, staged, mirrored = cluster
        req = post({"Pod": pod.raw, "NodeNames": names})
        want = json.loads(host.filter(req).body)
        assert json.loads(staged.filter(req).body) == want, tag
        assert json.loads(mirrored.filter(req).body) == want, tag
        # called directly, a device-path exception is not swallowed into
        # the host loop
        got = mirrored._device.batch_fit(pod, names, with_reasons=True)
        assert got == staged._device.batch_fit(pod, names, with_reasons=True), tag
        mirror = mirrored._device.mirror
        with mirror._lock:
            assert np.array_equal(self._device_used(mirror), mirror._used), tag

    def test_random_walk_agrees_with_host_and_staged(self, cluster):
        kube, cache, names, _host, _staged, mirrored = cluster
        mirror = mirrored._device.mirror
        rng = np.random.default_rng(2027)
        booked = []
        before = self._counters()

        def book(step, card=None, extra=None):
            node = names[int(rng.integers(0, len(names)))]
            pod = gpu_pod(f"b{step}-{len(booked)}",
                          millicores=str(int(rng.integers(1, 400))),
                          node_name=node)
            if extra:
                pod.raw["spec"]["containers"][0]["resources"]["requests"][
                    extra] = "1"
            card = card or f"card{int(rng.integers(0, 4))}"
            cache.adjust_pod_resources_locked(pod, True, card, node)
            booked.append((pod, card, node))

        def release():
            if booked:
                pod, card, node = booked.pop(int(rng.integers(0, len(booked))))
                cache.adjust_pod_resources_locked(pod, False, card, node)

        def node_event(change):
            structure = mirror._structure
            change()
            assert wait_until(lambda: mirror._structure > structure)

        def bigger(name, **more):
            node = gpu_node(name, cards=4, i915=8, millicores=4000,
                            memory=8000)
            node.raw["status"]["allocatable"].update(more)
            node.metadata["resourceVersion"] = str(1000 + len(booked))
            return node

        scripted = {
            8: lambda s: book(s, card="card9"),  # a never-seen card
            14: lambda s: [book(s) for _ in range(UPDATE_SLOTS + 3)],
            20: lambda s: book(s, extra="gpu.intel.com/never-booked"),
            26: lambda s: node_event(lambda: kube.add_node(bigger("n3"))),
            32: lambda s: node_event(lambda: kube.add_node(
                bigger("n5", **{"gpu.intel.com/tiles": "4"}))),
            38: lambda s: node_event(lambda: kube.delete_node("n7")),
        }
        for step in range(44):
            if step in scripted:
                scripted[step](step)
            elif rng.random() < 0.6 or not booked:
                book(step)
            else:
                release()
                book(step)
            requests = {
                "gpu.intel.com/i915": str(int(rng.integers(1, 3))),
                "gpu.intel.com/millicores": str(int(rng.integers(0, 2500))),
            }
            if step == 29:  # a request resource no node and no booking has
                requests["gpu.intel.com/never-seen"] = "1"
            pod = make_pod(f"probe{step}", container_requests=[requests] * int(
                rng.integers(1, 3)))
            self._agree(cluster, pod, f"step {step}")
        moved = self._moved(before)
        # the burst, the new card, the new resources and the node events
        # restage; everything else rides in update blocks
        assert moved["full_restage"] >= 6
        assert moved["incremental"] > moved["full_restage"]
        assert moved["rows_applied"] >= moved["incremental"] / 2

    def test_one_array_in_one_back_in_steady_state(self, cluster, monkeypatch):
        _kube, cache, names, _host, _staged, mirrored = cluster
        packer = mirrored._device
        crossed = {"uploads": 0, "kernel_host_args": [], "readbacks": []}
        upload, kernel = gas_device._to_device, gas_device.binpack_kernel

        def counted_upload(array):
            crossed["uploads"] += 1
            return upload(array)

        def counted_kernel(state, request, max_gpus):
            crossed["kernel_host_args"].append(sum(
                isinstance(leaf, np.ndarray)
                for leaf in jax.tree_util.tree_leaves((state, request))))
            result = kernel(state, request, max_gpus)
            return result._replace(
                fits=_Readback(result.fits, "fits", crossed["readbacks"]),
                cards=_Readback(result.cards, "cards", crossed["readbacks"]))

        monkeypatch.setattr(gas_device, "_to_device", counted_upload)
        monkeypatch.setattr(gas_device, "binpack_kernel", counted_kernel)

        def filtered(millicores):
            crossed.update(uploads=0, kernel_host_args=[], readbacks=[])
            before = self._counters()
            pod = gpu_pod("probe", millicores=str(millicores))
            assert packer.batch_fit(pod, names) is not None
            return dict(crossed), self._moved(before)

        filtered(100)  # brings the structure to the device: 8 uploads
        held = gpu_pod("held", millicores="50", node_name=names[0])
        cache.adjust_pod_resources_locked(held, True, "card0", names[0])
        for cycle in range(1, 5):  # steady state: a booking and a release
            pod = gpu_pod(f"c{cycle}", millicores="70", node_name=names[cycle])
            cache.adjust_pod_resources_locked(pod, True, "card0", names[cycle])
            cache.adjust_pod_resources_locked(
                held, False, "card0", names[cycle - 1])
            held = pod
            seen, moved = filtered(100)
            assert seen == {"uploads": 0, "kernel_host_args": [1],
                            "readbacks": ["fits"]}, (cycle, seen)
            assert moved == {"incremental": 1, "full_restage": 0,
                             "rows_applied": 2}
        # nothing changed, another template: its request crosses, no state
        seen, moved = filtered(300)
        assert seen == {"uploads": 0, "kernel_host_args": [1],
                        "readbacks": ["fits"]}
        assert moved == {"incremental": 1, "full_restage": 0,
                         "rows_applied": 0}
        # nothing changed, a template seen at this version: nothing crosses
        seen, moved = filtered(300)
        assert seen == {"uploads": 0, "kernel_host_args": [],
                        "readbacks": []}
        assert moved == {"incremental": 0, "full_restage": 0,
                         "rows_applied": 0}

    def test_every_dirty_count_runs_one_compiled_program(self, cluster):
        kube, cache, names, _host, _staged, mirrored = cluster
        mirror = mirrored._device.mirror
        self._agree(cluster, gpu_pod("warm", millicores="10"), "warm")
        programs = binpack_kernel._cache_size()
        serial = 0
        for dirty in list(range(UPDATE_SLOTS + 2)) + ["structure"]:
            before = self._counters()
            if dirty == "structure":
                structure = mirror._structure
                node = gpu_node("n4", cards=3, i915=8, millicores=3000)
                node.metadata["resourceVersion"] = "77"
                kube.add_node(node)
                assert wait_until(lambda: mirror._structure > structure)
                expected = {"incremental": 0, "full_restage": 1,
                            "rows_applied": 0}
            else:
                for node in names[:dirty]:
                    serial += 1
                    pod = gpu_pod(f"d{serial}", millicores="5",
                                  node_name=node)
                    cache.adjust_pod_resources_locked(pod, True, "card0", node)
                full = dirty > UPDATE_SLOTS
                expected = {"incremental": int(not full),
                            "full_restage": int(full),
                            "rows_applied": 0 if full else dirty}
            # one solve on the mirror path (a template of its own: the
            # zero-row case must miss the fits cache), then the agreement
            pod = gpu_pod("probe", millicores=str(100 + serial * 7 + len(
                str(dirty))))
            assert mirrored._device.batch_fit(pod, names) is not None
            assert self._moved(before) == expected, dirty
            self._agree(cluster, pod, f"dirty {dirty}")
            # the staged control solves 12 rows, the mirror 16: its one
            # program was compiled by the warm-up above
            assert binpack_kernel._cache_size() == programs, dirty

    def test_filters_race_a_booker(self, cluster):
        _kube, cache, names, _host, _staged, mirrored = cluster
        packer, mirror = mirrored._device, mirrored._device.mirror
        installed, errors = [], []
        install = mirror.install

        def recording_install(base, used):
            state = install(base, used)
            installed.append(mirror._device[0])
            return state

        mirror.install = recording_install
        stop = threading.Event()

        def filtering(offset):
            try:
                turn = 0
                while not stop.is_set():
                    turn += 1
                    pod = gpu_pod("probe", millicores=str(
                        50 + (offset + turn) % 40))
                    fits = packer.batch_fit(pod, names)
                    assert fits is not None and len(fits) == len(names)
            except BaseException as exc:  # noqa: BLE001 — reported below
                errors.append(exc)

        def booking():
            try:
                rng = np.random.default_rng(5)
                turn = 0
                while not stop.is_set():
                    turn += 1
                    node = names[int(rng.integers(0, len(names)))]
                    pod = gpu_pod(f"r{turn}", millicores="3", node_name=node)
                    cache.adjust_pod_resources_locked(pod, True, "card0", node)
                    if turn % 3:
                        cache.adjust_pod_resources_locked(
                            pod, False, "card0", node)
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=filtering, args=(0,)),
                   threading.Thread(target=filtering, args=(17,)),
                   threading.Thread(target=booking)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for thread in threads:
                thread.start()
            time.sleep(1.5)
        finally:
            stop.set()
            for thread in threads:
                thread.join(timeout=30)
            sys.setswitchinterval(interval)
            mirror.install = install
        assert not any(thread.is_alive() for thread in threads)
        assert not errors, errors
        # a stale solve never replaced a newer version's state
        assert len(installed) > 3 and installed == sorted(installed)
        self._agree(cluster, gpu_pod("after", millicores="60"), "after")


class _HeldFits:
    """A solve's ``fits`` whose readback waits for the test."""

    def __init__(self, fits, reached, release):
        self.fits, self.reached, self.release = fits, reached, release

    def __array__(self, dtype=None, copy=None):
        self.reached.set()
        assert self.release.wait(30)
        return np.asarray(self.fits)


class TestVerbLocking:
    """PR 34: which lock a Filter takes follows which path answers it —
    the usage mirror's for a device Filter, the verbs' mutex for the
    host loop — so a Bind books while a device Filter waits for the
    chip (gas/scheduler.py ``_filter_nodes``)."""

    NAMES = ["n0", "n1", "n2"]
    CARD_MILLICORES = 1000

    @pytest.fixture
    def verbs(self):
        kube = FakeKubeClient()
        for name in self.NAMES:
            # i915 to spare: millicores decide every fit
            kube.add_node(gpu_node(
                name, cards=2, i915=64, millicores=2 * self.CARD_MILLICORES))
        for index in range(400):
            kube.add_pod(gpu_pod(f"p{index}", millicores=str(
                (350, 600, 250, 700)[index % 4])))
        cache = Cache(kube, start=False)
        ext = GASExtender(kube, cache=cache, use_device=True, use_mirror=True)
        host = GASExtender(kube, cache=cache, use_device=False)
        cache.start()
        mirror = ext._device.mirror
        assert wait_until(lambda: int(mirror._known.sum()) == len(self.NAMES))
        yield kube, cache, ext, host
        cache.stop()

    @staticmethod
    def _overlapped():
        return trace.COUNTERS.get("pas_gas_bind_overlapped_total")

    def _filter(self, ext, pod):
        request = post({"Pod": pod.raw, "NodeNames": self.NAMES})
        request.span = trace.Span("POST /scheduler/filter")
        response = ext.filter(request)
        assert response.status == 200, response.body
        return json.loads(response.body), request.span

    @staticmethod
    def _bind(ext, name, node):
        request = post({"PodName": name, "PodNamespace": "default",
                        "PodUID": f"uid-{name}", "Node": node})
        request.span = trace.Span("POST /scheduler/bind")
        return ext.bind(request), request.span

    @staticmethod
    def _passed(answer):
        return set(answer["NodeNames"] or ())

    def test_a_bind_books_while_a_device_filter_waits_for_the_chip(
        self, verbs, monkeypatch
    ):
        _kube, cache, ext, host = verbs
        probe = gpu_pod("probe", millicores="600")
        # serial Filter then Bind (card0 of n0 to 350): no Bind beside a
        # Filter, the counter stays
        before = self._overlapped()
        answer, span = self._filter(ext, probe)
        assert self._passed(answer) == set(self.NAMES)
        assert span.attrs["path"] == "device"
        assert "lock_wait" not in span.stage_seconds()
        response, _span = self._bind(ext, "p0", "n0")
        assert response.status == 200, response.body
        assert self._overlapped() == before

        reached, release = threading.Event(), threading.Event()
        inner = gas_device.binpack_kernel

        def held_kernel(*args, **kwargs):
            result = inner(*args, **kwargs)
            return result._replace(
                fits=_HeldFits(result.fits, reached, release))

        monkeypatch.setattr(gas_device, "binpack_kernel", held_kernel)
        # 700 fits card1 of n0 only: after p3 (700) books there it does not
        wide = gpu_pod("wide", millicores="700")
        held = {}
        filtering = threading.Thread(
            target=lambda: held.update(answer=self._filter(ext, wide)))
        filtering.start()
        try:
            assert reached.wait(30)
            monkeypatch.setattr(gas_device, "binpack_kernel", inner)
            # the Filter has staged and dispatched and waits for the
            # chip: the Bind is answered meanwhile
            response, bind_span = self._bind(ext, "p3", "n0")
            assert response.status == 200, response.body
            assert filtering.is_alive() and not release.is_set()
            assert self._overlapped() == before + 1
            assert "lock_wait" in bind_span.stage_seconds()
            used = cache.get_node_resource_status("n0")
            assert used["card1"]["gpu.intel.com/millicores"] == 700
        finally:
            release.set()
            filtering.join(timeout=30)
        assert not filtering.is_alive()
        # first fit on the state it staged: the one before the booking
        answer, span = held["answer"]
        assert self._passed(answer) == set(self.NAMES)
        assert span.attrs["path"] == "device"
        # the next Filter sees the booking, as the host loop does
        answer, _span = self._filter(ext, wide)
        assert self._passed(answer) == {"n1", "n2"}
        assert answer == self._filter(host, wide)[0]
        assert self._overlapped() == before + 1

    @pytest.mark.parametrize("why", ["no-device", "no-card-demand",
                                     "device-raises"])
    def test_a_host_path_filter_still_holds_the_verbs_mutex(
        self, verbs, monkeypatch, why
    ):
        _kube, cache, device_ext, host = verbs
        ext = host if why == "no-device" else device_ext
        pod = gpu_pod("probe", millicores="600")
        if why == "no-card-demand":
            pod = make_pod("probe", container_requests=[
                {"gpu.intel.com/millicores": "600"}])
        elif why == "device-raises":
            def broken(*args, **kwargs):
                raise RuntimeError("device lost")

            monkeypatch.setattr(ext._device, "batch_fit", broken)
        reached, release = threading.Event(), threading.Event()
        pod_read = threading.Event()
        logic, fetch_pod = ext._run_scheduling_logic, cache.fetch_pod

        def held_logic(pod, node_name):
            if threading.current_thread() is filtering:
                reached.set()
                assert release.wait(30)
            return logic(pod, node_name)

        def seen_fetch_pod(namespace, name):
            try:
                return fetch_pod(namespace, name)
            finally:
                pod_read.set()

        monkeypatch.setattr(ext, "_run_scheduling_logic", held_logic)
        monkeypatch.setattr(cache, "fetch_pod", seen_fetch_pod)
        before = self._overlapped()
        done = {}
        filtering = threading.Thread(
            target=lambda: done.update(filter=self._filter(ext, pod)))
        binding = threading.Thread(
            target=lambda: done.update(bind=self._bind(ext, "p0", "n0")))
        filtering.start()
        try:
            assert reached.wait(30)
            binding.start()
            assert pod_read.wait(30)
            # the Bind has its pod and wants the mutex the host loop holds
            binding.join(timeout=0.2)
            assert binding.is_alive()
            assert cache.get_node_resource_status("n0") == {}
        finally:
            release.set()
            filtering.join(timeout=30)
            binding.join(timeout=30)
        assert not filtering.is_alive() and not binding.is_alive()
        answer, span = done["filter"]
        assert span.attrs["path"] == "host"
        assert "lock_wait" in span.stage_seconds()
        assert self._passed(answer) == set(self.NAMES)
        response, bind_span = done["bind"]
        assert response.status == 200, response.body
        assert bind_span.stage_seconds()["lock_wait"] >= 0.1
        # only a Filter the device has counts as one a Bind overlapped
        assert self._overlapped() == before

    def test_two_filters_and_a_bind_race_through_the_verbs(self, verbs):
        kube, cache, ext, host = verbs
        mirror = ext._device.mirror
        cap = self.CARD_MILLICORES
        model = {name: {"card0": 0, "card1": 0} for name in self.NAMES}
        stop, errors, counts = threading.Event(), [], {"bound": 0, "refused": 0}

        def filtering(millicores):
            try:
                pod = gpu_pod("probe", millicores=str(millicores))
                passing = set(self.NAMES)
                while not stop.is_set():
                    answer, span = self._filter(ext, pod)
                    assert span.attrs["path"] == "device"
                    assert "lock_wait" not in span.stage_seconds()
                    # bookings only: each solve runs on a version no older
                    # than the last, so a node that filled never comes back
                    now = self._passed(answer)
                    assert now <= passing, (now, passing)
                    passing = now
            except BaseException as exc:  # noqa: BLE001 — reported below
                errors.append(exc)

        def binding():
            try:
                rng = np.random.default_rng(34)
                for index in range(400):
                    if stop.is_set():
                        break
                    name = f"p{index}"
                    node = self.NAMES[int(rng.integers(0, len(self.NAMES)))]
                    need = int(container_requests(
                        kube.get_pod("default", name)
                    )[0]["gpu.intel.com/millicores"])
                    want = next((card for card in ("card0", "card1")
                                 if model[node][card] + need <= cap), None)
                    response, _span = self._bind(ext, name, node)
                    if response.status == 200:
                        cards = kube.get_pod(
                            "default", name).get_annotations()[CARD_ANNOTATION]
                        assert cards == want, (name, node, cards, want)
                        model[node][cards] += need
                        counts["bound"] += 1
                    else:
                        # every refused Bind is a pod that did not fit
                        assert want is None, (name, node, model[node])
                        counts["refused"] += 1
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=filtering, args=(300,)),
                   threading.Thread(target=filtering, args=(650,)),
                   threading.Thread(target=binding)]
        before = self._overlapped()
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for thread in threads:
                thread.start()
            time.sleep(1.5)
        finally:
            stop.set()
            for thread in threads:
                thread.join(timeout=30)
            sys.setswitchinterval(interval)
        assert not any(thread.is_alive() for thread in threads)
        assert not errors, errors
        assert counts["bound"] >= 6 and self._overlapped() > before, counts
        assert ext._device_filters_in_flight == 0
        # no card over capacity, and the cache holds what was acknowledged
        for node, cards in model.items():
            used = cache.get_node_resource_status(node)
            for card, millicores in cards.items():
                assert millicores <= cap
                assert used.get(card, {}).get(
                    "gpu.intel.com/millicores", 0) == millicores
        # device and host loop agree afterwards
        for millicores in ("250", "600", "1000"):
            pod = gpu_pod("after", millicores=millicores)
            assert self._filter(ext, pod)[0] == self._filter(host, pod)[0]
        with mirror._lock:
            _version, _structure, state = mirror._device
            assert np.array_equal(i64.to_int64_np(state.used), mirror._used)

    def test_a_rolled_back_booking_is_seen_then_gone(self, verbs, monkeypatch):
        kube, cache, ext, host = verbs
        mirror = ext._device.mirror
        wide = gpu_pod("wide", millicores="700")
        # card0 of n0 to 600: 700 then fits card1 of n0 only
        assert self._bind(ext, "p1", "n0")[0].status == 200
        assert self._passed(self._filter(ext, wide)[0]) == set(self.NAMES)
        with mirror._lock:
            row = mirror._used[mirror._node_index["n0"]].copy()
        reached, release = threading.Event(), threading.Event()

        def failing_bind(*args, **kwargs):
            reached.set()
            assert release.wait(30)
            raise RuntimeError("the API refused the binding")

        monkeypatch.setattr(kube, "bind_pod", failing_bind)
        done = {}
        binding = threading.Thread(
            target=lambda: done.update(bind=self._bind(ext, "p3", "n0")))
        binding.start()
        try:
            assert reached.wait(30)
            # the booking is made and its Bind not yet answered: a Filter
            # beside it refuses the node it could have passed, never the
            # reverse
            answer, span = self._filter(ext, wide)
            assert span.attrs["path"] == "device"
            assert self._passed(answer) == {"n1", "n2"}
        finally:
            release.set()
            binding.join(timeout=30)
        assert not binding.is_alive()
        response, _span = done["bind"]
        assert response.status == 404
        assert "refused the binding" in json.loads(response.body)["Error"]
        with mirror._lock:
            assert np.array_equal(
                mirror._used[mirror._node_index["n0"]], row)
        answer, _span = self._filter(ext, wide)
        assert self._passed(answer) == set(self.NAMES)
        assert answer == self._filter(host, wide)[0]
