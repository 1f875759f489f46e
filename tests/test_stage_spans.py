"""Sub-stages on the one span system (utils/trace.py): GAS inside
``kernel``, ``read`` + ``handle`` + ``write`` on both front-ends, the TAS
Filter probe, the refresh pass by counters, collector pauses.

Counts and tilings, never wall-clock thresholds: where a tiling bar is
set, a slow fake (device call, API write) dominates the timeline, so the
bar passes exactly when the stages tile their container — any
unattributed gap would blow the 10% (the pattern of
test_observability.TestAccounting)."""

import gc
import json
import subprocess
import sys
import threading
import time

import pytest

from benchmarks.http_load import build_extender, make_bodies
from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.gas import device as gas_device
from platform_aware_scheduling_tpu.gas.cache import Cache
from platform_aware_scheduling_tpu.gas.scheduler import GASExtender
from platform_aware_scheduling_tpu.kube.informer import Informer, ListWatch
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import (
    DummyMetricsClient,
    NodeMetric,
)
from platform_aware_scheduling_tpu.testing.builders import make_node, make_pod
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.utils import decisions, trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity
from wirehelpers import (
    post_bytes, raw_request, start_async, start_threaded,
    wait_for_span as _wait_for_span,
)

SLOW_S = 0.25  # what a slow fake takes: 10% of it is the flake budget

GAS_FILTER_PARTS = (
    "lock_wait", "mirror_wait", "state_upload", "req_upload", "solve",
    "rows", "verdict",
)
GAS_BIND_PARTS = ("pod_get", "lock_wait", "book", "api_write", "record")


def _gpu_node(name):
    return make_node(
        name,
        labels={"gpu.intel.com/cards": "card0.card1"},
        allocatable={
            "gpu.intel.com/i915": "2",
            "gpu.intel.com/millicores": "2000",
            "gpu.intel.com/memory.max": "4000",
        },
    )


def _gpu_pod(name):
    return make_pod(name, container_requests=[
        {"gpu.intel.com/i915": "1", "gpu.intel.com/millicores": "500"}
    ])


def _request(path, obj):
    span = trace.Span(f"POST {path}")
    return HTTPRequest(
        method="POST", path=path,
        headers={"Content-Type": "application/json"},
        body=json.dumps(obj).encode(), span=span,
    )


@pytest.fixture
def gas():
    kube = FakeKubeClient()
    for index in range(4):
        kube.add_node(_gpu_node(f"n{index}"))
    cache = Cache(kube, start=False)
    ext = GASExtender(kube, cache=cache, use_device=True, use_mirror=True)
    cache.start()
    yield kube, cache, ext
    cache.stop()


def _wait_until(predicate, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.01)
    raise AssertionError("the informer never delivered")


def _fetched(cache, name):
    try:
        return cache.fetch_pod("default", name) is not None
    except Exception:
        return False


@pytest.fixture
def every_span_sampled(monkeypatch):
    """The sampled stages (handle, write_arm, the TAS native paths'
    sub-stages) on every span, not on one in SAMPLE_EVERY."""
    monkeypatch.setattr(trace, "SAMPLE_EVERY", 1)


def _tiles(span, container, parts):
    stages = span.stage_seconds()
    assert container in stages, sorted(stages)
    return sum(stages.get(p, 0.0) for p in parts), stages[container], stages


class TestGasStages:
    def test_filter_stages_tile_kernel(self, gas, monkeypatch):
        kube, _cache, ext = gas
        names = [f"n{i}" for i in range(4)]
        pod = _gpu_pod("probe")
        # warm: compiles the binpack kernel outside the measured request
        ext.filter(_request("/scheduler/filter",
                            {"Pod": pod.raw, "NodeNames": names}))
        inner = gas_device.binpack_kernel

        def slow_kernel(*args, **kwargs):
            time.sleep(SLOW_S)
            return inner(*args, **kwargs)

        monkeypatch.setattr(gas_device, "binpack_kernel", slow_kernel)
        # a new template: the fits cache must miss, so every stage runs
        other = make_pod("probe-2", container_requests=[
            {"gpu.intel.com/i915": "1", "gpu.intel.com/millicores": "700"}
        ])
        request = _request("/scheduler/filter",
                           {"Pod": other.raw, "NodeNames": names})
        assert ext.filter(request).status == 200
        covered, kernel, stages = _tiles(request.span, "kernel",
                                         GAS_FILTER_PARTS)
        for required in ("mirror_wait", "req_upload", "solve", "rows",
                         "verdict"):
            assert required in stages, (required, sorted(stages))
        # the device answers under the mirror's lock, not the verbs' mutex
        assert "lock_wait" not in stages, sorted(stages)
        assert stages["solve"] >= SLOW_S
        assert abs(kernel - covered) <= 0.10 * kernel, (covered, kernel, stages)
        assert request.span.attrs["path"] == "device"

    @pytest.mark.parametrize("why", ["no-device", "no-card-demand",
                                     "device-raises"])
    def test_a_host_path_filter_carries_lock_wait(self, gas, monkeypatch, why):
        """The host loop answers under the verbs' mutex, so its span
        carries the stage ``gas_lock_wait_ms`` reads — and none of the
        device's."""
        kube, cache, ext = gas
        names = [f"n{i}" for i in range(4)]
        pod = _gpu_pod("probe")
        if why == "no-device":
            ext = GASExtender(kube, cache=cache, use_device=False)
        elif why == "no-card-demand":
            pod = make_pod("probe", container_requests=[
                {"gpu.intel.com/millicores": "500"}])
        else:
            def broken(*args, **kwargs):
                raise RuntimeError("device lost")

            monkeypatch.setattr(ext._device, "batch_fit", broken)
        request = _request("/scheduler/filter",
                           {"Pod": pod.raw, "NodeNames": names})
        assert ext.filter(request).status == 200
        stages = request.span.stage_seconds()
        assert request.span.attrs["path"] == "host"
        assert "lock_wait" in stages, sorted(stages)
        assert not {"solve", "verdict"} & set(stages), sorted(stages)

    def test_a_moved_version_shows_as_state_upload(self, gas):
        kube, cache, ext = gas
        names = [f"n{i}" for i in range(4)]
        pod = _gpu_pod("probe")
        first = _request("/scheduler/filter",
                         {"Pod": pod.raw, "NodeNames": names})
        ext.filter(first)
        assert "state_upload" in first.span.stage_seconds()
        # nothing moved: the memoized device state serves, no upload
        again = _request("/scheduler/filter",
                         {"Pod": pod.raw, "NodeNames": names})
        ext.filter(again)
        assert "state_upload" not in again.span.stage_seconds()
        # a booking moves the mirror's version: the next Filter restages
        booked = _gpu_pod("booked")
        kube.add_pod(booked)
        cache.adjust_pod_resources_locked(booked, True, "card0", "n1")
        after = _request("/scheduler/filter",
                         {"Pod": pod.raw, "NodeNames": names})
        ext.filter(after)
        assert "state_upload" in after.span.stage_seconds()

    @pytest.mark.parametrize("change", ["rows", "nothing", "structure"])
    def test_the_device_stages_stay_on_every_solve(self, gas, change):
        """``state_upload``, ``req_upload`` and ``solve`` are on every
        Filter that reaches the device, whichever way its state was
        brought current (the per-layer metrics gas_*_ms read them)."""
        kube, cache, ext = gas
        names = [f"n{i}" for i in range(4)]
        ext.filter(_request("/scheduler/filter",
                            {"Pod": _gpu_pod("probe").raw, "NodeNames": names}))
        mirror = ext._device.mirror
        if change == "rows":  # travels in the update block
            booked = _gpu_pod("booked")
            cache.adjust_pod_resources_locked(booked, True, "card0", "n1")
        elif change == "structure":  # a full restage
            structure = mirror._structure
            node = _gpu_node("n2")
            node.metadata["resourceVersion"] = "9"
            kube.add_node(node)
            _wait_until(lambda: mirror._structure > structure)
        other = make_pod("probe-2", container_requests=[
            {"gpu.intel.com/i915": "1", "gpu.intel.com/millicores": "700"}
        ])
        request = _request("/scheduler/filter",
                           {"Pod": other.raw, "NodeNames": names})
        assert ext.filter(request).status == 200
        stages = request.span.stage_seconds()
        assert {"state_upload", "req_upload", "solve"} <= set(stages), stages
        assert request.span.attrs["path"] == "device"

    def test_bind_stages_tile_kernel(self, gas, monkeypatch):
        kube, cache, ext = gas
        pod = _gpu_pod("p")
        kube.add_pod(pod)
        _wait_until(lambda: _fetched(cache, "p"))
        inner = kube.bind_pod

        def slow_bind(*args, **kwargs):
            time.sleep(SLOW_S)
            return inner(*args, **kwargs)

        monkeypatch.setattr(kube, "bind_pod", slow_bind)
        request = _request("/scheduler/bind", {
            "PodName": "p", "PodNamespace": "default",
            "PodUID": pod.uid, "Node": "n0",
        })
        assert ext.bind(request).status == 200
        covered, kernel, stages = _tiles(request.span, "kernel",
                                         GAS_BIND_PARTS)
        for required in ("lock_wait", "book", "api_write"):
            assert required in stages, (required, sorted(stages))
        assert stages["api_write"] >= SLOW_S
        assert abs(kernel - covered) <= 0.10 * kernel, (covered, kernel, stages)

    def test_no_stage_name_is_shared_by_the_two_verbs_but_lock_wait(self):
        # a mean over POST /scheduler/* spans must mix no two stages
        shared = set(GAS_FILTER_PARTS) & set(GAS_BIND_PARTS)
        assert shared == {"lock_wait"}


class _SlowVerb:
    """Wraps an extender so that Prioritize takes SLOW_S inside handle."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def prioritize(self, request):
        time.sleep(SLOW_S)
        return self._inner.prioritize(request)


TOP_STAGES = ("arrive", "read", "handle", "write_arm", "write")


@pytest.mark.parametrize("front_end", ["threaded", "async"])
def test_read_handle_write_tile_the_span(front_end, every_span_sampled):
    ext, names = build_extender(48, device=True)
    slow = _SlowVerb(ext)
    server = (start_threaded(slow) if front_end == "threaded"
              else start_async(slow, window_s=0.001))
    try:
        body = make_bodies(names, "nodenames", count=1)[0]
        trace_id = f"tile-{front_end}"
        status, _headers, _ = raw_request(server.port, post_bytes(
            "/scheduler/prioritize", body,
            extra=f"X-Request-ID: {trace_id}\r\n"))
        assert status == 200
        span = _wait_for_span(trace_id)
        stages = span.stage_seconds()
        for required in ("read", "handle", "write"):
            assert required in stages, (required, sorted(stages))
        assert stages["handle"] >= SLOW_S
        # the threaded server stamps the wait before its first bytecode
        # (arrive, PR 37) where its reads are the native ones, and arms
        # its write timeout between handle and write where they are not
        # (write_arm; which path records which: tests/test_trace_cpu.py)
        tiled = (stages.get("arrive", 0.0) + stages["read"] + stages["handle"]
                 + stages.get("write_arm", 0.0) + stages["write"])
        assert "write_arm" not in stages or front_end == "threaded"
        assert abs(span.duration_s - tiled) <= 0.10 * span.duration_s, (
            tiled, span.duration_s, stages)
        # the verb's own stages (Prioritize's are all leaves) lie inside
        # handle, never beside it, and none is still open when it ends
        at = {name: (start, start + dur) for name, start, dur in span.stages}
        inside = [n for n in at if n not in TOP_STAGES]
        assert {"decode", "intern", "kernel", "encode"} <= set(inside), inside
        began, ended = at["handle"]
        for name in inside:
            assert began - 1e-6 <= at[name][0] <= at[name][1] <= ended + 1e-6, (
                name, at[name], at["handle"])
        assert sum(stages[n] for n in inside) <= stages["handle"], stages
    finally:
        server.shutdown()


@pytest.mark.parametrize("sampled", [False, True])
def test_an_unsampled_span_records_what_it_always_did(sampled, monkeypatch):
    """The sub-stages of the sub-millisecond verbs are sampled: a span
    that is not sampled carries the stages these verbs recorded before
    there were sub-stages, a sampled one the sub-stages beside them."""
    monkeypatch.setattr(trace, "SAMPLE_EVERY", 1 if sampled else 10**9)
    ext, names = build_extender(48, device=True)
    bodies = make_bodies(names, "nodenames", rotate_span=True, count=2)
    recorded = {}
    for verb in ("filter", "prioritize"):
        span = trace.Span(f"POST /scheduler/{verb}")
        span.sampled = sampled  # whatever the sequence number says
        response = getattr(ext, verb)(HTTPRequest(
            method="POST", path=f"/scheduler/{verb}",
            headers={"Content-Type": "application/json"}, body=bodies[1],
            span=span))
        assert response.status == 200
        recorded[verb] = sorted(set(span.stage_seconds()))
    always = {"filter": ["cache_probe", "intern"],
              "prioritize": ["decode", "encode", "intern", "kernel"]}
    if not sampled:
        assert recorded == always
    else:
        assert recorded["filter"] == sorted(
            always["filter"] + ["scan", "policy", "lookup", "fencode",
                                "record"])
        assert recorded["prioritize"] == sorted(
            always["prioritize"] + ["policy", "lookup", "record"])


def test_tas_filter_probe_carries_its_sub_stages(every_span_sampled):
    ext, names = build_extender(48, device=True)
    body = make_bodies(names, "nodenames", rotate_span=True, count=2)[1]
    span = trace.Span("POST /scheduler/filter")
    response = ext.filter(HTTPRequest(
        method="POST", path="/scheduler/filter",
        headers={"Content-Type": "application/json"}, body=body, span=span))
    assert response.status == 200
    stages = span.stage_seconds()
    for required in ("cache_probe", "scan", "policy", "intern", "lookup",
                     "fencode"):
        assert required in stages, (required, sorted(stages))
    inside = sum(stages[n] for n in ("scan", "policy", "intern", "lookup",
                                     "fencode", "record") if n in stages)
    assert inside <= stages["cache_probe"]


@pytest.mark.parametrize("sampled", [False, True])
def test_the_nodes_wire_filter_is_answered_inside_the_probe(
    sampled, monkeypatch
):
    """A Filter that carried ``Nodes``: with the native scanner the probe
    answers it (``cache_probe`` holds an always-on ``encode``, the native
    assembly; no ``decode``), the decision record reads ``native`` and the
    request counts once as a miss; without it the exact path decodes,
    and the parsed answer is the same object either way."""
    monkeypatch.setattr(trace, "SAMPLE_EVERY", 1 if sampled else 10**9)
    decisions.DECISIONS.clear()
    ext, names = build_extender(48, device=True)
    body = make_bodies(names, "nodes", rotate_span=True, count=2)[1]
    # counter -> the series read (None: the family's sum)
    COUNTED = {"cache_miss": None, "cache_bypass": None, "cache_hit": None,
               "native": {"wire": "nodes"}}

    def read():
        return {name: trace.COUNTERS.get(f"pas_filter_{name}_total",
                                         labels=labels)
                for name, labels in COUNTED.items()}

    def serve():
        span = trace.Span("POST /scheduler/filter")
        span.sampled = sampled
        before = read()
        response = ext.filter(HTTPRequest(
            method="POST", path="/scheduler/filter",
            headers={"Content-Type": "application/json"}, body=body,
            span=span))
        assert response.status == 200
        moved = {name: value - before[name] for name, value in read().items()}
        record = decisions.DECISIONS.snapshot(verb="filter")["records"][0]
        return response, span, moved, record

    native, span, moved, record = serve()
    stages = span.stage_seconds()
    assert "decode" not in stages and "kernel" not in stages, sorted(stages)
    assert stages["encode"] <= stages["cache_probe"]
    assert span.attrs["filter_cache"] == "miss"
    assert moved == {"cache_miss": 1, "cache_bypass": 0, "cache_hit": 0,
                     "native": 1}
    assert record["path"] == "native" and record["candidates"] == 48

    monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
    exact, span, moved, record = serve()
    stages = span.stage_seconds()
    assert "cache_probe" in stages and "decode" in stages, sorted(stages)
    assert moved == {"cache_miss": 0, "cache_bypass": 1, "cache_hit": 0,
                     "native": 0}
    assert record["path"] == "bypass"
    assert json.loads(native.body) == json.loads(exact.body)
    assert json.loads(native.body)["Nodes"]["items"]
    assert record["filtered"] == len(json.loads(native.body)["FailedNodes"])


class TestRefreshPassCounters:
    FAMILIES = ("pass", "fetch", "publish", "warm")

    def _read(self):
        return {f: trace.COUNTERS.get(f"pas_refresh_{f}_seconds_total")
                for f in self.FAMILIES}

    def test_one_pass_moves_the_four_counters(self):
        ext, names = build_extender(48, device=True)
        cache = ext.cache
        cache.write_metric("load_metric")  # registered: the pass fetches it
        client = DummyMetricsClient({"load_metric": {
            name: NodeMetric(value=Quantity(str(1000 + index)))
            for index, name in enumerate(names)}})
        before = self._read()
        cache.update_all_metrics(client)
        moved = {f: self._read()[f] - before[f] for f in self.FAMILIES}
        for family in self.FAMILIES:
            assert moved[family] > 0, (family, moved)
        assert (moved["fetch"] + moved["publish"] + moved["warm"]
                <= moved["pass"] + 1e-9), moved

    def test_a_cache_with_its_own_counters_keeps_them_there(self):
        from platform_aware_scheduling_tpu.utils.tracing import CounterSet

        counters = CounterSet()
        cache = AutoUpdatingCache(counters=counters)
        cache.write_metric("m")
        cache.update_all_metrics(DummyMetricsClient(
            {"m": {"a": NodeMetric(value=Quantity("1"))}}))
        for family in ("pass", "fetch", "publish"):
            assert counters.get(f"pas_refresh_{family}_seconds_total") > 0


class TestCollectorPauses:
    def test_gc_ms_on_a_span_a_collection_ran_inside(self):
        trace.watch_gc()
        trace.watch_gc()  # idempotent: one gc.callbacks entry
        assert gc.callbacks.count(trace._on_gc) == 1
        quiet = trace.Span("quiet").finish()
        assert "gc_ms" not in quiet.attrs
        span = trace.Span("collected")
        gc.collect()
        span.finish()
        assert span.attrs["gc_ms"] > 0
        # a span that began after the collection does not inherit it
        later = trace.Span("later").finish()
        assert "gc_ms" not in later.attrs

    def test_gc_families_are_valid_exposition(self):
        trace.watch_gc()
        gc.collect()
        families = trace.parse_prometheus_text(trace.exposition())
        assert families["pas_gc_pause_seconds_total"]["type"] == "counter"
        collections = families["pas_gc_collections_total"]["samples"]
        assert {labels["generation"] for _n, labels, _v in collections} <= {
            "0", "1", "2"}
        assert sum(v for _n, _l, v in collections) >= 1
        # the exposition moves the callback's tallies into COUNTERS, each
        # collection once (another may run between the two reads)
        flushed = trace.COUNTERS.get("pas_gc_collections_total")
        assert 1 <= flushed <= sum(trace._gc_counts)

    def test_the_callback_takes_no_lock_the_counters_take(self):
        """A collection can start inside CounterSet.inc, lock held: the
        callback must not deadlock on it."""
        trace.watch_gc()
        done = threading.Event()

        def collect_under_the_lock():
            with trace.COUNTERS._lock:
                gc.collect()
            done.set()

        worker = threading.Thread(target=collect_under_the_lock, daemon=True)
        worker.start()
        worker.join(timeout=10)
        assert done.is_set()


class TestStagePrimitive:
    def test_module_level_stage_feeds_a_seconds_counter(self):
        before = trace.COUNTERS.get("pas_refresh_warm_seconds_total")
        with trace.stage("rf.warm", "pas_refresh_warm_seconds_total"):
            pass
        assert trace.COUNTERS.get("pas_refresh_warm_seconds_total") > before

    def test_leaves_are_annotated_while_a_profile_is_taken(self, monkeypatch):
        class Annotation:
            enabled = True
            opened, closed = [], []

            def __init__(self, name):
                self.name = name
                self.opened.append(name)

            @classmethod
            def is_enabled(cls):
                return cls.enabled

            def __exit__(self, *exc):
                self.closed.append(self.name)

        monkeypatch.setattr(trace, "_ANNOTATION", Annotation)
        span = trace.Span("x")
        with span.stage("handle", leaf=False):
            with span.stage("decode"):
                pass
        with span.stage("lock_wait"):
            pass
        span.finish()
        # the container is recorded on the span and never annotated
        assert [n for n, _s, _d in span.stages] == [
            "decode", "handle", "lock_wait"]
        assert Annotation.opened == ["pas:decode", "pas:lock_wait"]
        assert Annotation.closed == Annotation.opened
        # no profile being taken: the span still records, nothing opens
        Annotation.enabled = False
        with span.stage("encode"):
            pass
        assert span.stages[-1][0] == "encode"
        assert len(Annotation.opened) == 2

    def test_sampled_stages_land_on_one_span_in_sample_every(self):
        """An odd period: of a scheduler's alternating verbs both are
        sampled, and a stage mean over the ring has both to read."""
        assert trace.SAMPLE_EVERY % 2 == 1
        verbs = ("filter", "prioritize")
        spans = []
        for index in range(4 * trace.SAMPLE_EVERY):
            span = trace.Span(verbs[index % 2])
            with span.stage("handle", leaf=False, sampled=True):
                with span.stage("decode"):
                    pass
                with span.stage("scan", sampled=True):
                    pass
            spans.append(span.finish())
        carrying = [s for s in spans if "scan" in s.stage_seconds()]
        # other threads of the test process may take sequence numbers too
        assert 3 <= len(carrying) <= 5, len(carrying)
        assert all(s.sampled and "handle" in s.stage_seconds()
                   for s in carrying)
        assert {s.name for s in carrying} == set(verbs)
        # every span carries the stages that are not sampled
        assert all("decode" in s.stage_seconds() for s in spans)
        with trace.NULL_SPAN.stage("scan", sampled=True):
            pass  # a no-op, like every null stage

    def test_annotation_names_fit_the_ledger(self):
        """``pas:`` + at most 12 characters: the ledger keeps ~30
        characters of a gap's host part."""
        import re

        pattern = re.compile(r"""\.stage\(\s*"([^"]+)\"""")
        root = __import__("pathlib").Path(trace.__file__).parents[1]
        names = set()
        for path in root.rglob("*.py"):
            names.update(pattern.findall(path.read_text()))
        assert {"handle", "lock_wait", "state_upload", "rf.wait",
                "inf.sync"} <= names
        too_long = sorted(n for n in names if len(n) > 12)
        assert not too_long, too_long

    def test_trace_imports_and_stages_run_with_jax_absent(self):
        code = (
            "import sys\n"
            "sys.modules['jax'] = None  # any import of jax now raises\n"
            "from platform_aware_scheduling_tpu.utils import trace\n"
            "span = trace.Span('x')\n"
            "with span.stage('decode'):\n"
            "    pass\n"
            "with trace.stage('rf.wait'):\n"
            "    pass\n"
            "assert [s[0] for s in span.stages] == ['decode']\n"
            "assert trace._ANNOTATION is False, trace._ANNOTATION\n"
            "print('ok')\n"
        )
        done = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
            cwd=str(__import__("pathlib").Path(trace.__file__).parents[2]))
        assert done.returncode == 0, done.stderr
        assert done.stdout.strip() == "ok"


def test_informer_resync_delivers_under_one_annotation(monkeypatch):
    """The resync delivery is wrapped in the ``inf.sync`` stage (and still
    re-delivers every cached object once)."""
    seen = []
    opened = []
    inner = trace.stage

    def spy(name, *args, **kwargs):
        opened.append(name)
        return inner(name, *args, **kwargs)

    monkeypatch.setattr(trace, "stage", spy)
    informer = Informer(
        ListWatch(lambda: ([], "1"), lambda rv: iter(()), lambda obj: ""),
        on_update=lambda old, new: seen.append(new),
    )
    informer._store = {"a": object(), "b": object()}
    informer._resync_once()
    assert len(seen) == 2
    assert opened == ["inf.sync"]


def test_new_families_are_declared():
    expected = {
        "pas_refresh_pass_seconds_total", "pas_refresh_fetch_seconds_total",
        "pas_refresh_publish_seconds_total", "pas_refresh_warm_seconds_total",
        "pas_gc_pause_seconds_total", "pas_gc_collections_total",
        "pas_filter_native_total", "pas_gas_bind_overlapped_total",
    }
    for name in expected:
        assert trace.METRICS[name][0] == "counter", name
