"""The tracing layer over live sockets (docs/observability.md):
X-Request-ID echo (including 503 backpressure), span parity between the
threaded and async front-ends, batch spans linking their members,
/debug/traces boundedness, /metrics as valid Prometheus exposition, the
stage-sum-vs-end-to-end accounting bar, and the JAX retrace counter
under a shape-varying request sequence.

Everything is hermetic: in-process servers on 127.0.0.1 ephemeral ports,
small synthetic clusters seeded like benchmarks/http_load.
"""

import json
import threading
import time

from benchmarks.http_load import build_extender, make_bodies
from platform_aware_scheduling_tpu.extender.server import (
    HTTPRequest,
    HTTPResponse,
)
from platform_aware_scheduling_tpu.utils import trace
from wirehelpers import (
    get_request as _get,
    post_bytes as _post,
    raw_request as _raw,
    start_async as _start_async,
    start_threaded as _start_threaded,
)

HANDLER_STAGES = {"decode", "kernel", "encode"}


def _wait_for_span(trace_id: str, timeout: float = 5.0):
    """The span lands in TRACES after the response bytes are written;
    poll briefly so readers never race the writer."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        span = trace.TRACES.find(trace_id)
        if span is not None:
            return span
        time.sleep(0.005)
    raise AssertionError(f"span {trace_id} never recorded")


class TestRequestIdEcho:
    def test_threaded_echoes_provided_id(self):
        ext, names = build_extender(48, device=True)
        server = _start_threaded(ext)
        try:
            body = make_bodies(names, "nodenames", count=1)[0]
            status, headers, _ = _raw(
                server.port,
                _post(
                    "/scheduler/prioritize", body,
                    extra="X-Request-ID: tid-echo-1\r\n",
                ),
            )
            assert status == 200
            assert headers["x-request-id"] == "tid-echo-1"
            # absent header -> a generated id comes back
            status, headers, _ = _raw(
                server.port, _post("/scheduler/prioritize", body)
            )
            assert status == 200
            assert len(headers["x-request-id"]) == 32
            # non-verb responses carry it too (404 catch-all)
            status, headers, _ = _raw(server.port, _post("/nope", b"{}"))
            assert status == 404
            assert headers["x-request-id"]
        finally:
            server.shutdown()

    def test_async_echoes_on_503_backpressure(self):
        """The 503 shed at a saturated admission queue still carries the
        caller's X-Request-ID (and Retry-After)."""

        class Blocking:
            release = threading.Event()

            def prioritize(self, request):
                Blocking.release.wait(15)
                return HTTPResponse.json(b"[]\n")

            filter = prioritize

            def bind(self, request):
                return HTTPResponse(status=404)

        server = _start_async(
            Blocking(), window_s=0.0, max_batch=1, max_queue_depth=1
        )
        try:
            n = 5
            results = [None] * n

            def client(i):
                results[i] = _raw(
                    server.port,
                    _post(
                        "/scheduler/prioritize", b"{}",
                        extra=f"X-Request-ID: shed-{i}\r\n",
                    ),
                )

            threads = [
                threading.Thread(target=client, args=(i,)) for i in range(n)
            ]
            for t in threads:
                t.start()
                time.sleep(0.05)
            time.sleep(0.2)
            Blocking.release.set()
            for t in threads:
                t.join(20)
            statuses = [r[0] for r in results]
            assert 503 in statuses and 200 in statuses
            for i, (status, headers, _) in enumerate(results):
                assert headers["x-request-id"] == f"shed-{i}", status
                if status == 503:
                    assert "retry-after" in headers
                    span = _wait_for_span(f"shed-{i}")
                    assert span.attrs.get("rejected") is True
        finally:
            server.shutdown()


class TestSpanParity:
    def test_same_request_same_handler_stages_both_paths(self):
        """One request shape through the threaded and the async front-end
        produces spans with the SAME handler stages and path attribution —
        the trace vocabulary must not depend on the front-end."""
        ext_t, names = build_extender(64, device=True, seed=3)
        ext_a, _ = build_extender(64, device=True, seed=3)
        body = make_bodies(names, "nodenames", count=1)[0]
        threaded = _start_threaded(ext_t)
        try:
            status, _, t_body = _raw(
                threaded.port,
                _post(
                    "/scheduler/prioritize", body,
                    extra="X-Request-ID: parity-t\r\n",
                ),
            )
            assert status == 200
        finally:
            threaded.shutdown()
        asynchronous = _start_async(ext_a)
        try:
            status, _, a_body = _raw(
                asynchronous.port,
                _post(
                    "/scheduler/prioritize", body,
                    extra="X-Request-ID: parity-a\r\n",
                ),
            )
            assert status == 200
        finally:
            asynchronous.shutdown()
        assert t_body == a_body  # wire parity, as pinned by test_serving
        span_t = _wait_for_span("parity-t")
        span_a = _wait_for_span("parity-a")
        stages_t = {name for name, _, _ in span_t.stages}
        stages_a = {name for name, _, _ in span_a.stages}
        # identical handler-stage vocabulary...
        assert stages_t & HANDLER_STAGES == stages_a & HANDLER_STAGES
        assert "decode" in stages_t
        # ...identical attribution...
        assert span_t.attrs.get("verb") == span_a.attrs.get("verb")
        assert span_t.attrs.get("path") == span_a.attrs.get("path")
        # ...and the async extras are exactly the dispatch stages
        assert "queue_wait" in stages_a and "coalesce" in stages_a
        assert "queue_wait" not in stages_t

    def test_batch_span_links_n_request_spans(self):
        """N requests coalesced into one batch -> ONE serving_batch span
        linking all N member trace ids, each member pointing back."""
        n = 5
        ext, names = build_extender(96, device=True)
        server = _start_async(ext, window_s=0.25, max_batch=64)
        try:
            bodies = make_bodies(names, "nodenames", count=n)
            _raw(
                server.port, _post("/scheduler/prioritize", bodies[0])
            )  # warm: connection setup + caches
            barrier = threading.Barrier(n)
            errors = []

            def client(i):
                try:
                    barrier.wait(5)
                    status, _, _ = _raw(
                        server.port,
                        _post(
                            "/scheduler/prioritize", bodies[i],
                            extra=f"X-Request-ID: member-{i}\r\n",
                        ),
                    )
                    assert status == 200
                except Exception as exc:  # pragma: no cover - diagnostics
                    errors.append(exc)

            threads = [
                threading.Thread(target=client, args=(i,)) for i in range(n)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(20)
            assert not errors
            member_ids = {f"member-{i}" for i in range(n)}
            spans = [_wait_for_span(tid) for tid in sorted(member_ids)]
            batch_ids = {s.attrs.get("batch_id") for s in spans}
            assert len(batch_ids) == 1, "all members share one batch"
            snapshot = trace.TRACES.snapshot()
            batch = [
                entry
                for entry in snapshot["recent"]
                if entry["name"] == "serving_batch"
                and entry["id"] in batch_ids
            ]
            assert batch, "the batch span itself is recorded"
            assert member_ids <= set(batch[0]["links"])
            assert batch[0]["attrs"]["size"] >= n
            stage_names = {s["name"] for s in batch[0]["stages"]}
            assert {"coalesce", "batch_solve"} <= stage_names
        finally:
            server.shutdown()


class TestDebugTraces:
    def test_bounded_and_json(self, monkeypatch):
        """/debug/traces stays bounded no matter how many requests flow:
        recent <= capacity, slowest <= slow_capacity."""
        monkeypatch.setattr(
            trace, "TRACES", trace.TraceBuffer(capacity=8, slow_capacity=4)
        )
        ext, names = build_extender(48, device=True)
        server = _start_threaded(ext)
        try:
            body = make_bodies(names, "nodenames", count=1)[0]
            for _ in range(25):
                _raw(server.port, _post("/scheduler/prioritize", body))
            status, _, payload = _get(server.port, "/debug/traces")
            assert status == 200
            data = json.loads(payload)
            assert len(data["recent"]) <= 8
            assert len(data["slowest"]) <= 4
            assert data["capacity"] == 8
            # entries carry the span vocabulary
            entry = data["recent"][-1]
            assert entry["duration_ms"] > 0
            assert {s["name"] for s in entry["stages"]} & HANDLER_STAGES
            # non-GET is rejected
            status, _, _ = _raw(server.port, _post("/debug/traces", b"{}"))
            assert status == 405
        finally:
            server.shutdown()


class TestObservabilityUnderLoad:
    def test_debug_endpoints_bypass_saturated_queue(self):
        """GET /debug/traces and /metrics stay readable while the
        admission queue is saturated — the diagnostic surface must work
        exactly when the condition it diagnoses is happening."""

        class Blocking:
            release = threading.Event()

            def prioritize(self, request):
                Blocking.release.wait(15)
                return HTTPResponse.json(b"[]\n")

            filter = prioritize

            def bind(self, request):
                return HTTPResponse(status=404)

        server = _start_async(
            Blocking(), window_s=0.0, max_batch=1, max_queue_depth=1
        )
        try:
            # saturate: one request blocks the solver, one fills the queue
            blockers = [
                threading.Thread(
                    target=lambda: _raw(
                        server.port, _post("/scheduler/prioritize", b"{}")
                    )
                )
                for _ in range(2)
            ]
            for t in blockers:
                t.start()
                time.sleep(0.05)
            time.sleep(0.1)
            status, _, payload = _get(server.port, "/debug/traces")
            assert status == 200
            json.loads(payload)
            status, _, _ = _get(server.port, "/metrics")
            assert status == 200
        finally:
            Blocking.release.set()
            for t in blockers:
                t.join(20)
            server.shutdown()


class TestMetricsExposition:
    def test_threaded_metrics_round_trip(self):
        ext, names = build_extender(48, device=True)
        server = _start_threaded(ext)
        try:
            body = make_bodies(names, "nodenames", count=1)[0]
            _raw(server.port, _post("/scheduler/prioritize", body))
            _raw(server.port, _post("/scheduler/filter", body))
            status, _, payload = _get(server.port, "/metrics")
            assert status == 200
            families = trace.parse_prometheus_text(payload.decode())
            hist = families["pas_request_duration_seconds"]
            assert hist["type"] == "histogram"
            verbs = {
                labels.get("verb")
                for name, labels, _ in hist["samples"]
                if name.endswith("_count")
            }
            assert {"prioritize", "filter"} <= verbs
            assert families["pas_prioritize_native_total"]["type"] == "counter"
        finally:
            server.shutdown()

    def test_async_metrics_round_trip(self):
        ext, names = build_extender(48, device=True)
        server = _start_async(ext)
        try:
            body = make_bodies(names, "nodenames", count=1)[0]
            _raw(server.port, _post("/scheduler/prioritize", body))
            status, _, payload = _get(server.port, "/metrics")
            assert status == 200
            families = trace.parse_prometheus_text(payload.decode())
            hist = families["pas_request_duration_seconds"]
            assert hist["type"] == "histogram"
            verbs = {
                labels.get("verb")
                for name, labels, _ in hist["samples"]
                if name.endswith("_count")
            }
            # the extender's verb latencies and the serving stages share
            # ONE histogram family (a second family header would be
            # invalid exposition)
            assert {"prioritize", "serving_batch_solve"} <= verbs
            assert "pas_serving_requests_total" in families
        finally:
            server.shutdown()


class TestAccounting:
    def test_stage_sum_matches_end_to_end(self):
        """ISSUE 2 acceptance: one Prioritize request through the async
        path yields a trace whose queue_wait + coalesce + decode + kernel
        + encode stages sum to within 10% of the recorded end-to-end
        latency.  A generous coalescing window dominates the timeline, so
        the bar passes exactly when the stages tile the span — any
        unattributed gap would blow the 10%.  The window doubles as the
        flake budget: 10% of 0.5 s leaves ~50 ms for scheduler hiccups in
        the read/handoff/write slivers outside the five named stages."""
        ext, names = build_extender(64, device=True)
        server = _start_async(ext, window_s=0.5, max_batch=8)
        try:
            # a rotated candidate span: guaranteed response-cache MISS, so
            # decode/kernel/encode are all exercised (a hit legitimately
            # skips encode)
            body = make_bodies(names, "nodenames", rotate_span=True, count=2)[1]
            status, headers, _ = _raw(
                server.port,
                _post(
                    "/scheduler/prioritize", body,
                    extra="X-Request-ID: acct-1\r\n",
                ),
            )
            assert status == 200
            span = _wait_for_span("acct-1")
            stages = span.stage_seconds()
            for required in (
                "queue_wait", "coalesce", "decode", "kernel", "encode"
            ):
                assert required in stages, (required, sorted(stages))
            accounted = sum(
                stages[k]
                for k in ("queue_wait", "coalesce", "decode", "kernel", "encode")
            )
            total = span.duration_s
            assert total > 0
            assert abs(total - accounted) <= 0.10 * total, (
                accounted, total, stages,
            )
        finally:
            server.shutdown()

    def test_shape_varying_requests_increment_retrace_counter(self):
        """ISSUE 2 acceptance: a request sequence whose cluster grows past
        the current capacity bucket forces a kernel re-lowering, and that
        shows up on pas_jax_retrace_total — a recompile is a metric, not
        a mystery."""

        def req(body):
            return HTTPRequest(
                method="POST",
                path="/scheduler/prioritize",
                headers={"Content-Type": "application/json"},
                body=body,
            )

        before = trace.COUNTERS.get("pas_jax_retrace_total")
        ext1, names1 = build_extender(48, device=True)  # 64-node bucket
        assert ext1.prioritize(req(make_bodies(names1, "nodenames", count=1)[0])).status == 200
        # 3000 nodes -> a 4096-node capacity bucket: a shape no other
        # fixture in the suite compiles (test_record.py's 2,000 nodes take
        # the 2048 bucket, and a worker may run that file first), so the
        # ranking pass MUST re-lower
        ext2, names2 = build_extender(3000, device=True)
        assert ext2.prioritize(req(make_bodies(names2, "nodenames", count=1)[0])).status == 200
        after = trace.COUNTERS.get("pas_jax_retrace_total")
        assert after > before
        # the lowering shim also counted the compile itself
        assert trace.COUNTERS.get("pas_jax_kernel_compile_total") > 0


class TestPathAttribution:
    def test_prioritize_path_counters_partition_requests(self):
        """pas_prioritize_{native,native_host,exact}_total partition the
        verb's requests: their sum moves by exactly one per request, no
        matter which path answers (host_fallback is a separate overlap
        counter for degradation events)."""
        partition = (
            "pas_prioritize_native_total",
            "pas_prioritize_native_host_total",
            "pas_prioritize_exact_total",
        )

        def totals():
            return sum(trace.COUNTERS.get(name) for name in partition)

        ext, names = build_extender(48, device=True)
        bodies = make_bodies(names, "nodenames", count=3)
        before = totals()
        for body in bodies:
            response = ext.prioritize(
                HTTPRequest(
                    method="POST",
                    path="/scheduler/prioritize",
                    headers={"Content-Type": "application/json"},
                    body=body,
                )
            )
            assert response.status == 200
        assert totals() - before == 3

    def test_filter_cache_tier_counters_move(self):
        from platform_aware_scheduling_tpu.native import get_wirec

        ext, names = build_extender(48, device=True)
        body = make_bodies(names, "nodenames", count=1)[0]

        def req(b):
            return HTTPRequest(
                method="POST",
                path="/scheduler/filter",
                headers={"Content-Type": "application/json"},
                body=b,
            )

        tiers = (
            "pas_filter_cache_hit_total",
            "pas_filter_cache_miss_total",
            "pas_filter_cache_bypass_total",
        )

        def totals():
            return sum(trace.COUNTERS.get(name) for name in tiers)

        hit0 = trace.COUNTERS.get("pas_filter_cache_hit_total")
        bypass0 = trace.COUNTERS.get("pas_filter_cache_bypass_total")
        before = totals()
        assert ext.filter(req(body)).status == 200
        assert ext.filter(req(body)).status == 200
        # the tiers PARTITION requests: exactly one tick per request
        assert totals() - before == 2
        if get_wirec() is None:
            # no native scanner: every request is a bypass, still counted
            assert (
                trace.COUNTERS.get("pas_filter_cache_bypass_total")
                >= bypass0 + 2
            )
        else:
            # second identical request serves from the span cache
            assert trace.COUNTERS.get("pas_filter_cache_hit_total") > hit0
