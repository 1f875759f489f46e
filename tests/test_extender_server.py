"""Wire-layer tests: middleware parity, routing, and JSON round-trips
(modeled on the reference's httptest-driven handler tests,
telemetry-aware-scheduling/pkg/telemetryscheduler/scheduler_test.go)."""

import hashlib
import http.client
import json
import signal
import socket
import socketserver
import struct
import threading
import time

import pytest

from platform_aware_scheduling_tpu.extender import server as server_module
from platform_aware_scheduling_tpu.extender.server import (
    HTTPRequest,
    HTTPResponse,
    Server,
    apply_middleware,
)
from platform_aware_scheduling_tpu.extender.types import (
    Args,
    BindingArgs,
    BindingResult,
    DecodeError,
    FilterResult,
    HostPriority,
    decode_host_priority_list,
    encode_host_priority_list,
)
from platform_aware_scheduling_tpu.kube.objects import Node, Pod
from platform_aware_scheduling_tpu.utils import trace
from wirehelpers import post_bytes, wait_for_span


class EchoScheduler:
    """Records calls; returns canned bodies."""

    def __init__(self):
        self.calls = []

    def filter(self, request):
        self.calls.append(("filter", request.body))
        return HTTPResponse.json(b'{"Error": ""}')

    def prioritize(self, request):
        self.calls.append(("prioritize", request.body))
        return HTTPResponse.json(b"[]")

    def bind(self, request):
        self.calls.append(("bind", request.body))
        return HTTPResponse.json(b'{"Error": ""}')


def make_request(method="POST", path="/scheduler/filter", content_type="application/json", body=b"{}"):
    headers = {}
    if content_type is not None:
        headers["Content-Type"] = content_type
    return HTTPRequest(method=method, path=path, headers=headers, body=body)


class TestMiddleware:
    """Status-code parity with extender/scheduler.go:15-52."""

    def handler(self, request):
        return HTTPResponse(status=200, body=b"ok")

    def test_wrong_content_type_404(self):
        resp = apply_middleware(self.handler, make_request(content_type="text/plain"))
        assert resp.status == 404

    def test_content_type_with_charset_rejected(self):
        # exact string comparison, as in the reference
        resp = apply_middleware(
            self.handler, make_request(content_type="application/json; charset=utf-8")
        )
        assert resp.status == 404

    def test_missing_content_type_404(self):
        resp = apply_middleware(self.handler, make_request(content_type=None))
        assert resp.status == 404

    def test_oversized_body_500(self):
        req = make_request()
        req.body = b"x"  # fake the size via a slotted override of len check
        big = HTTPRequest(req.method, req.path, req.headers, b"0" * 10)
        big.body = b"0" * 10
        # build a request whose body exceeds 1 GB without allocating one:
        class FakeBody(bytes):
            def __len__(self):
                return 2 * 1000 * 1000 * 1000

        big.body = FakeBody()
        resp = apply_middleware(self.handler, big)
        assert resp.status == 500

    def test_non_post_405(self):
        resp = apply_middleware(self.handler, make_request(method="GET"))
        assert resp.status == 405

    def test_ok_passthrough(self):
        resp = apply_middleware(self.handler, make_request())
        assert resp.status == 200 and resp.body == b"ok"


class TestRouting:
    def test_known_routes_dispatch(self):
        scheduler = EchoScheduler()
        server = Server(scheduler)
        for verb in ("filter", "prioritize", "bind"):
            resp = server.route(make_request(path=f"/scheduler/{verb}"))
            assert resp.status == 200
        assert [c[0] for c in scheduler.calls] == ["filter", "prioritize", "bind"]

    def test_unknown_path_404_with_json_header(self):
        server = Server(EchoScheduler())
        resp = server.route(make_request(path="/nope"))
        assert resp.status == 404
        assert resp.headers.get("Content-Type") == "application/json"


class TestWireTypes:
    def test_args_roundtrip(self):
        pod = Pod({"metadata": {"name": "p1", "namespace": "default",
                                "labels": {"telemetry-policy": "pol"}}})
        nodes = [Node({"metadata": {"name": "node1"}}),
                 Node({"metadata": {"name": "node2"}})]
        args = Args(pod=pod, nodes=nodes, node_names=None)
        decoded = Args.from_json(args.to_json())
        assert decoded.pod.name == "p1"
        assert decoded.pod.get_labels()["telemetry-policy"] == "pol"
        assert [n.name for n in decoded.nodes] == ["node1", "node2"]
        assert decoded.node_names is None

    def test_args_node_names_mode(self):
        args = Args.from_json(json.dumps(
            {"Pod": {"metadata": {"name": "p"}}, "Nodes": None,
             "NodeNames": ["a", "b"]}).encode())
        assert args.nodes is None
        assert args.node_names == ["a", "b"]

    def test_host_priority_list_roundtrip(self):
        hps = [HostPriority("node1", 10), HostPriority("node2", 9)]
        body = encode_host_priority_list(hps)
        obj = json.loads(body)
        assert obj == [{"Host": "node1", "Score": 10}, {"Host": "node2", "Score": 9}]
        assert decode_host_priority_list(body) == hps

    def test_filter_result_shape(self):
        result = FilterResult(
            nodes=[Node({"metadata": {"name": "n1"}})],
            node_names=["n1", ""],
            failed_nodes={"n2": "Node violates"},
            error="",
        )
        obj = json.loads(result.to_json())
        assert obj["Nodes"]["items"][0]["metadata"]["name"] == "n1"
        assert obj["NodeNames"] == ["n1", ""]
        assert obj["FailedNodes"] == {"n2": "Node violates"}
        assert obj["Error"] == ""

    def test_binding_args_decode(self):
        args = BindingArgs.from_json(json.dumps(
            {"PodName": "p", "PodNamespace": "ns", "PodUID": "u1", "Node": "n1"}
        ).encode())
        assert (args.pod_name, args.pod_namespace, args.pod_uid, args.node) == (
            "p", "ns", "u1", "n1")

    def test_binding_args_type_mismatch_is_decode_error(self):
        """Go decode parity: non-string Bind fields fail the whole decode
        (null into a value-typed string field has no effect and keeps the
        zero value)."""
        for body in (
            b'{"PodName": 3, "Node": "n"}',
            b'{"podUID": ["u"], "Node": "n"}',
            b'{"Node": {"name": "n"}}',
        ):
            with pytest.raises(DecodeError):
                BindingArgs.from_json(body)
        args = BindingArgs.from_json(b'{"PodName": null, "Node": "n"}')
        assert (args.pod_name, args.node) == ("", "n")

    def test_binding_result(self):
        assert json.loads(BindingResult().to_json()) == {"Error": ""}
        assert BindingResult.from_json(b'{"Error": "boom"}').error == "boom"


class TestLiveServer:
    """End-to-end over a real socket (unsafe/plain-HTTP mode)."""

    @pytest.fixture()
    def server(self):
        scheduler = EchoScheduler()
        server = Server(scheduler)
        server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
        assert server.wait_ready()
        yield server, scheduler
        server.shutdown()

    def post(self, port, path, body=b"{}", content_type="application/json"):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        headers = {"Content-Type": content_type} if content_type else {}
        conn.request("POST", path, body=body, headers=headers)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        return resp.status, data

    def test_post_filter(self, server):
        srv, scheduler = server
        status, data = self.post(srv.port, "/scheduler/filter")
        assert status == 200
        assert json.loads(data) == {"Error": ""}
        assert scheduler.calls[0][0] == "filter"

    def test_unknown_path(self, server):
        srv, _ = server
        status, _ = self.post(srv.port, "/bogus")
        assert status == 404

    def test_wrong_content_type(self, server):
        srv, _ = server
        status, _ = self.post(srv.port, "/scheduler/filter", content_type="text/plain")
        assert status == 404

    def test_get_rejected(self, server):
        srv, _ = server
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
        conn.request("GET", "/scheduler/filter", headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        conn.close()
        assert resp.status == 405

    def test_concurrent_posts(self, server):
        srv, scheduler = server
        errors = []

        def worker():
            try:
                status, _ = self.post(srv.port, "/scheduler/prioritize")
                assert status == 200
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(scheduler.calls) == 8


class DigestScheduler:
    """Answers with what it was given: the body's length and SHA-256."""

    def __init__(self):
        self.calls = 0

    def filter(self, request):
        self.calls += 1
        assert type(request.body) is bytes
        return HTTPResponse.json(json.dumps(_digest(request.body)).encode())

    prioritize = bind = filter


def _digest(body):
    return {"n": len(body), "sha": hashlib.sha256(body).hexdigest()}


def _body(n, salt):
    """``n`` bytes that differ wherever a misplaced piece would show."""
    block = hashlib.sha256(salt).digest()
    return (block * (n // len(block) + 1))[:n]


def _responses(sock, count):
    """``count`` answers off one connection: [(head, parsed JSON body)]."""
    out, buf = [], bytearray()
    while len(out) < count:
        end = buf.find(b"\r\n\r\n")
        length = None
        if end >= 0:
            for line in bytes(buf[:end]).split(b"\r\n")[1:]:
                name, _, value = line.partition(b":")
                if name.lower() == b"content-length":
                    length = int(value)
        if length is not None and len(buf) >= end + 4 + length:
            out.append((bytes(buf[:end]),
                        json.loads(bytes(buf[end + 4:end + 4 + length]))))
            del buf[:end + 4 + length]
            continue
        chunk = sock.recv(1 << 16)
        assert chunk, ("closed early", out, bytes(buf[:200]))
        buf += chunk
    assert not buf
    return out


def _closed_without_an_answer(sock):
    sock.settimeout(10)
    return sock.recv(1 << 16) == b""


#: the front-end's two read paths (extender/server.py _serve): _wirec's
#: stamped reads on a plain socket, the socket's own where there is none
READ_PATHS = [
    pytest.param("native", marks=pytest.mark.skipif(
        server_module.native_io() is None,
        reason="_wirec (recv_stamped, recv_body, send_answer) unavailable")),
    "no-native",
]


@pytest.mark.parametrize("path", READ_PATHS)
class TestBodyReads:
    """A body that does not come whole with its head, on both read paths:
    the same answers byte for byte, the framing and the time-outs kept."""

    @pytest.fixture()
    def served(self, path, monkeypatch):
        if path == "no-native":
            monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        monkeypatch.setattr(trace, "SAMPLE_EVERY", 1)
        scheduler = DigestScheduler()
        server = Server(scheduler)
        server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
        assert server.wait_ready()
        assert (server._httpd.RequestHandlerClass.native is not None) == (
            path == "native")
        sock = socket.create_connection(("127.0.0.1", server.port), timeout=15)
        try:
            yield sock, scheduler
        finally:
            sock.close()
            server.shutdown()

    def test_a_body_in_4k_pieces_is_answered_as_when_sent_whole(self, served):
        sock, _ = served
        body = _body(1_000_000, b"pieces")
        whole = post_bytes("/scheduler/filter", body, extra="X-Request-ID: same\r\n")
        sock.sendall(whole)
        sent_whole = _responses(sock, 1)
        for at in range(0, len(whole), 4096):
            sock.sendall(whole[at:at + 4096])
        in_pieces = _responses(sock, 1)
        assert in_pieces == sent_whole
        assert in_pieces[0][1] == _digest(body)

    def test_two_pipelined_requests_the_first_with_a_multi_read_body(self, served):
        sock, scheduler = served
        first, second = _body(300_000, b"first"), _body(70_000, b"second")
        one = post_bytes("/scheduler/filter", first)
        two = post_bytes("/scheduler/prioritize", second)
        sock.sendall(one[:100_000])
        time.sleep(0.05)  # the server is inside the first body's read
        sock.sendall(one[100_000:] + two)  # its tail and all of the next
        answers = _responses(sock, 2)
        assert [a[1] for a in answers] == [_digest(first), _digest(second)]
        assert all(a[0].startswith(b"HTTP/1.1 200 OK") for a in answers)
        # and a third, whole in the buffer the second one's read filled
        sock.sendall(two + two)
        assert [a[1] for a in _responses(sock, 2)] == [_digest(second)] * 2
        assert scheduler.calls == 4

    def test_a_body_whose_first_bytes_ride_with_the_head(self, served):
        sock, _ = served
        body = _body(200_000, b"rides")
        request = post_bytes("/scheduler/filter", body)
        head_end = request.index(b"\r\n\r\n") + 4
        sock.sendall(request[:head_end + 10])
        time.sleep(0.05)
        sock.sendall(request[head_end + 10:])
        assert _responses(sock, 1)[0][1] == _digest(body)

    def test_the_span_of_a_multi_read_verb(self, served, path):
        """One native read a body, however many pieces it came in (the
        kernel's recvs are counted apart); one call a piece through the
        socket.  The top stages tile the span with the body's read in it
        (which stages a path records: tests/test_trace_cpu.py)."""
        sock, _ = served
        request = post_bytes(
            "/scheduler/filter", _body(400_000, b"span"),
            extra=f"X-Request-ID: multi-{path}\r\n")
        for at in range(0, len(request), 40_000):
            sock.sendall(request[at:at + 40_000])
            time.sleep(0.005)
        _responses(sock, 1)
        span = wait_for_span(f"multi-{path}")
        if path == "native":
            assert 2 <= span.attrs["read_calls"] <= 3
            assert span.attrs["read_recvs"] > 1
            assert span.attrs["read_gil_ms"] >= 0
        else:
            assert span.attrs["read_calls"] > 3
            assert "read_recvs" not in span.attrs
        stages = span.stage_seconds()
        tiled = sum(stages.get(name, 0.0) for name in (
            "arrive", "read", "handle", "write_arm", "write"))
        assert abs(span.duration_s - tiled) <= 0.05 * span.duration_s, (
            tiled, span.duration_s, stages)
        # a body that came whole with its head is no read of its own
        sock.sendall(post_bytes(
            "/scheduler/filter", b"{}", extra=f"X-Request-ID: small-{path}\r\n"))
        _responses(sock, 1)
        small = wait_for_span(f"small-{path}")
        assert small.attrs["read_calls"] == 1
        assert "read_recvs" not in small.attrs

    def test_a_trickle_is_read_and_a_stall_ends_the_connection(
        self, served, monkeypatch
    ):
        """READ_HEADER_TIMEOUT_S is so long without a byte, not so long for
        the body; past it the connection ends without an answer."""
        sock, scheduler = served
        monkeypatch.setattr(server_module, "READ_HEADER_TIMEOUT_S", 0.5)
        body = _body(160, b"trickle")
        request = post_bytes("/scheduler/filter", body)
        head_end = request.index(b"\r\n\r\n") + 4
        began = time.monotonic()
        sock.sendall(request[:head_end])
        for at in range(head_end, len(request), 10):  # 16 pieces, 0.8 s
            time.sleep(0.05)
            sock.sendall(request[at:at + 10])
        assert _responses(sock, 1)[0][1] == _digest(body)
        assert time.monotonic() - began > 0.5
        sock.sendall(request[:head_end + 80])  # half a body, then silence
        assert _closed_without_an_answer(sock)
        assert scheduler.calls == 1

    def test_a_peer_that_closes_mid_body_gets_no_answer(self, served):
        sock, scheduler = served
        request = post_bytes("/scheduler/filter", _body(100_000, b"closes"))
        sock.sendall(request[:50_000])
        sock.shutdown(socket.SHUT_WR)
        assert _closed_without_an_answer(sock)
        assert scheduler.calls == 0

    def test_a_length_there_is_no_room_for_ends_the_connection(
        self, served, path, monkeypatch
    ):
        """The body's room is taken when only its length has been declared:
        where there is none the connection ends like one that stalled."""
        first, scheduler = served
        declared = 64 << 20

        def no_room(*args):  # recv_body's, or bytearray's, arguments
            if declared in args:
                raise MemoryError
            return bytearray(*args)

        if path == "native":
            monkeypatch.setattr(server_module.native_io(), "recv_body", no_room)
        else:
            monkeypatch.setattr(server_module, "bytearray", no_room, raising=False)
        # socketserver's own handler would end the connection too, with a
        # traceback on stderr: the front-end's is meant to, silently
        unhandled = []
        monkeypatch.setattr(
            socketserver.BaseServer, "handle_error",
            lambda self, request, address: unhandled.append(address))
        request = post_bytes("/scheduler/filter", b"x" * 100).replace(
            b"Content-Length: 100", b"Content-Length: %d" % declared)
        # a connection of its own: a handler picks its reads when it begins
        sock = socket.create_connection(first.getpeername(), timeout=15)
        try:
            sock.sendall(request)
            assert _closed_without_an_answer(sock)
        finally:
            sock.close()
        assert scheduler.calls == 0 and not unhandled

    def test_an_oversized_length_is_refused_before_any_read(self, served):
        sock, scheduler = served
        sock.sendall(
            b"POST /scheduler/filter HTTP/1.1\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {server_module.MAX_CONTENT_LENGTH + 1}".encode()
            + b"\r\n\r\nxx")
        data = sock.recv(1 << 16)
        assert data.startswith(b"HTTP/1.1 500 ") and b"Connection: close" in data
        assert scheduler.calls == 0


class SizedScheduler:
    """Answers a request whose body is a size ``n`` with ``n`` bytes, once
    ``gate`` is open; ``asked`` is set as a request reaches it."""

    def __init__(self):
        self.gate, self.asked = threading.Event(), threading.Event()
        self.gate.set()

    def filter(self, request):
        self.asked.set()
        assert self.gate.wait(10)
        return HTTPResponse.json(_body(int(request.body), b"answer"))

    prioritize = bind = filter


def _ask(size, request_id):
    return post_bytes("/scheduler/filter", str(size).encode(),
                      extra=f"X-Request-ID: {request_id}\r\n")


def _expected(size, request_id):
    """The answer's bytes as the reference's net/http frames them."""
    return (b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
            + f"X-Request-ID: {request_id}\r\nContent-Length: {size}\r\n\r\n"
            .encode()) + _body(size, b"answer")


def _raw_answer(sock):
    """One answer off a keep-alive connection, head and body, as sent; or
    what came before the peer closed."""
    buf = bytearray()
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(1 << 16)
        if not chunk:
            return bytes(buf)
        buf += chunk
    head_end = buf.index(b"\r\n\r\n") + 4
    length = int(bytes(buf[:head_end]).split(b"Content-Length: ")[1].split(b"\r\n")[0])
    while len(buf) < head_end + length:
        chunk = sock.recv(1 << 16)
        if not chunk:
            break
        buf += chunk
    return bytes(buf)


def _small_window_connection(port):
    """A client whose receive buffer is a few KB (set before the connect,
    so that the window it offers is that small)."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    sock.settimeout(15)
    sock.connect(("127.0.0.1", port))
    return sock


#: from nothing to past what a loopback socket's send buffer holds (4 MB)
ANSWER_SIZES = [0, 2_000, 1_400_000, 6_000_000]


@pytest.mark.parametrize("path", READ_PATHS)
class TestAnswers:
    """An answer's way out on both write paths (extender/server.py _serve):
    ``send_answer`` on a plain socket with _wirec, ``sendall`` where there is
    none — the same bytes, and the write's time-out and errors kept."""

    @pytest.fixture()
    def answering(self, path, monkeypatch):
        if path == "no-native":
            monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        monkeypatch.setattr(trace, "SAMPLE_EVERY", 1)
        scheduler = SizedScheduler()
        server = Server(scheduler)
        server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
        assert server.wait_ready()
        try:
            yield server, scheduler
        finally:
            scheduler.gate.set()
            server.shutdown()

    @pytest.mark.parametrize("size", ANSWER_SIZES)
    def test_the_same_bytes_on_either_write_path(self, answering, path, size):
        server, _ = answering
        with socket.create_connection(("127.0.0.1", server.port), timeout=15) as sock:
            for turn in range(2):  # keep-alive: the second follows the first
                request_id = f"answer-{path}-{size}-{turn}"
                sock.sendall(_ask(size, request_id))
                assert _raw_answer(sock) == _expected(size, request_id)
        span = wait_for_span(f"answer-{path}-{size}-1")
        names = [name for name, _start, _dur in span.stages]
        if path == "native":
            assert span.attrs["write_sends"] >= 1
            assert span.attrs["write_releases"] in (0, 1)
            assert "write_arm" not in names
        else:
            assert "write_sends" not in span.attrs
            assert "write_releases" not in span.attrs
            assert "write_arm" in names

    def test_a_slow_reader_drives_the_partial_send(self, answering, path):
        """6 MB against a few KB of window and a send buffer of at most
        4 MB: the kernel cannot take the answer whole, and what it did not
        take goes out under one release of the interpreter."""
        server, _ = answering
        request_id = f"slow-{path}"
        with _small_window_connection(server.port) as sock:
            sock.sendall(_ask(6_000_000, request_id))
            time.sleep(0.2)  # both buffers fill before a byte is read
            assert _raw_answer(sock) == _expected(6_000_000, request_id)
        span = wait_for_span(request_id)
        if path == "native":
            assert span.attrs["write_releases"] == 1
            assert span.attrs["write_sends"] > 1

    def test_a_peer_that_stops_reading_ends_the_connection(
        self, answering, path, monkeypatch
    ):
        server, _ = answering
        monkeypatch.setattr(server_module, "WRITE_TIMEOUT_S", 0.3)
        request_id = f"stops-{path}"
        with _small_window_connection(server.port) as sock:
            sock.sendall(_ask(6_000_000, request_id))
            span = wait_for_span(request_id, timeout=10)
            # what was sent before the time-out, then the end: no hang
            assert len(_raw_answer(sock)) < len(_expected(6_000_000, request_id))
        assert span.attrs["error"] == "write failed"
        assert 0.25 <= span.stage_seconds()["write"] < 5.0

    def test_a_peer_that_resets_gets_write_failed_and_no_signal(
        self, answering, path
    ):
        server, scheduler = answering
        scheduler.gate.clear()
        request_id = f"reset-{path}"
        signals = []
        previous = signal.signal(signal.SIGPIPE, lambda *args: signals.append(args))
        try:
            sock = socket.create_connection(("127.0.0.1", server.port), timeout=15)
            sock.sendall(_ask(6_000_000, request_id))
            assert scheduler.asked.wait(10)
            # linger 0: close sends a reset, and the answer meets it
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
            sock.close()
            scheduler.gate.set()
            span = wait_for_span(request_id)
            time.sleep(0.05)  # a pending handler runs at a bytecode boundary
        finally:
            signal.signal(signal.SIGPIPE, previous)
        assert span.attrs["error"] == "write failed"
        if path == "native":  # sendall leaves SIGPIPE to its disposition
            assert not signals


@pytest.mark.skipif(server_module.native_io() is None,
                    reason="_wirec (send_answer) unavailable")
def test_beside_a_spinning_thread_a_small_answer_keeps_the_interpreter(monkeypatch):
    """A thread that runs Python and lets go of nothing: sendall under a
    time-out would hand it the GIL twice an answer; a 2 KB answer goes out
    whole in the one send made with the GIL held."""
    server = Server(SizedScheduler())
    server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
    assert server.wait_ready()
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    spinner = threading.Thread(target=spin)
    spinner.start()
    try:
        with socket.create_connection(("127.0.0.1", server.port), timeout=15) as sock:
            for index in range(10):
                sock.sendall(_ask(2_000, f"spin-{index}"))
                assert _raw_answer(sock) == _expected(2_000, f"spin-{index}")
    finally:
        stop.set()
        spinner.join(10)
        server.shutdown()
    for index in range(10):
        span = wait_for_span(f"spin-{index}")
        assert (span.attrs["write_sends"], span.attrs["write_releases"]) == (1, 0)


def test_write_releases_fold_as_spans_finish():
    ring = trace.TraceBuffer()
    for releases in (1, 0, None, 1):
        span = trace.Span("POST /scheduler/filter")
        if releases is not None:
            span.set("write_releases", releases)
        ring.add(span.finish(200))
    other = trace.Span("GET /metrics")  # not a verb: not counted
    other.set("write_releases", 1)
    ring.add(other.finish(200))
    tally = dict(zip(trace.VERB_FAMILIES, ring.take_verb_tallies()))
    assert tally["pas_verb_total"] == 4
    assert tally["pas_verb_write_releases_total"] == 2
    assert "pas_verb_write_releases_total" in trace.METRICS


# _wirec.send_answer alone, over a socketpair


def _any_buffer_and_nothing_at_all(wirec, left, right):
    sent = []
    for head, body in ((b"head:", b"body"), (bytearray(b"head:"), memoryview(b"body"))):
        sent.append(wirec.send_answer(left.fileno(), head, body, 1.0))
        assert right.recv(64) == b"head:body"
    assert sent == [(1, 0), (1, 0)]
    assert wirec.send_answer(left.fileno(), b"", b"", 1.0) == (0, 0)
    assert wirec.send_answer(left.fileno(), b"", b"tail", 1.0) == (1, 0)
    assert right.recv(64) == b"tail"


def _what_the_kernel_will_not_take_waits_under_one_release(wirec, left, right):
    left.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
    head, body = b"h" * 100, _body(2_000_000, b"rest")
    got = bytearray()

    def read_slowly():
        time.sleep(0.1)
        while len(got) < len(head) + len(body):
            chunk = right.recv(1 << 16)
            if not chunk:
                return
            got.extend(chunk)

    reader = threading.Thread(target=read_slowly)
    reader.start()
    sends, released = wirec.send_answer(left.fileno(), head, body, 10.0)
    reader.join(10)
    assert (released, bytes(got)) == (1, head + body)
    assert sends > 1


def _a_reader_that_never_comes_times_out(wirec, left, right):
    began = time.monotonic()
    with pytest.raises(TimeoutError):
        wirec.send_answer(left.fileno(), b"", b"x" * 8_000_000, 0.2)
    assert 0.2 <= time.monotonic() - began < 5.0


def _a_blocking_descriptor_is_never_blocked_on(wirec, left, right):
    left.settimeout(None)  # what an unarmed accepted socket is
    with pytest.raises(TimeoutError):
        wirec.send_answer(left.fileno(), b"", b"x" * 8_000_000, 0.2)


def _a_peer_that_went_is_an_oserror_and_no_signal(wirec, left, right):
    right.close()
    signals = []
    previous = signal.signal(signal.SIGPIPE, lambda *args: signals.append(args))
    try:
        with pytest.raises(BrokenPipeError):  # an OSError: what _serve catches
            wirec.send_answer(left.fileno(), b"head", b"body", 1.0)
        time.sleep(0.01)
    finally:
        signal.signal(signal.SIGPIPE, previous)
    assert not signals


def _arguments_are_checked_before_any_send(wirec, left, right):
    with pytest.raises(OSError):
        wirec.send_answer(-1, b"", b"x", 0.1)
    with pytest.raises(TypeError):
        wirec.send_answer(left.fileno(), "text", b"", 0.1)
    fd = left.fileno()
    left.close()
    with pytest.raises(OSError):
        wirec.send_answer(fd, b"", b"x", 0.1)


SEND_ANSWER_CASES = {
    case.__name__.lstrip("_"): case for case in (
        _any_buffer_and_nothing_at_all,
        _what_the_kernel_will_not_take_waits_under_one_release,
        _a_reader_that_never_comes_times_out,
        _a_blocking_descriptor_is_never_blocked_on,
        _a_peer_that_went_is_an_oserror_and_no_signal,
        _arguments_are_checked_before_any_send,
    )
}


@pytest.mark.skipif(server_module.native_io() is None,
                    reason="_wirec (send_answer) unavailable")
@pytest.mark.parametrize("case", sorted(SEND_ANSWER_CASES))
def test_send_answer(case):
    left, right = socket.socketpair()
    try:
        SEND_ANSWER_CASES[case](server_module.native_io(), left, right)
    finally:
        left.close()
        right.close()


class TestDuration:
    def test_parse(self):
        from platform_aware_scheduling_tpu.utils.duration import parse_duration

        assert parse_duration("5s") == 5.0
        assert parse_duration("2s") == 2.0
        assert parse_duration("100ms") == 0.1
        assert parse_duration("1.5h") == 5400.0
        assert parse_duration("1m30s") == 90.0
        with pytest.raises(ValueError):
            parse_duration("5")
        with pytest.raises(ValueError):
            parse_duration("")
