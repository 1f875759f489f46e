"""HTTP(S) extender server: routing, middleware, and mTLS.

Route and middleware parity with the reference (extender/scheduler.go):
  * routes ``/scheduler/{prioritize,filter,bind}`` plus a 404 catch-all
    (scheduler.go:86-91);
  * middleware chain content-type -> length -> method: a request whose
    ``Content-Type`` is not exactly ``application/json`` gets 404
    (scheduler.go:41-52), a body over 1 GB gets 500 (scheduler.go:28-38),
    a non-POST gets 405 (scheduler.go:15-26);
  * TLS: >=1.2, ECDHE-{RSA,ECDSA}-AES256-GCM-SHA384 cipher pinning, required
    and verified client certificates against a CA pool, 5 s read-header /
    10 s write timeouts (scheduler.go:110-143).
"""

from __future__ import annotations

import socket
import socketserver
import ssl
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, TYPE_CHECKING

from platform_aware_scheduling_tpu.native import get_wirec
from platform_aware_scheduling_tpu.utils import (
    decisions,
    devicewatch,
    events,
    health,
    klog,
    trace,
)

if TYPE_CHECKING:  # pragma: no cover
    from platform_aware_scheduling_tpu.extender.types import Scheduler

MAX_CONTENT_LENGTH = 1 * 1000 * 1000 * 1000  # 1 GB (scheduler.go:30)
# request-head ceiling (status line + all headers); net/http's default is
# 1 MB, http.server enforced 64 KiB lines — without a cap a client that
# streams endless header bytes grows the buffer without bound
MAX_HEAD_LENGTH = 64 * 1024
READ_HEADER_TIMEOUT_S = 5.0
WRITE_TIMEOUT_S = 10.0


@dataclass
class HTTPRequest:
    method: str
    path: str
    headers: Dict[str, str]
    body: bytes
    # the request's trace span (utils/trace.py), attached by whichever
    # front-end accepted the connection; excluded from equality/repr so
    # request objects still compare by wire content alone
    span: Optional[object] = field(default=None, compare=False, repr=False)

    def header(self, name: str) -> str:
        # HTTP header names are case-insensitive
        for k, v in self.headers.items():
            if k.lower() == name.lower():
                return v
        return ""


@dataclass
class HTTPResponse:
    status: int = 200
    headers: Dict[str, str] = field(default_factory=dict)
    body: bytes = b""

    @classmethod
    def json(cls, body: bytes, status: int = 200) -> "HTTPResponse":
        return cls(status=status, headers={"Content-Type": "application/json"}, body=body)


#: the debug/observability surface, served by BOTH front-ends (each entry
#: also bypasses the async admission queue); ``GET /debug`` renders this
#: as the index so an operator can discover the endpoints from curl alone
DEBUG_ENDPOINTS = [
    {"path": "/healthz", "description": "process liveness (200 = alive)"},
    {"path": "/readyz", "description": "composite readiness: 503 + condition list until warm/fresh/synced"},
    {"path": "/metrics", "description": "Prometheus exposition: verb histograms, path attribution, pas_* families"},
    {"path": "/debug/traces", "description": "recent + slowest request traces; filters: ?verb=<verb>&min_ms=<float>"},
    {"path": "/debug/decisions", "description": "scheduling decision provenance records; filters: ?pod=<name>&verb=<verb>&limit=<n> (404 when --decisionLog=off)"},
    {"path": "/debug/rebalance", "description": "last rebalance plan + loop state (404 when --rebalance=off)"},
    {"path": "/debug/gangs", "description": "gang reservations + lifecycle state (404 when --gang=off)"},
    {"path": "/debug/admission", "description": "admission plane: priority queue entries, fairness streak, preemption planner state (404 when --admission=off)"},
    {"path": "/debug/forecast", "description": "per-metric forecast fits: slopes, horizons, uncertainty bands (404 when --forecast=off)"},
    {"path": "/debug/leader", "description": "leader-election state: role, lease holder, fencing token (404 when --leaderElect is off)"},
    {"path": "/debug/slo", "description": "SLO compliance, error budgets, and multi-window burn rates (404 when --slo=off)"},
    {"path": "/debug/control", "description": "budget feedback controller: knob settings, ladder levels, recent actuations with provenance (404 when --sloControl=off)"},
    {"path": "/debug/wire", "description": "wire-path caches: interned node-name universes, intern hit/miss/eviction counts, response-skeleton keys (404 without a device fastpath)"},
    {"path": "/debug/profile", "description": "bounded jax.profiler capture: ?ms=<window> (404 when unavailable)"},
    {"path": "/debug/explain", "description": "causal event spine: the ordered event chain + narrative for one entity; filters: ?pod=<ns/name>&gang=<id>&request_id=<id>&node=<name> (404 when --events=off)"},
    {"path": "/debug/record", "description": "flight-recorder capture as versioned JSONL: anonymized verb arrivals, telemetry deciles, eviction/leader events, spine passthrough (404 when --flightRecorder=off)"},
    {"path": "/debug/solve", "description": "solve observatory: per-stage solve attribution (snapshot/transfer/compile/execute/readback/encode), refresh churn per metric, recompile watch (404 when --solveObs=off)"},
    {"path": "/debug/shard", "description": "partition plane: partition map, journaled ownership + fencing epochs, digest ages, gossip health (404 when --shard=off)"},
    {"path": "/debug/whatif", "method": "POST", "description": "twin replay of a capture under transform knobs (load_multiplier, remove_nodes, thresholds): projected SLO verdicts + budget ledgers (404 when --flightRecorder=off)"},
]

#: index paths that must stay readable when the async admission queue is
#: saturated — every debug/observability endpoint (they exist to
#: diagnose exactly that condition and never touch the device).  Derived
#: from the index above so a new endpoint cannot be routed here but
#: silently left queued (or unindexed) on the async front-end;
#: /debug/profile is excluded because its bounded capture SLEEPS, and
#: /debug/whatif because it RUNS a twin replay — both must execute
#: off-loop on the async front-end (serving/http.py special-cases them).
EXECUTOR_DEBUG_PATHS = frozenset({"/debug/profile", "/debug/whatif"})
QUEUE_BYPASS_PATHS = frozenset(
    entry["path"] for entry in DEBUG_ENDPOINTS
    if entry["path"] not in EXECUTOR_DEBUG_PATHS
) | {"/debug", "/debug/"}


def parse_query(path: str) -> Dict[str, str]:
    """The ``?k=v&k2=v2`` tail of a request path as a dict with standard
    percent-decoding (a client sending ``?pod=default%2Fmy-pod`` must
    match the record keyed ``default/my-pod``); last occurrence of a
    repeated key wins."""
    from urllib.parse import unquote_plus

    _, _, query = path.partition("?")
    params: Dict[str, str] = {}
    for pair in query.split("&"):
        if not pair:
            continue
        key, _, value = pair.partition("=")
        params[unquote_plus(key)] = unquote_plus(value)
    return params


def not_found_handler(request: HTTPRequest) -> HTTPResponse:
    """404 catch-all for unknown paths (scheduler.go:79-84)."""
    klog.v(2).info_s(
        f"Requested resource: '{request.path}' not found", component="extender"
    )
    return HTTPResponse(status=404, headers={"Content-Type": "application/json"})


def apply_middleware(handler, request: HTTPRequest) -> HTTPResponse:
    """content-type -> content-length -> POST-only prechecks (scheduler.go:69-75).

    The content-type check is an exact string comparison, as in the reference
    (so ``application/json; charset=utf-8`` is rejected)."""
    if request.header("Content-Type") != "application/json":
        klog.v(2).info_s("request content type not application/json", component="extender")
        return HTTPResponse(status=404)
    if len(request.body) > MAX_CONTENT_LENGTH:
        klog.v(2).info_s("request size too large", component="extender")
        return HTTPResponse(status=500)
    if request.method != "POST":
        klog.v(2).info_s("method Type not POST", component="extender")
        return HTTPResponse(status=405)
    return handler(request)


_STATUS_REASON = {
    200: "OK",
    400: "Bad Request",
    404: "Not Found",
    405: "Method Not Allowed",
    431: "Request Header Fields Too Large",
    500: "Internal Server Error",
    503: "Service Unavailable",
}


class HeadParseError(Exception):
    """A request head that must be answered with a simple error response
    and a closed connection; ``status`` is the response status."""

    def __init__(self, status: int):
        super().__init__(f"bad request head ({status})")
        self.status = status


def parse_request_head(head: bytes):
    """Sans-IO parse of one request head (the bytes before ``CRLFCRLF``):
    returns ``(method, path, version, headers, lowered, body_length)`` or
    raises :class:`HeadParseError`.  This is the single source of the
    framing rules — strict Content-Length validation, duplicate-CL and
    Transfer-Encoding rejection, the 1 GB body refusal — shared by the
    threaded handler below and the asyncio front-end (serving/http.py),
    so both front-ends keep byte-identical wire behavior."""
    lines = head.split(b"\r\n")
    parts = lines[0].split(b" ")
    if len(parts) != 3:
        raise HeadParseError(400)
    try:
        method = parts[0].decode("ascii")
        path = parts[1].decode("ascii")
        version = parts[2].decode("ascii")
    except UnicodeDecodeError:
        raise HeadParseError(400) from None
    headers: Dict[str, str] = {}
    content_lengths = []
    for line in lines[1:]:
        name, sep, value = line.partition(b":")
        if not sep:
            continue
        if name != name.rstrip(b" \t"):
            # whitespace before the colon lets 'Transfer-Encoding :'
            # dodge the checks below (RFC 7230 §3.2.4 says reject)
            raise HeadParseError(400)
        key = name.decode("latin-1")
        headers[key] = value.strip().decode("latin-1")
        if key.lower() == "content-length":
            content_lengths.append(headers[key])
    lowered = {k.lower(): v for k, v in headers.items()}
    if "transfer-encoding" in lowered:
        # chunked bodies aren't deframed here; leaving one in the
        # keep-alive buffer would desync pipelining (request
        # smuggling surface behind a proxy) — reject outright
        raise HeadParseError(400)
    if len(set(content_lengths)) > 1:
        # differing duplicates MUST 400 (RFC 7230 §3.3.2): a
        # first-wins proxy in front would frame differently
        raise HeadParseError(400)
    raw_length = content_lengths[0] if content_lengths else "0"
    # strict framing: ASCII digits only (int() would accept '+5',
    # '5_0', whitespace — all desync vectors)
    if not (raw_length.isascii() and raw_length.isdigit()):
        raise HeadParseError(400)
    length = int(raw_length)
    if length > MAX_CONTENT_LENGTH:
        # parity with the ContentLength middleware check: refuse to
        # slurp oversized bodies
        raise HeadParseError(500)
    return method, path, version, headers, lowered, length


def render_head(response: HTTPResponse, close: bool) -> bytes:
    """Status line + headers + the blank line: all of an answer but its
    body (which ``send_answer`` sends from where it lies)."""
    reason = _STATUS_REASON.get(response.status, "Unknown")
    out = [f"HTTP/1.1 {response.status} {reason}\r\n".encode("ascii")]
    for k, v in response.headers.items():
        out.append(f"{k}: {v}\r\n".encode("latin-1"))
    out.append(f"Content-Length: {len(response.body)}\r\n".encode())
    if close:
        out.append(b"Connection: close\r\n")
    out.append(b"\r\n")
    return b"".join(out)


def render_response(response: HTTPResponse, close: bool) -> bytes:
    """Status line + headers + body as one buffer (one sendall/write)."""
    return render_head(response, close) + response.body


def render_simple(
    status: int, close: bool = False, request_id: str = ""
) -> bytes:
    """An empty-body status response (the head-framing error answers).
    ``request_id`` rides as ``X-Request-ID`` so even framing rejections
    are correlatable — for an unparseable head it is freshly generated
    (nothing client-sent survived the parse to echo)."""
    reason = _STATUS_REASON.get(status, "Unknown")
    extra = b"Connection: close\r\n" if close else b""
    if request_id:
        extra += f"X-Request-ID: {request_id}\r\n".encode("latin-1")
    return (
        f"HTTP/1.1 {status} {reason}\r\nContent-Length: 0\r\n".encode()
        + extra
        + b"\r\n"
    )


def native_io():
    """``_wirec`` where it has the socket helpers and their stamps are on
    the spans' clock, else None: ``recv_stamped`` for a request's head,
    ``recv_body`` for a body that did not come with it, ``send_answer``
    for every write (native/wirec.c).  The reads say when their bytes were
    there and when this thread held the interpreter again; each helper
    carries its own time-out.  The helpers stamp ``CLOCK_MONOTONIC``; spans
    run on ``time.perf_counter()``, so they are used only where that is the
    same clock."""
    if "CLOCK_MONOTONIC" not in time.get_clock_info("perf_counter").implementation:
        return None
    wirec = get_wirec()
    if all(hasattr(wirec, name)
           for name in ("recv_stamped", "recv_body", "send_answer")):
        return wirec
    return None


class _FastHTTPHandler(socketserver.BaseRequestHandler):
    """Minimal HTTP/1.1 connection handler for the extender hot path.

    Reads each request with a single rolling buffer (no per-line reads),
    dispatches through ``route`` (set by the enclosing Server), and writes
    status line + headers + body as one answer.  Supports
    keep-alive, pipelined requests, and ``Expect: 100-continue``.  Read
    and write timeouts mirror the reference server's 5 s / 10 s
    (scheduler.go:136-137): 5 s without a byte on the way in, 10 s for
    the answer.

    On a plain socket with ``_wirec`` loaded (``native``, set by the
    enclosing Server) a request gives the interpreter away once for its
    head and once for a body that did not come with it: the head through
    ``recv_stamped``, the body through ONE ``recv_body``, which fills a
    ``bytes`` of ``Content-Length`` in place however many ``recv`` the
    kernel needs.  An answer leaves through ONE ``send_answer``: head and
    body in one ``sendmsg`` with the interpreter HELD, given away once
    only where the kernel does not take it whole (span attributes
    ``write_sends``, ``write_releases``).  Each helper carries its own
    time-out, so the socket's is never armed.  The span begins where the
    request's first byte WAS THERE, not where this thread next ran: the
    stage ``arrive`` is the wait for the interpreter between the two.  A
    TLS connection, or a process without ``_wirec``, reads with
    ``sock.recv`` / ``sock.recv_into`` (one release a call), arms the
    socket's time-out before the head and before the answer (stage
    ``write_arm``), writes with ``sendall`` and records no ``arrive``; the
    answers are the same bytes."""

    route = staticmethod(lambda request: HTTPResponse(status=500))
    native = None
    rbufsize = 1 << 16

    def handle(self) -> None:
        # the thread ledger (utils/trace.py): socketserver names no thread,
        # and a closed connection's CPU seconds stay on its role's account
        trace.name_connection_thread()
        try:
            self._serve()
        finally:
            trace.fold_thread_cpu()

    def _serve(self) -> None:  # noqa: C901 — one tight loop, deliberately
        sock = self.request
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass
        # an SSLSocket is a socket.socket too: its bytes are not the fd's
        native = type(self).native if type(sock) is socket.socket else None
        # the native helpers carry their own time-outs and never consult
        # the socket's, which is left unarmed
        stamped = read_body = None
        if native is not None:
            stamped, read_body = native.recv_stamped, native.recv_body
        fd = sock.fileno()
        buf = bytearray()
        while True:
            # -- read the request head --------------------------------------
            # span timing starts at the request's FIRST byte (leftover
            # pipelined bytes count as already-arrived): stamping at loop
            # entry would charge keep-alive idle time between requests to
            # the next request's read stage (utils/trace.py).  t_ready:
            # the first byte was there (stamped reads only); t_accept:
            # this thread held the interpreter with it; cpu0: its CPU
            # clock then, on the spans picked to read it (a system call:
            # trace.cpu_sample_due).  gil: what the reads after the first
            # waited for the interpreter with their bytes in hand.  calls:
            # the reads made from Python, each one release of the GIL;
            # recvs: the kernel's, inside recv_body's one.
            t_ready = cpu0 = gil = recvs = None
            calls = 0
            t_accept = time.perf_counter() if buf else None
            if buf and trace.cpu_sample_due(t_accept):
                cpu0 = time.thread_time()
            if native is None:
                sock.settimeout(READ_HEADER_TIMEOUT_S)
            head_end = buf.find(b"\r\n\r\n")
            while head_end < 0:
                if len(buf) > MAX_HEAD_LENGTH:
                    self._send_simple(sock, native, 431, close=True)
                    return
                try:
                    if stamped is not None:
                        chunk, ready, held = stamped(
                            fd, self.rbufsize, READ_HEADER_TIMEOUT_S
                        )
                    else:
                        chunk = sock.recv(self.rbufsize)
                except (TimeoutError, OSError):
                    return
                if not chunk:
                    return
                calls += 1
                if t_accept is None:
                    if stamped is not None:
                        t_ready, t_accept = ready, held
                    else:
                        t_accept = time.perf_counter()
                    if trace.cpu_sample_due(t_accept):
                        cpu0 = time.thread_time()
                elif stamped is not None:
                    gil = (gil or 0.0) + (held - ready)
                buf += chunk
                head_end = buf.find(b"\r\n\r\n")
            if head_end > MAX_HEAD_LENGTH:
                self._send_simple(sock, native, 431, close=True)
                return
            head = bytes(buf[:head_end])
            del buf[: head_end + 4]
            try:
                method, path, version, headers, lowered, length = (
                    parse_request_head(head)
                )
            except HeadParseError as exc:
                self._send_simple(sock, native, exc.status, close=True)
                return
            if lowered.get("expect", "").lower() == "100-continue":
                try:
                    self._send(sock, native, b"HTTP/1.1 100 Continue\r\n\r\n")
                except OSError:
                    return
            # -- read the body ----------------------------------------------
            if len(buf) < length:
                # a body that did not come whole with its head (the Nodes
                # wire's 6 MB, a planner cell's 0.5 MB): annotated, so that
                # a profiled window shows the receive; the span's read
                # stage below covers it by hand
                with trace.stage("read"):
                    try:
                        if read_body is not None:
                            body, ready, held, recvs = read_body(
                                fd, buf, length, READ_HEADER_TIMEOUT_S
                            )
                            calls += 1
                            gil = (gil or 0.0) + (held - ready)
                        else:
                            whole = bytearray(length)
                            filled = len(buf)
                            whole[:filled] = buf
                            with memoryview(whole) as into:
                                while filled < length:
                                    got = sock.recv_into(into[filled:])
                                    if not got:
                                        return
                                    calls += 1
                                    filled += got
                            body = bytes(whole)
                    except (TimeoutError, OSError, MemoryError):
                        # no byte for 5 s, a peer that went, or no room
                        # for a length that was only declared so far
                        return
                buf.clear()
            else:
                body = bytes(buf[:length])
                del buf[:length]
            # -- dispatch + respond ------------------------------------------
            request_id = lowered.get("x-request-id") or trace.new_request_id()
            span = trace.Span(
                f"{method} {path}",
                request_id,
                t0=t_accept if t_ready is None else t_ready,
                cpu0=cpu0,
            )
            arrived = 0.0
            if t_ready is not None:
                # by hand, at offset 0: the wait ended before this thread
                # could record anything
                arrived = t_accept - t_ready
                span.add_stage("arrive", arrived, offset=0.0)
            span.add_stage(
                "read",
                time.perf_counter() - t_accept,
                offset=arrived,
                cpu=None if cpu0 is None else time.thread_time() - cpu0,
            )
            if gil is not None:
                span.set("read_gil_ms", round(gil * 1e3, 4))
            span.set("read_calls", calls)
            if recvs is not None:
                span.set("read_recvs", recvs)
            request = HTTPRequest(
                method=method, path=path, headers=headers, body=body,
                span=span,
            )
            # arrive + read + handle + write tile the span (handle on
            # sampled spans), with write_arm before write where the socket
            # has to be armed; handle contains the verb's own stages, so it
            # is never annotated
            with span.stage("handle", leaf=False, sampled=True):
                try:
                    response = type(self).route(request)
                except Exception as exc:
                    klog.error("handler raised: %r", exc)
                    span.set("error", repr(exc))
                    response = HTTPResponse(status=500)
            response.headers.setdefault("X-Request-ID", request_id)
            close = (
                version == "HTTP/1.0"
                or lowered.get("connection", "").lower() == "close"
            )
            if native is None:
                # the reads ran under the socket's own 5 s.  Arming the
                # write's is an ioctl: the GIL is released for it and has to
                # be won back from whatever the verb woke (informers, the
                # refresh thread) — a stage of its own, so that the stages
                # still tile the span and write keeps its meaning
                with span.stage("write_arm", sampled=True):
                    sock.settimeout(WRITE_TIMEOUT_S)
            t_write = time.perf_counter()
            cpu_write = None if cpu0 is None else time.thread_time()
            try:
                if native is not None:
                    # head and body as they lie: no join copy of the body
                    sends, released = native.send_answer(
                        fd, render_head(response, close), response.body,
                        WRITE_TIMEOUT_S,
                    )
                    span.set("write_sends", sends)
                    span.set("write_releases", released)
                else:
                    sock.sendall(render_response(response, close))
            except OSError:
                span.set("error", "write failed")
                return
            finally:
                span.add_stage(
                    "write",
                    time.perf_counter() - t_write,
                    cpu=None if cpu_write is None
                    else time.thread_time() - cpu_write,
                )
                trace.TRACES.add(span.finish(response.status))
            if close:
                return

    @staticmethod
    def _send(sock, native, data: bytes) -> None:
        """``data`` whole: through ``send_answer`` where the connection
        writes natively, else ``sendall``."""
        if native is None:
            sock.sendall(data)
        else:
            native.send_answer(sock.fileno(), data, b"", WRITE_TIMEOUT_S)

    @classmethod
    def _send_simple(cls, sock, native, status: int, close: bool = False) -> None:
        try:
            cls._send(
                sock, native,
                render_simple(status, close, request_id=trace.new_request_id()),
            )
        except OSError:
            pass


class Server:
    """Wraps a Scheduler implementation with the HTTP(S) extender endpoint
    (reference extender/types.go:18-20, scheduler.go:86-143)."""

    def __init__(self, scheduler: "Scheduler", metrics_provider=None, probe=None):
        """``metrics_provider``: optional zero-arg callable returning
        Prometheus exposition text, served on GET /metrics.  The reference
        consumes metrics but exports none of its own (SURVEY §5.5); since
        this framework's north star is p99 latency, the extenders' latency
        histograms (utils/tracing.py) are exported here.

        ``probe``: the /readyz ReadinessProbe; defaults to one seeded from
        the scheduler's ``readiness_conditions()`` duck-type
        (utils/health.py) — a scheduler without conditions is always
        ready."""
        self.scheduler = scheduler
        self.metrics_provider = metrics_provider
        self.probe = probe if probe is not None else health.probe_for(scheduler)
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._ready = threading.Event()

    # -- routing -------------------------------------------------------------

    def route(self, request: HTTPRequest) -> HTTPResponse:
        # structured log lines emitted while serving this request carry
        # its X-Request-ID (utils/klog.py), so /debug/traces entries can
        # be joined against the logs
        rid = getattr(trace.of(request), "trace_id", "")
        with klog.request_context(rid):
            return self._route(request)

    def _route(self, request: HTTPRequest) -> HTTPResponse:
        bare_path = request.path.partition("?")[0]
        if bare_path == "/healthz":
            # process liveness: answering at all IS the signal
            if request.method != "GET":
                return HTTPResponse(status=405)
            return HTTPResponse.json(health.HEALTHZ_BODY)
        if bare_path == "/readyz":
            # composite readiness (utils/health.py): 503 + reason list
            # until kernels are warm, telemetry is fresh, informers are
            # synced, and (async) the admission queue has headroom
            if request.method != "GET":
                return HTTPResponse(status=405)
            status, body = self.probe.readyz_response()
            return HTTPResponse.json(body, status=status)
        if bare_path == "/debug/profile":
            # bounded on-demand jax.profiler capture (utils/devicewatch.py)
            if request.method != "GET":
                return HTTPResponse(status=405)
            status, body = devicewatch.profile_response(request.path)
            return HTTPResponse.json(body, status=status)
        if bare_path == "/debug/rebalance":
            # last rebalance plan + loop state (rebalance/loop.py); 404
            # when no rebalancer is wired (--rebalance=off or GAS)
            if request.method != "GET":
                return HTTPResponse(status=405)
            rebalancer = getattr(self.scheduler, "rebalancer", None)
            if rebalancer is None:
                # bytes, not a dict: a dict body renders fine through the
                # in-process route but crashes render_response on a real
                # socket (caught by the /debug index completeness gate)
                return HTTPResponse.json(
                    b'{"error": "rebalancer not configured"}\n', status=404
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=rebalancer.to_json(),
            )
        if bare_path == "/debug/gangs":
            # gang reservations + lifecycle state (gang/group.py); 404
            # when no tracker is wired (--gang=off or GAS)
            if request.method != "GET":
                return HTTPResponse(status=405)
            gangs = getattr(self.scheduler, "gangs", None)
            if gangs is None:
                return HTTPResponse.json(
                    b'{"error": "gang scheduling not configured"}\n',
                    status=404,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=gangs.to_json(),
            )
        if bare_path == "/debug/admission":
            # priority queue + preemption planner state
            # (admission/plane.py); 404 when no plane is wired
            # (--admission=off)
            if request.method != "GET":
                return HTTPResponse(status=405)
            admission = getattr(self.scheduler, "admission", None)
            if admission is None:
                return HTTPResponse.json(
                    b'{"error": "admission plane not configured"}\n',
                    status=404,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=admission.to_json(),
            )
        if bare_path == "/debug/forecast":
            # forecast fits + extrapolation state (forecast/engine.py);
            # 404 when no forecaster is wired (--forecast=off or GAS)
            if request.method != "GET":
                return HTTPResponse(status=405)
            forecaster = getattr(self.scheduler, "forecaster", None)
            if forecaster is None:
                return HTTPResponse.json(
                    b'{"error": "forecasting not configured"}\n',
                    status=404,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=forecaster.to_json(),
            )
        if bare_path == "/debug/leader":
            # leader-election state (kube/lease.py); 404 when no elector
            # is wired (--leaderElect off, or GAS)
            if request.method != "GET":
                return HTTPResponse(status=405)
            leadership = getattr(self.scheduler, "leadership", None)
            if leadership is None:
                return HTTPResponse.json(
                    b'{"error": "leader election not configured"}\n',
                    status=404,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=leadership.to_json(),
            )
        if bare_path == "/debug/slo":
            # SLO compliance + burn rates (utils/slo.py); 404 when no
            # engine is wired (--slo=off), the off-path convention
            if request.method != "GET":
                return HTTPResponse(status=405)
            slo_engine = getattr(self.scheduler, "slo", None)
            if slo_engine is None:
                return HTTPResponse.json(
                    b'{"error": "slo engine not configured"}\n',
                    status=404,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=slo_engine.to_json(),
            )
        if bare_path == "/debug/control":
            # budget feedback controller (utils/control.py); 404 when no
            # controller is wired (--sloControl=off), same convention
            if request.method != "GET":
                return HTTPResponse(status=405)
            controller = getattr(self.scheduler, "control", None)
            if controller is None:
                return HTTPResponse.json(
                    b'{"error": "budget controller not configured"}\n',
                    status=404,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=controller.to_json(),
            )
        if bare_path == "/debug/solve":
            # solve observatory (ops/solveobs.py): per-stage attribution
            # rings, refresh churn, recompile watch; 404 when no
            # observatory is wired (--solveObs=off), same convention
            if request.method != "GET":
                return HTTPResponse(status=405)
            observatory = getattr(self.scheduler, "solveobs", None)
            if observatory is None:
                return HTTPResponse.json(
                    b'{"error": "solve observatory not configured"}\n',
                    status=404,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=observatory.to_json(),
            )
        if bare_path == "/debug/shard":
            # partition plane (shard/plane.py): ownership, fencing
            # epochs, digest ages — and the GOSSIP surface: peers pull
            # this JSON and ingest the digests it carries; 404 when no
            # plane is wired (--shard=off), same convention
            if request.method != "GET":
                return HTTPResponse(status=405)
            shard_plane = getattr(self.scheduler, "shard", None)
            if shard_plane is None:
                return HTTPResponse.json(
                    b'{"error": "shard plane not configured"}\n',
                    status=404,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=shard_plane.to_json(),
            )
        if bare_path == "/debug/wire":
            # wire-path cache state (tas/fastpath.py wire_debug): interned
            # universes, intern counters, skeleton keys; 404 when the
            # scheduler has no device fastpath (host-only TAS, or GAS)
            if request.method != "GET":
                return HTTPResponse(status=405)
            fastpath = getattr(self.scheduler, "fastpath", None)
            if fastpath is None:
                return HTTPResponse.json(
                    b'{"error": "no device fastpath (host-only mode)"}\n',
                    status=404,
                )
            import json

            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=json.dumps(fastpath.wire_debug()).encode() + b"\n",
            )
        if bare_path == "/debug/record":
            # flight-recorder export (utils/record.py): versioned JSONL
            # of anonymized events; 404 when no recorder is wired
            # (--flightRecorder=off), the off-path convention
            if request.method != "GET":
                return HTTPResponse(status=405)
            flight = getattr(self.scheduler, "flight", None)
            if flight is None:
                return HTTPResponse.json(
                    b'{"error": "flight recorder not configured"}\n',
                    status=404,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/x-ndjson"},
                body=flight.to_jsonl(),
            )
        if bare_path == "/debug/whatif":
            # what-if serving (testing/replay.py): replay a capture
            # through the digital twin under transform knobs and return
            # projected SLO verdicts + ledgers.  POST-only — it RUNS a
            # replay; the async front-end executes it off-loop like
            # /debug/profile.  404 while no recorder is wired.
            if request.method != "POST":
                return HTTPResponse(status=405)
            flight = getattr(self.scheduler, "flight", None)
            if flight is None:
                return HTTPResponse.json(
                    b'{"error": "flight recorder not configured"}\n',
                    status=404,
                )
            import json

            from platform_aware_scheduling_tpu.testing import replay

            try:
                spec = json.loads(request.body or b"{}")
                if not isinstance(spec, dict):
                    raise ValueError("not an object")
            except Exception:
                trace.COUNTERS.inc("pas_whatif_failures_total")
                return HTTPResponse.json(
                    b'{"error": "body must be a JSON object"}\n',
                    status=400,
                )
            try:
                result = replay.whatif_from_spec(spec, flight=flight)
            except replay.CaptureError as exc:
                trace.COUNTERS.inc("pas_whatif_failures_total")
                return HTTPResponse.json(
                    json.dumps({"error": str(exc)}).encode() + b"\n",
                    status=400,
                )
            except Exception as exc:
                trace.COUNTERS.inc("pas_whatif_failures_total")
                klog.error("what-if replay failed: %r", exc)
                return HTTPResponse.json(
                    json.dumps({"error": f"replay failed: {exc}"}).encode()
                    + b"\n",
                    status=500,
                )
            trace.COUNTERS.inc("pas_whatif_runs_total")
            return HTTPResponse.json(
                json.dumps(result).encode() + b"\n"
            )
        if bare_path == "/debug/traces":
            # observability extension (utils/trace.py): a bounded ring of
            # recent + slowest completed request traces as JSON.  Always
            # on — tracing has no off switch, matching its near-zero cost.
            # ?verb= keeps spans of one verb; ?min_ms= keeps slow spans
            if request.method != "GET":
                return HTTPResponse(status=405)
            params = parse_query(request.path)
            min_ms = None
            if "min_ms" in params:
                try:
                    min_ms = float(params["min_ms"])
                except ValueError:
                    return HTTPResponse.json(
                        b'{"error": "min_ms must be a number"}\n', status=400
                    )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=trace.TRACES.to_json(
                    verb=params.get("verb"), min_ms=min_ms
                ),
            )
        if bare_path == "/debug/decisions":
            # decision provenance (utils/decisions.py): recent scheduling
            # decisions with per-node reasons + outcome feedback; 404
            # while the log is disabled (--decisionLog=off), like an
            # unwired /debug/rebalance
            if request.method != "GET":
                return HTTPResponse(status=405)
            if not decisions.DECISIONS.enabled:
                return HTTPResponse.json(
                    b'{"error": "decision log disabled"}\n', status=404
                )
            params = parse_query(request.path)
            try:
                limit = int(params.get("limit", "64"))
            except ValueError:
                return HTTPResponse.json(
                    b'{"error": "limit must be an integer"}\n', status=400
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=decisions.DECISIONS.to_json(
                    pod=params.get("pod"),
                    verb=params.get("verb"),
                    limit=limit,
                ),
            )
        if bare_path == "/debug/explain":
            # causal event spine (utils/events.py): the ordered event
            # chain + human narrative for one pod/gang/request/node,
            # joined across admission, preemption, rebalance, control,
            # SLO, and the wire; 404 while disabled (--events=off)
            if request.method != "GET":
                return HTTPResponse(status=405)
            if not events.JOURNAL.enabled:
                return HTTPResponse.json(
                    b'{"error": "event journal disabled"}\n', status=404
                )
            params = parse_query(request.path)
            query = {
                key: params.get(key, "")
                for key in ("request_id", "pod", "gang", "node")
            }
            if not any(query.values()):
                return HTTPResponse.json(
                    b'{"error": "one of ?pod= ?gang= ?request_id= ?node= '
                    b'is required"}\n',
                    status=400,
                )
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=events.JOURNAL.to_json(**query),
            )
        if bare_path in ("/debug", "/debug/"):
            # tiny index so the debug surface is discoverable from curl
            if request.method != "GET":
                return HTTPResponse(status=405)
            import json

            return HTTPResponse(
                status=200,
                headers={"Content-Type": "application/json"},
                body=json.dumps({"endpoints": DEBUG_ENDPOINTS}).encode()
                + b"\n",
            )
        if request.path == "/metrics" and self.metrics_provider is not None:
            # observability extension: outside the POST/JSON middleware
            if request.method != "GET":
                return HTTPResponse(status=405)
            return HTTPResponse(
                status=200,
                headers={"Content-Type": "text/plain; version=0.0.4"},
                body=self.metrics_provider().encode(),
            )
        routes = {
            "/scheduler/prioritize": self.scheduler.prioritize,
            "/scheduler/filter": self.scheduler.filter,
            "/scheduler/bind": self.scheduler.bind,
        }
        handler = routes.get(request.path, not_found_handler)
        if klog.v(5).enabled():
            # full wire dump (reference GAS logs the request at V(5),
            # scheduler.go:491-495; the response dump is what the kind
            # e2e's wire-capture artifact harvests to refresh
            # tests/golden/ from a real kube-scheduler).  Bodies are
            # base64 so each record is one unambiguous log line and the
            # extractor (tests/golden/from_capture.py) recovers EXACT
            # bytes — raw dumps would split on embedded newlines and
            # could collide with the log's own field delimiters
            import base64

            klog.v(5).info_s(
                f"WIRE request {request.method} {request.path} "
                f"len={len(request.body)} "
                f"b64={base64.b64encode(request.body).decode('ascii')}",
                component="extender",
            )
            response = apply_middleware(handler, request)
            klog.v(5).info_s(
                f"WIRE response {request.path} status={response.status} "
                f"len={len(response.body)} "
                f"b64={base64.b64encode(response.body).decode('ascii')}",
                component="extender",
            )
            return response
        return apply_middleware(handler, request)

    # -- serving -------------------------------------------------------------

    def start_server(
        self,
        port: str,
        cert_file: str = "",
        key_file: str = "",
        ca_file: str = "",
        unsafe: bool = False,
        host: str = "",
        block: bool = True,
    ) -> None:
        """Start serving; mirrors ``Server.StartServer`` (scheduler.go:86-108).

        With ``unsafe=True`` serves plain HTTP; otherwise mutual-TLS with the
        pinned configuration.  ``block=False`` serves on a daemon thread
        (callers use :meth:`wait_ready` / :meth:`shutdown`).

        The connection loop is a slim hand-rolled HTTP/1.1 handler
        (keep-alive, single-buffer header parse, one send per response,
        TCP_NODELAY) rather than http.server's per-line machinery — at 10k
        nodes this layer runs on every request and its cost lands straight
        in p99 (the Go reference gets the equivalent from net/http's
        optimized server for free)."""
        server = self
        trace.watch_gc()

        class Handler(_FastHTTPHandler):
            route = staticmethod(server.route)
            # resolved once, here: loading _wirec may build it
            native = native_io()

        httpd = socketserver.ThreadingTCPServer(
            (host, int(port)), Handler, bind_and_activate=False
        )
        httpd.allow_reuse_address = True
        httpd.daemon_threads = True
        httpd.server_bind()
        httpd.server_activate()

        if unsafe:
            klog.v(2).info_s(f"Extender Listening on HTTP {port}", component="extender")
        else:
            context = configure_secure_context(cert_file, key_file, ca_file)
            httpd.socket = context.wrap_socket(httpd.socket, server_side=True)
            klog.v(2).info_s(f"Extender Listening on HTTPS {port}", component="extender")

        self._httpd = httpd
        self._ready.set()
        if block:
            httpd.serve_forever()
        else:
            thread = threading.Thread(
                target=httpd.serve_forever, name="pas-serve", daemon=True
            )
            thread.start()

    @property
    def port(self) -> int:
        assert self._httpd is not None
        return self._httpd.server_address[1]

    def wait_ready(self, timeout: float = 10.0) -> bool:
        return self._ready.wait(timeout)

    def shutdown(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
            self._ready.clear()


def configure_secure_context(
    cert_file: str, key_file: str, ca_file: str
) -> ssl.SSLContext:
    """The mTLS configuration of ``configureSecureServer`` (scheduler.go:110-143):
    TLS >= 1.2, pinned AES-256-GCM ECDHE suites, client certs required and
    verified against the CA pool."""
    context = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    context.minimum_version = ssl.TLSVersion.TLSv1_2
    context.verify_mode = ssl.CERT_REQUIRED
    try:
        context.load_verify_locations(cafile=ca_file)
    except (OSError, ssl.SSLError) as exc:
        klog.v(2).info_s(f"caCert read failed: {exc}", component="extender")
    context.load_cert_chain(certfile=cert_file, keyfile=key_file)
    # TLS 1.2 suites pinned as in the reference; TLS 1.3 suites are not
    # configurable (same stance as Go's CipherSuites field).
    context.set_ciphers("ECDHE-RSA-AES256-GCM-SHA384:ECDHE-ECDSA-AES256-GCM-SHA384")
    return context
