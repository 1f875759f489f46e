"""Who held the interpreter (utils/trace.py, extender/server.py,
native/wirec.c): the arrival stamp, CPU seconds beside wall seconds on
spans and sampled stages, the label-free verb families and the thread
ledger on /metrics.

Orders and signs, never wall-clock budgets: where a wait has to show, the
test makes it (a thread that holds the interpreter past a lengthened
switch interval) and compares it with the same request served alone."""

import ast
import os
import socket
import ssl
import statistics
import sys
import threading
import time

import pytest

import platform_aware_scheduling_tpu
from benchmarks.http_load import build_extender, make_bodies
from platform_aware_scheduling_tpu.extender import server as server_module
from platform_aware_scheduling_tpu.extender.server import HTTPResponse, Server
from platform_aware_scheduling_tpu.native import get_wirec
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import DummyMetricsClient
from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.tracing import CounterSet
from wirehelpers import (
    get_request, post_bytes, raw_request, start_threaded,
    wait_for_span as _span,
)

wirec = get_wirec()
needs_wirec = pytest.mark.skipif(
    wirec is None or server_module.native_io() is None,
    reason="_wirec (recv_stamped, recv_body, send_answer) unavailable",
)

#: what tiles a span, by read path: the native reads stamp ``arrive`` and
#: leave the socket's time-out alone; the socket's own reads arm it
TOP_STAGES = {
    "native": ("arrive", "read", "handle", "write"),
    "fallback": ("read", "handle", "write_arm", "write"),
}

#: every family of ISSUE 37's table, and ISSUE 38's and 40's one each
VERB_FAMILIES = (
    "pas_verb_total", "pas_verb_seconds_total", "pas_verb_cpu_seconds_total",
    "pas_verb_cpu_wall_seconds_total",
    "pas_verb_arrive_total", "pas_verb_arrive_wait_seconds_total",
    "pas_verb_read_seconds_total", "pas_verb_read_gil_seconds_total",
    "pas_verb_read_calls_total", "pas_verb_write_releases_total",
    "pas_stage_handle_total", "pas_stage_handle_seconds_total",
    "pas_stage_scan_total", "pas_stage_scan_seconds_total",
)
CPU_FAMILIES = (
    "pas_cpu_verbs_seconds_total", "pas_cpu_refresh_seconds_total",
    "pas_cpu_informers_seconds_total", "pas_cpu_other_seconds_total",
    "pas_cpu_wall_seconds_total",
)


class _Stub:
    """The three verbs as one fixed answer; ``slow_s`` sleeps in handle."""

    def __init__(self, slow_s=0.0):
        self.slow_s = slow_s

    def filter(self, request):
        trace.of(request).set("verb", "filter")
        if self.slow_s:
            time.sleep(self.slow_s)
        return HTTPResponse.json(b'{"NodeNames": ["n1"], "Error": ""}')

    prioritize = bind = filter

    def metrics_text(self):
        return trace.exposition()


def _serve(stub=None):
    return start_threaded(stub or _Stub())


def _exchange(sock, payload):
    """One request on an open keep-alive connection -> the response's raw
    bytes (head and body)."""
    sock.sendall(payload)
    return _read_response(sock)


def _read_response(sock):
    buf = bytearray()
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(1 << 16)
        assert chunk, "closed before the head"
        buf += chunk
    head, _, rest = bytes(buf).partition(b"\r\n\r\n")
    length = 0
    for line in head.split(b"\r\n")[1:]:
        name, _, value = line.partition(b":")
        if name.lower() == b"content-length":
            length = int(value)
    body = bytearray(rest)
    while len(body) < length:
        chunk = sock.recv(1 << 16)
        assert chunk, "closed mid-body"
        body += chunk
    return head + b"\r\n\r\n" + bytes(body[:length])


def _request(trace_id, body=b"{}"):
    return post_bytes(
        "/scheduler/filter", body, extra=f"X-Request-ID: {trace_id}\r\n"
    )


@pytest.fixture
def every_span_sampled(monkeypatch):
    monkeypatch.setattr(trace, "SAMPLE_EVERY", 1)


@pytest.fixture
def every_span_reads_cpu(monkeypatch):
    """No gap between the spans that read their CPU clock (in service at
    most one every CPU_SAMPLE_GAP_S does: the read is a system call)."""
    monkeypatch.setattr(trace, "CPU_SAMPLE_GAP_S", 0.0)
    monkeypatch.setattr(trace, "_cpu_sample_after", 0.0)


@pytest.fixture
def long_switch_interval():
    """A waiter asks the holder for the interpreter after this long: 50 ms
    makes a forced wait tower over anything a loaded host adds alone."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(0.05)
    try:
        yield 0.05
    finally:
        sys.setswitchinterval(was)


# ---------------------------------------------------------------------------
# (a) the arrival stamp
# ---------------------------------------------------------------------------


@needs_wirec
def test_a_thread_that_holds_the_interpreter_shows_as_arrive(
    long_switch_interval, every_span_reads_cpu,
):
    """Alone, a verb's bytes are held as soon as they are there.  Beside a
    thread that runs pure Python and lets go of nothing, the handler has
    its bytes (recv returned, GIL released) and waits out the switch
    interval for its first bytecode: that wait is ``arrive``, the span
    begins before it, and the thread's CPU seconds do not hold it."""
    server = _serve()
    rounds = 8
    try:
        sock = socket.create_connection(("127.0.0.1", server.port), timeout=15)
        for index in range(rounds):
            _exchange(sock, _request(f"alone-{index}"))
        alone = [_span(f"alone-{index}") for index in range(rounds)]

        def hold_the_interpreter():
            # send, then run bytecode for four switch intervals without
            # one voluntary release: the handler wakes into a held GIL
            for index in range(rounds):
                sock.sendall(_request(f"held-{index}"))
                until = time.perf_counter() + 4 * long_switch_interval
                while time.perf_counter() < until:
                    pass
                _read_response(sock)

        holder = threading.Thread(target=hold_the_interpreter)
        holder.start()
        holder.join(60)
        assert not holder.is_alive()
        held = [_span(f"held-{index}") for index in range(rounds)]
        sock.close()
    finally:
        server.shutdown()

    def arrive(span):
        assert span.stages[0][0] == "arrive", span.stages
        assert span.stages[0][1] == 0.0  # at the span's own start
        return span.stages[0][2]

    assert all(arrive(span) > 0 for span in alone + held)
    waited = max(held, key=arrive)
    # the holder loses the race for the GIL after its send at most now and
    # then; one forced wait in eight tops every unforced one
    assert arrive(waited) > max(arrive(span) for span in alone)
    assert arrive(waited) > statistics.median(arrive(span) for span in alone)
    # wall time holds the wait, the thread's CPU does not
    assert waited.cpu_s < waited.duration_s
    assert waited.duration_s >= arrive(waited)
    entry = waited.to_dict()
    assert entry["cpu_ms"] < entry["duration_ms"]
    assert entry["stages"][0]["name"] == "arrive"


# ---------------------------------------------------------------------------
# (b) recv_stamped and recv_body, and the front-end without them
# ---------------------------------------------------------------------------


@needs_wirec
class TestRecvStamped:
    def test_bytes_and_ordered_stamps_on_the_spans_clock(self):
        left, right = socket.socketpair()
        try:
            left.settimeout(1.0)
            before = time.perf_counter()
            right.sendall(b"hello")
            data, t_ready, t_held = wirec.recv_stamped(left.fileno(), 1 << 16, 1.0)
            after = time.perf_counter()
            assert data == b"hello"
            assert before <= t_ready <= t_held <= after
            right.sendall(b"x" * 10)
            data, _, _ = wirec.recv_stamped(left.fileno(), 4, 1.0)
            assert data == b"xxxx"  # max_bytes bounds one read, as recv's
        finally:
            left.close()
            right.close()

    def test_a_silent_peer_times_out_and_a_closed_one_reads_empty(self):
        left, right = socket.socketpair()
        try:
            left.settimeout(0.05)
            with pytest.raises(TimeoutError):
                wirec.recv_stamped(left.fileno(), 1 << 16, 0.05)
            with pytest.raises(TimeoutError):  # what sock.recv raises
                left.recv(1 << 16)
            right.close()
            data, t_ready, t_held = wirec.recv_stamped(left.fileno(), 1 << 16, 0.05)
            assert data == b"" and t_ready <= t_held
        finally:
            left.close()

    def test_errors_are_oserrors(self):
        left, right = socket.socketpair()
        fd = left.fileno()
        left.close()
        right.close()
        with pytest.raises(OSError):
            wirec.recv_stamped(fd, 16, 0.05)
        with pytest.raises(OSError):
            wirec.recv_stamped(-1, 16, 0.05)
        with pytest.raises(ValueError):
            wirec.recv_stamped(0, -1, 0.05)

    def test_a_blocking_descriptor_waits_for_ever_when_told_to(self):
        left, right = socket.socketpair()
        try:
            threading.Timer(0.05, right.sendall, args=(b"late",)).start()
            data, _, _ = wirec.recv_stamped(left.fileno(), 16, -1.0)
            assert data == b"late"
        finally:
            left.close()
            right.close()


def _feed(right, pieces, gap_s):
    """``pieces`` sent from a thread, ``gap_s`` apart."""
    def run():
        for piece in pieces:
            right.sendall(piece)
            time.sleep(gap_s)

    feeder = threading.Thread(target=run)
    feeder.start()
    return feeder


def _body_in_many_small_writes(left, right):
    pieces = [bytes([65 + index % 26]) * 997 for index in range(64)]
    feeder = _feed(right, pieces, 0.001)
    before = time.perf_counter()
    body, t_ready, t_held, n_recv = wirec.recv_body(
        left.fileno(), b"", 64 * 997, 5.0)
    after = time.perf_counter()
    feeder.join(10)
    assert not feeder.is_alive()
    assert body == b"".join(pieces) and type(body) is bytes
    assert n_recv > 1  # the kernel's recvs, all inside one call
    assert before <= t_ready <= t_held <= after  # the spans' clock


def _a_prefix_stays_in_front(left, right):
    right.sendall(b"-the-rest")
    for prefix in (bytearray(b"head's-leftover"), b"head's-leftover"):
        body, _, _, n_recv = wirec.recv_body(
            left.fileno(), prefix, len(prefix) + 9, 1.0)
        assert body == b"head's-leftover-the-rest" and n_recv == 1
        right.sendall(b"-the-rest")
    # a body that was whole already asks the kernel for nothing
    body, t_ready, t_held, n_recv = wirec.recv_body(left.fileno(), b"abc", 3, 0.0)
    assert (body, n_recv) == (b"abc", 0) and t_ready <= t_held
    assert wirec.recv_body(left.fileno(), b"", 0, 0.0)[0] == b""
    assert left.recv(64) == b"-the-rest"


def _what_follows_the_body_stays_in_the_socket(left, right):
    right.sendall(b"body-of-ten" + b"POST /next HTTP/1.1")
    body, _, _, _ = wirec.recv_body(left.fileno(), b"", 11, 1.0)
    assert body == b"body-of-ten"
    assert left.recv(1 << 16) == b"POST /next HTTP/1.1"  # pipelined: untouched


def _the_time_out_is_without_a_byte_not_for_the_body(left, right):
    timeout_s, gap_s, pieces = 0.5, 0.05, 16  # 0.8 s of trickle > 0.5
    feeder = _feed(right, [b"p" * 10] * pieces, gap_s)
    began = time.perf_counter()
    body, _, _, n_recv = wirec.recv_body(
        left.fileno(), b"", 10 * pieces, timeout_s)
    assert time.perf_counter() - began > timeout_s  # and no time-out
    assert body == b"p" * 10 * pieces and n_recv > 1
    feeder.join(10)
    # half a body, then silence: timeout_s after the last byte
    right.sendall(b"half")
    sent = time.perf_counter()
    with pytest.raises(TimeoutError):
        wirec.recv_body(left.fileno(), b"", 8, timeout_s)
    assert time.perf_counter() - sent >= timeout_s
    with pytest.raises(TimeoutError):  # 0: what is there, or nothing
        wirec.recv_body(left.fileno(), b"", 8, 0.0)


def _a_peer_that_closes_early(left, right):
    right.sendall(b"seven b")
    right.close()
    with pytest.raises(ConnectionResetError, match="3 bytes short"):
        wirec.recv_body(left.fileno(), b"", 10, 1.0)
    with pytest.raises(OSError):  # what _serve catches
        wirec.recv_body(left.fileno(), b"pre", 10, 1.0)


def _arguments_are_checked_before_any_read(left, right):
    right.sendall(b"untouched")
    with pytest.raises(OSError):
        wirec.recv_body(-1, b"", 4, 0.05)
    with pytest.raises(ValueError):
        wirec.recv_body(left.fileno(), b"", -1, 0.05)
    with pytest.raises(ValueError):
        wirec.recv_body(left.fileno(), b"longer", 5, 0.05)
    with pytest.raises(TypeError):
        wirec.recv_body(left.fileno(), "text", 5, 0.05)
    assert left.recv(64) == b"untouched"
    fd = left.fileno()
    left.close()
    with pytest.raises(OSError):
        wirec.recv_body(fd, b"", 4, 0.05)


def _a_blocking_descriptor_waits_for_ever_when_told_to(left, right):
    left.settimeout(None)
    threading.Timer(0.05, right.sendall, args=(b"late",)).start()
    body, t_ready, t_held, _ = wirec.recv_body(left.fileno(), b"", 4, -1.0)
    assert body == b"late" and t_held >= t_ready


RECV_BODY_CASES = {
    case.__name__.lstrip("_"): case for case in (
        _body_in_many_small_writes,
        _a_prefix_stays_in_front,
        _what_follows_the_body_stays_in_the_socket,
        _the_time_out_is_without_a_byte_not_for_the_body,
        _a_peer_that_closes_early,
        _arguments_are_checked_before_any_read,
        _a_blocking_descriptor_waits_for_ever_when_told_to,
    )
}


@needs_wirec
@pytest.mark.parametrize("case", sorted(RECV_BODY_CASES))
def test_recv_body(case):
    """``_wirec.recv_body``: a body of ``Content-Length`` in one call, as
    the front-end's native path reads it (a socket with a time-out: a
    non-blocking descriptor)."""
    left, right = socket.socketpair()
    try:
        left.settimeout(server_module.WRITE_TIMEOUT_S)
        RECV_BODY_CASES[case](left, right)
    finally:
        left.close()
        right.close()


def _answers(server, tag, bodies):
    """Raw responses of one keep-alive connection, and the spans."""
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=15)
    try:
        raw = [
            _exchange(sock, _request(f"{tag}-{index}", body))
            for index, body in enumerate(bodies)
        ]
    finally:
        sock.close()
    return raw, [_span(f"{tag}-{index}") for index in range(len(bodies))]


@needs_wirec
def test_without_wirec_the_answers_are_the_same_bytes_and_carry_no_arrive(
    monkeypatch,
):
    """The helper replaces sock.recv call for call: a front-end without it
    (no compiler, PAS_TPU_NO_NATIVE) serves byte-identical responses —
    over a body that outlasts one recv too — and stamps nothing."""
    bodies = [b"{}", b'{"Pod": {}}', b"x" * 300_000, b"{}"]
    ids = lambda raw, tag: [r.replace(tag, b"T") for r in raw]  # noqa: E731
    stamped_server = _serve()
    try:
        stamped, stamped_spans = _answers(stamped_server, "stamped", bodies)
    finally:
        stamped_server.shutdown()
    monkeypatch.setattr(server_module, "native_io", lambda: None)
    plain_server = _serve()
    try:
        plain, plain_spans = _answers(plain_server, "plain", bodies)
    finally:
        plain_server.shutdown()
    assert ids(stamped, b"stamped") == ids(plain, b"plain")
    for span in stamped_spans:
        assert [s[0] for s in span.stages][:2] == ["arrive", "read"]
    # the large body took reads after the first: their waits are counted
    assert "read_gil_ms" in stamped_spans[2].attrs
    assert stamped_spans[2].attrs["read_gil_ms"] >= 0
    for span in plain_spans:
        names = [s[0] for s in span.stages]
        assert "arrive" not in names and names[0] == "read"
        assert "read_gil_ms" not in span.attrs


def test_a_tls_connection_reads_through_the_ssl_socket(
    tmp_path, every_span_reads_cpu
):
    """An SSLSocket is a socket.socket whose bytes are not its
    descriptor's: the front-end keeps sock.recv and sendall there and
    records no stamp."""
    from test_hardening import gen_certs

    ca, certs = gen_certs(tmp_path)
    server = Server(_Stub())
    threading.Thread(
        target=lambda: server.start_server(
            port="0", cert_file=certs["server"][0], key_file=certs["server"][1],
            ca_file=ca, unsafe=False, host="127.0.0.1", block=True,
        ),
        daemon=True,
    ).start()
    assert server.wait_ready()
    plain_server = _serve()
    try:
        ctx = ssl.create_default_context(cafile=ca)
        ctx.check_hostname = False
        ctx.load_cert_chain(*certs["client"])
        raw = socket.create_connection(("127.0.0.1", server.port), timeout=15)
        tls = ctx.wrap_socket(raw)
        try:
            over_tls = _exchange(tls, _request("tls-0"))
        finally:
            tls.close()
        plain, _spans = _answers(plain_server, "tcp", [b"{}"])
    finally:
        server.shutdown()
        plain_server.shutdown()
    assert over_tls.replace(b"tls-0", b"T") == plain[0].replace(b"tcp-0", b"T")
    span = _span("tls-0")
    assert "arrive" not in [s[0] for s in span.stages]
    assert "write_sends" not in span.attrs  # sendall, as before
    assert span.cpu_s is not None and span.cpu_s >= 0


# ---------------------------------------------------------------------------
# (c) tiling, and CPU seconds on sampled stages
# ---------------------------------------------------------------------------


READ_PATHS = [pytest.param("native", marks=needs_wirec), "fallback"]


@pytest.mark.parametrize("path", READ_PATHS)
def test_the_top_stages_tile_a_sampled_span(
    path, monkeypatch, every_span_sampled, every_span_reads_cpu
):
    """arrive + read + handle + write on the native reads, which leave the
    socket's time-out alone; read + handle + write_arm + write where the
    socket's own reads need it armed (no ``_wirec``, TLS)."""
    if path == "fallback":
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
    top = TOP_STAGES[path]
    slow_s = 0.2  # dominates: an unattributed gap would blow the 5%
    server = _serve(_Stub(slow_s))
    try:
        _answers(server, "tile", [b"{}", b"y" * 200_000])
    finally:
        server.shutdown()
    for index in range(2):
        span = _span(f"tile-{index}")
        stages = span.stage_seconds()
        every = set(TOP_STAGES["native"] + TOP_STAGES["fallback"])
        assert every & set(stages) == set(top), sorted(stages)
        tiled = sum(stages[name] for name in top)
        assert abs(span.duration_s - tiled) <= 0.05 * span.duration_s, (
            tiled, span.duration_s, stages)
        at = {name: (start, start + dur) for name, start, dur in span.stages}
        # each begins where the one before it ended, the first at the start
        assert at[top[0]][0] == 0.0
        order = [at[name] for name in top]
        for (_b0, e0), (b1, _e1) in zip(order, order[1:]):
            assert e0 - 1e-6 <= b1
        # a span that reads its CPU clock: its stages carry their thread
        # CPU seconds, and the sleeping handle ran for far less than it
        # lasted
        assert span.sampled
        by_name = {s["name"]: s for s in span.to_dict()["stages"]}
        assert by_name["handle"]["cpu_ms"] < by_name["handle"]["duration_ms"]
        assert by_name["handle"]["duration_ms"] >= slow_s * 1e3
        for name in top:
            if name == "arrive":
                assert "cpu_ms" not in by_name[name]  # nobody ran
            else:
                assert by_name[name]["cpu_ms"] >= 0
        assert span.cpu_s < slow_s < span.duration_s


@pytest.mark.parametrize("sampled", [False, True])
def test_a_span_that_was_not_picked_reads_no_cpu_clock(sampled, monkeypatch):
    """thread_time() is a system call: only the spans cpu_sample_due picks
    read it, whether or not the sequence number samples their stages."""
    def no_clock():
        raise AssertionError("a span that was not picked read its CPU clock")

    monkeypatch.setattr(trace.time, "thread_time", no_clock)
    span = trace.Span("POST /scheduler/filter")
    span.sampled = sampled
    with span.stage("decode"):
        pass
    assert type(span.stage("decode")) is trace._Stage
    carried = span.stage("scan", sampled=True)
    assert (carried is trace._NULL_STAGE) != sampled
    span.add_stage("write", 0.0, cpu=0.001)  # a caller's reading is dropped
    span.finish(200)
    assert span.stage_cpu is None and span.cpu_s is None
    entry = span.to_dict()
    assert "cpu_ms" not in entry
    assert all("cpu_ms" not in s for s in entry["stages"])


def test_cpu_clocks_are_read_at_most_once_a_gap(monkeypatch):
    monkeypatch.setattr(trace, "CPU_SAMPLE_GAP_S", 0.1)
    monkeypatch.setattr(trace, "_cpu_sample_after", 0.0)
    assert trace.cpu_sample_due(1000.0)
    assert not trace.cpu_sample_due(1000.05)
    assert not trace.cpu_sample_due(1000.0999)
    assert trace.cpu_sample_due(1000.1)
    assert not trace.cpu_sample_due(1000.15)
    # the span it picks times the stages it records on three clocks; which
    # stages it records stays the sequence number's business (the pick is
    # by time and favours the slow stretches: a stage mean must not)
    span = trace.Span("POST /scheduler/filter", cpu0=time.thread_time())
    assert span.stage_cpu == {}
    span.sampled = False
    assert span.stage("scan", sampled=True) is trace._NULL_STAGE
    span.sampled = True
    with span.stage("scan", sampled=True):
        sum(range(1000))
    span.add_stage("arrive", 0.25, offset=0.0)
    span.finish(200)
    assert span.stage_cpu[0] >= 0 and span.cpu_s >= span.stage_cpu[0]
    assert span.to_dict()["cpu_ms"] >= 0


def test_a_stage_off_a_request_sums_cpu_seconds_beside_wall_seconds():
    counters = CounterSet()
    with trace.stage("rf.pass", "pas_refresh_pass_seconds_total", counters,
                     cpu_counter="pas_refresh_pass_cpu_seconds_total",
                     leaf=False):
        time.sleep(0.05)
    wall = counters.get("pas_refresh_pass_seconds_total")
    cpu = counters.get("pas_refresh_pass_cpu_seconds_total")
    assert wall >= 0.05 and 0 <= cpu < wall


def test_the_refresh_pass_counts_its_threads_cpu_seconds():
    cache = AutoUpdatingCache()
    cache.write_metric("m", None)
    cache.update_all_metrics(DummyMetricsClient({}))
    wall = cache.counters.get("pas_refresh_pass_seconds_total")
    cpu = cache.counters.get("pas_refresh_pass_cpu_seconds_total")
    assert wall > 0 and 0 <= cpu <= wall + 1e-3
    text = trace.exposition(counter_sets=[cache.counters], include_global=False)
    assert "pas_refresh_pass_cpu_seconds_total" in text


# ---------------------------------------------------------------------------
# (d) the families on /metrics and the thread ledger
# ---------------------------------------------------------------------------


def _families(port):
    _status, _headers, body = get_request(port, "/metrics")
    return trace.parse_prometheus_text(body.decode())


def _value(families, name):
    return sum(value for _n, _labels, value in families[name]["samples"])


def _exact():
    """The verbs' families off the process's counters, unrounded."""
    return {name: trace.COUNTERS.get(name) for name in VERB_FAMILIES}


@needs_wirec
def test_metrics_show_every_family_and_they_count_the_verbs(
    monkeypatch, every_span_reads_cpu
):
    monkeypatch.setattr(trace, "SAMPLE_EVERY", 1)
    ext, names = build_extender(48, device=True)
    bodies = make_bodies(names, "nodenames", rotate_span=True, count=3)
    server = start_threaded(ext)
    try:
        before = _families(server.port)
        exact_before = _exact()  # a scrape has just moved the tallies over
        for body in bodies:
            status, _h, _b = raw_request(
                server.port, post_bytes("/scheduler/filter", body))
            assert status == 200
        # a handler books its answer after the bytes are out
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            after = _families(server.port)
            if _value(after, "pas_verb_total") - (
                    _value(before, "pas_verb_total")
                    if "pas_verb_total" in before else 0) >= len(bodies):
                break
            time.sleep(0.01)
        exact = {name: value - exact_before[name]
                 for name, value in _exact().items()}
    finally:
        server.shutdown()
    for name in VERB_FAMILIES + CPU_FAMILIES:
        assert name in after, name
        assert after[name]["type"] == "counter"
        assert name in trace.METRICS
        # label-free: a reader that sums a family reads each one alone
        assert all(not labels for _n, labels, _v in after[name]["samples"])

    def moved(name):
        return _value(after, name) - (
            _value(before, name) if name in before else 0.0)

    assert moved("pas_verb_total") == len(bodies)
    assert moved("pas_verb_arrive_total") == len(bodies)
    assert moved("pas_stage_handle_total") == len(bodies)
    assert moved("pas_stage_scan_total") == len(bodies)  # Filter's native scan
    assert (0 < moved("pas_verb_cpu_seconds_total")
            <= moved("pas_verb_cpu_wall_seconds_total") + 1e-4)
    # the arrival wait is left out of cpu_wall, and nothing else is: every
    # span here read its CPU clock.  Off the counters themselves: three waits
    # of microseconds are lost in the exposition's six significant digits
    waited = exact["pas_verb_arrive_wait_seconds_total"]
    assert waited > 0
    assert (exact["pas_verb_cpu_wall_seconds_total"]
            < exact["pas_verb_seconds_total"])
    assert (exact["pas_verb_seconds_total"]
            - exact["pas_verb_cpu_wall_seconds_total"]
            == pytest.approx(waited, rel=1e-6, abs=1e-9))
    assert moved("pas_verb_arrive_wait_seconds_total") >= 0
    assert (moved("pas_stage_scan_seconds_total")
            < moved("pas_stage_handle_seconds_total")
            < moved("pas_verb_seconds_total"))
    assert moved("pas_verb_read_seconds_total") < moved("pas_verb_seconds_total")
    # a names-wire Filter comes whole with its head: one read a verb
    assert moved("pas_verb_read_calls_total") == len(bodies)
    # and its answer of a few KB goes whole with the interpreter held
    assert moved("pas_verb_write_releases_total") == 0


def test_the_roles_sum_to_the_process_and_never_go_back():
    server = _serve()
    try:
        raw_request(server.port, _request("ledger-0"))
        first = _families(server.port)
        raw_request(server.port, _request("ledger-1"))
        second = _families(server.port)
        process = time.process_time()
    finally:
        server.shutdown()
    roles = [name for name in CPU_FAMILIES if "wall" not in name]
    total = sum(_value(second, name) for name in roles)
    assert abs(total - process) <= 0.02 * process, (total, process)
    for name in CPU_FAMILIES:
        assert _value(second, name) >= _value(first, name) >= 0
    assert _value(second, "pas_cpu_wall_seconds_total") > 0
    assert _value(second, "pas_cpu_verbs_seconds_total") > 0


def test_a_closed_connections_seconds_stay_on_its_role():
    """A handler folds its thread's CPU seconds into its role as it ends;
    the walk over the live threads then skips it, so nothing is counted
    twice and nothing is lost."""
    server = _serve()
    try:
        sock = socket.create_connection(("127.0.0.1", server.port), timeout=15)
        for index in range(20):
            _exchange(sock, _request(f"fold-{index}", b"z" * 20_000))
        serving = [t for t in threading.enumerate()
                   if t.name.startswith("pas-conn-")]
        assert serving, [t.name for t in threading.enumerate()]
        while_open = trace.thread_cpu()["verbs"]
        assert while_open > 0
        sock.close()
        for thread in serving:
            thread.join(5)
        assert not any(t.is_alive() for t in serving)
        closed = trace.thread_cpu()["verbs"]
        assert closed >= while_open
        assert all(getattr(t, "_pas_cpu_folded", False) for t in serving)
    finally:
        server.shutdown()


def test_roles_by_thread_name():
    assert trace.thread_role("pas-conn-17") == "verbs"
    assert trace.thread_role("pas-refresh") == "refresh"
    assert trace.thread_role("pas-informer-pods") == "informers"
    assert trace.thread_role("pas-informer-pods-resync") == "informers"
    assert trace.thread_role("pas-gas-worker") == "informers"
    for other in ("pas-serve", "pas-enforce", "pas-lease", "pas-slo",
                  "pas-devicewatch", "MainThread", "Thread-3"):
        assert trace.thread_role(other) is None
    # a thread of no role books nothing when it folds
    before = dict(trace.thread_cpu())
    trace.fold_thread_cpu()
    assert not getattr(threading.current_thread(), "_pas_cpu_folded", False)
    assert trace.thread_cpu()["verbs"] >= before["verbs"]


def test_the_refresh_thread_is_on_the_ledger():
    cache = AutoUpdatingCache()
    cache.write_metric("m", None)
    ran = threading.Event()
    cache.on_refresh_pass.append(lambda: (sum(range(200_000)), ran.set()))
    before = trace.thread_cpu()["refresh"]
    stop = cache.start_periodic_update(0.01, DummyMetricsClient({}))
    try:
        assert ran.wait(10)
        assert any(t.name == "pas-refresh" for t in threading.enumerate())
        deadline = time.monotonic() + 5
        while (trace.thread_cpu()["refresh"] <= before
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert trace.thread_cpu()["refresh"] > before
    finally:
        stop.set()


def _thread_calls():
    """(file, line, name keyword or None) of every ``threading.Thread(``
    and ``threading.Timer(`` in the package."""
    root = os.path.dirname(platform_aware_scheduling_tpu.__file__)
    for folder, _dirs, files in os.walk(root):
        for file in files:
            if not file.endswith(".py"):
                continue
            path = os.path.join(folder, file)
            with open(path) as handle:
                tree = ast.parse(handle.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "Thread"
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id == "threading"):
                    name = next((k.value for k in node.keywords
                                 if k.arg == "name"), None)
                    yield os.path.relpath(path, root), node.lineno, name


def test_every_thread_the_program_starts_carries_a_role_prefix():
    calls = list(_thread_calls())
    assert len(calls) >= 17
    for path, line, name in calls:
        assert name is not None, f"{path}:{line}: a thread without a name"
        if isinstance(name, ast.Constant):
            text = name.value
        else:  # an f-string: its literal head
            assert isinstance(name, ast.JoinedStr), f"{path}:{line}"
            text = name.values[0].value
        assert text.startswith("pas-"), f"{path}:{line}: {text!r}"


# ---------------------------------------------------------------------------
# /debug/traces' summary
# ---------------------------------------------------------------------------


@needs_wirec
def test_debug_traces_summarises_who_held_the_interpreter(
    every_span_sampled, every_span_reads_cpu
):
    import json

    server = _serve(_Stub(0.01))
    try:
        _answers(server, "sum", [b"{}", b"w" * 150_000, b"{}"])
        _status, _headers, body = get_request(
            server.port, "/debug/traces?verb=filter")
    finally:
        server.shutdown()
    page = json.loads(body)
    summary = page["summary"]
    assert summary["spans"] == len(page["recent"]) >= 3
    assert summary["arrive_spans"] >= 3 and summary["arrive_ms"] > 0
    assert summary["cpu_spans"] >= 3
    assert summary["cpu_ms"] < summary["duration_ms"]  # handle slept
    assert 0 < summary["oncpu_pct"] < 100
    assert summary["read_gil_spans"] >= 1 and summary["read_gil_ms"] >= 0
    spans, wall_ms, cpu_ms = summary["stage_cpu"]["handle"]
    assert spans >= 3 and cpu_ms < wall_ms
    assert set(page["cpu_seconds"]) == {
        "verbs", "refresh", "informers", "other", "wall"}
    entry = page["recent"][-1]
    assert entry["cpu_ms"] <= entry["duration_ms"]


# ---------------------------------------------------------------------------
# benchmarks/stage_split.py: the window's verbs by stalled and plain cycles
# ---------------------------------------------------------------------------


def test_stage_split_puts_the_stalled_cycles_verbs_apart():
    from benchmarks import stage_split

    # ten cycles of 1 ms, one of 9 ms; two verbs a cycle, 0.2 ms each
    records, kept, at = [], [], 100.0
    for index in range(11):
        length = 0.009 if index == 5 else 0.001
        records.append({"t": [at, at + length / 2, at + length / 2, at + length]})
        wait = 0.004 if index == 5 else 0.000002
        for verb_at in (at + 0.0001, at + length / 2 + 0.0001):
            # one verb a cycle read its CPU clock: (cpu s, the wall s it is of)
            cpu = (0.00015, 0.0002) if verb_at == at + 0.0001 else None
            if cpu and index == 5:
                cpu = (0.00005, 0.0002)
            # (read s, read_gil_ms, read_calls): the stalled verbs waited
            # 3 of their 4 ms of read for the interpreter, in two reads
            read = (0.004, 3.0, 2) if index == 5 else (0.0001, None, 1)
            # (write s, write_releases): the stalled verbs' answers gave
            # the interpreter away, the plain ones' went whole
            write = (0.0001, 1) if index == 5 else (0.00005, 0)
            kept.append((verb_at, 0.0002 + wait, wait, cpu, index == 0,
                         0.96 if index == 0 else None, read, write))
        at += length + 0.0005
    # before the window
    kept.append((50.0, 1.0, 1.0, None, False, None, (0.0, None, None),
                 (0.0, None)))
    window = {"began": 100.0, "ended": at, "records": records}
    split = stage_split.interpreter_split(
        kept, window, lambda r: r["t"][3] - r["t"][0])
    assert split["all"]["verbs"] == 22
    assert split["stalled_cycles_pct"] == pytest.approx(100 / 11)
    assert split["stalled"]["verbs"] == 2 and split["plain"]["verbs"] == 20
    assert split["stalled"]["arrive_ms"]["mean"] == pytest.approx(4.0)
    assert split["plain"]["arrive_ms"]["mean"] == pytest.approx(0.002)
    assert split["stalled"]["cpu_verbs"] == 1 and split["plain"]["cpu_verbs"] == 10
    assert split["stalled"]["oncpu_pct"] == pytest.approx(25.0)
    assert split["plain"]["oncpu_pct"] == pytest.approx(75.0)
    assert split["all"]["stamped_pct"] == 100.0
    assert split["tiles_span"] == [pytest.approx(0.96), 2]
    stalled, plain = split["stalled"], split["plain"]
    assert stalled["read_ms"] == pytest.approx(4.0)
    assert stalled["read_gil_ms"] == pytest.approx(3.0)
    assert stalled["read_gil_seconds"] == pytest.approx(0.006)
    assert (stalled["read_calls"], plain["read_calls"]) == (2.0, 1.0)
    assert plain["read_gil_ms"] is None  # no read after the first
    assert split["all"]["read_calls"] == pytest.approx(24 / 22)
    assert (stalled["write_ms"], plain["write_ms"]) == (
        pytest.approx(0.1), pytest.approx(0.05))
    assert stalled["write_pct"] == pytest.approx(100 * 0.0001 / 0.0042)
    assert (stalled["write_releases"], plain["write_releases"]) == (1.0, 0.0)
    assert split["all"]["write_releases"] == pytest.approx(2 / 22)


def test_stage_split_counts_a_process_minor_faults():
    from benchmarks import stage_split

    before = stage_split.minor_faults(os.getpid())
    fresh = bytearray(8 << 20)
    fresh[::4096] = b"x" * len(fresh[::4096])  # touch every page
    after = stage_split.minor_faults(os.getpid())
    # a sandboxed kernel may report 0 for both (the chip's machine does)
    assert 0 <= before <= after
    with pytest.raises(OSError):
        stage_split.minor_faults(2 ** 22 + 1)  # past pid_max: no such process


def test_stage_split_reads_the_roles_as_shares_of_the_wall_clock():
    from benchmarks import stage_split

    before = {"pas_cpu_wall_seconds_total": 10.0, "pas_cpu_verbs_seconds_total": 1.0}
    after = {"pas_cpu_wall_seconds_total": 50.0, "pas_cpu_verbs_seconds_total": 17.0,
             "pas_cpu_refresh_seconds_total": 2.0, "pas_cpu_other_seconds_total": 1.0}
    shares = stage_split.cpu_shares(before, after)
    assert shares["wall_s"] == 40.0
    assert shares["pct_of_wall"] == {
        "verbs": 40.0, "refresh": 5.0, "informers": 0.0, "other": 2.5}
    assert shares["process_cpu_pct_of_wall"] == pytest.approx(47.5)
    assert shares["verbs_oncpu_pct"] is None  # no verb counted
    after["pas_verb_seconds_total"] = 32.0
    assert stage_split.cpu_shares(before, after)["verbs_oncpu_pct"] == 50.0
    assert stage_split.cpu_shares({}, {}) == {}  # a parent's program
