"""Shared raw-socket HTTP helpers for the tracing/observability suites:
one place owns the test-side wire framing (request rendering, response
parse, server starters) so a framing change never has to be fixed in
several copies."""

import json
import socket
import threading
import time

from platform_aware_scheduling_tpu.extender.server import Server
from platform_aware_scheduling_tpu.serving import AsyncServer
from platform_aware_scheduling_tpu.utils import trace


def start_threaded(ext) -> Server:
    server = Server(ext, metrics_provider=ext.metrics_text)
    threading.Thread(
        target=lambda: server.start_server(
            port="0", unsafe=True, host="127.0.0.1", block=True
        ),
        daemon=True,
    ).start()
    assert server.wait_ready(10)
    return server


def start_async(ext, **kwargs) -> AsyncServer:
    server = AsyncServer(ext, **kwargs)
    server.start_server(port="0", unsafe=True, host="127.0.0.1", block=False)
    assert server.wait_ready(10)
    return server


def wait_for_span(trace_id: str, timeout: float = 5.0):
    """The finished span of ``trace_id`` (a handler books it after its
    answer's bytes are out, so a client that has its answer may be early)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        span = trace.TRACES.find(trace_id)
        if span is not None:
            return span
        time.sleep(0.002)
    raise AssertionError(f"span {trace_id} never recorded")


def post_bytes(path: str, body: bytes, extra: str = "") -> bytes:
    """Rendered POST request bytes (keep-alive, JSON content type)."""
    return (
        f"POST {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Type: application/json\r\n{extra}"
        f"Content-Length: {len(body)}\r\n\r\n"
    ).encode() + body


def raw_request(port: int, payload: bytes, timeout: float = 15.0):
    """(status, lowercased headers, body) for one request over a fresh
    socket."""
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


def get_request(port: int, path: str):
    payload = (
        f"GET {path} HTTP/1.1\r\nHost: t\r\nConnection: close\r\n\r\n"
    ).encode()
    return raw_request(port, payload)


ECHO_HEAD = '{"Nodes": {"metadata": {}, "items": ['


def split_filter_echo(body: bytes):
    """(frame, items) of a Nodes-mode FilterResult: the answer's text with
    every echoed ``v1.Node`` replaced by ``@``, and the echoed objects
    parsed.  The native Nodes-wire Filter echoes a passing node as the
    slice of the request it arrived in, so against the exact path the
    frame is byte-equal and each item JSON-equal (the request's
    separators and escapes are kept, not ``json.dumps``' own).  An answer
    that echoes nothing (``"Nodes": null``, ``"items": null``) is all
    frame."""
    text = body.decode("utf-8")
    if not text.startswith(ECHO_HEAD + "{"):
        return text, []
    decoder = json.JSONDecoder()
    frame, items, at = [ECHO_HEAD], [], len(ECHO_HEAD)
    while True:
        item, at = decoder.raw_decode(text, at)
        items.append(item)
        frame.append("@")
        if not text.startswith(", {", at):
            break
        frame.append(", ")
        at += 2
    frame.append(text[at:])
    return "".join(frame), items
