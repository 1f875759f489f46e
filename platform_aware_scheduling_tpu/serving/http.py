"""Event-loop HTTP(S) front-end: asyncio transport over the existing wire
parity stack (docs/serving.md).

Drop-in alternative to the threaded ``extender.server.Server`` —
identical constructor-and-serve surface (``start_server`` / ``port`` /
``wait_ready`` / ``shutdown``), identical wire behavior:

  * framing comes from the SAME sans-IO head parser the threaded handler
    uses (``extender.server.parse_request_head``: strict Content-Length,
    Transfer-Encoding and duplicate-CL rejection, 64 KiB head cap, 1 GB
    body refusal, 100-continue, keep-alive + pipelining, 5 s read /
    10 s write timeouts);
  * routing/middleware IS ``extender.server.Server.route`` (exact
    content-type check, 405, 404 catch-all, /metrics, V(5) wire capture)
    — this class wraps an unstarted ``Server`` purely for routing;
  * mTLS uses the same pinned ``configure_secure_context``.

What changes is the concurrency model: connections are served by ONE
event loop (no thread per connection), and verb execution goes through
the micro-batching dispatcher — concurrent requests coalesce into one
fused device solve with responses demultiplexed per request
(serving/dispatcher.py, serving/batch.py).  The threaded server remains
the reference-parity default; this front-end is opt-in via
``--serving=async`` on the service mains.
"""

from __future__ import annotations

import asyncio
import socket
import threading
import time
from typing import Optional

from platform_aware_scheduling_tpu.extender.server import (
    HTTPRequest,
    HTTPResponse,
    EXECUTOR_DEBUG_PATHS,
    HeadParseError,
    MAX_HEAD_LENGTH,
    QUEUE_BYPASS_PATHS,
    READ_HEADER_TIMEOUT_S,
    Server,
    WRITE_TIMEOUT_S,
    configure_secure_context,
    parse_request_head,
    render_response,
    render_simple,
)
from platform_aware_scheduling_tpu.serving.batch import BatchExecutor
from platform_aware_scheduling_tpu.serving.dispatcher import (
    DEFAULT_MAX_BATCH,
    DEFAULT_MAX_QUEUE_DEPTH,
    DEFAULT_WINDOW_S,
    MicroBatchDispatcher,
)
from platform_aware_scheduling_tpu.utils import klog, trace
from platform_aware_scheduling_tpu.utils.tracing import (
    CounterSet,
    LatencyRecorder,
)

_RBUF = 1 << 16


class AsyncServer:
    """Asyncio front-end + micro-batched dispatch around a Scheduler."""

    def __init__(
        self,
        scheduler,
        metrics_provider=None,
        window_s: float = DEFAULT_WINDOW_S,
        max_batch: int = DEFAULT_MAX_BATCH,
        max_queue_depth: int = DEFAULT_MAX_QUEUE_DEPTH,
        retry_after_s: float = 1.0,
    ):
        self.scheduler = scheduler
        # serving-stage observability, merged into the same /metrics
        # endpoint the extender's verb histograms use.  The scheduler's
        # own LatencyRecorder is shared when it has one so the whole
        # process emits ONE pas_request_duration_seconds family (a second
        # recorder would need a second # TYPE header — invalid exposition)
        scheduler_recorder = getattr(scheduler, "recorder", None)
        self.recorder = scheduler_recorder or LatencyRecorder()
        self.counters = CounterSet()
        # the admission-shed counter (pas_serving_rejected_total) lives
        # in THIS layer-local set — an SLO engine judging the scheduler's
        # verb availability must read it, or a saturated queue shedding
        # half the traffic would score compliance 1.0 (utils/slo.py; the
        # mains attach the engine before building the server)
        slo_engine = getattr(scheduler, "slo", None)
        if (
            slo_engine is not None
            and hasattr(slo_engine, "counter_sets")
            and self.counters not in slo_engine.counter_sets
        ):
            slo_engine.counter_sets.append(self.counters)
        trace.install_jax_hooks()

        if metrics_provider is not None:
            # legacy explicit provider: its text is prepended verbatim
            # (the caller owns exposition validity for that fragment).
            # When the recorder is privately owned (scheduler has none),
            # the serving-stage histograms must still be exposed here —
            # the provider's text cannot contain them
            _extra = metrics_provider
            own_recorders = [] if scheduler_recorder is not None else [
                self.recorder
            ]

            def provider() -> str:
                return _extra() + trace.exposition(
                    recorders=own_recorders, counter_sets=[self.counters]
                )

        else:

            def provider() -> str:
                # dynamic: the SLO engine may be wired after construction
                # (assembly order, tests) and its families must appear on
                # /metrics only while it is (utils/slo.py off-path rule)
                sets = [self.counters]
                slo_engine = getattr(self.scheduler, "slo", None)
                if slo_engine is not None:
                    sets.append(slo_engine.counters)
                controller = getattr(self.scheduler, "control", None)
                if controller is not None:
                    sets.append(controller.counters)
                flight = getattr(self.scheduler, "flight", None)
                if flight is not None:
                    sets.append(flight.counters)
                admission = getattr(self.scheduler, "admission", None)
                if admission is not None:
                    sets.append(admission.counters)
                shard = getattr(self.scheduler, "shard", None)
                if shard is not None:
                    sets.append(shard.counters)
                return trace.exposition(
                    recorders=[self.recorder], counter_sets=sets
                )

        # unstarted Server: routing + middleware + /metrics/health only
        self._router = Server(scheduler, metrics_provider=provider)
        # readiness gains the async-only condition: admission-queue
        # headroom.  A saturated queue answers /readyz 503 (with the
        # queue named in the reasons) while the endpoint itself stays
        # readable — it bypasses the very queue it reports on
        self._router.probe.register("admission_queue", self._queue_condition)
        self.batch = BatchExecutor(self._router)
        self.dispatcher = MicroBatchDispatcher(
            route=self._router.route,
            batch_route=self.batch,
            window_s=window_s,
            max_batch=max_batch,
            max_queue_depth=max_queue_depth,
            retry_after_s=retry_after_s,
            recorder=self.recorder,
            counters=self.counters,
        )
        self._ready = threading.Event()
        self._stop: Optional[asyncio.Event] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._port: Optional[int] = None
        self._startup_error: Optional[BaseException] = None

    @property
    def probe(self):
        """The /readyz ReadinessProbe (scheduler conditions + the
        admission-queue condition registered above)."""
        return self._router.probe

    def _queue_condition(self):
        depth = len(self.dispatcher._queue)
        limit = self.dispatcher.max_queue_depth
        if depth >= limit:
            return False, f"admission queue saturated ({depth}/{limit})"
        return True, f"depth {depth}/{limit}"

    # -- serving ---------------------------------------------------------------

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
        """Same contract as ``Server.start_server``: plain HTTP when
        ``unsafe``, pinned mTLS otherwise; ``block=False`` serves on a
        daemon thread (startup failures re-raise in the caller)."""
        trace.watch_gc()
        ssl_context = None
        if not unsafe:
            ssl_context = configure_secure_context(cert_file, key_file, ca_file)
        if block:
            self._serve_loop(host, port, ssl_context, unsafe, reraise=True)
            return
        self._thread = threading.Thread(
            target=self._serve_loop,
            args=(host, port, ssl_context, unsafe, False),
            name="pas-serve-async",
            daemon=True,
        )
        self._thread.start()
        while not self._ready.wait(0.05):
            if not self._thread.is_alive():
                raise self._startup_error or RuntimeError(
                    "async server died during startup"
                )

    def _serve_loop(self, host, port, ssl_context, unsafe, reraise) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop
        try:
            loop.run_until_complete(
                self._main(host, port, ssl_context, unsafe)
            )
        except BaseException as exc:  # surfaced by start_server(block=False)
            self._startup_error = exc
            if reraise:
                raise
            klog.error("async extender server failed: %s", exc)
        finally:
            self._loop = None
            try:
                loop.close()
            except Exception:
                pass

    async def _main(self, host, port, ssl_context, unsafe) -> None:
        self._stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        self.dispatcher.start(loop)
        server = await asyncio.start_server(
            self._handle_conn,
            host or None,
            int(port),
            ssl=ssl_context,
        )
        self._port = server.sockets[0].getsockname()[1]
        scheme = "HTTP" if unsafe else "HTTPS"
        klog.v(2).info_s(
            f"Extender Listening on {scheme} {self._port} (async)",
            component="extender",
        )
        self._ready.set()
        try:
            async with server:
                await self._stop.wait()
        finally:
            await self.dispatcher.stop()
            # cancel lingering connection handlers so loop.close() is
            # quiet (keep-alive connections outlive the stop signal)
            tasks = [
                t
                for t in asyncio.all_tasks()
                if t is not asyncio.current_task()
            ]
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

    # -- connection handling ---------------------------------------------------

    async def _handle_conn(self, reader, writer) -> None:
        sock = writer.get_extra_info("socket")
        if sock is not None:
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
        buf = bytearray()
        try:
            while True:
                # -- read the request head (same framing as the threaded
                #    handler; shared parse_request_head).  Span timing
                #    starts at the request's FIRST byte, not loop entry —
                #    keep-alive idle time belongs to no request ----------
                t_accept = time.perf_counter() if buf else None
                head_end = buf.find(b"\r\n\r\n")
                while head_end < 0:
                    if len(buf) > MAX_HEAD_LENGTH:
                        await self._send_simple(writer, 431)
                        return
                    chunk = await self._read(reader)
                    if not chunk:
                        return
                    if t_accept is None:
                        t_accept = time.perf_counter()
                    buf += chunk
                    head_end = buf.find(b"\r\n\r\n")
                if head_end > MAX_HEAD_LENGTH:
                    await self._send_simple(writer, 431)
                    return
                head = bytes(buf[:head_end])
                del buf[: head_end + 4]
                try:
                    method, path, version, headers, lowered, length = (
                        parse_request_head(head)
                    )
                except HeadParseError as exc:
                    await self._send_simple(writer, exc.status)
                    return
                if lowered.get("expect", "").lower() == "100-continue":
                    writer.write(b"HTTP/1.1 100 Continue\r\n\r\n")
                    try:
                        await writer.drain()
                    except (ConnectionError, OSError):
                        return
                # -- read the body ----------------------------------------
                while len(buf) < length:
                    chunk = await self._read(reader)
                    if not chunk:
                        return
                    buf += chunk
                body = bytes(buf[:length])
                del buf[:length]
                # -- dispatch through the micro-batcher + respond ---------
                request_id = (
                    lowered.get("x-request-id") or trace.new_request_id()
                )
                span = trace.Span(f"{method} {path}", request_id, t0=t_accept)
                span.add_stage("read", time.perf_counter() - t_accept)
                request = HTTPRequest(
                    method=method, path=path, headers=headers, body=body,
                    span=span,
                )
                bare_path = path.partition("?")[0]
                # read + handle + write tile the span (handle on sampled
                # spans); queue_wait, coalesce and the verb's stages lie
                # inside it, so it is a container and never annotated
                with span.stage("handle", leaf=False, sampled=True):
                    if bare_path in QUEUE_BYPASS_PATHS:
                        # observability endpoints bypass the admission queue:
                        # they must stay readable precisely when the queue is
                        # saturated (the condition they exist to diagnose),
                        # and they never touch the device.  The set derives
                        # from the DEBUG_ENDPOINTS index (extender/server.py)
                        # so a new debug route cannot silently queue here
                        try:
                            response = self._router.route(request)
                        except Exception as exc:
                            klog.error("handler raised: %r", exc)
                            response = HTTPResponse(status=500)
                    elif bare_path in EXECUTOR_DEBUG_PATHS:
                        # also bypass the queue, but these BLOCK: the
                        # bounded profile capture sleeps for its window and
                        # a what-if runs a whole twin replay — run them
                        # off-loop so the event loop keeps serving meanwhile
                        try:
                            response = await asyncio.get_running_loop().run_in_executor(
                                None, self._router.route, request
                            )
                        except Exception as exc:
                            klog.error("handler raised: %r", exc)
                            response = HTTPResponse(status=500)
                    else:
                        response = await self.dispatcher.submit(request)
                # every response carries the id — INCLUDING the 503
                # backpressure rejection the dispatcher answers directly
                response.headers.setdefault("X-Request-ID", request_id)
                close = (
                    version == "HTTP/1.0"
                    or lowered.get("connection", "").lower() == "close"
                )
                t_write = time.perf_counter()
                writer.write(render_response(response, close))
                try:
                    await asyncio.wait_for(writer.drain(), WRITE_TIMEOUT_S)
                except (asyncio.TimeoutError, ConnectionError, OSError):
                    span.set("error", "write failed")
                    return
                finally:
                    span.add_stage("write", time.perf_counter() - t_write)
                    trace.TRACES.add(span.finish(response.status))
                if close:
                    return
        finally:
            try:
                writer.close()
            except Exception:
                pass

    @staticmethod
    async def _read(reader) -> bytes:
        """One socket read under the head/body timeout; b'' = give up on
        the connection (EOF, timeout, reset) — as the threaded handler."""
        try:
            return await asyncio.wait_for(
                reader.read(_RBUF), READ_HEADER_TIMEOUT_S
            )
        except (asyncio.TimeoutError, ConnectionError, OSError):
            return b""

    @staticmethod
    async def _send_simple(writer, status: int) -> None:
        try:
            writer.write(
                render_simple(
                    status, close=True, request_id=trace.new_request_id()
                )
            )
            await writer.drain()
        except (ConnectionError, OSError):
            pass

    # -- control surface (Server parity) ---------------------------------------

    @property
    def port(self) -> int:
        assert self._port is not None
        return self._port

    def wait_ready(self, timeout: float = 10.0) -> bool:
        return self._ready.wait(timeout)

    def shutdown(self) -> None:
        loop = self._loop
        if loop is not None and self._stop is not None:
            try:
                loop.call_soon_threadsafe(self._stop.set)
            except RuntimeError:
                pass  # loop already closed
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        self._ready.clear()
        self._port = None
