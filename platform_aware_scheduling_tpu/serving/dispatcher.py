"""Micro-batching dispatcher: the continuous-batching core of the async
serving path (docs/serving.md).

Requests submitted from the event loop land in a bounded admission queue;
a single batcher coroutine coalesces whatever arrives within a short
window (default 1 ms, tunable) into one batch and hands it to a
single-worker thread pool, where the batch route fuses the device work
(one batched solve per coalesced batch — serving/batch.py) and demuxes
per-request responses.  One worker thread means the Python-side encode
work of concurrent requests is SERIALIZED instead of racing N handler
threads into the interpreter lock — at c=8 this is the difference between
one device dispatch + 8 cheap encodes and 8 GIL-thrashing threads (the
round-5 verdict's 8-12x p99 inflation).

Backpressure: past ``max_queue_depth`` queued requests, new submissions
are rejected immediately with 503 + ``Retry-After`` (never queued, never
dropped silently); the queue draining restores admission with no other
recovery action needed.

Every stage records into utils/tracing.py primitives, exported on the
server's /metrics endpoint:

  * ``serving_queue_wait`` / ``serving_batch_solve`` / ``serving_total``
    latency histograms (LatencyRecorder);
  * ``pas_serving_queue_depth`` gauge, ``pas_serving_requests_total`` /
    ``pas_serving_batches_total`` / ``pas_serving_rejected_total`` /
    ``pas_serving_batch_fallback_total`` counters (CounterSet).
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional

from platform_aware_scheduling_tpu.extender.server import (
    HTTPRequest,
    HTTPResponse,
)
from platform_aware_scheduling_tpu.utils import events, klog, trace
from platform_aware_scheduling_tpu.utils.tracing import (
    CounterSet,
    LatencyRecorder,
)

#: micro-batch coalescing window, seconds
DEFAULT_WINDOW_S = 0.001
#: max requests fused per batch
DEFAULT_MAX_BATCH = 64
#: admission queue bound; past it requests get 503 + Retry-After
DEFAULT_MAX_QUEUE_DEPTH = 256


class MicroBatchDispatcher:
    """Admission queue + coalescing window + single-worker batch solve."""

    def __init__(
        self,
        route: Callable[[HTTPRequest], HTTPResponse],
        batch_route: Optional[
            Callable[[List[HTTPRequest]], List[HTTPResponse]]
        ] = None,
        window_s: float = DEFAULT_WINDOW_S,
        max_batch: int = DEFAULT_MAX_BATCH,
        max_queue_depth: int = DEFAULT_MAX_QUEUE_DEPTH,
        retry_after_s: float = 1.0,
        recorder: Optional[LatencyRecorder] = None,
        counters: Optional[CounterSet] = None,
    ):
        self.route = route
        self.batch_route = batch_route
        self.window_s = window_s
        self.max_batch = max(1, max_batch)
        self.max_queue_depth = max(1, max_queue_depth)
        self.retry_after_s = retry_after_s
        self.recorder = recorder if recorder is not None else LatencyRecorder()
        self.counters = counters if counters is not None else CounterSet()
        self._queue: deque = deque()  # (request, future, t_enqueue)
        self._wakeup: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        # ONE worker: batches execute serially by design (module doc)
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pas-serving-batch"
        )

    # -- lifecycle (event-loop thread only) -----------------------------------

    def start(self, loop: asyncio.AbstractEventLoop) -> None:
        self._wakeup = asyncio.Event()
        self._task = loop.create_task(self._run(loop))

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        for _, future, _ in self._queue:
            if not future.done():
                future.set_result(HTTPResponse(status=503))
        self._queue.clear()
        self._executor.shutdown(wait=False)

    # -- submission (event-loop thread only) ----------------------------------

    def submit(self, request: HTTPRequest) -> "asyncio.Future[HTTPResponse]":
        """Queue one request; resolves to its response.  A saturated queue
        answers 503 + Retry-After immediately (backpressure, module doc)."""
        loop = asyncio.get_running_loop()
        future: asyncio.Future = loop.create_future()
        self.counters.inc("pas_serving_requests_total")
        if len(self._queue) >= self.max_queue_depth:
            self.counters.inc("pas_serving_rejected_total")
            trace.of(request).set("rejected", True)
            events.JOURNAL.publish(
                "serving",
                "request shed",
                request_id=trace.of(request).trace_id,
                data={"path": request.path, "depth": len(self._queue)},
            )
            future.set_result(
                HTTPResponse(
                    status=503,
                    headers={
                        "Retry-After": str(
                            max(1, int(round(self.retry_after_s)))
                        )
                    },
                )
            )
            return future
        self._queue.append((request, future, time.perf_counter()))
        self.counters.set_gauge("pas_serving_queue_depth", len(self._queue))
        if self._wakeup is not None:
            self._wakeup.set()
        return future

    # -- the batcher loop ------------------------------------------------------

    async def _run(self, loop: asyncio.AbstractEventLoop) -> None:
        while True:
            while not self._queue:
                self._wakeup.clear()
                await self._wakeup.wait()
            t_wake = time.perf_counter()
            # coalescing window, deadline-based: the batch dispatches at
            # head-arrival + window_s, so stragglers landing within the
            # window of the FIRST request fuse with it (skipped when a
            # full batch is already waiting — no reason to add latency
            # then, and never over-slept when the batcher wakes late)
            if self.window_s > 0 and len(self._queue) < self.max_batch:
                remaining = self.window_s - (
                    time.perf_counter() - self._queue[0][2]
                )
                if remaining > 0:
                    await asyncio.sleep(remaining)
            batch = []
            while self._queue and len(batch) < self.max_batch:
                batch.append(self._queue.popleft())
            self.counters.set_gauge(
                "pas_serving_queue_depth", len(self._queue)
            )
            self.counters.inc("pas_serving_batches_total")
            self.counters.inc("pas_serving_batched_requests_total", len(batch))
            t_solve = time.perf_counter()
            # the BATCH span: links every member request span, records the
            # coalesce window + fused solve (the N:1 edge of the trace
            # graph — member spans carry their own queue_wait/coalesce)
            batch_span = trace.Span("serving_batch", t0=t_wake)
            batch_span.set("size", len(batch))
            batch_span.add_stage("coalesce", t_solve - t_wake)
            for request, _, t_enq in batch:
                span = trace.of(request)
                span.add_stage("queue_wait", max(0.0, t_wake - t_enq))
                span.add_stage(
                    "coalesce", max(0.0, t_solve - max(t_enq, t_wake))
                )
                if span is not trace.NULL_SPAN:
                    batch_span.link(span.trace_id)
                    span.set("batch_id", batch_span.trace_id)
                self.recorder.observe("serving_queue_wait", t_solve - t_enq)
            requests = [request for request, _, _ in batch]
            try:
                responses = await loop.run_in_executor(
                    self._executor, self._solve, requests
                )
            except Exception as exc:  # executor trouble: fail the batch loud
                klog.error("batch executor failed: %s", exc)
                responses = [HTTPResponse(status=500) for _ in batch]
            done = time.perf_counter()
            self.recorder.observe("serving_batch_solve", done - t_solve)
            batch_span.add_stage("batch_solve", done - t_solve)
            trace.TRACES.add(batch_span.finish())
            for (_, future, t_enq), response in zip(batch, responses):
                if not future.done():
                    future.set_result(response)
                self.recorder.observe("serving_total", done - t_enq)

    # -- batch execution (worker thread) ---------------------------------------

    def _solve(self, requests: List[HTTPRequest]) -> List[HTTPResponse]:
        if self.batch_route is not None:
            try:
                responses = self.batch_route(requests)
                if len(responses) == len(requests):
                    return responses
                klog.error(
                    "batch route returned %d responses for %d requests; "
                    "per-request fallback",
                    len(responses),
                    len(requests),
                )
            except Exception as exc:
                klog.error(
                    "batch route failed, per-request fallback: %s", exc
                )
            self.counters.inc("pas_serving_batch_fallback_total")
        out = []
        for request in requests:
            try:
                out.append(self.route(request))
            except Exception as exc:
                klog.error("handler raised: %r", exc)
                out.append(HTTPResponse(status=500))
        return out
