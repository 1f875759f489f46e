"""What each always-on observer on the served path costs, per request.

    python3 benchmarks/observer_cost.py [--loops 200000]

A micro-loop on this machine's host: microseconds per call of the span
primitive (a ``Span`` with the stages of a names-wire Filter, on a sampled
span and on one that is not; a leaf stage, a container stage and a sampled
stage on a span that is not sampled, alone; the bare profiler annotation),
``TRACES.add`` with its ``SPAN_OBSERVERS``, ``LatencyRecorder.observe``,
``events.JOURNAL.publish``, the decision log's two records and one
``COUNTERS.inc``.  No observer is switched off; nothing is served.  The
numbers ROADMAP C8 (one observer system instead of eight) starts from.

Since PR 37 the span is the one the threaded front-end makes — the
``arrive`` stage, the question whether this span reads its CPU clock
(``trace.cpu_sample_due``: at most one span every ``CPU_SAMPLE_GAP_S``) —
``span_with_filter_stages_cpu`` is a span that does: the clock read where
the first byte is held, in ``finish()`` and around every stage — and
``TRACES.add`` folds a verb span's label-free families; beside
them: one ``time.thread_time()``, ``recv_stamped`` against ``sock.recv`` over
a socket pair (each with the ``send`` that feeds it) and the thread ledger's
walk a live thread, which no request pays.  Run the parent's own copy of
this file on the parent's tree for the figure before.

Host times only: jax is imported so that leaf stages open their profiler
annotation (inactive: no profile is being taken), no device is touched.
One JSON object on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the stages a names-wire Filter records inside handle > cache_probe, in
#: order; all sampled but intern
FILTER_STAGES = ("scan", "policy", "intern", "lookup", "fencode", "record")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--loops", type=int, default=200_000)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)

    import jax  # noqa: F401 — leaf stages annotate only once jax is imported
    from jax.profiler import TraceAnnotation

    from platform_aware_scheduling_tpu.utils import decisions, events, trace
    from platform_aware_scheduling_tpu.utils.tracing import LatencyRecorder

    loops = args.loops

    def us(fn, number=loops) -> float:
        best = min(timeit.repeat(fn, number=number, repeat=3))
        return best / number * 1e6

    def empty():
        pass

    base = us(empty)
    span = trace.Span("POST /scheduler/filter")

    def leaf_stage():
        with span.stage("decode"):
            pass
        span.stages.clear()

    def container_stage():
        with span.stage("handle", leaf=False):
            pass
        span.stages.clear()

    span.sampled = False

    def sampled_stage_off():
        with span.stage("scan", sampled=True):
            pass
        span.stages.clear()

    def clear_only():
        span.stages.clear()

    def annotation():
        with TraceAnnotation("pas:decode"):
            pass

    def whole_span(sampled=True, cpu=False):
        # as _FastHTTPHandler._serve makes it: whether this span reads its
        # CPU clock, the clock where the first byte is held, the stamped
        # wait, read
        t0 = time.perf_counter()
        cpu0 = time.thread_time() if trace.cpu_sample_due(t0) or cpu else None
        one = trace.Span("POST /scheduler/filter", "rid", t0=t0, cpu0=cpu0)
        one.sampled = sampled
        one.add_stage("arrive", 0.0, offset=0.0)
        one.add_stage("read", 0.0, offset=0.0,
                      cpu=None if cpu0 is None else time.thread_time() - cpu0)
        with one.stage("handle", leaf=False, sampled=True):
            with one.stage("cache_probe", leaf=False):
                for name in FILTER_STAGES:
                    with one.stage(name, sampled=name != "intern"):
                        pass
        one.set("read_calls", 1)
        cpu_write = None if cpu0 is None else time.thread_time()
        one.add_stage("write", 0.0, cpu=None if cpu_write is None
                      else time.thread_time() - cpu_write)
        one.set("verb", "filter")
        one.finish(200)
        return one

    def whole_span_unsampled():
        return whole_span(False)

    def whole_span_cpu():
        return whole_span(True, cpu=True)

    buffer = trace.TraceBuffer()
    finished = whole_span()

    def traces_add():
        # the process-wide SPAN_OBSERVERS (utils/events.py's wire event)
        # run inside add(), as they do behind both front-ends
        buffer.add(finished)

    recorder = LatencyRecorder()

    def recorder_observe():
        recorder.observe("filter", 0.0005, trace_id="rid")

    def journal_publish():
        events.JOURNAL.publish(
            "verdict", "filter", request_id="rid", pod="default/p",
            data={"failed": 50, "path": "miss"})

    reasons = {f"node-{i}": "rule" for i in range(50)}

    def decision_filter():
        decisions.DECISIONS.record_filter(
            request_id="rid", pod_namespace="default", pod_name="p",
            policy="pol", path="native", candidates=500, filtered=50,
            violating=reasons, violating_scope="policy_state")

    def decision_prioritize():
        decisions.DECISIONS.record_prioritize(
            request_id="rid", pod_namespace="default", pod_name="p",
            policy="pol", path="native", candidates=500, metric="m",
            operator="GreaterThan", score_head=[], planned=None,
            ranked=None, node_index=None, detail=None)

    def counter_inc():
        trace.COUNTERS.inc("pas_filter_cache_miss_total")

    from platform_aware_scheduling_tpu.extender.server import native_io

    left, right = socket.socketpair()
    left.settimeout(5.0)
    fd = left.fileno()
    stamped = getattr(native_io(), "recv_stamped", None)

    def recv_stamped():
        right.send(b"x")
        return stamped(fd, 65536, 5.0)

    def sock_recv():
        right.send(b"x")
        return left.recv(65536)

    # the ledger's walk: eight parked threads of a role beside this one
    parked = threading.Event()
    for index in range(8):
        threading.Thread(target=parked.wait, daemon=True,
                         name=f"pas-informer-cost-{index}").start()
    fewer = max(loops // 10, 1000)
    walk = us(trace.thread_cpu, fewer) - base

    clear = us(clear_only)
    out = {
        "loops": loops,
        "us": {
            "stage_leaf": us(leaf_stage) - clear,
            "stage_container": us(container_stage) - clear,
            "stage_sampled_off": us(sampled_stage_off) - clear,
            "annotation_alone": us(annotation) - base,
            "span_with_filter_stages": us(whole_span, fewer) - base,
            "span_with_filter_stages_unsampled": (
                us(whole_span_unsampled, fewer) - base
            ),
            "span_with_filter_stages_cpu": us(whole_span_cpu, fewer) - base,
            "traces_add_with_observers": us(traces_add, fewer) - base,
            "recorder_observe": us(recorder_observe) - base,
            "journal_publish": us(journal_publish, fewer) - base,
            "decision_record_filter": us(decision_filter, fewer) - base,
            "decision_record_prioritize": us(decision_prioritize, fewer) - base,
            "counter_inc": us(counter_inc) - base,
            "thread_time": us(time.thread_time) - base,
            "recv_stamped_with_send": (
                us(recv_stamped, fewer) - base if stamped else None
            ),
            "sock_recv_with_send": us(sock_recv, fewer) - base,
            # the whole walk over this process's threads, and a thread
            "ledger_walk": walk,
            "ledger_walk_a_thread": walk / threading.active_count(),
        },
        "span_observers": len(trace.SPAN_OBSERVERS),
        "filter_stages": len(FILTER_STAGES) + 5,
        "sample_every": trace.SAMPLE_EVERY,
    }
    parked.set()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
