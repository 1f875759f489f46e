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

Host times only: jax is imported so that leaf stages open their profiler
annotation (inactive: no profile is being taken), no device is touched.
One JSON object on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
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

    def whole_span(sampled=True):
        one = trace.Span("POST /scheduler/filter", "rid")
        one.sampled = sampled
        one.add_stage("read", 0.0)
        with one.stage("handle", leaf=False, sampled=True):
            with one.stage("cache_probe", leaf=False):
                for name in FILTER_STAGES:
                    with one.stage(name, sampled=name != "intern"):
                        pass
        with one.stage("write_arm", sampled=True):
            pass
        one.add_stage("write", 0.0)
        one.set("verb", "filter")
        one.finish(200)
        return one

    def whole_span_unsampled():
        return whole_span(False)

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

    clear = us(clear_only)
    fewer = max(loops // 10, 1000)
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
            "traces_add_with_observers": us(traces_add, fewer) - base,
            "recorder_observe": us(recorder_observe) - base,
            "journal_publish": us(journal_publish, fewer) - base,
            "decision_record_filter": us(decision_filter, fewer) - base,
            "decision_record_prioritize": us(decision_prioritize, fewer) - base,
            "counter_inc": us(counter_inc) - base,
        },
        "span_observers": len(trace.SPAN_OBSERVERS),
        "filter_stages": len(FILTER_STAGES) + 5,
        "sample_every": trace.SAMPLE_EVERY,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
