"""Per-request latency tracing primitives.

The reference has no tracing/profiling at all (SURVEY §5.1: no pprof, no
OpenTelemetry — only klog verbosity).  Since this framework's north-star
metric is p99 Prioritize latency, latency histograms are built in: every
extender verb records into a :class:`LatencyRecorder`, and serving-layer
counters live in :class:`CounterSet`, both exposed as real Prometheus
text exposition (``# HELP``/``# TYPE``, ``_bucket``/``_sum``/``_count``
histogram series) on ``/metrics``.

The request-level span model, the trace ring buffer, and the metric-name
inventory build on these in utils/trace.py (docs/observability.md).
"""

from __future__ import annotations

import math
import threading
from collections import deque
from typing import Deque, Dict, Iterable, List, Optional, Tuple

# bucket bounds in seconds: a doubling ladder from 100 µs to ~105 s,
# densified below 1 ms (250/500/750 µs).  The serving floor at 10k nodes
# is ~755 µs host-side (ROADMAP item 1), and with the bare 2x ladder the
# whole sub-millisecond story — and every latency SLO computed from these
# buckets (utils/slo.py) — collapsed into the 400 µs -> 800 µs step; the
# extra bounds resolve it.  Sorted and deduplicated by construction so
# the exposition's cumulative-bucket invariant cannot be violated by a
# misordered literal.  PUBLIC: this ladder is the one definition shared
# by the histogram exposition, the SLO quantile math (utils/slo.py), and
# the exemplar store below — consumers import ``BUCKETS``, never a copy.
BUCKETS: List[float] = sorted(
    {0.00025, 0.0005, 0.00075} | {0.0001 * (2**i) for i in range(21)}
)
#: backward-compatible alias (pre-explain-plane importers)
_BUCKETS = BUCKETS


def quantile_from_buckets(
    buckets: List[int], q: float, bounds: Optional[List[float]] = None
) -> float:
    """Estimate the q-quantile in seconds from per-bucket counts.

    ``buckets`` holds one count per bound in ``bounds`` (default: the
    shared ``BUCKETS`` ladder) plus a trailing +Inf overflow count —
    exactly the shape :meth:`LatencyRecorder.snapshot` returns, and the
    shape the SLO engine's windowed bucket deltas take (utils/slo.py).

    The estimate interpolates LINEARLY WITHIN the bucket containing the
    target rank (between the previous bound — 0 for the first bucket —
    and the bucket's own bound), at the continuous rank ``q * total``
    inside the bucket's samples — the Prometheus ``histogram_quantile``
    convention, which assumes samples spread uniformly across the
    bucket.  Returning the bucket's upper bound outright would overstate
    sparse distributions by up to a whole bucket width, and an EMPTY
    family would "estimate" the top bound of the ladder.  Edge cases,
    each pinned in tests/test_slo.py:

      * zero observations -> 0.0 (no data is not "as slow as possible");
      * all samples in one bucket -> a value inside that bucket;
      * samples in the +Inf overflow bucket -> the last finite bound
        (there is no upper edge to interpolate toward — the estimate is
        a floor, as for any +Inf-bucket quantile)."""
    if bounds is None:
        bounds = BUCKETS
    total = sum(buckets)
    if total <= 0:
        return 0.0
    # continuous rank (histogram_quantile convention), clamped into
    # (0, total] so q=0 and q=1 stay inside the observed range
    rank = min(float(total), max(1e-9, q * total))
    cumulative = 0.0
    for i, count in enumerate(buckets):
        if count <= 0:
            continue
        if cumulative + count >= rank:
            if i >= len(bounds):
                # +Inf bucket: no finite upper edge — floor estimate
                return bounds[-1]
            lower = bounds[i - 1] if i > 0 else 0.0
            upper = bounds[i]
            fraction = (rank - cumulative) / count
            return lower + (upper - lower) * fraction
        cumulative += count
    return bounds[-1]  # unreachable when counts sum to total


def bucket_count_below(
    buckets: List[int],
    threshold_s: float,
    bounds: Optional[List[float]] = None,
) -> float:
    """How many of the bucketed samples fall at or under ``threshold_s``
    — the latency-SLI "good event" count (utils/slo.py).  Whole buckets
    whose bound is <= threshold count fully; the bucket straddling the
    threshold contributes the linearly interpolated fraction of its
    width below it (the same within-bucket model as
    :func:`quantile_from_buckets`); +Inf samples never count."""
    if bounds is None:
        bounds = BUCKETS
    good = 0.0
    for i, count in enumerate(buckets):
        if count <= 0:
            continue
        if i >= len(bounds):
            break  # +Inf bucket: all above any finite threshold
        lower = bounds[i - 1] if i > 0 else 0.0
        upper = bounds[i]
        if upper <= threshold_s:
            good += count
        elif lower < threshold_s:
            good += count * (threshold_s - lower) / (upper - lower)
    return good


def quantile(sorted_values: List[float], q: float) -> float:
    """Nearest-rank quantile over an ascending-sorted sample.

    ``ceil(q * n)`` is the classic nearest-rank definition: p99 of 100
    samples is the 99th value (index 98), p50 of 4 samples is the 2nd.
    The previous ``int(q * n)`` overshot by one rank — for small windows
    p99 collapsed to the out-of-range-clamped max every time."""
    if not sorted_values:
        return 0.0
    rank = math.ceil(q * len(sorted_values))
    idx = min(len(sorted_values) - 1, max(0, rank - 1))
    return sorted_values[idx]


def _fmt_value(value) -> str:
    """Prometheus sample value: ints stay exact, floats go %g."""
    if isinstance(value, int):
        return str(value)
    if isinstance(value, float) and value.is_integer():
        return str(int(value))
    return f"{value:g}"


#: a family's series map: label tuple (sorted (k, v) pairs) -> value.
#: The unlabeled series uses the empty tuple.
_LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Optional[Dict[str, str]]) -> _LabelKey:
    return tuple(sorted(labels.items())) if labels else ()


def _escape_label_value(value: str) -> str:
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _render_series(name: str, key: _LabelKey) -> str:
    if not key:
        return name
    inner = ",".join(
        f'{k}="{_escape_label_value(v)}"' for k, v in key
    )
    return f"{name}{{{inner}}}"


class CounterSet:
    """Thread-safe named counters and gauges with Prometheus text
    exposition — the non-latency half of the serving metrics (queue
    depth, admission rejections, batch sizes; docs/serving.md), the
    path-attribution / JAX-compile counters (utils/trace.py), and the
    control-plane/device families (telemetry ages, workqueue depth,
    device watermarks).  Names are emitted verbatim, so callers pass
    fully-qualified metric names (``pas_serving_queue_depth`` etc.; the
    inventory lives in trace.METRICS and ``make trace-lint`` enforces
    it).  A family may carry labeled series (``labels={"metric": ...}``)
    — one ``# TYPE`` line per family, one sample line per label set."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Dict[_LabelKey, float]] = {}
        self._gauges: Dict[str, Dict[_LabelKey, float]] = {}

    def inc(
        self,
        name: str,
        by: float = 1,
        labels: Optional[Dict[str, str]] = None,
    ) -> None:
        key = _label_key(labels)
        with self._lock:
            series = self._counters.setdefault(name, {})
            series[key] = series.get(key, 0) + by

    def inc_many(self, updates: Iterable[Tuple[str, float]]) -> None:
        """``inc`` of several unlabelled counters under ONE acquisition of
        the lock: what the exposition moves in from tallies kept elsewhere
        (utils/trace.py: the verb families, the thread ledger)."""
        with self._lock:
            counters = self._counters
            for name, by in updates:
                series = counters.get(name)
                if series is None:
                    series = counters[name] = {}
                series[()] = series.get((), 0) + by

    def set_gauge(
        self,
        name: str,
        value: float,
        labels: Optional[Dict[str, str]] = None,
    ) -> None:
        with self._lock:
            self._gauges.setdefault(name, {})[_label_key(labels)] = value

    def get(
        self,
        name: str,
        kind: Optional[str] = None,
        labels: Optional[Dict[str, str]] = None,
    ) -> float:
        """The value under ``name``: the exact series when ``labels`` is
        given, the sum over every series otherwise (for an unlabeled
        family that is just its single value).  When a counter and a
        gauge collide on one name, ``kind`` ("counter" or "gauge")
        disambiguates; without it the counter wins (the historical
        precedence)."""
        key = None if labels is None else _label_key(labels)

        def read(table: Dict[str, Dict[_LabelKey, float]]) -> float:
            series = table.get(name, {})
            if key is not None:
                return series.get(key, 0)
            return sum(series.values()) if series else 0

        with self._lock:
            if kind == "counter":
                return read(self._counters)
            if kind == "gauge":
                return read(self._gauges)
            if kind is not None:
                raise ValueError(f"unknown kind {kind!r}")
            if name in self._counters:
                return read(self._counters)
            return read(self._gauges)

    def remove(
        self,
        name: str,
        labels: Optional[Dict[str, str]] = None,
        kind: Optional[str] = None,
    ) -> None:
        """Drop a series (or, with ``labels=None``, the whole family)
        from future exposition — for label sets whose subject no longer
        exists (an evicted telemetry metric's age gauge must not stay
        frozen in /metrics forever)."""
        key = None if labels is None else _label_key(labels)
        tables = (
            [self._counters] if kind == "counter"
            else [self._gauges] if kind == "gauge"
            else [self._counters, self._gauges]
        )
        with self._lock:
            for table in tables:
                if key is None:
                    table.pop(name, None)
                    continue
                series = table.get(name)
                if series is not None:
                    series.pop(key, None)
                    if not series:
                        del table[name]

    def prometheus_text(
        self, help_texts: Optional[Dict[str, str]] = None
    ) -> str:
        """Valid exposition: ``# HELP`` (when the name is in the declared
        inventory) + ``# TYPE`` per family, then one sample per series.
        A name colliding across counter and gauge emits the counter only
        — two TYPE lines for one name would be invalid exposition
        (get(kind=) still reads both)."""
        with self._lock:
            counters = sorted(
                (name, sorted(series.items()))
                for name, series in self._counters.items()
            )
            gauges = sorted(
                (name, sorted(series.items()))
                for name, series in self._gauges.items()
                if name not in self._counters
            )
        lines: List[str] = []
        for kind, families in (("counter", counters), ("gauge", gauges)):
            for name, series in families:
                if help_texts and name in help_texts:
                    lines.append(f"# HELP {name} {help_texts[name]}")
                lines.append(f"# TYPE {name} {kind}")
                for key, value in series:
                    lines.append(
                        f"{_render_series(name, key)} {_fmt_value(value)}"
                    )
        return "\n".join(lines) + ("\n" if lines else "")


class LatencyRecorder:
    """Thread-safe per-label latency stats: histogram buckets plus a bounded
    window of raw samples for exact quantiles."""

    def __init__(self, window: int = 4096):
        self._lock = threading.Lock()
        self._window = window
        self._samples: Dict[str, Deque[float]] = {}
        self._counts: Dict[str, int] = {}
        self._sums: Dict[str, float] = {}
        self._buckets: Dict[str, List[int]] = {}
        #: last exemplar per (label, bucket index): trace id + value.
        #: Bounded by labels x buckets by construction; "last one wins"
        #: is the OpenMetrics-conventional choice — the newest slow
        #: request is the one worth opening in /debug/explain
        self._exemplars: Dict[str, Dict[int, Tuple[str, float]]] = {}

    def observe(
        self, label: str, seconds: float, trace_id: str = ""
    ) -> None:
        with self._lock:
            if label not in self._samples:
                self._samples[label] = deque(maxlen=self._window)
                self._counts[label] = 0
                self._sums[label] = 0.0
                self._buckets[label] = [0] * (len(BUCKETS) + 1)
            self._samples[label].append(seconds)
            self._counts[label] += 1
            self._sums[label] += seconds
            for i, bound in enumerate(BUCKETS):
                if seconds <= bound:
                    self._buckets[label][i] += 1
                    break
            else:
                i = len(BUCKETS)
                self._buckets[label][-1] += 1
            if trace_id:
                self._exemplars.setdefault(label, {})[i] = (
                    trace_id, seconds,
                )

    def exemplars(self) -> Dict[str, Dict[int, Tuple[str, float]]]:
        """label -> {bucket index -> (trace_id, seconds)}: the newest
        exemplar recorded in each bucket (copy; merge surface for
        :func:`histograms_text`)."""
        with self._lock:
            return {
                label: dict(per_bucket)
                for label, per_bucket in self._exemplars.items()
            }

    def labels(self) -> List[str]:
        with self._lock:
            return list(self._counts)

    def summary(self, label: str) -> Dict[str, float]:
        with self._lock:
            samples = sorted(self._samples.get(label, ()))
            count = self._counts.get(label, 0)
            total = self._sums.get(label, 0.0)
        return {
            "count": count,
            "mean": (total / count) if count else 0.0,
            "p50": quantile(samples, 0.50),
            "p90": quantile(samples, 0.90),
            "p99": quantile(samples, 0.99),
            "max": samples[-1] if samples else 0.0,
        }

    def snapshot(self) -> Dict[str, Tuple[List[int], int, float]]:
        """label -> (bucket counts copy, count, sum): the merge surface
        behind :func:`histograms_text` (several recorders, one family)."""
        with self._lock:
            return {
                label: (list(buckets), self._counts[label], self._sums[label])
                for label, buckets in self._buckets.items()
            }

    def prometheus_text(self) -> str:
        """Cumulative-histogram text exposition (the format the reference's
        own metrics pipeline scrapes, docs/custom-metrics.md)."""
        return histograms_text([self])


HISTOGRAM_METRIC = "pas_request_duration_seconds"


def histograms_text(
    recorders: Iterable["LatencyRecorder"],
    metric: str = HISTOGRAM_METRIC,
    help_texts: Optional[Dict[str, str]] = None,
    label_name: str = "verb",
) -> str:
    """All recorders' labels merged under ONE histogram family with a
    single ``# TYPE`` line — concatenating per-recorder dumps would emit
    duplicate family headers, which is invalid exposition.  A label
    recorded by several recorders sums (the serving layer and a verb
    handler never share labels in practice, but the merge must still be
    well-formed exposition if they do).

    Bucket lines carry OpenMetrics EXEMPLARS when the recorder has them
    (``... 12 # {trace_id="..."} 0.000431``): the newest trace id that
    landed in that bucket, joining a slow histogram bucket to its
    ``/debug/traces`` span and ``/debug/explain`` chain.  Prometheus'
    text parser ignores everything after ``#`` on a sample line, so the
    page stays scrape-compatible; our own parser
    (``trace.parse_prometheus_text``) strips the annotation explicitly."""
    merged: Dict[str, Tuple[List[int], int, float]] = {}
    exemplars: Dict[str, Dict[int, Tuple[str, float]]] = {}
    for recorder in recorders:
        for label, (buckets, count, total) in recorder.snapshot().items():
            if label in merged:
                old_buckets, old_count, old_sum = merged[label]
                merged[label] = (
                    [a + b for a, b in zip(old_buckets, buckets)],
                    old_count + count,
                    old_sum + total,
                )
            else:
                merged[label] = (buckets, count, total)
        for label, per_bucket in recorder.exemplars().items():
            exemplars.setdefault(label, {}).update(per_bucket)
    if not merged:
        return ""

    def exemplar_suffix(label: str, index: int) -> str:
        entry = exemplars.get(label, {}).get(index)
        if entry is None:
            return ""
        trace_id, seconds = entry
        return (
            f' # {{trace_id="{_escape_label_value(trace_id)}"}} '
            f"{seconds:.9f}"
        )

    help_text = (help_texts or {}).get(metric)
    lines: List[str] = []
    if help_text:
        lines.append(f"# HELP {metric} {help_text}")
    lines.append(f"# TYPE {metric} histogram")
    for label in sorted(merged):
        buckets, count, total = merged[label]
        cumulative = 0
        for i, (bound, n) in enumerate(zip(BUCKETS, buckets)):
            cumulative += n
            lines.append(
                f'{metric}_bucket{{{label_name}="{label}",le="{bound:g}"}} '
                f"{cumulative}{exemplar_suffix(label, i)}"
            )
        cumulative += buckets[-1]
        lines.append(
            f'{metric}_bucket{{{label_name}="{label}",le="+Inf"}} '
            f"{cumulative}{exemplar_suffix(label, len(BUCKETS))}"
        )
        lines.append(f'{metric}_sum{{{label_name}="{label}"}} {total:.9f}')
        lines.append(f'{metric}_count{{{label_name}="{label}"}} {count}')
    return "\n".join(lines) + "\n"
