"""The readers of per-layer metrics, by kind.  A metric's own file
(``layer_metrics/<name>.json``) names its kind and parameters; a later PR
adds a metric of an existing kind by adding a file and a BENCHMARK.json
entry.  A reader that finds nothing to read returns None and the metric is
left out of the line — it never returns 0 for a share.

``ctx`` holds what one traced run gathered: ``records`` (the generator's),
``counters`` (before/after scrapes of /metrics), ``stages`` (/debug/traces),
``system``, ``window`` (began/ended), ``trace`` (the reduced profile),
``sizes`` (the cell's logical sizes) and ``device_kind``.
"""

from __future__ import annotations

import statistics

import numpy as np

import trace_reduce
import work
from generator import cycle_span


def client_verb_p50(spec: dict, ctx: dict):
    """Median, on the generator's clock, of one verb's round trip (or of the
    whole cycle) in milliseconds."""
    if spec["verb"] == "cycle":
        spans = [cycle_span(r) for r in ctx["records"]]
    else:
        first, last = {"filter": (0, 1), "second": (2, 3)}[spec["verb"]]
        spans = [r["t"][last] - r["t"][first] for r in ctx["records"]
                 if not np.isnan(r["t"][last])]
    return statistics.median(spans) * 1e3 if spans else None


def client_stalled_share(spec: dict, ctx: dict):
    """Share (%) of the window's cycles that took over ``over_median`` times
    the median cycle: the cycles a refresh pass, a collection or the host
    held up, which is what the tail is made of."""
    spans = [cycle_span(r) for r in ctx["records"]]
    if not spans:
        return None
    limit = spec["over_median"] * statistics.median(spans)
    return 100.0 * sum(1 for s in spans if s > limit) / len(spans)


def trace_stage_mean(spec: dict, ctx: dict):
    """Mean milliseconds of one stage over the served verbs in the program's
    /debug/traces ring, scraped when the window closed."""
    durations = [
        stage["duration_ms"]
        for entry in ctx["stages"]
        if entry.get("name", "").startswith("POST /scheduler/")
        for stage in entry.get("stages", ())
        if stage["name"] == spec["stage"]
    ]
    return sum(durations) / len(durations) if durations else None


def counter_ratio(spec: dict, ctx: dict):
    """100 x the window's increase of some counters over that of others (or
    over the second verbs the generator sent)."""
    before, after = ctx["counters"]

    def delta(names):
        return sum(after.get(n, 0.0) - before.get(n, 0.0) for n in names)

    if spec["denominator"] == "second_verbs_sent":
        below = sum(1 for r in ctx["records"] if r["second"])
    else:
        below = delta(spec["denominator"])
    return 100.0 * delta(spec["numerator"]) / below if below > 0 else None


def hook_interval(spec: dict, ctx: dict):
    """Mean milliseconds from a refresh pass's first fetch at the played
    custom-metrics API to the program's end-of-pass hook."""
    intervals = ctx["system"].pass_intervals(
        ctx["window"]["began"], ctx["window"]["ended"])
    return sum(intervals) / len(intervals) * 1e3 if intervals else None


def module_roofline(spec: dict, ctx: dict):
    """Share of the chip's roofline reached by the jitted programs whose
    trace names match ``pattern``: the least time their runs could take, by
    ``work.py`` on the cell's logical sizes, over their device time."""
    if ctx["trace"] is None:
        return None
    runs, seconds = trace_reduce.module_time(ctx["trace"], spec["pattern"])
    if not runs or seconds <= 0:
        return None
    least, _bound = work.roofline_seconds(
        work.WORK[spec["work"]](ctx["sizes"]), work.peaks(ctx["device_kind"]))
    return 100.0 * runs * least / seconds


def device_idle(spec: dict, ctx: dict):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


READERS = {
    "client_verb_p50": client_verb_p50,
    "client_stalled_share": client_stalled_share,
    "trace_stage_mean": trace_stage_mean,
    "counter_ratio": counter_ratio,
    "hook_interval": hook_interval,
    "module_roofline": module_roofline,
    "device_idle": device_idle,
}
