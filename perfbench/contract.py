"""The driver's contract for a result line, as a check the benchmark runs
on its own line before printing it (and the tests run on good and bad
lines).  ``check_line`` returns the reasons a line would be refused; an
empty list means it may be printed.
"""

from __future__ import annotations

import json
import math
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def cell_metrics(benchmark: dict, workload: str, trace: bool) -> dict:
    """{metric name: unit} the cell has to report in this trace mode."""
    group = benchmark["per_layer"] if trace else benchmark["end_to_end"]
    names = {}
    for metric in group:
        cells = metric.get("workloads")
        if cells is None and trace:
            # without the key: every cell that reports the metric it moves
            moved = next(m for m in benchmark["end_to_end"]
                         if m["name"] == metric["moves"])
            cells = moved.get("workloads")
        if cells is None or workload in cells:
            names[metric["name"]] = metric["unit"]
    return names


def is_number(value) -> bool:
    return (isinstance(value, (int, float)) and not isinstance(value, bool)
            and math.isfinite(value))


def check_line(line: str, benchmark: dict, workload: str, trace: bool,
               chips: int = None, optional=()) -> list:
    """Reasons the driver would refuse ``line`` for this cell and trace mode.
    ``optional`` names metrics a CPU rehearsal may leave out (a roofline has
    no peak there); a chip run passes none."""
    reasons = []
    try:
        result = json.loads(line)
    except ValueError as exc:
        return [f"the line is not JSON: {exc}"]
    if not isinstance(result, dict):
        return ["the line is not a JSON object"]
    for key in TOP_KEYS:
        if key not in result:
            reasons.append(f"key {key!r} is missing")
    if reasons:
        return reasons
    if not isinstance(result["correct"], bool):
        reasons.append("'correct' is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(result[key], int) or isinstance(result[key], bool) \
                or result[key] < 0:
            reasons.append(f"{key!r} is not a whole number")
    if list(result)[-1] != "compared":
        reasons.append("the compared numbers ('compared') must be the last key")
    metrics = result["metrics"]
    wanted = cell_metrics(benchmark, workload, trace)
    if not isinstance(metrics, dict):
        return reasons + ["'metrics' is not an object"]
    for name, unit in wanted.items():
        entry = metrics.get(name)
        if entry is None:
            if name not in optional:
                reasons.append(f"metric {name!r} of this cell is missing")
            continue
        if not isinstance(entry, dict) or "value" not in entry or "unit" not in entry:
            reasons.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if not is_number(entry["value"]):
            reasons.append(f"metric {name!r} has no finite number as value")
        if entry["unit"] != unit:
            reasons.append(
                f"metric {name!r} has unit {entry['unit']!r}, not {unit!r}")
        if (re.search(r"(_roofline|mfu)", name) and is_number(entry["value"])
                and not 0 < entry["value"] <= 105):
            reasons.append(f"{name!r} = {entry['value']} is not a share in (0, 105]")
    for name, entry in metrics.items():
        if not NAME.match(name):
            reasons.append(f"metric name {name!r} uses other characters than allowed")
        if isinstance(entry, dict) and not UNIT.match(str(entry.get("unit", ""))):
            reasons.append(f"unit {entry.get('unit')!r} of {name!r} is not allowed")
        if name not in wanted:
            reasons.append(f"metric {name!r} does not belong to this cell and mode")
    device = result["device"]
    if not isinstance(device, dict):
        return reasons + ["'device' is not an object"]
    for key in DEVICE_KEYS:
        if key not in device:
            reasons.append(f"device.{key} is missing")
    if not is_number(device.get("memory_peak_bytes", 0)) or \
            device.get("memory_peak_bytes", 1) <= 0:
        reasons.append("device.memory_peak_bytes is not a positive number")
    if chips is not None and device.get("count", chips) < chips:
        reasons.append(f"device.count {device.get('count')} is under the cell's {chips}")
    if trace:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not is_number(busy) or not is_number(window):
            reasons.append("a traced line needs device.busy_s and device.window_s")
        elif not 0 < busy <= window:
            reasons.append(
                f"busy_s {busy} is not above 0 and at most window_s {window}")
        breakdown = result.get("breakdown")
        if breakdown is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = breakdown.get(key)
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and is_number(r[1]))
                        for r in rows):
                    reasons.append(f"breakdown.{key} is not <= 10 [name, seconds] pairs")
    return reasons
