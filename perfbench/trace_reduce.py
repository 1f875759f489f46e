"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the benchmark
reports: the device's busy seconds, each jitted program's device time, the
operations that took most of it and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` only.  On a TPU the device planes are
``/device:TPU:<n>``: their ``XLA Modules`` line holds one event per run of a
jitted program (``jit__prioritize_kernel(<fingerprint>)``) and ``XLA Ops``
one per operation.  Busy time is the union of the operation intervals (of
the module intervals where a plane has no operation line), averaged over
the device planes that ran anything.

A CPU rehearsal has no device plane.  With ``allow_host=True`` the host
plane's XLA executor threads stand in for one (so the reduction's code is
exercised end to end), and the result says ``"stand_in": True`` — it is
never a device number.
"""

from __future__ import annotations

import glob
import os
import re

MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
HOST_EXECUTOR = "tf_XLAPjRtCpuClient"


def find_trace(directory: str) -> str:
    found = sorted(glob.glob(
        os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str) -> list:
    """[{name, lines: {line name: [(event name, start ns, duration ns)]}}]."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list) -> tuple:
    """(covered ns, merged [(start, end)]) of [(start, end)]."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(end - start for start, end in merged), merged


def module_name(event_name: str) -> str:
    """``jit__prioritize_kernel(1234)`` -> ``jit__prioritize_kernel``;
    a host ``PjitFunction(f)`` span -> ``jit_f``."""
    if event_name.startswith("PjitFunction("):
        return "jit_" + event_name[len("PjitFunction("):].rstrip(")")
    return re.sub(r"\(\d+\)$", "", event_name)


def device_planes(planes: list, allow_host: bool) -> tuple:
    """([{modules, ops}], stand_in): one entry per device that ran anything."""
    out = []
    for plane in planes:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        modules = [e for n in MODULE_LINES for e in plane["lines"].get(n, [])]
        ops = [e for n in OP_LINES for e in plane["lines"].get(n, [])]
        if modules or ops:
            out.append({"modules": modules, "ops": ops or modules})
    if out or not allow_host:
        return out, False
    for plane in planes:
        if plane["name"] != "/host:CPU":
            continue
        ops = [e for name, events in plane["lines"].items()
               if name.startswith(HOST_EXECUTOR) for e in events if e[2] > 0]
        modules = [e for events in plane["lines"].values() for e in events
                   if e[0].startswith("PjitFunction(")]
        if ops:
            out.append({"modules": modules, "ops": ops})
    return out, True


def host_spans(planes: list) -> list:
    """[(start, end, name)] of what the host's traced threads were doing."""
    spans = []
    for plane in planes:
        if plane["name"] == "/host:CPU":
            for events in plane["lines"].values():
                spans += [(e[1], e[1] + e[2], e[0]) for e in events if e[2] > 0]
    return spans


def op_name(event_name: str) -> str:
    """``%while.30 = (s32[] ...) while(...)`` -> ``while.30``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def host_activity(spans: list, start: float, end: float) -> str:
    """What the host's traced threads spent most of an idle gap in, as
    shares of the gap; the rest is Python the profiler does not trace."""
    shares = {}
    for s, e, name in spans:
        if s < end and e > start:
            shares[name] = shares.get(name, 0.0) + min(e, end) - max(s, start)
    top = sorted(shares.items(), key=lambda kv: -kv[1])[:2]
    if not top:
        return "host: nothing traced (untraced Python, or waiting for a request)"
    return "host: " + ", ".join(
        f"{100 * t / (end - start):.0f}% {name[:40]}" for name, t in top)


def reduce_trace(planes: list, window_s: float, allow_host: bool = False) -> dict:
    """The numbers of one traced window; raises if no device operation ran."""
    devices, stand_in = device_planes(planes, allow_host)
    if not devices:
        raise ValueError(
            "the trace holds no device operation: the traced window drove "
            "nothing on the device, or the profiler did not record it")
    busy = []
    modules, ops = {}, {}
    gaps = []
    spans = host_spans(planes)
    for device in devices:
        covered, merged = union([(s, s + d) for _n, s, d in device["ops"]])
        busy.append(covered / 1e9)
        for name, _start, duration in device["modules"]:
            entry = modules.setdefault(module_name(name), [0, 0.0])
            entry[0] += 1
            entry[1] += duration / 1e9
        for name, _start, duration in device["ops"]:
            ops[op_name(name)] = ops.get(op_name(name), 0.0) + duration / 1e9
        named = sorted((s, n) for n, s, _d in device["modules"] or device["ops"])
        for (_s0, end), (start, _e1) in zip(merged, merged[1:]):
            gaps.append((start - end, end, start, named))
    gaps.sort(key=lambda g: -g[0])
    idle = []
    for length, end, start, named in gaps[:10]:
        following = next((n for s, n in named if s >= start), "the window's end")
        idle.append([
            f"before {module_name(op_name(following))}; "
            f"{host_activity(spans, end, start)}", length / 1e9])
    busy_s = sum(busy) / len(busy)
    return {
        "window_s": float(window_s),
        "busy_s": min(busy_s, float(window_s)) if stand_in else busy_s,
        "devices": len(devices),
        "stand_in": stand_in,
        "modules": modules,
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": idle,
    }


def module_time(reduced: dict, pattern: str) -> tuple:
    """(runs, device seconds) of the jitted programs whose name matches."""
    runs, seconds = 0, 0.0
    for name, (count, total) in reduced["modules"].items():
        if re.search(pattern, name):
            runs += count
            seconds += total
    return runs, seconds
