"""Device & compile visibility: memory watermarks, per-kernel XLA cost
analysis, and the on-demand bounded profiler capture
(docs/observability.md).

Three independent surfaces, all graceful on backends that lack them:

  * :class:`DeviceWatcher` — a periodic sampler exporting
    ``jax.local_devices()[i].memory_stats()`` as per-device gauges
    (``pas_device_memory_{in_use,peak,limit}_bytes``).  CPU devices
    return no stats; the sampler is then a clean no-op, so the metric
    families simply don't appear rather than lying with zeros.
  * :func:`capture_kernel_cost` / :func:`install_cost_hooks` — one-shot
    ``lower().compile().cost_analysis()`` per watched scoring kernel,
    captured at the kernel's FIRST compile via the
    ``trace.FIRST_COMPILE_HOOKS`` hook point (utils/trace.py), exported
    as ``pas_device_kernel_{flops,bytes}`` gauges.  The cost pass runs in
    the warm thread (where first compiles happen in production), never on
    a steady-state request.
  * :func:`profile_response` — ``GET /debug/profile?ms=N``: a bounded
    ``jax.profiler`` trace into a fresh temp dir, returning the path.
    404 cleanly when the profiler is unavailable; one capture at a time.

This module must import without jax (the host layer's rule); everything
jax touches is imported lazily inside the functions.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time
from typing import Dict, Optional, Tuple

from platform_aware_scheduling_tpu.utils import klog, trace
from platform_aware_scheduling_tpu.utils.tracing import CounterSet

#: memory_stats() key -> exported gauge family
_MEM_GAUGES = {
    "bytes_in_use": "pas_device_memory_in_use_bytes",
    "peak_bytes_in_use": "pas_device_memory_peak_bytes",
    "bytes_limit": "pas_device_memory_limit_bytes",
}


class DeviceWatcher:
    """Periodic device-memory watermark sampler."""

    def __init__(
        self, counters: Optional[CounterSet] = None, period_s: float = 10.0
    ):
        self.counters = counters if counters is not None else trace.COUNTERS
        self.period_s = period_s
        self._thread: Optional[threading.Thread] = None

    def sample(self) -> int:
        """Sample every local device once; returns how many devices
        actually reported stats (0 on CPU / without jax — a no-op, not
        an error)."""
        try:
            import jax

            devices = jax.local_devices()
        except Exception:
            return 0
        sampled = 0
        for i, device in enumerate(devices):
            try:
                stats = device.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            labels = {"device": str(getattr(device, "id", i))}
            for key, gauge in _MEM_GAUGES.items():
                if key in stats:
                    self.counters.set_gauge(
                        gauge, float(stats[key]), labels=labels
                    )
            sampled += 1
        return sampled

    def start(self, stop: Optional[threading.Event] = None) -> threading.Event:
        """Sample on a daemon thread every period until ``stop`` is set;
        returns the stop event."""
        stop = stop or threading.Event()

        def loop() -> None:
            while not stop.is_set():
                try:
                    self.sample()
                except Exception as exc:  # sampling must never take serving down
                    klog.v(4).info_s(f"device sample failed: {exc}")
                stop.wait(self.period_s)

        self._thread = threading.Thread(
            target=loop, name="pas-devicewatch", daemon=True
        )
        self._thread.start()
        return stop


# ---------------------------------------------------------------------------
# per-kernel XLA cost analysis (captured at first compile)
# ---------------------------------------------------------------------------

_cost_lock = threading.Lock()
_cost_captured: set = set()


def capture_kernel_cost(
    name: str, fn, args, kwargs=None, counters: Optional[CounterSet] = None
) -> bool:
    """One-shot FLOPs/bytes gauges for one jitted kernel at the given
    arguments; deduped per kernel name (the first capture wins — cost is
    shape-dependent and the warm shapes are the production shapes).
    Returns True when gauges were exported."""
    with _cost_lock:
        if name in _cost_captured:
            return False
        _cost_captured.add(name)
    try:
        cost = fn.lower(*args, **(kwargs or {})).compile().cost_analysis()
    except Exception as exc:  # backend without cost analysis: stay silent
        klog.v(4).info_s(f"cost analysis unavailable for {name}: {exc}")
        with _cost_lock:
            _cost_captured.discard(name)  # a later backend may succeed
        return False
    c = counters if counters is not None else trace.COUNTERS
    labels = {"kernel": name}
    exported = False
    for key, gauge in (
        ("flops", "pas_device_kernel_flops"),
        ("bytes accessed", "pas_device_kernel_bytes"),
    ):
        value = cost.get(key) if hasattr(cost, "get") else None
        if value is not None:
            c.set_gauge(gauge, float(value), labels=labels)
            exported = True
    return exported


def install_cost_hooks(counters: Optional[CounterSet] = None):
    """Register the cost capture on trace.FIRST_COMPILE_HOOKS so every
    watched kernel's first compile exports its FLOPs/bytes; returns the
    hook (tests remove it to stay hermetic).  Idempotent per counters
    target in spirit — the per-name dedup makes double installation
    harmless."""

    def hook(name, fn, args, kwargs):
        capture_kernel_cost(name, fn, args, kwargs, counters=counters)

    trace.FIRST_COMPILE_HOOKS.append(hook)
    return hook


# ---------------------------------------------------------------------------
# on-demand bounded profiler capture (GET /debug/profile?ms=N)
# ---------------------------------------------------------------------------

PROFILE_DEFAULT_MS = 100
PROFILE_MAX_MS = 10_000
_profile_lock = threading.Lock()


def _profiler_tracers():
    """(start_trace, stop_trace) or None when the profiler is missing —
    split out so tests can simulate unavailability."""
    try:
        from jax import profiler

        return profiler.start_trace, profiler.stop_trace
    except Exception:
        return None


def profile_response(
    path_with_query: str, counters: Optional[CounterSet] = None
) -> Tuple[int, bytes]:
    """(status, JSON body) for ``GET /debug/profile?ms=N``: captures a
    bounded jax.profiler trace into a fresh temp dir and returns its
    path.  404 when the profiler is unavailable, 400 on a malformed
    ``ms``, 503 while another capture is running (one at a time — the
    profiler is process-global)."""

    def body(obj: Dict) -> bytes:
        return json.dumps(obj).encode() + b"\n"

    ms = PROFILE_DEFAULT_MS
    query = path_with_query.partition("?")[2]
    for part in query.split("&"):
        key, _, value = part.partition("=")
        if key == "ms":
            try:
                ms = int(value)
            except ValueError:
                return 400, body({"error": "ms must be an integer"})
    ms = max(1, min(ms, PROFILE_MAX_MS))
    tracers = _profiler_tracers()
    if tracers is None:
        return 404, body({"error": "jax profiler unavailable"})
    start_trace, stop_trace = tracers
    if not _profile_lock.acquire(blocking=False):
        return 503, body({"error": "a profile capture is already running"})
    try:
        out_dir = tempfile.mkdtemp(prefix="pas_profile_")
        start_trace(out_dir)
        try:
            time.sleep(ms / 1000.0)  # pascheck: allow[clock] -- the /debug/profile capture window IS real wall time; the profiler samples the live process
        finally:
            stop_trace()
    except Exception as exc:  # profiler present but not functional here
        return 404, body({"error": f"profiler capture failed: {exc}"})
    finally:
        _profile_lock.release()
    c = counters if counters is not None else trace.COUNTERS
    c.inc("pas_profile_captures_total")
    return 200, body({"path": out_dir, "ms": ms})
