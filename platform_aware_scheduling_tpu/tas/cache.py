"""TAS state cache: policies + refcounted, self-updating metrics.

Reference: telemetry-aware-scheduling/pkg/cache/.  The reference serializes
all access through a single goroutine reading a request channel
(cache.go:20-63); here the same observable semantics — serialized reads and
writes, WRITE-with-nil-payload preserving the existing value (cache.go:52-57)
— are provided by a mutex-guarded store (the idiomatic Python translation;
there is no perf reason for channel hand-off since the hot path reads the
tensorized mirror, not this cache).

On top sits :class:`AutoUpdatingCache` (autoupdating.go:20-137): two
keyspaces ``policies/<ns>/<name>`` and ``metrics/<metric>``, a refcount map
so a metric shared by several policies is only evicted when the last one is
deleted, and ``periodic_update`` re-fetching every registered metric each
sync period.  Mutation listeners let the device-tensor mirror
(models/state.py) track changes without polling.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from collections.abc import Mapping
from typing import Any, Callable, Dict, List, Optional, Tuple

from platform_aware_scheduling_tpu.tas.metrics import (
    Client,
    MetricColumns,
    NodeMetricsInfo,
)
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.utils import klog, trace
from platform_aware_scheduling_tpu.utils.tracing import CounterSet

POLICY_PATH = "policies/{}/{}"
METRIC_PATH = "metrics/{}"


class CacheMissError(KeyError):
    pass


def _refresh_error_reason(exc: BaseException) -> str:
    """Bounded ``reason`` label for pas_telemetry_refresh_errors_total:
    circuit_open / throttled / server_error / network / no_data /
    fetch_error — never a raw message (unbounded label values are a
    cardinality leak).  Walks the ``__cause__`` chain first: the
    production metrics client (tas/metrics.CustomMetricsClient) wraps
    every failure in a bare MetricsError whose CAUSE carries the real
    KubeError/CircuitOpenError — classifying only the wrapper would
    collapse the whole taxonomy to fetch_error."""
    seen = 0
    while exc.__cause__ is not None and seen < 8:
        exc = exc.__cause__
        seen += 1
    # local import: kube.retry pulls in kube.client; keep the cache
    # importable in metric-only unit tests that stub the kube layer
    try:
        from platform_aware_scheduling_tpu.kube.retry import CircuitOpenError

        if isinstance(exc, CircuitOpenError):
            return "circuit_open"
    except Exception:
        pass
    status = getattr(exc, "status", None)
    if isinstance(status, int) and status:
        if status == 429:
            return "throttled"
        if status >= 500:
            return "server_error"
        return "fetch_error"
    if isinstance(exc, (TimeoutError, OSError)):
        return "network"
    if "no metric" in str(exc) or "no metrics returned" in str(exc):
        return "no_data"
    return "fetch_error"


class _SerializedStore:
    """Serialized KV with the reference's write-nil-preserves rule."""

    def __init__(self):
        self._data: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def add(self, key: str, payload: Any) -> None:
        with self._lock:
            if payload is None and key in self._data:
                return  # nil write preserves existing value (cache.go:52-57)
            self._data[key] = payload

    def read(self, key: str) -> Any:
        with self._lock:
            return self._data.get(key)

    def delete(self, key: str) -> None:
        with self._lock:
            self._data.pop(key, None)


class AutoUpdatingCache:
    """Reader/Writer/SelfUpdating cache (reference pkg/cache/types.go)."""

    def __init__(
        self,
        counters: Optional[CounterSet] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self._store = _SerializedStore()
        self._metric_refcounts: Dict[str, int] = {}
        self._mtx = threading.Lock()
        # injectable monotonic clock: freshness/aging decisions gate real
        # evictions (docs/robustness.md), so the chaos tests drive them
        # from a fake clock instead of sleeping
        self._clock = clock
        # telemetry-freshness bookkeeping (docs/observability.md): when
        # each metric last carried data, when the last refresh pass
        # completed, and the configured refresh period — the inputs to
        # the /readyz "telemetry_fresh" condition and the
        # pas_telemetry_* metric families
        self.counters = counters if counters is not None else trace.COUNTERS
        self._last_refresh: Dict[str, float] = {}  # metric -> monotonic
        self._last_pass: Optional[float] = None
        self._refresh_period: Optional[float] = None
        self._synced_once = threading.Event()
        #: freshness bound override (seconds); None = 3x the refresh period
        self.freshness_max_age_s: Optional[float] = None
        # held across store mutation + hook delivery so mirror subscribers
        # observe mutations in store order (the reference gets this from its
        # single cache goroutine, cache.go:43-63)
        self._mutation_lock = threading.RLock()
        # mirror hooks: fired after a successful mutation
        self.on_metric_write: List[Callable[[str, Optional[NodeMetricsInfo]], None]] = []
        self.on_metric_delete: List[Callable[[str], None]] = []
        self.on_policy_write: List[Callable[[str, str, TASPolicy], None]] = []
        self.on_policy_delete: List[Callable[[str, str], None]] = []
        # fired once at the END of each update_all_metrics pass (after
        # every per-metric write of the pass landed) — the forecast
        # subsystem refits here, once per pass instead of once per metric
        self.on_refresh_pass: List[Callable[[], None]] = []
        # optional fetched-map transform applied between the metrics API
        # fetch and write_metric: the shard plane's ~1/P ingest cut drops
        # non-owned nodes here (shard/plane.py).  None (the default) is a
        # straight passthrough — full-world mode unchanged.
        self.refresh_filter: Optional[Callable] = None
        # refresh-history substrate (docs/forecast.md): a bounded ring of
        # the last W data-bearing refreshes per metric — (monotonic stamp,
        # {node: milli int}) samples.  A FAILED refresh appends nothing,
        # so gaps stay visible through the stamps; delete_metric drops the
        # ring with the metric.  Off (window 0) until configure_history.
        self._history_window = 0
        self._history: Dict[str, deque] = {}
        self._history_generation = 0

    # -- Reader ---------------------------------------------------------------

    def read_metric(self, metric_name: str) -> NodeMetricsInfo:
        value = self._store.read(METRIC_PATH.format(metric_name))
        if isinstance(value, Mapping) and value:
            return value
        raise CacheMissError(f"no metric {metric_name} found")

    def read_policy(self, namespace: str, policy_name: str) -> TASPolicy:
        value = self._store.read(POLICY_PATH.format(namespace, policy_name))
        if isinstance(value, TASPolicy):
            return value
        raise CacheMissError(f"no policy {policy_name} found")

    # -- Writer ---------------------------------------------------------------

    def write_policy(self, namespace: str, policy_name: str, policy: TASPolicy) -> None:
        with self._mutation_lock:
            self._store.add(POLICY_PATH.format(namespace, policy_name), policy)
            for hook in self.on_policy_write:
                hook(namespace, policy_name, policy)

    def write_metric(
        self, metric_name: str, data: Optional[NodeMetricsInfo] = None
    ) -> None:
        """Empty/None data registers the metric (incrementing its refcount)
        without clobbering current values (autoupdating.go:105-122)."""
        payload = data if data else None
        with self._mutation_lock:
            self._store.add(METRIC_PATH.format(metric_name), payload)
            if payload is None:
                with self._mtx:
                    self._metric_refcounts[metric_name] = (
                        self._metric_refcounts.get(metric_name, 0) + 1
                    )
            else:
                # a data-bearing write IS a refresh — the freshness clock
                # this metric is judged by (telemetry_freshness)
                stamp = self._clock()
                # the history sample (one milli value per node) is
                # built OUTSIDE the lock — at 10k nodes that work must
                # not block request-path readers of metric_ages()/
                # history_snapshot().  The bare int read of the window
                # is racy only against configure_history; the locked
                # re-check below decides
                sample = None
                if self._history_window:
                    if isinstance(payload, MetricColumns):
                        sample = dict(zip(payload.names, payload.milli.tolist()))
                    else:
                        sample = {
                            node: metric.value.milli_value_exact()[0]
                            for node, metric in payload.items()
                        }
                with self._mtx:
                    self._last_refresh[metric_name] = stamp
                    if self._history_window and sample is not None:
                        ring = self._history.get(metric_name)
                        if ring is None:
                            ring = deque(maxlen=self._history_window)
                            self._history[metric_name] = ring
                        ring.append((stamp, sample))
                        self._history_generation += 1
            for hook in self.on_metric_write:
                hook(metric_name, payload)

    def delete_policy(self, namespace: str, policy_name: str) -> None:
        klog.v(2).info_s(
            "deleting " + POLICY_PATH.format(namespace, policy_name),
            component="controller",
        )
        with self._mutation_lock:
            self._store.delete(POLICY_PATH.format(namespace, policy_name))
            for hook in self.on_policy_delete:
                hook(namespace, policy_name)

    def delete_metric(self, metric_name: str) -> None:
        """Refcounted delete: evicted only when the last registered policy
        using it is removed (autoupdating.go:124-137)."""
        with self._mutation_lock:
            evicted = False
            with self._mtx:
                total = self._metric_refcounts.get(metric_name)
                if total == 1:
                    del self._metric_refcounts[metric_name]
                    self._store.delete(METRIC_PATH.format(metric_name))
                    self._last_refresh.pop(metric_name, None)
                    # the history ring dies with the metric: a later
                    # re-registration must not forecast from a ghost
                    # series (docs/forecast.md)
                    if self._history.pop(metric_name, None) is not None:
                        self._history_generation += 1
                    evicted = True
                elif total is not None:
                    self._metric_refcounts[metric_name] = total - 1
                else:
                    self._metric_refcounts[metric_name] = -1
            if evicted:
                # the age gauge must not stay frozen in /metrics for a
                # metric that no longer exists
                self.counters.remove(
                    "pas_telemetry_metric_age_seconds",
                    labels={"metric": metric_name},
                    kind="gauge",
                )
                for hook in self.on_metric_delete:
                    hook(metric_name)

    # -- SelfUpdating -----------------------------------------------------------

    def registered_metric_names(self) -> List[str]:
        with self._mtx:
            return [name for name in self._metric_refcounts if name]

    def update_all_metrics(self, client: Client) -> None:
        """One refresh pass.  A pass is no request, so it lands on no
        span: its seconds go to the four ``pas_refresh_*_seconds_total``
        counters (fetch + publish + warm <= pass), and this thread's CPU
        seconds inside it to ``pas_refresh_pass_cpu_seconds_total`` — two
        ``thread_time()`` reads a pass: what is left of the pass's wall
        seconds it was blocked, asleep by design, or waiting for the
        interpreter.  A container of the rf.* stages: never annotated."""
        with trace.stage(
            "rf.pass",
            "pas_refresh_pass_seconds_total",
            self.counters,
            cpu_counter="pas_refresh_pass_cpu_seconds_total",
            leaf=False,
        ):
            self._refresh_pass(client)

    def _refresh_pass(self, client: Client) -> None:
        with self._mtx:
            names = list(self._metric_refcounts)
        errors: Dict[str, int] = {}  # reason -> count
        for name in names:
            if not name:
                with self._mtx:
                    self._metric_refcounts.pop(name, None)
                continue
            try:
                self._update_metric(client, name)
            except Exception as exc:
                # a failed refresh preserves the prior NodeMetricsInfo
                # (the store's write-nil rule — last-known-good) while
                # the metric keeps AGING (_last_refresh untouched), so
                # freshness decay stays visible
                reason = _refresh_error_reason(exc)
                errors[reason] = errors.get(reason, 0) + 1
                klog.v(2).info_s(str(exc), component="controller")
        # pass accounting: refresh counters + per-metric age gauges (a
        # metric whose fetch keeps failing shows a GROWING age while the
        # loop itself keeps ticking — the two failure modes separate)
        now = self._clock()
        with self._mtx:
            self._last_pass = now
            ages = {
                name: now - stamp
                for name, stamp in self._last_refresh.items()
                if name in self._metric_refcounts
            }
        self._synced_once.set()
        self.counters.inc("pas_telemetry_refresh_total")
        for reason, count in errors.items():
            self.counters.inc(
                "pas_telemetry_refresh_errors_total",
                count,
                labels={"reason": reason},
            )
        for name, age in ages.items():
            self.counters.set_gauge(
                "pas_telemetry_metric_age_seconds",
                round(age, 6),
                labels={"metric": name},
            )
        # one end-of-pass notification (never per metric): the forecast
        # subsystem refits against the pass's complete sample set here,
        # in the refresh thread — requests only ever read a finished fit
        for hook in list(self.on_refresh_pass):
            try:
                hook()
            except Exception as exc:  # a subscriber must not stop refreshes
                klog.error("refresh-pass subscriber failed: %r", exc)

    # -- refresh history (docs/forecast.md) -------------------------------------

    def configure_history(self, window: int) -> None:
        """Enable (or re-bound) the per-metric refresh-history rings:
        each data-bearing write appends one ``(stamp, {node: milli})``
        sample, bounded at the last ``window`` samples.  Failed refreshes
        append nothing — the gap shows up as stamp spacing, never as a
        fabricated sample."""
        window = int(window)
        if window < 1:
            raise ValueError(f"history window must be >= 1, got {window}")
        with self._mtx:
            if window != self._history_window:
                self._history = {
                    name: deque(ring, maxlen=window)
                    for name, ring in self._history.items()
                }
                self._history_window = window
                self._history_generation += 1

    def history_window(self) -> int:
        with self._mtx:
            return self._history_window

    def history_generation(self) -> int:
        """Monotonic counter bumped on every history mutation — the
        forecaster's memoization key (tas/forecast engine refits only
        when this moves)."""
        with self._mtx:
            return self._history_generation

    def history_snapshot(
        self,
    ) -> Tuple[int, Dict[str, List[Tuple[float, Dict[str, int]]]]]:
        """(generation, {metric: [(stamp, {node: milli}), ...]}) oldest
        first.  Sample dicts are shared read-only — consumers must not
        mutate them."""
        with self._mtx:
            return self._history_generation, {
                name: list(ring) for name, ring in self._history.items()
            }

    def metric_ages(self) -> Dict[str, Optional[float]]:
        """Registered metric -> seconds since its last data-bearing write
        (None = never refreshed)."""
        now = self._clock()
        with self._mtx:
            return {
                name: (
                    now - self._last_refresh[name]
                    if name in self._last_refresh
                    else None
                )
                for name in self._metric_refcounts
                if name
            }

    def telemetry_freshness(self) -> Tuple[bool, str]:
        """The /readyz "telemetry_fresh" condition (utils/health.py):
        ok when the cache has no refresh loop configured (static seed —
        as fresh as it gets), or when at least one refresh pass has
        completed, the loop's last pass is recent, and every registered
        metric's age is within bound (``freshness_max_age_s``, default
        3x the refresh period)."""
        period = self._refresh_period
        if period is None:
            return True, "static cache (no refresh loop configured)"
        if not self._synced_once.is_set():
            return False, "telemetry cache has not completed a refresh pass"
        bound = self.freshness_bound()
        now = self._clock()
        with self._mtx:
            last_pass = self._last_pass
            stale = sorted(
                name
                for name in self._metric_refcounts
                if name
                and (
                    name not in self._last_refresh
                    or now - self._last_refresh[name] > bound
                )
            )
            registered = sum(1 for name in self._metric_refcounts if name)
        if last_pass is None or now - last_pass > bound:
            since = "never" if last_pass is None else f"{now - last_pass:.1f}s"
            return False, (
                f"refresh loop stalled (last pass {since} ago, bound "
                f"{bound:.1f}s)"
            )
        if stale:
            return False, (
                f"metrics stale past {bound:.1f}s: {stale[:5]}"
            )
        return True, f"{registered} metrics fresh within {bound:.1f}s"

    def freshness_bound(self) -> Optional[float]:
        """The staleness bound in seconds (``freshness_max_age_s`` or 3x
        the refresh period); None for a static cache.  Degraded-mode
        consumers derive their last-known-good window from this
        (tas/degraded.py)."""
        period = self._refresh_period
        if period is None:
            return None
        if self.freshness_max_age_s is not None:
            return self.freshness_max_age_s
        return max(3.0 * period, 1.0)

    def _update_metric(self, client: Client, metric_name: str) -> None:
        with trace.stage(
            "rf.fetch", "pas_refresh_fetch_seconds_total", self.counters
        ):
            info = client.get_node_metric(metric_name)
            if self.refresh_filter is not None and info:
                info = self.refresh_filter(info)
        # publish is write_metric through the mirror's publish, less the
        # warm that publish triggers and the hand-off after it (both time
        # themselves, into the process-wide set; the mirror annotates its
        # own part rf.publish)
        began = time.perf_counter()
        not_publish = self._warm_and_handoff_seconds()
        try:
            self.write_metric(metric_name, info)
        finally:
            took = time.perf_counter() - began
            not_publish = self._warm_and_handoff_seconds() - not_publish
            self.counters.inc(
                "pas_refresh_publish_seconds_total", max(took - not_publish, 0.0)
            )

    @staticmethod
    def _warm_and_handoff_seconds() -> float:
        return trace.COUNTERS.get(
            "pas_refresh_warm_seconds_total"
        ) + trace.COUNTERS.get("pas_refresh_handoff_seconds_total")

    def periodic_update(
        self,
        period_seconds: float,
        client: Client,
        initial_data: Optional[Dict[str, Any]] = None,
        stop: Optional[threading.Event] = None,
    ) -> None:
        """Refresh every registered metric each period until ``stop`` is set
        (autoupdating.go:37-43: update first, then wait the tick)."""
        for key, value in (initial_data or {}).items():
            self._store.add(key, value)
        self._refresh_period = period_seconds
        stop = stop or threading.Event()
        while not stop.is_set():
            self.update_all_metrics(client)
            # idle by design: a profiled gap that reads rf.wait is the
            # sync period, not something the host made
            with trace.stage("rf.wait"):
                stop.wait(period_seconds)

    def start_periodic_update(
        self,
        period_seconds: float,
        client: Client,
        initial_data: Optional[Dict[str, Any]] = None,
        stop: Optional[threading.Event] = None,
    ) -> threading.Event:
        """Run :meth:`periodic_update` on a daemon thread; returns the stop
        event (caller-supplied ``stop`` is used when given)."""
        self._refresh_period = period_seconds
        stop = stop or threading.Event()
        thread = threading.Thread(
            target=self.periodic_update,
            args=(period_seconds, client, initial_data, stop),
            name="pas-refresh",
            daemon=True,
        )
        thread.start()
        return stop
