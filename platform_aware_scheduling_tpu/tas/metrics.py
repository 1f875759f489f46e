"""Node-metric ingestion from the custom-metrics API.

Reference: telemetry-aware-scheduling/pkg/metrics/client.go.  ``NodeMetric``
carries timestamp / window / value (client.go:25-32); ``get_node_metric``
queries root-scoped Node metrics with empty selectors (client.go:51-61) and
``wrap_metrics`` converts the MetricValueList with a default 60 s window
(client.go:64-78).  The live client hands a fetched round on as
:class:`MetricColumns`: the same mapping, kept as columns, because its
first reader — the tensor mirror — wants integers, not objects.
"""

from __future__ import annotations

import re
import time
from collections.abc import Mapping
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Protocol, Tuple

import numpy as np

from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity


@dataclass
class NodeMetric:
    """One piece of telemetry for one node."""

    value: Quantity
    timestamp: str = ""
    window_seconds: float = 60.0


# node name -> NodeMetric (reference client.go:34-35): a plain dict, or a
# fetched round's MetricColumns
NodeMetricsInfo = Mapping[str, "NodeMetric"]


class MetricsError(Exception):
    pass


class Client(Protocol):
    """Knows how to fetch one named metric for every node
    (reference client.go:20-22)."""

    def get_node_metric(self, metric_name: str) -> NodeMetricsInfo: ...


def wrap_metrics(metric_value_list: Dict[str, Any]) -> Dict[str, NodeMetric]:
    """MetricValueList -> NodeMetricsInfo (reference client.go:64-78);
    default window one minute when windowSeconds is absent.  The object
    form of a round, and what :class:`MetricColumns` must read as."""
    result: Dict[str, NodeMetric] = {}
    for item in metric_value_list.get("items") or []:
        window = item.get("windowSeconds")
        result[(item.get("describedObject") or {}).get("name", "")] = NodeMetric(
            value=Quantity(str(item.get("value", "0"))),
            timestamp=item.get("timestamp", ""),
            window_seconds=float(window) if window is not None else 60.0,
        )
    return result


_INT64 = np.iinfo(np.int64)
_PLAIN_INT = re.compile(r"[+-]?[0-9]+")


def _milli_column(values: List[str]) -> Tuple[np.ndarray, bool, int]:
    """``Quantity(v).milli_value_exact()`` of every string as ``(int64
    vector, every one exact, strings the Quantity parser took)``.  A plain
    decimal integer whose milli value fits int64 is ``int(v) * 1000``;
    every other string is Quantity's, so the pair is the same for every
    input and a string it refuses raises as it does in ``wrap_metrics``."""
    joined = "".join(values)
    if joined.isascii() and joined.isdigit() and "" not in values:
        # unsigned ASCII digits throughout: one conversion for the round
        try:
            whole = np.array(values, dtype=np.int64)
        except OverflowError:
            whole = None
        if whole is not None and int(whole.max()) <= _INT64.max // 1000:
            return whole * 1000, True, 0
    milli = np.empty(len(values), dtype=np.int64)
    all_exact, fallbacks = True, 0
    for i, text in enumerate(values):
        if _PLAIN_INT.fullmatch(text):
            scaled = int(text) * 1000
            if _INT64.min <= scaled <= _INT64.max:
                milli[i] = scaled
                continue
        fallbacks += 1
        milli[i], exact = Quantity(text).milli_value_exact()
        all_exact = all_exact and exact
    return milli, all_exact, fallbacks


class MetricColumns(Mapping):
    """One fetched round of one metric, as columns: what ``wrap_metrics``
    gives for the same MetricValueList (duplicate names: the last wins, in
    the first one's place), read-only, with the ``NodeMetric`` objects made
    when a reader first asks for one.  ``names`` is in the items' order;
    ``milli`` / ``exact`` are ``milli_value_exact()`` of every value and
    whether all are exact — what the mirror scatters (ops/state.py)."""

    __slots__ = (
        "names", "raw", "timestamps", "windows", "milli", "exact",
        "quantity_fallbacks", "_metrics",
    )

    def __init__(self, metric_value_list: Dict[str, Any]):
        items = metric_value_list.get("items") or []
        names = [(item.get("describedObject") or {}).get("name", "") for item in items]
        values = [str(item.get("value", "0")) for item in items]
        timestamps = [item.get("timestamp", "") for item in items]
        windows = [
            60.0 if (window := item.get("windowSeconds")) is None else float(window)
            for item in items
        ]
        if len(set(names)) != len(names):
            last = {name: i for i, name in enumerate(names)}
            names = list(last)
            values = [values[i] for i in last.values()]
            timestamps = [timestamps[i] for i in last.values()]
            windows = [windows[i] for i in last.values()]
        self.names: List[str] = names
        self.raw: List[str] = values
        self.timestamps: List[Any] = timestamps
        self.windows: List[float] = windows
        self.milli, self.exact, self.quantity_fallbacks = _milli_column(values)
        self.milli.flags.writeable = False
        self._metrics: Optional[Dict[str, NodeMetric]] = None

    def _objects(self) -> Dict[str, NodeMetric]:
        made = self._metrics
        if made is None:
            made = self._metrics = {
                name: NodeMetric(Quantity(value), timestamp, window)
                for name, value, timestamp, window in zip(
                    self.names, self.raw, self.timestamps, self.windows
                )
            }
        return made

    def __len__(self) -> int:
        return len(self.names)

    def __iter__(self) -> Iterator[str]:
        return iter(self.names)

    def __getitem__(self, name: str) -> NodeMetric:
        return self._objects()[name]

    def items(self):
        return self._objects().items()

    def values(self):
        return self._objects().values()


class CustomMetricsClient:
    """Live client over the kube custom-metrics API
    (reference client.go:38-61)."""

    def __init__(self, kube_client):
        self._kube = kube_client

    def get_node_metric(self, metric_name: str) -> NodeMetricsInfo:
        try:
            value_list = self._kube.get_node_custom_metric(metric_name)
        except Exception as exc:
            raise MetricsError(
                "unable to fetch metrics from custom metrics API: " + str(exc)
            ) from exc
        if not (value_list.get("items") or []):
            raise MetricsError("no metrics returned from custom metrics API")
        began = time.perf_counter()
        try:
            info = MetricColumns(value_list)
            if info.quantity_fallbacks:
                trace.COUNTERS.inc(
                    "pas_refresh_ingest_quantity_fallback_total",
                    info.quantity_fallbacks,
                )
            return info
        finally:
            trace.COUNTERS.inc(
                "pas_refresh_parse_seconds_total", time.perf_counter() - began
            )


class DummyMetricsClient:
    """Canned metrics client (the reference's test fake,
    pkg/metrics/mocks.go:40-75)."""

    def __init__(self, store: Dict[str, NodeMetricsInfo] | None = None):
        self.store: Dict[str, NodeMetricsInfo] = store if store is not None else {}

    def get_node_metric(self, metric_name: str) -> NodeMetricsInfo:
        if metric_name not in self.store:
            raise MetricsError(f"no metric {metric_name} found")
        return dict(self.store[metric_name])


def instance_of_mock_metric_client_map(
    metric_name: str = "dummyMetric1",
) -> Dict[str, NodeMetricsInfo]:
    """Pre-seeded per-node metric vectors in the spirit of the reference's
    ``InstanceOfMockMetricClientMap`` / ``TestNodeMetricCustomInfo``."""
    return {
        metric_name: {
            "node A": NodeMetric(value=Quantity("100")),
            "node B": NodeMetric(value=Quantity("200")),
        }
    }
