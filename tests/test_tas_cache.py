"""TAS cache + metrics client tests (reference pkg/cache/autoupdating_test.go,
pkg/metrics/client_test.go)."""

import time

import numpy as np
import pytest

from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache, CacheMissError
from platform_aware_scheduling_tpu.tas.metrics import (
    CustomMetricsClient,
    DummyMetricsClient,
    MetricColumns,
    MetricsError,
    NodeMetric,
    instance_of_mock_metric_client_map,
    wrap_metrics,
)
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity, QuantityParseError


def seeded_cache():
    cache = AutoUpdatingCache()
    cache.write_metric("dummyMetric1", None)  # register
    cache.write_metric(
        "dummyMetric1",
        {"node A": NodeMetric(value=Quantity("100")),
         "node B": NodeMetric(value=Quantity("200"))},
    )
    return cache


class TestAutoUpdatingCache:
    def test_read_write_metric(self):
        cache = seeded_cache()
        info = cache.read_metric("dummyMetric1")
        assert info["node A"].value.cmp_int64(100) == 0

    def test_read_missing_metric_raises(self):
        with pytest.raises(CacheMissError):
            AutoUpdatingCache().read_metric("nope")

    def test_register_does_not_clobber(self):
        cache = seeded_cache()
        # a second nil registration must preserve the data
        cache.write_metric("dummyMetric1", None)
        assert cache.read_metric("dummyMetric1")["node B"].value.cmp_int64(200) == 0

    def test_refcounted_delete(self):
        cache = seeded_cache()
        cache.write_metric("dummyMetric1", None)  # second registration (refcount 2)
        cache.delete_metric("dummyMetric1")
        # still present: one registration remains
        assert cache.read_metric("dummyMetric1")
        cache.delete_metric("dummyMetric1")
        with pytest.raises(CacheMissError):
            cache.read_metric("dummyMetric1")

    def test_policy_roundtrip(self):
        cache = AutoUpdatingCache()
        policy = TASPolicy(metadata={"name": "p", "namespace": "default"})
        cache.write_policy("default", "p", policy)
        assert cache.read_policy("default", "p").name == "p"
        cache.delete_policy("default", "p")
        with pytest.raises(CacheMissError):
            cache.read_policy("default", "p")

    def test_periodic_update_refreshes(self):
        """Values change after a ticker period (autoupdating_test.go:15-62)."""
        cache = AutoUpdatingCache()
        cache.write_metric("m", None)
        client = DummyMetricsClient({"m": {"n1": NodeMetric(value=Quantity("1"))}})
        stop = cache.start_periodic_update(0.02, client)
        try:
            deadline = time.time() + 2
            while time.time() < deadline:
                try:
                    if cache.read_metric("m")["n1"].value.cmp_int64(1) == 0:
                        break
                except CacheMissError:
                    pass
                time.sleep(0.01)
            assert cache.read_metric("m")["n1"].value.cmp_int64(1) == 0
            # now the backend changes; cache must follow
            client.store["m"] = {"n1": NodeMetric(value=Quantity("5"))}
            deadline = time.time() + 2
            while time.time() < deadline:
                if cache.read_metric("m")["n1"].value.cmp_int64(5) == 0:
                    break
                time.sleep(0.01)
            assert cache.read_metric("m")["n1"].value.cmp_int64(5) == 0
        finally:
            stop.set()

    def test_mirror_hooks_fire(self):
        cache = AutoUpdatingCache()
        events = []
        cache.on_metric_write.append(lambda name, data: events.append(("w", name)))
        cache.on_metric_delete.append(lambda name: events.append(("d", name)))
        cache.write_metric("m", None)
        cache.write_metric("m", {"n": NodeMetric(value=Quantity("1"))})
        cache.delete_metric("m")
        assert events == [("w", "m"), ("w", "m"), ("d", "m")]


class TestLastKnownGoodRetention:
    """ISSUE 5 satellite: a failed per-metric refresh preserves the
    prior NodeMetricsInfo (the store's write-nil rule) while the metric
    keeps AGING for freshness, and the refresh-error counter carries a
    bounded ``reason`` label."""

    def _cache_on_fake_clock(self):
        from platform_aware_scheduling_tpu.testing.faults import FakeClock
        from platform_aware_scheduling_tpu.utils.tracing import CounterSet

        clock = FakeClock()
        counters = CounterSet()
        cache = AutoUpdatingCache(counters=counters, clock=clock.now)
        cache._refresh_period = 1.0
        cache.write_metric(
            "m1", {"node A": NodeMetric(value=Quantity("7"))}
        )
        cache.write_metric("m1")  # register for refresh
        return cache, clock, counters

    def test_failed_refresh_keeps_values_but_ages_them(self):
        cache, clock, counters = self._cache_on_fake_clock()
        good = DummyMetricsClient(
            {"m1": {"node A": NodeMetric(value=Quantity("7"))}}
        )
        cache.update_all_metrics(good)
        assert cache.metric_ages()["m1"] == 0
        fresh_ok, _ = cache.telemetry_freshness()
        assert fresh_ok
        # the API goes away; passes keep running
        bad = DummyMetricsClient({})
        for _ in range(4):
            clock.advance(1.0)
            cache.update_all_metrics(bad)
        # last-known-good value still served (write-nil rule)...
        assert cache.read_metric("m1")["node A"].value.cmp_int64(7) == 0
        # ...but the metric AGED: freshness decayed past the 3x bound
        assert cache.metric_ages()["m1"] == pytest.approx(4.0)
        fresh_ok, reason = cache.telemetry_freshness()
        assert not fresh_ok and "m1" in reason

    def test_refresh_errors_carry_reason_label(self):
        from platform_aware_scheduling_tpu.kube.retry import CircuitOpenError
        from platform_aware_scheduling_tpu.tas.cache import (
            _refresh_error_reason,
        )

        cache, clock, counters = self._cache_on_fake_clock()

        class Failing:
            def __init__(self, exc):
                self.exc = exc

            def get_node_metric(self, name):
                raise self.exc

        cache.update_all_metrics(Failing(MetricsError("no metric m1 found")))
        assert counters.get(
            "pas_telemetry_refresh_errors_total",
            labels={"reason": "no_data"},
        ) == 1
        cache.update_all_metrics(Failing(CircuitOpenError("metrics")))
        assert counters.get(
            "pas_telemetry_refresh_errors_total",
            labels={"reason": "circuit_open"},
        ) == 1
        # unlabeled get() still sums across reasons (dashboards keep
        # their totals)
        assert counters.get("pas_telemetry_refresh_errors_total") == 2
        # classifier edges stay bounded
        from platform_aware_scheduling_tpu.kube.client import KubeError

        assert _refresh_error_reason(KubeError("x", status=429)) == "throttled"
        assert _refresh_error_reason(KubeError("x", status=503)) == "server_error"
        assert _refresh_error_reason(TimeoutError()) == "network"
        assert _refresh_error_reason(ValueError("weird")) == "fetch_error"
        # the PRODUCTION path: CustomMetricsClient wraps everything in a
        # bare MetricsError whose __cause__ carries the real error — the
        # classifier must walk the chain, not collapse to fetch_error
        def wrapped(cause):
            try:
                try:
                    raise cause
                except Exception as inner:
                    raise MetricsError("unable to fetch metrics") from inner
            except MetricsError as outer:
                return outer

        assert _refresh_error_reason(
            wrapped(KubeError("x", status=503))
        ) == "server_error"
        assert _refresh_error_reason(
            wrapped(CircuitOpenError("metrics"))
        ) == "circuit_open"


class TestHistoryRing:
    """ISSUE 8 satellite: the refresh-history ring's semantics under
    failure (docs/forecast.md).  A failed refresh appends NO sample while
    the last-known-good value keeps aging; the ring stays bounded at W
    across 10x W passes; a full delete drops the ring and the forecast
    gauges with the metric."""

    def _cache_on_fake_clock(self, window=4):
        from platform_aware_scheduling_tpu.testing.faults import FakeClock
        from platform_aware_scheduling_tpu.utils.tracing import CounterSet

        clock = FakeClock()
        counters = CounterSet()
        cache = AutoUpdatingCache(counters=counters, clock=clock.now)
        cache._refresh_period = 1.0
        cache.configure_history(window)
        cache.write_metric("m1")  # register for refresh
        return cache, clock, counters

    def test_failed_refresh_appends_nothing_while_lkg_ages(self):
        cache, clock, _counters = self._cache_on_fake_clock()
        good = DummyMetricsClient(
            {"m1": {"node A": NodeMetric(value=Quantity("7"))}}
        )
        cache.update_all_metrics(good)
        clock.advance(1.0)
        cache.update_all_metrics(good)
        t_last_good = clock.now()
        gen_before = cache.history_generation()
        _gen, rings = cache.history_snapshot()
        assert len(rings["m1"]) == 2
        # the API goes away; passes keep running but the ring is frozen
        bad = DummyMetricsClient({})
        for _ in range(3):
            clock.advance(1.0)
            cache.update_all_metrics(bad)
        assert cache.history_generation() == gen_before
        _gen, rings = cache.history_snapshot()
        assert len(rings["m1"]) == 2  # no fabricated samples
        # the GAP is visible: the newest stamp predates the failures
        assert rings["m1"][-1][0] == pytest.approx(t_last_good)
        # while the LKG value is still served AND aging
        assert cache.read_metric("m1")["node A"].value.cmp_int64(7) == 0
        assert cache.metric_ages()["m1"] == pytest.approx(3.0)

    def test_ring_bounded_at_window_across_many_passes(self):
        window = 4
        cache, clock, _counters = self._cache_on_fake_clock(window)
        for i in range(10 * window):
            clock.advance(1.0)
            cache.update_all_metrics(
                DummyMetricsClient(
                    {"m1": {"n": NodeMetric(value=Quantity(str(i)))}}
                )
            )
        _gen, rings = cache.history_snapshot()
        assert len(rings["m1"]) == window
        # the ring holds exactly the LAST W samples, oldest first
        values = [sample["n"] for _stamp, sample in rings["m1"]]
        assert values == [
            (10 * window - window + i) * 1000 for i in range(window)
        ]

    def test_delete_metric_drops_ring_and_gauges(self):
        from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
        from platform_aware_scheduling_tpu.forecast import Forecaster

        cache, clock, counters = self._cache_on_fake_clock()
        mirror = TensorStateMirror()
        mirror.attach(cache)
        forecaster = Forecaster(
            cache, mirror, window=4, period_s=1.0, counters=counters,
            clock=clock.now,
        )
        for i in range(3):
            clock.advance(1.0)
            cache.update_all_metrics(
                DummyMetricsClient(
                    {"m1": {"n": NodeMetric(value=Quantity(str(i)))}}
                )
            )
        assert forecaster.ensure_current() is not None
        # the ramp (0, 1, 2) publishes a positive slope gauge
        assert counters.get(
            "pas_forecast_metric_slope", labels={"metric": "m1"},
            kind="gauge",
        ) > 0
        gen_before = cache.history_generation()
        cache.delete_metric("m1")
        # the ring is gone (a re-registration must not forecast from a
        # ghost series) and the generation moved so consumers refit
        _gen, rings = cache.history_snapshot()
        assert "m1" not in rings
        assert cache.history_generation() > gen_before
        # ...and the per-metric gauges died with it (a removed series
        # reads back as the 0 default)
        assert counters.get(
            "pas_forecast_metric_slope", labels={"metric": "m1"},
            kind="gauge",
        ) == 0
        assert counters.get(
            "pas_telemetry_metric_age_seconds", labels={"metric": "m1"},
            kind="gauge",
        ) == 0


class TestMetricsClient:
    def test_wrap_metrics_default_window(self):
        info = wrap_metrics(
            {"items": [{"describedObject": {"kind": "Node", "name": "n1"},
                        "value": "50"}]}
        )
        assert info["n1"].window_seconds == 60.0
        assert info["n1"].value.cmp_int64(50) == 0

    def test_wrap_metrics_explicit_window(self):
        info = wrap_metrics(
            {"items": [{"describedObject": {"name": "n1"}, "windowSeconds": 30,
                        "value": "104857600000m"}]}
        )
        assert info["n1"].window_seconds == 30.0
        assert info["n1"].value.cmp_int64(104857600) == 0

    def test_custom_metrics_client_via_fake(self):
        fake = FakeKubeClient()
        fake.set_node_metric("health_metric", "node1", "0")
        fake.set_node_metric("health_metric", "node2", "1")
        client = CustomMetricsClient(fake)
        info = client.get_node_metric("health_metric")
        assert set(info) == {"node1", "node2"}

    def test_empty_items_error(self):
        client = CustomMetricsClient(FakeKubeClient())
        with pytest.raises(MetricsError, match="no metrics returned"):
            client.get_node_metric("missing")

    def test_dummy_client(self):
        client = DummyMetricsClient(instance_of_mock_metric_client_map())
        assert client.get_node_metric("dummyMetric1")["node A"].value.cmp_int64(100) == 0
        with pytest.raises(MetricsError):
            client.get_node_metric("other")


def value_list(*pairs):
    """A MetricValueList of (node, value) pairs."""
    return {"items": [{"describedObject": {"kind": "Node", "name": node},
                       "value": value} for node, value in pairs]}


#: (value as the API gives it, takes the Quantity parser)
VALUE_STRINGS = [
    ("0", False), ("97", False), ("-5", False), ("+5", False), ("007", False),
    ("100m", True), ("1Ki", True), ("1.5", True), ("1e3", True),
    ("333333n", True), (" 97 ", True),
    ("9223372036854775", False),  # the largest integer whose milli fits
    ("9223372036854776", True), ("9223372036854775807", True),
    ("-9223372036854775808", True), ("99999999999999999999999", True),
    (97, False), (1.5, True),
]
#: what Quantity refuses, and int() would not: both forms raise
REFUSED_VALUES = ["", "1_000", "\u0663", "abc", None]


class TestMetricColumns:
    """ISSUE 32: a fetched round as columns reads as ``wrap_metrics`` of
    the same list does, for every value string and every odd item."""

    @pytest.mark.parametrize("beside", [None, "3", "250m"])
    @pytest.mark.parametrize("value,parsed", VALUE_STRINGS)
    def test_milli_is_quantitys_for_every_value(self, value, parsed, beside):
        pairs = [("a", value)] + ([("b", beside)] if beside is not None else [])
        columns = MetricColumns(value_list(*pairs))
        expected = [Quantity(str(v)).milli_value_exact() for _n, v in pairs]
        assert columns.milli.dtype == np.int64
        assert columns.milli.tolist() == [milli for milli, _exact in expected]
        assert columns.exact == all(exact for _milli, exact in expected)
        assert columns.quantity_fallbacks == parsed + (beside == "250m")
        assert columns == wrap_metrics(value_list(*pairs))

    @pytest.mark.parametrize("value", REFUSED_VALUES)
    def test_a_refused_value_raises_as_wrap_metrics_does(self, value):
        for form in (wrap_metrics, MetricColumns):
            with pytest.raises(QuantityParseError):
                form(value_list(("a", "1"), ("b", value)))

    def test_a_refused_window_raises_as_wrap_metrics_does(self):
        bad = {"items": [{"describedObject": {"name": "a"}, "value": "1",
                          "windowSeconds": "soon"}]}
        for form in (wrap_metrics, MetricColumns):
            with pytest.raises(ValueError):
                form(bad)

    def test_reads_as_wrap_metrics_does(self):
        odd = {"items": [
            {"describedObject": {"name": "n1"}, "value": "5", "timestamp": "t1"},
            {"describedObject": {"name": "n2"}, "value": "100m",
             "windowSeconds": 30, "timestamp": "t2"},
            {"value": "7"},  # no describedObject: the name is ""
            {"describedObject": None, "value": "8", "timestamp": "t3"},
            {"describedObject": {"name": "n3"}},  # no value: "0"
            {"describedObject": {"name": "n1"}, "value": "6", "windowSeconds": 15},
        ]}
        expected = wrap_metrics(odd)
        cache = AutoUpdatingCache()
        cache.write_metric("m", MetricColumns(odd))
        got = cache.read_metric("m")
        assert isinstance(got, MetricColumns)
        assert list(got) == list(expected) == ["n1", "n2", "", "n3"]
        assert len(got) == len(expected) and bool(got)
        assert list(got.items()) == list(expected.items())
        assert list(got.values()) == list(expected.values())
        assert got == expected and expected == dict(got)
        assert got["n1"] == NodeMetric(Quantity("6"), "", 15.0)
        assert got[""].timestamp == "t3" and got["n2"].window_seconds == 30.0
        assert "n3" in got and "n4" not in got and got.get("n4") is None
        assert got["n1"] is got["n1"]  # made once, like a dict's values
        # the last n1 wins in the first one's place, for the mirror too
        assert got.names == list(expected)
        assert got.milli.tolist() == [6000, 100, 8000, 0]

    def test_a_round_is_read_only_and_falsy_when_empty(self):
        columns = MetricColumns(value_list(("a", "1")))
        with pytest.raises(ValueError):
            columns.milli[0] = 2
        with pytest.raises(TypeError):
            columns["a"] = NodeMetric(Quantity("2"))
        with pytest.raises(AttributeError):
            columns.extra = 1
        assert not MetricColumns({"items": []})
        assert not MetricColumns({})

    def test_client_hands_on_columns_and_counts_its_part(self):
        fake = FakeKubeClient()
        for node, value in (("n1", "0"), ("n2", "100m"), ("n3", "1.5")):
            fake.set_node_metric("m", node, value, timestamp="t")
        names = ("pas_refresh_ingest_quantity_fallback_total",
                 "pas_refresh_parse_seconds_total")
        before = [trace.COUNTERS.get(name) for name in names]
        info = CustomMetricsClient(fake).get_node_metric("m")
        assert isinstance(info, MetricColumns)
        assert info == wrap_metrics(fake.get_node_custom_metric("m"))
        fallbacks, parse_s = (
            trace.COUNTERS.get(name) - was for name, was in zip(names, before))
        assert fallbacks == 2 and parse_s > 0

    @pytest.mark.parametrize("form", [MetricColumns, wrap_metrics])
    def test_history_sample_is_the_milli_column(self, form):
        cache = AutoUpdatingCache()
        cache.configure_history(2)
        cache.write_metric("m", form(value_list(("a", "2"), ("b", "1500m"),
                                                ("a", "3"))))
        _gen, rings = cache.history_snapshot()
        (_stamp, sample), = rings["m"]
        assert sample == {"a": 3000, "b": 1500}
        assert all(type(v) is int for v in sample.values())

    def test_flight_recorder_reads_through_the_mapping(self):
        from platform_aware_scheduling_tpu.utils.record import FlightRecorder

        cache = AutoUpdatingCache()
        cache.write_metric("m")
        cache.write_metric("m", MetricColumns(value_list(("a", "2"), ("b", "4"))))
        seen = []
        recorder = FlightRecorder()
        recorder.record_telemetry = lambda name, values: seen.append((name, values))
        recorder.observe_cache(cache)
        assert seen == [("m", [2.0, 4.0])]
