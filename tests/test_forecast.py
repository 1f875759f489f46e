"""Predictive-telemetry suite (ISSUE 8, docs/forecast.md).

Covers the whole forecasting layer:

  * EWMA/Holt kernel device<->host parity (byte-exact arrays, >= 25
    randomized histories incl. missing samples and constant series) and
    its fit behavior;
  * the history tensor staging: view alignment, right-aligned ragged
    series, the int32 de-scale for huge metrics;
  * the Forecaster engine: refit-on-generation memoization, the widening
    horizon through staleness, host/native predicted values agreeing;
  * the ACCEPTANCE invariant through the REAL verbs on BOTH front-ends:
    scheduleonmetric rankings on forecasts are byte-comparable
    native<->host and across front-ends, and genuinely differ from
    snapshot rankings on a trending cluster;
  * trend-aware hysteresis: transient spikes with negative slope hold
    drift streaks (suppressed-eviction counter) while real trends
    escalate unchanged;
  * degraded LKG mode's bounded extrapolation: forecasts serve past the
    frozen-LKG window while the band holds, then the pre-forecast
    fallback returns;
  * /debug/forecast 200/404/405 on both front-ends + the /debug index;
  * the gang-mode Filter response cache restore: non-gang pods hit the
    cache keyed on the reservation version, gang members still bypass
    it (the native encoder answers them from the tracker's verdict).
"""

import json

import numpy as np
import pytest

from benchmarks.forecast_load import spike_ab, trending_ab
from benchmarks.gang_load import _gang_pod_obj, build_mesh_service
from benchmarks.http_load import build_extender, make_bodies
from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.forecast import Forecaster
from platform_aware_scheduling_tpu.ops import forecast as ops_forecast
from platform_aware_scheduling_tpu.ops.state import (
    TensorStateMirror,
    build_history_tensor,
)
from platform_aware_scheduling_tpu.rebalance.drift import DriftDetector
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas import degraded as degraded_mode
from platform_aware_scheduling_tpu.tas.degraded import DegradedModeController
from platform_aware_scheduling_tpu.tas.metrics import (
    DummyMetricsClient,
    NodeMetric,
)
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.testing.faults import FakeClock
from platform_aware_scheduling_tpu.utils import labels as shared_labels
from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity
from platform_aware_scheduling_tpu.utils.tracing import CounterSet
from wirehelpers import get_request, post_bytes, raw_request, start_async, \
    start_threaded


# ---------------------------------------------------------------------------
# kernel parity + behavior
# ---------------------------------------------------------------------------


class TestForecastKernel:
    def test_device_host_parity_byte_exact(self):
        """ACCEPTANCE: >= 25 randomized histories — missing samples,
        constant series, full masks — byte-exact device<->host."""
        rng = np.random.default_rng(11)
        for case in range(30):
            m = int(rng.integers(1, 6))
            n = int(rng.integers(1, 10))
            w = int(rng.integers(1, 40))
            values = rng.integers(
                -(2**30), 2**30, size=(m, n, w)
            ).astype(np.int32)
            valid = rng.random((m, n, w)) < 0.7
            if case % 5 == 0:
                values[:] = 54321  # constant series
            if case % 7 == 0:
                valid[:] = True  # dense
            if case % 11 == 0:
                valid[:] = False  # fully missing
            horizon = int(rng.integers(1, 8))
            device = ops_forecast.forecast_device(values, valid, horizon)
            host = ops_forecast.forecast_host(values, valid, horizon)
            for name, d_arr, h_arr in zip(device._fields, device, host):
                assert d_arr.dtype == h_arr.dtype, (case, name)
                assert np.array_equal(d_arr, h_arr), (case, name)

    def test_constant_series_is_flat_certainty(self):
        values = np.full((1, 1, 16), 5000, np.int32)
        fit = ops_forecast.forecast_host(
            values, np.ones((1, 1, 16), bool), 3
        )
        assert fit.level[0, 0] == 5000
        assert fit.trend[0, 0] == 0
        assert fit.predicted[0, 0] == 5000
        assert fit.band[0, 0] == 0  # zero residual -> zero uncertainty

    def test_linear_ramp_tracks_slope_and_extrapolates(self):
        w = 16
        values = (np.arange(w, dtype=np.int32) * 1000).reshape(1, 1, w)
        fit = ops_forecast.forecast_host(values, np.ones((1, 1, w), bool), 1)
        # the Holt trend converges near the true 1000/step slope and the
        # prediction lands near the next sample (16000)
        assert 900 <= fit.trend[0, 0] <= 1100
        assert 15_500 <= fit.predicted[0, 0] <= 16_500
        assert fit.band[0, 0] > 0  # nonzero residual during convergence

    def test_missing_samples_never_update_state(self):
        values = np.full((1, 1, 8), 7777, np.int32)
        valid = np.zeros((1, 1, 8), bool)
        fit = ops_forecast.forecast_host(values, valid, 1)
        assert fit.samples[0, 0] == 0
        assert fit.level[0, 0] == 0 and fit.predicted[0, 0] == 0
        # a single valid sample seeds the level with zero trend
        valid[0, 0, 3] = True
        fit = ops_forecast.forecast_host(values, valid, 5)
        assert fit.samples[0, 0] == 1
        assert fit.level[0, 0] == 7777
        assert fit.trend[0, 0] == 0
        assert fit.predicted[0, 0] == 7777

    def test_residual_accumulator_headroom_on_noisy_ceiling_series(self):
        """REVIEW: the staging bit budget is WINDOW-AWARE — `acc` sums up
        to W-1 absolute errors, so a full-window noisy series de-scaled
        to the per-step ceiling alone would wrap `acc` negative in int32
        (garbage resid/band on BOTH paths identically)."""
        from platform_aware_scheduling_tpu.ops.state import (
            history_value_bits,
        )

        w = 32
        bits = history_value_bits(w)
        assert bits <= 30 - 1 - (w - 1).bit_length()
        rng = np.random.default_rng(7)
        # a worst-case series inside the budget: alternating near the
        # magnitude ceiling, so every one-step error is ~2x the range
        ceiling = (1 << bits) - 1
        values = (
            rng.integers(0, 2, size=(2, 3, w)) * 2 * ceiling - ceiling
        ).astype(np.int32)
        valid = np.ones((2, 3, w), bool)
        for fit in (
            ops_forecast.forecast_host(values, valid, 1),
            ops_forecast.forecast_device(values, valid, 1),
        ):
            assert (fit.resid >= 0).all()
            assert (fit.band >= 0).all()

    def test_band_widens_with_horizon(self):
        rng = np.random.default_rng(3)
        values = (
            1000 + rng.integers(-200, 200, size=(1, 1, 12))
        ).astype(np.int32)
        valid = np.ones((1, 1, 12), bool)
        near = ops_forecast.forecast_host(values, valid, 1)
        far = ops_forecast.forecast_host(values, valid, 9)
        assert far.band[0, 0] > near.band[0, 0]
        # extend_horizon reproduces the fresh far fit exactly
        extended = ops_forecast.extend_horizon(near, 9)
        assert np.array_equal(extended.predicted, far.predicted)
        assert np.array_equal(extended.band, far.band)


# ---------------------------------------------------------------------------
# history tensor staging
# ---------------------------------------------------------------------------


def _seeded_cache_mirror(window=8, clock=None):
    cache = (
        AutoUpdatingCache(clock=clock) if clock else AutoUpdatingCache()
    )
    cache.configure_history(window)
    mirror = TensorStateMirror()
    mirror.attach(cache)
    return cache, mirror


class TestHistoryTensor:
    def test_alignment_and_right_padding(self):
        cache, mirror = _seeded_cache_mirror(window=4)
        cache.write_metric(
            "m", {"a": NodeMetric(value=Quantity("1")),
                  "b": NodeMetric(value=Quantity("2"))}
        )
        cache.write_metric(
            "m", {"a": NodeMetric(value=Quantity("3"))}  # b missing
        )
        view = mirror.device_view()
        _gen, history = cache.history_snapshot()
        tensor = build_history_tensor(view, history, 4)
        row = view.metric_index["m"]
        col_a, col_b = view.node_index["a"], view.node_index["b"]
        # 2 samples right-aligned at slots 2, 3
        assert not tensor.valid[row, :, :2].any()
        assert tensor.values[row, col_a, 2] == 1000
        assert tensor.values[row, col_a, 3] == 3000
        assert tensor.valid[row, col_b, 2]
        assert not tensor.valid[row, col_b, 3]  # the gap stays visible
        assert tensor.shift[row] == 0

    def test_huge_values_descale_into_int32(self):
        cache, mirror = _seeded_cache_mirror(window=4)
        big = 10**15  # ~2^50 milli: far past int32
        cache.write_metric(
            "mem", {"a": NodeMetric(value=Quantity(str(big)))}
        )
        view = mirror.device_view()
        _gen, history = cache.history_snapshot()
        tensor = build_history_tensor(view, history, 4)
        row = view.metric_index["mem"]
        shift = int(tensor.shift[row])
        assert shift > 0
        col = view.node_index["a"]
        staged = int(tensor.values[row, col, 3])
        assert abs(staged) < 2**31
        # unscaling recovers the value to within the dropped low bits
        assert abs((staged << shift) - big * 1000) < (1 << shift)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class TestForecasterEngine:
    def _trending(self, steps=6, clock=None, **kwargs):
        cache, mirror = _seeded_cache_mirror(window=8, clock=clock)
        if clock is not None:
            kwargs["clock"] = clock.now
        forecaster = Forecaster(
            cache, mirror, window=8, period_s=1.0, **kwargs
        )
        for step in range(steps):
            cache.write_metric(
                "cpu",
                {
                    "riser": NodeMetric(value=Quantity(100 + step * 300)),
                    "flat": NodeMetric(value=Quantity(1950)),
                },
            )
        forecaster.refresh()
        return cache, mirror, forecaster

    def test_refit_memoized_per_generation(self):
        counters = CounterSet()
        cache, mirror = _seeded_cache_mirror(window=8)
        forecaster = Forecaster(
            cache, mirror, window=8, period_s=1.0, counters=counters
        )
        cache.write_metric("cpu", {"n": NodeMetric(value=Quantity("5"))})
        forecaster.refresh()
        assert counters.get("pas_forecast_fit_passes_total") == 1
        forecaster.refresh()  # no history movement -> no refit
        assert counters.get("pas_forecast_fit_passes_total") == 1
        cache.write_metric("cpu", {"n": NodeMetric(value=Quantity("6"))})
        forecaster.refresh()
        assert counters.get("pas_forecast_fit_passes_total") == 2

    def test_ranking_view_none_without_history(self):
        cache, mirror = _seeded_cache_mirror()
        forecaster = Forecaster(cache, mirror, window=8, period_s=1.0)
        assert forecaster.ranking_view("cpu") is None

    def test_predictions_exceed_snapshot_on_uptrend(self):
        _cache, _mirror, forecaster = self._trending()
        fit = forecaster.ensure_current()
        row = fit.rows["cpu"]
        col = fit.fview.node_index["riser"]
        # last sample 1600; prediction continues the +300 trend
        assert int(fit.predicted[row, col]) > 1_600_000
        assert forecaster.trend_milli("cpu", "riser") > 0
        assert forecaster.trend_milli("cpu", "flat") == 0
        described = forecaster.describe("cpu", "riser")
        assert described.startswith("predicted cpu=")
        assert "slope +" in described and described.endswith("/s)")

    def test_horizon_widens_with_staleness(self):
        clock = FakeClock()
        _cache, _mirror, forecaster = self._trending(clock=clock)
        fit = forecaster.ensure_current()
        assert fit.horizon_steps == 1
        band_fresh = int(fit.band[fit.rows["cpu"]].max())
        clock.advance(5.0)  # five silent periods
        fit = forecaster.ensure_current()
        assert fit.horizon_steps == 6
        assert int(fit.band[fit.rows["cpu"]].max()) > band_fresh

    def test_successive_extensions_grow_linearly(self):
        """REVIEW: the horizon is anchored on the BASE horizon plus
        elapsed periods, never on an already-extended fit — one
        ensure_current per silent period must yield 2, 3, 4, ... steps,
        not the compounding 2, 4, 7, ... re-adding elapsed periods to the
        previous extension would produce."""
        clock = FakeClock()
        _cache, _mirror, forecaster = self._trending(clock=clock)
        assert forecaster.ensure_current().horizon_steps == 1
        for expected in (2, 3, 4, 5):
            clock.advance(1.0)
            fit = forecaster.ensure_current()
            assert fit.horizon_steps == expected
        # and the extended predictions stay exact: equal to a fresh
        # re-extrapolation of the stored fit at the same horizon
        manual = ops_forecast.extend_horizon(fit.scaled, 5)
        shift = fit.shift[:, None]
        assert np.array_equal(
            fit.predicted, manual.predicted.astype(np.int64) << shift
        )
        assert np.array_equal(
            fit.band, manual.band.astype(np.int64) << shift
        )

    def test_configured_horizon_capped_at_window(self):
        """REVIEW: an unbounded --forecastHorizon would feed the int32
        kernel tails (trend*h, resid*(1+h)) a wrap-scale h — the base
        horizon caps at the lookback window (no fit predicts further
        ahead than it looked back)."""
        _cache, _mirror, forecaster = self._trending(horizon_s=100_000.0)
        fit = forecaster.ensure_current()
        assert fit.horizon_steps == 8  # window, not 100k steps
        assert (fit.band >= 0).all()

    def test_host_only_metric_never_forecasts(self):
        """REVIEW: host-only metrics are host-only precisely because
        their values are not milli-exact — the milli-truncated history
        must never replace the exact-Quantity host ranking."""
        from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import (
            TASPolicyRule,
        )

        cache, mirror = _seeded_cache_mirror(window=8)
        # sub-milli values: milli_value_exact is inexact -> the mirror
        # marks the metric host-only
        for step in range(3):
            cache.write_metric(
                "submilli",
                {
                    "a": NodeMetric(value=Quantity("0.0004")),
                    "b": NodeMetric(value=Quantity("0.0006")),
                },
            )
        assert mirror.metric_host_only("submilli")
        ext = MetricsExtender(cache, mirror=mirror)

        class MustNotForecast:
            def host_metric(self, name):
                raise AssertionError("host-only metric consulted forecast")

        ext.forecaster = MustNotForecast()
        rule = TASPolicyRule(
            metricname="submilli", operator="GreaterThan", target=0
        )
        ranked = ext._prioritize_host(rule, ["a", "b"])
        # exact Quantity ordering: 0.0006 > 0.0004 (milli-truncated both
        # read 0 and would tie on dict order)
        assert [p.host for p in ranked] == ["b", "a"]

    def test_ranking_falls_back_to_snapshot_past_window(self):
        """REVIEW: assemblies WITHOUT a DegradedModeController must not
        rank on unboundedly diverging extrapolations — once staleness has
        grown the horizon a full lookback window past its base,
        ranking_view AND host_metric fall back (None -> snapshot), and
        the horizon itself clamps instead of growing toward int32 wrap."""
        clock = FakeClock()
        _cache, _mirror, forecaster = self._trending(clock=clock)
        # window=8, base horizon 1: stale but within base + window
        clock.advance(8.0)
        assert forecaster.ensure_current().horizon_steps == 9
        assert forecaster.ranking_view("cpu") is not None
        assert forecaster.host_metric("cpu") is not None
        # one more silent period crosses the gate: both paths fall back
        # TOGETHER (native<->host parity holds through the fallback)
        clock.advance(1.0)
        assert forecaster.ranking_view("cpu") is None
        assert forecaster.host_metric("cpu") is None
        # a month of staleness: the horizon is clamped one past the
        # outermost gate, far from int32 territory
        clock.advance(2_600_000.0)
        assert forecaster.ensure_current().horizon_steps == 10

    def test_host_metric_matches_forecast_view(self):
        _cache, _mirror, forecaster = self._trending()
        fit = forecaster.ensure_current()
        info = forecaster.host_metric("cpu")
        row = fit.rows["cpu"]
        for node, metric in info.items():
            col = fit.fview.node_index[node]
            milli, exact = metric.value.milli_value_exact()
            assert exact
            assert milli == int(fit.predicted[row, col])


# ---------------------------------------------------------------------------
# ranking through the real verbs — the acceptance invariant
# ---------------------------------------------------------------------------


def _forecast_extender(num_nodes=12, trending=True):
    """A device extender over load-pol whose history makes node 0 the
    lowest-now-but-rising series (build_extender's universe + a scripted
    trend), plus its Forecaster."""
    ext, names = build_extender(num_nodes, device=True)
    forecaster = Forecaster(ext.cache, ext.mirror, window=8, period_s=300.0)
    for step in range(7):
        ext.cache.write_metric(
            "load_metric",
            {
                n: NodeMetric(
                    value=Quantity(
                        100 + step * 300 if (i == 0 and trending) else 1950
                    )
                )
                for i, n in enumerate(names)
            },
        )
    forecaster.refresh()
    ext.forecaster = forecaster
    ext.warm_fastpath()
    return ext, names


def _post(ext, verb, body):
    return getattr(ext, verb)(
        HTTPRequest(
            method="POST",
            path=f"/scheduler/{verb}",
            headers={"Content-Type": "application/json"},
            body=body,
        )
    )


class TestForecastRankingVerbs:
    def test_native_and_host_rankings_byte_equal(self):
        """ACCEPTANCE: the native fastpath and the exact host strategy
        path rank on the same predicted values — byte-identical wire
        responses."""
        ext, names = _forecast_extender()
        body = make_bodies(names, "nodenames", count=1)[0]
        native = _post(ext, "prioritize", body)
        assert native.status == 200
        # force the exact host path on a fresh-but-identical extender
        ext2, names2 = _forecast_extender()
        ext2._device_prioritize_ok = lambda *a, **k: False
        host = _post(ext2, "prioritize", body)
        assert host.status == 200
        assert native.body == host.body
        # and the full-Nodes wire mode agrees too
        nodes_body = make_bodies(names, "nodes", count=1)[0]
        assert _post(ext, "prioritize", nodes_body).body == _post(
            ext2, "prioritize", nodes_body
        ).body

    def test_forecast_ranking_differs_from_snapshot(self):
        ext, names = _forecast_extender()
        body = make_bodies(names, "nodenames", count=1)[0]
        with_forecast = json.loads(_post(ext, "prioritize", body).body)
        ext.forecaster = None  # snapshot ranking
        snapshot = json.loads(_post(ext, "prioritize", body).body)
        top_forecast = max(with_forecast, key=lambda e: e["Score"])["Host"]
        top_snapshot = max(snapshot, key=lambda e: e["Score"])["Host"]
        # GreaterThan policy prefers HIGH values: the riser's predicted
        # value tops the forecast ranking while the snapshot still sees
        # it below the flat nodes
        assert top_forecast == names[0]
        assert top_snapshot != names[0]

    def test_both_front_ends_serve_identical_forecast_rankings(self):
        """ACCEPTANCE: the same forecast ranking over real sockets on the
        threaded AND async front-ends."""
        ext, names = _forecast_extender()
        body = make_bodies(names, "nodenames", count=1)[0]
        payload = post_bytes("/scheduler/prioritize", body)
        threaded = start_threaded(ext)
        try:
            status, _headers, threaded_body = raw_request(
                threaded.port, payload
            )
            assert status == 200
        finally:
            threaded.shutdown()
        ext2, _names = _forecast_extender()
        async_server = start_async(ext2)
        try:
            status, _headers, async_body = raw_request(
                async_server.port, payload
            )
            assert status == 200
        finally:
            async_server.shutdown()
        assert threaded_body == async_body
        ranked = json.loads(threaded_body)
        assert max(ranked, key=lambda e: e["Score"])["Host"] == names[0]

    def test_decision_records_carry_forecast_provenance(self):
        from platform_aware_scheduling_tpu.utils import decisions

        decisions.DECISIONS.configure(enabled=True, capacity=64)
        try:
            ext, names = _forecast_extender()
            body = make_bodies(names, "nodenames", count=1)[0]
            _post(ext, "prioritize", body)
            snap = decisions.DECISIONS.snapshot(verb="prioritize", limit=1)
            record = snap["records"][0]
            assert record["detail"]["ranking"] == "forecast"
            assert record["detail"]["top"].startswith(
                "predicted load_metric="
            )
            assert "slope" in record["detail"]["top"]
        finally:
            decisions.DECISIONS.configure(enabled=True, capacity=512)

    def test_forecast_off_path_unchanged(self):
        """--forecast=off (forecaster None) serves byte-identically to an
        extender built without any forecast plumbing."""
        ext, names = build_extender(12, device=True)
        body = make_bodies(names, "nodenames", count=1)[0]
        baseline = _post(ext, "prioritize", body).body
        ext.forecaster = None
        assert _post(ext, "prioritize", body).body == baseline


# ---------------------------------------------------------------------------
# trend-aware hysteresis
# ---------------------------------------------------------------------------


class TestTrendHysteresis:
    def test_drift_hold_semantics(self):
        drift = DriftDetector(k=2)
        violations = {"hot": ["pol"]}
        # held from the start: the streak never advances
        assert drift.observe(violations, hold=frozenset({"hot"})) == {}
        assert drift.streaks()["hot"] == 0
        assert drift.observe(violations, hold=frozenset({"hot"})) == {}
        # the hold lifts (trend flipped up): escalation resumes
        assert drift.observe(violations) == {}
        assert drift.streaks()["hot"] == 1
        assert drift.observe(violations) == {"hot": ["pol"]}
        # REVIEW: a node already AT the threshold (its eviction deferred)
        # that starts trending down is not a candidate while held — the
        # hold blocks candidacy outright, not just streak advancement
        assert drift.streaks()["hot"] == 2
        assert drift.observe(violations, hold=frozenset({"hot"})) == {}
        assert drift.streaks()["hot"] == 2  # frozen, not reset
        # hold lifts while still violating: candidacy resumes at once
        assert drift.observe(violations) == {"hot": ["pol"]}
        # recovery still resets immediately
        assert drift.observe({}) == {}
        assert drift.streaks() == {}

    def test_spike_suppression_end_to_end(self):
        """ACCEPTANCE: the transient-spike A/B through the real
        enforcement -> drift -> rebalance loop — snapshot mode evicts,
        forecast mode suppresses every eviction and still converges."""
        result = spike_ab()
        assert result["snapshot"]["evictions"] >= 1
        assert result["forecast"]["evictions"] == 0
        assert result["forecast"]["suppressed"] >= 1
        # both end clean: the spike resolves either way — forecast just
        # got there without destroying work
        assert result["forecast"]["final_violations"] == 0
        assert result["snapshot"]["final_violations"] == 0

    def test_suppression_counted_once_per_spike(self):
        """REVIEW: a held node's streak STAYS at k-1, so it re-satisfies
        the would-have-evicted test every cycle of the spike — one spike
        must count ONE suppressed eviction, however long it lasts; a
        fresh spike after recovery counts again."""
        from platform_aware_scheduling_tpu.rebalance.loop import Rebalancer

        class CountingForecaster:
            suppressed = 0

            def count_suppressed_eviction(self, n=1):
                self.suppressed += n

        rebalancer = Rebalancer(None, None, hysteresis_cycles=2)
        counting = CountingForecaster()
        rebalancer.forecaster = counting
        rebalancer._trend_holds = lambda violations: frozenset(violations)
        violations = {"hot": ["pol"]}
        rebalancer.cycle(violations)  # streak would reach 1: below k
        assert counting.suppressed == 0
        rebalancer.drift._streaks["hot"] = 1  # next advance would evict
        for _ in range(4):  # a four-cycle spike, held at k-1 throughout
            rebalancer.cycle(violations)
        assert counting.suppressed == 1
        rebalancer._trend_holds = lambda violations: frozenset()
        rebalancer.cycle({})  # spike resolves: streak + counted set clear
        rebalancer._trend_holds = lambda violations: frozenset(violations)
        rebalancer.drift._streaks["hot"] = 1
        rebalancer.cycle(violations)  # a NEW spike: one more
        assert counting.suppressed == 2
        # REVIEW: a node held at/past the threshold (deferred eviction,
        # now resolving) is both blocked from candidacy and counted
        rebalancer._trend_holds = lambda violations: frozenset()
        rebalancer.cycle({})
        rebalancer.drift._streaks["late"] = 3  # already past k=2
        rebalancer._trend_holds = lambda violations: frozenset(violations)
        record = rebalancer.cycle({"late": ["pol"]})
        assert record["candidate_nodes"] == []
        assert counting.suppressed == 3

    def test_trending_up_violation_still_escalates(self):
        """A genuine trend must evict exactly as before: rising series
        never hold streaks."""
        cache, mirror = _seeded_cache_mirror(window=8)
        forecaster = Forecaster(cache, mirror, window=8, period_s=1.0)
        for step in range(4):
            cache.write_metric(
                "load",
                {"hot": NodeMetric(value=Quantity(2000 + step * 100))},
            )
        forecaster.refresh()
        assert forecaster.trending_down("hot", ["load"]) is False

    def test_trending_ab_reduces_violated_at_bind(self):
        """ACCEPTANCE: forecast-on strictly reduces violated-at-bind
        placements on the trending scenario."""
        result = trending_ab(num_nodes=6, pods=4)
        assert (
            result["forecast"]["violated_at_bind"]
            < result["snapshot"]["violated_at_bind"]
        )
        assert result["forecast"]["violated_at_bind"] == 0
        assert result["snapshot"]["chose_riser"] == 4


# ---------------------------------------------------------------------------
# degraded bounded extrapolation
# ---------------------------------------------------------------------------


class TestDegradedExtrapolation:
    def _stale_setup(
        self, noisy: bool, band_bound: float = 0.25, window: int = 64
    ):
        # forecaster window 64 >> the 8 samples written: these tests
        # probe the BAND bound at 20ish-period staleness, which must stay
        # inside the horizon-vs-window cap (its own test below)
        clock = FakeClock()
        cache, mirror = _seeded_cache_mirror(window=8, clock=clock)
        cache._refresh_period = 1.0
        cache.write_metric("cpu")  # register for the refresh loop
        forecaster = Forecaster(
            cache, mirror, window=window, period_s=1.0,
            band_bound=band_bound, clock=clock.now,
        )
        rng = np.random.default_rng(5)
        client_values = []
        for step in range(8):
            noise = int(rng.integers(-400, 400)) if noisy else 0
            client_values.append(1000 + noise)
        for value in client_values:
            clock.advance(1.0)
            cache.update_all_metrics(
                DummyMetricsClient(
                    {"cpu": {"n": NodeMetric(value=Quantity(value))}}
                )
            )
        controller = DegradedModeController(
            cache, mode=degraded_mode.MODE_LAST_KNOWN_GOOD,
            counters=CounterSet(),
        )
        controller.forecaster = forecaster
        return clock, cache, controller, forecaster

    def test_extrapolation_extends_lkg_window(self):
        clock, cache, controller, forecaster = self._stale_setup(noisy=False)
        action, _ = controller.prioritize_decision()
        assert action == degraded_mode.ACTION_NORMAL
        # stale past the frozen-LKG window (bound 3s x multiple 3 = 9s):
        # pre-forecast behavior was NEUTRAL; a zero-residual forecast
        # extrapolates with a zero-width band -> keeps serving LKG scores
        clock.advance(20.0)
        action, reason = controller.prioritize_decision()
        assert action == degraded_mode.ACTION_LAST_KNOWN_GOOD
        assert "extrapolating" in reason
        filter_action, filter_reason = controller.filter_decision()
        assert filter_action == degraded_mode.ACTION_LAST_KNOWN_GOOD
        assert "extrapolating" in filter_reason
        assert (
            forecaster.counters.get(
                "pas_forecast_extrapolated_serves_total"
            )
            >= 2
        )

    def test_wide_band_falls_back_to_frozen_lkg_behavior(self):
        """A noisy series' band widens with the horizon until the bound
        trips — then today's frozen-LKG fallbacks (neutral Prioritize,
        fail-open Filter) return."""
        clock, cache, controller, forecaster = self._stale_setup(
            noisy=True, band_bound=0.1
        )
        # 20 silent periods: horizon 21 (within the 64-step cap) but the
        # noisy residual has inflated the relative band far past 0.1
        clock.advance(20.0)
        ok, reason = forecaster.extrapolation_ok()
        assert not ok and "exceeds bound" in reason
        action, _ = controller.prioritize_decision()
        assert action == degraded_mode.ACTION_NEUTRAL
        filter_action, _ = controller.filter_decision()
        assert filter_action == degraded_mode.ACTION_FAIL_OPEN

    def test_horizon_past_window_trips_even_at_zero_band(self):
        """REVIEW: a zero-residual (constant) series keeps band == 0 at
        ANY horizon, so the band bound alone would extrapolate a dead
        telemetry source forever.  The lookback-window cap makes "a long
        enough outage always trips back" unconditional."""
        clock, cache, controller, forecaster = self._stale_setup(
            noisy=False, window=16
        )
        clock.advance(10.0)  # horizon 11 <= 16: still serving
        ok, _ = forecaster.extrapolation_ok()
        assert ok
        action, _ = controller.prioritize_decision()
        assert action == degraded_mode.ACTION_LAST_KNOWN_GOOD
        clock.advance(10.0)  # horizon 21 > 16: cap trips, band still 0
        ok, reason = forecaster.extrapolation_ok()
        assert not ok and "lookback window" in reason
        action, _ = controller.prioritize_decision()
        assert action == degraded_mode.ACTION_NEUTRAL
        filter_action, _ = controller.filter_decision()
        assert filter_action == degraded_mode.ACTION_FAIL_OPEN

    def test_evictions_stay_suspended_while_extrapolating(self):
        """Extrapolation serves VERBS only: the unconditional eviction
        suspension is untouched."""
        clock, cache, controller, _forecaster = self._stale_setup(
            noisy=False
        )
        clock.advance(20.0)
        action, _ = controller.prioritize_decision()
        assert action == degraded_mode.ACTION_LAST_KNOWN_GOOD
        allowed, reason = controller.evictions_allowed()
        assert not allowed and "suspended" in reason


# ---------------------------------------------------------------------------
# /debug/forecast on both front-ends
# ---------------------------------------------------------------------------


class TestDebugForecast:
    def test_threaded_and_async_endpoints(self):
        ext, _names = _forecast_extender()
        for start in (start_threaded, start_async):
            server = start(ext)
            try:
                status, _headers, body = get_request(
                    server.port, "/debug/forecast"
                )
                assert status == 200
                payload = json.loads(body)
                assert payload["enabled"] is True
                assert payload["fitted"] is True
                assert "load_metric" in payload["metrics"]
                status, _headers, body = get_request(server.port, "/debug")
                paths = [
                    e["path"]
                    for e in json.loads(body)["endpoints"]
                ]
                assert "/debug/forecast" in paths
            finally:
                server.shutdown()

    def test_404_when_off_and_405_non_get(self):
        ext, _names = build_extender(8, device=True)
        server = start_threaded(ext)
        try:
            status, _headers, _body = get_request(
                server.port, "/debug/forecast"
            )
            assert status == 404
        finally:
            server.shutdown()
        ext2, _names = _forecast_extender()
        server = start_threaded(ext2)
        try:
            status, _headers, _body = raw_request(
                server.port, post_bytes("/debug/forecast", b"{}")
            )
            assert status == 405
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# flags + assembly
# ---------------------------------------------------------------------------


class TestFlagsAndAssembly:
    def test_tas_has_forecast_flags_gas_does_not(self):
        from platform_aware_scheduling_tpu.cmd import gas, tas

        tas_args = tas.build_arg_parser().parse_args([])
        assert tas_args.forecast == "off"
        assert tas_args.forecastWindow == 32
        gas_parser = gas.build_arg_parser()
        with pytest.raises(SystemExit):
            gas_parser.parse_args(["--forecast", "on"])

    def test_forecast_options_off_is_none(self):
        from platform_aware_scheduling_tpu.cmd import common, tas

        args = tas.build_arg_parser().parse_args([])
        assert common.forecast_options(args, 5.0) is None
        args = tas.build_arg_parser().parse_args(
            ["--forecast", "on", "--forecastHorizon", "10s"]
        )
        options = common.forecast_options(args, 5.0)
        assert options["window"] == 32
        assert options["horizon_s"] == 10.0
        assert options["period_s"] == 5.0

    def test_assemble_wires_forecaster_everywhere(self):
        from platform_aware_scheduling_tpu.cmd import tas
        from platform_aware_scheduling_tpu.testing.fake_kube import (
            FakeKubeClient,
        )

        fake = FakeKubeClient()
        client = DummyMetricsClient({})
        cache, mirror, extender, _controller, enforcer, stop = tas.assemble(
            fake,
            client,
            sync_period_s=3600.0,
            rebalance_mode="dry-run",
            degraded_mode="last-known-good",
            forecast_options={"window": 8, "period_s": 3600.0},
        )
        try:
            assert extender.forecaster is not None
            assert extender.degraded.forecaster is extender.forecaster
            assert (
                extender.rebalancer.forecaster is extender.forecaster
            )
            # the cache history records at the configured window
            assert cache.history_window() == 8
            # REVIEW: the post-refit ranking warm is registered AFTER the
            # forecaster's own refit hook — warm_fastpath fires mid-pass,
            # before the refit, so without this ordering every fresh
            # forecast view would go cold to its first request
            hooks = cache.on_refresh_pass
            assert extender.warm_forecast_rankings in hooks
            assert hooks.index(extender.forecaster.refresh) < hooks.index(
                extender.warm_forecast_rankings
            )
        finally:
            stop.set()

    def test_host_only_assembly_disables_forecaster(self):
        from platform_aware_scheduling_tpu.cmd import common

        assert common.build_forecaster(
            AutoUpdatingCache(), None, {"window": 8}
        ) is None


# ---------------------------------------------------------------------------
# gang-mode Filter response cache restore (satellite)
# ---------------------------------------------------------------------------


def _plain_pod_body(names, name="plain"):
    return json.dumps(
        {
            "Pod": {
                "metadata": {
                    "name": name,
                    "namespace": "default",
                    "labels": {"telemetry-policy": "gang-pol"},
                }
            },
            "NodeNames": names,
        }
    ).encode()


def _counter(name):
    return trace.COUNTERS.get(name, kind="counter")


class TestGangFilterCacheRestore:
    def test_non_gang_pods_regain_cache_hits(self):
        """ISSUE 8 satellite pin: with gang mode ON, plain pods hit the
        Filter response cache again (hit/miss counters move) instead of
        bypassing every request."""
        extender, _kube, names = build_mesh_service(4, 4, gang=True)
        body = _plain_pod_body(names)
        before_hit = _counter("pas_filter_cache_hit_total")
        before_miss = _counter("pas_filter_cache_miss_total")
        before_bypass = _counter("pas_filter_cache_bypass_total")
        first = _post(extender, "filter", body)
        second = _post(extender, "filter", body)
        assert first.status == second.status == 200
        assert first.body == second.body
        assert _counter("pas_filter_cache_miss_total") == before_miss + 1
        assert _counter("pas_filter_cache_hit_total") == before_hit + 1
        assert _counter("pas_filter_cache_bypass_total") == before_bypass

    def test_rebalance_grouped_pods_keep_cache_hits(self):
        """REVIEW: ``pas-workload-group`` alone is the rebalancer's
        min-available grouping that ordinary NON-gang workloads carry —
        gang membership needs ``pas-gang-size`` too (labels.gang_id_for).
        A grouped-but-not-gang pod must keep its cache hits, not pay the
        exact path per request."""
        extender, _kube, names = build_mesh_service(4, 4, gang=True)
        body = json.dumps(
            {
                "Pod": {
                    "metadata": {
                        "name": "grouped",
                        "namespace": "default",
                        "labels": {
                            "telemetry-policy": "gang-pol",
                            shared_labels.GROUP_LABEL: "web-tier",
                        },
                    }
                },
                "NodeNames": names,
            }
        ).encode()
        before_hit = _counter("pas_filter_cache_hit_total")
        before_bypass = _counter("pas_filter_cache_bypass_total")
        first = _post(extender, "filter", body)
        second = _post(extender, "filter", body)
        assert first.body == second.body
        assert _counter("pas_filter_cache_hit_total") == before_hit + 1
        assert _counter("pas_filter_cache_bypass_total") == before_bypass

    def test_gang_members_still_bypass(self):
        """A member's answer never comes from the response cache (its
        verdict is its gang's, and its Filter has side effects): each is
        a miss the native encoder answers from the tracker's verdict."""
        extender, _kube, names = build_mesh_service(4, 4, gang=True)
        before_bypass = _counter("pas_filter_cache_bypass_total")
        before_hit = _counter("pas_filter_cache_hit_total")
        before_miss = _counter("pas_filter_cache_miss_total")
        before_native = _counter("pas_gang_filter_native_total")
        gang_body = json.dumps(
            {"Pod": _gang_pod_obj("a-0", "gang-a", 8, "2x4"),
             "NodeNames": names}
        ).encode()
        first = _post(extender, "filter", gang_body)
        second = _post(extender, "filter", gang_body)
        assert first.body == second.body
        assert _counter("pas_filter_cache_hit_total") == before_hit
        assert _counter("pas_filter_cache_miss_total") == before_miss + 2
        assert _counter("pas_gang_filter_native_total") == before_native + 2
        assert _counter("pas_filter_cache_bypass_total") == before_bypass

    def test_reservation_change_invalidates_cached_verdict(self):
        """A cached non-gang verdict must reflect every reservation
        change: after gang A reserves, the next plain request MISSES and
        fails A's slice with the concrete gang reason; cached bytes then
        hit again at the new version."""
        extender, _kube, names = build_mesh_service(4, 4, gang=True)
        body = _plain_pod_body(names)
        clean = _post(extender, "filter", body)
        assert json.loads(clean.body)["FailedNodes"] == {}
        hit = _post(extender, "filter", body)
        assert hit.body == clean.body
        # gang A reserves a 2x4 slice -> reservation version bumps
        _post(
            extender,
            "filter",
            json.dumps(
                {"Pod": _gang_pod_obj("a-0", "gang-a", 8, "2x4"),
                 "NodeNames": names}
            ).encode(),
        )
        after = _post(extender, "filter", body)
        failed = json.loads(after.body)["FailedNodes"]
        assert len(failed) == 8
        assert all(
            "reserved by gang default/gang-a" in reason
            for reason in failed.values()
        )
        # the merged verdict is itself cacheable at the new version
        before_hit = _counter("pas_filter_cache_hit_total")
        again = _post(extender, "filter", body)
        assert again.body == after.body
        assert _counter("pas_filter_cache_hit_total") == before_hit + 1

    def test_cached_and_exact_verdicts_byte_equal(self):
        """The native cached response equals the exact path's bytes for
        the same request under active reservations."""
        extender, _kube, names = build_mesh_service(4, 4, gang=True)
        _post(
            extender,
            "filter",
            json.dumps(
                {"Pod": _gang_pod_obj("a-0", "gang-a", 8, "2x4"),
                 "NodeNames": names}
            ).encode(),
        )
        body = _plain_pod_body(names)
        native = _post(extender, "filter", body)
        # identical scenario on a second service, exact path forced
        extender2, _kube2, names2 = build_mesh_service(4, 4, gang=True)
        _post(
            extender2,
            "filter",
            json.dumps(
                {"Pod": _gang_pod_obj("a-0", "gang-a", 8, "2x4"),
                 "NodeNames": names2}
            ).encode(),
        )
        extender2.fastpath = None  # no probe: exact path owns the verdict
        exact = _post(extender2, "filter", body)
        assert native.body == exact.body
