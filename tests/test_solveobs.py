"""Solve observatory (docs/observability.md "Solve observatory"):

  * stage attribution — the timer's marks are exhaustive (per-sample
    stage sums land within 10% of the independently measured end-to-end
    total) and forced solves attribute every pipeline seam;
  * refresh churn — a metric's FIRST pass counts every present column
    (full churn), a byte-identical refresh counts zero, a delete counts
    the columns it tore down, and a drain resets the accumulator;
  * off-path neutrality — with no observatory enabled the verb
    responses are byte-identical on the wire to an enabled build
    (modulo X-Request-ID) and /metrics emits no pas_solve_* /
    pas_state_churn_* families at all;
  * /debug/solve — indexed, 404 when unwired, 405 on non-GET, and the
    200 payload carries stages + churn + the recompile watch, on both
    front-ends;
  * recompile watch — a diurnal twin run recompiles NOTHING after a
    full-period warmup (pas_xla_compiles_total flat);
  * causal spine — churn/solve events join /debug/explain chains by
    tick as "the world changed under you" context, and churn passes
    export anonymized into the flight recorder (format /3).
"""

import json

import numpy as np
import pytest

from benchmarks.http_load import build_extender, make_bodies
from platform_aware_scheduling_tpu.extender.server import (
    DEBUG_ENDPOINTS,
    HTTPRequest,
)
from platform_aware_scheduling_tpu.ops import solveobs
from platform_aware_scheduling_tpu.ops.rules import OP_IDS
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.events import JOURNAL
from platform_aware_scheduling_tpu.utils.quantity import Quantity
from platform_aware_scheduling_tpu.utils.record import FORMAT, FlightRecorder
from wirehelpers import (
    get_request,
    post_bytes,
    raw_request,
    start_async,
    start_threaded,
)


@pytest.fixture(autouse=True)
def _observatory_off():
    """Every test starts and ends with the observatory disabled — the
    module-global gate must never leak between tests."""
    saved = solveobs.ACTIVE
    solveobs.ACTIVE = None
    yield
    solveobs.ACTIVE = saved


def info(**kv):
    return {node: NodeMetric(value=Quantity(v)) for node, v in kv.items()}


def attach_pair(node_capacity=8, metric_capacity=2):
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror(
        node_capacity=node_capacity, metric_capacity=metric_capacity
    )
    mirror.attach(cache)
    return cache, mirror


def verb_request(path, body):
    return HTTPRequest(
        method="POST",
        path=path,
        headers={"Content-Type": "application/json"},
        body=body,
    )


class TestSolveTimer:
    def test_marks_attribute_elapsed_and_done_commits(self):
        t = {"now": 0.0}
        obs = solveobs.SolveObservatory(capacity=4, clock=lambda: t["now"])
        timer = obs.begin("unit")
        t["now"] = 100e-6
        assert timer.mark("snapshot") == pytest.approx(100.0)
        t["now"] = 250e-6
        timer.mark("execute")
        t["now"] = 300e-6
        timer.mark("execute")  # repeat marks ACCUMULATE
        t["now"] = 310e-6
        total = timer.done(nodes=3)
        assert total == pytest.approx(310.0)
        (sample,) = obs.ring
        assert sample["kind"] == "unit"
        assert sample["stages"] == {"snapshot": 100.0, "execute": 200.0}
        assert sample["total_us"] == 310.0
        assert sample["nodes"] == 3
        stages = obs.to_json_dict()["stages"]
        assert stages["snapshot"]["count"] == 1
        assert stages["execute"]["mean"] == pytest.approx(200.0)

    def test_ring_is_bounded(self):
        obs = solveobs.SolveObservatory(capacity=3, clock=lambda: 0.0)
        for i in range(7):
            obs.begin("unit").done(i=i)
        assert [s["i"] for s in obs.ring] == [4, 5, 6]
        assert (
            obs.counters.get("pas_solve_samples_total", kind="counter") == 7
        )


class TestChurnAccounting:
    def test_first_pass_counts_every_present_column(self):
        cache, mirror = attach_pair()
        obs = solveobs.enable()
        obs.mirror = mirror
        cache.write_metric("load", info(a="1", b="2", c="3"))
        pending, world = mirror.drain_churn()
        assert pending == {"load": (3, False)}
        assert world == 3

    def test_byte_identical_refresh_counts_zero(self):
        cache, mirror = attach_pair()
        obs = solveobs.enable()
        obs.mirror = mirror
        cache.write_metric("load", info(a="1", b="2"))
        mirror.drain_churn()
        cache.write_metric("load", info(a="1", b="2"))
        pending, _world = mirror.drain_churn()
        assert pending == {"load": (0, False)}

    def test_partial_change_counts_moved_columns_only(self):
        cache, mirror = attach_pair()
        obs = solveobs.enable()
        obs.mirror = mirror
        cache.write_metric("load", info(a="1", b="2", c="3"))
        mirror.drain_churn()
        # one value moves, one column disappears -> 2 churned columns
        cache.write_metric("load", info(a="9", b="2"))
        pending, _world = mirror.drain_churn()
        assert pending == {"load": (2, False)}

    def test_delete_counts_torn_down_columns(self):
        cache, mirror = attach_pair()
        obs = solveobs.enable()
        obs.mirror = mirror
        cache.write_metric("load", info(a="1", b="2"))
        mirror.drain_churn()
        mirror.on_metric_delete("load")
        pending, _world = mirror.drain_churn()
        assert pending == {"load": (2, True)}
        # drain resets: nothing pending afterwards
        assert mirror.drain_churn()[0] == {}

    def test_no_accounting_while_disabled(self):
        cache, mirror = attach_pair()
        cache.write_metric("load", info(a="1", b="2"))
        mirror.on_metric_delete("load")
        assert mirror.drain_churn()[0] == {}

    def test_flush_publishes_histograms_spine_and_flight(self):
        cache, mirror = attach_pair()
        obs = solveobs.enable()
        obs.mirror = mirror
        exported = []

        class _Flight:
            def record_churn(self, metrics, rows, world, fraction):
                exported.append((metrics, rows, world, fraction))

        obs.flight = _Flight()
        JOURNAL.reset()
        try:
            cache.write_metric("load", info(a="1", b="2", c="3", d="4"))
            cache.write_metric("temp", info(a="5", b="6"))
            obs.flush_refresh_pass()
            churn = obs.churn_summary()
            assert churn["world"] == 4
            assert churn["passes"] == 1
            last = churn["last_pass"]
            assert last["metrics"]["load"]["rows"] == 4
            assert last["metrics"]["load"]["fraction"] == 1.0
            assert last["metrics"]["temp"]["rows"] == 2
            assert last["total_rows"] == 6
            # pass fraction = 6 changed / (4 world * 2 metrics)
            assert last["fraction"] == pytest.approx(0.75)
            assert exported == [(2, 6, 4, pytest.approx(0.75))]
            churn_events = [
                r for r in JOURNAL.snapshot() if r["kind"] == "churn"
            ]
            assert len(churn_events) == 1
            assert churn_events[0]["data"]["rows"] == 6
            text = obs.metrics_text()
            assert 'pas_state_churn_rows_bucket{metric="load"' in text
            assert "pas_state_churn_fraction_bucket" in text
            assert "pas_state_churn_passes_total 1" in text
            assert "pas_state_churn_rows_changed_total 6" in text
        finally:
            JOURNAL.reset()

    def test_flush_without_pending_records_no_pass(self):
        _cache, mirror = attach_pair()
        obs = solveobs.enable()
        obs.mirror = mirror
        obs.flush_refresh_pass()
        assert obs.churn_summary()["passes"] == 0


class TestStageAttribution:
    """Forced solves through the REAL pipeline: every sample's stage
    marks must sum to the measured end-to-end total within 10% (plus a
    tiny absolute floor for sub-50us samples on a noisy CPU clock)."""

    def _assert_exhaustive(self, sample):
        total = sample["total_us"]
        attributed = sum(sample["stages"].values())
        assert abs(attributed - total) <= 0.10 * total + 25.0, sample

    def test_ranking_and_view_samples_sum_to_total(self):
        ext, _names = build_extender(64, device=True)
        obs = solveobs.enable()
        view = ext.mirror.device_view()
        row = view.metric_index["load_metric"]
        op = OP_IDS["GreaterThan"]
        for _ in range(3):
            with ext.fastpath._lock:
                ext.fastpath._rank.clear()
            ext.fastpath._ranking(view, row, op)
        with ext.mirror._lock:
            ext.mirror._version += 1  # invalidate the memoized view
        ext.mirror.device_view()
        kinds = {s["kind"] for s in obs.ring}
        assert {"prioritize_rank", "view_build"} <= kinds
        for sample in obs.ring:
            self._assert_exhaustive(sample)
        rank = [s for s in obs.ring if s["kind"] == "prioritize_rank"][-1]
        # post-warmup ranking touches every seam but compile/transfer
        assert {"execute", "readback", "encode"} <= set(rank["stages"])
        stages = obs.to_json_dict()["stages"]
        assert stages["execute"]["count"] >= 3

    def test_a_published_round_is_a_sample_of_its_own(self):
        """ISSUE 36: the observatory sees the one-row publish's round trip
        from the path itself (kind ``publish_round``), stages summing to
        the total, and the round still takes the incremental path."""
        from platform_aware_scheduling_tpu.tas.metrics import MetricColumns

        def columns(names, base):
            return MetricColumns({"items": [
                {"describedObject": {"name": n}, "value": str(base + i)}
                for i, n in enumerate(names)]})

        ext, names = build_extender(32, device=True)
        ext.cache.write_metric("load_metric", columns(names, 5))  # columns interned
        obs = solveobs.enable()
        incremental = trace.COUNTERS.get(
            "pas_refresh_warm_total", labels={"path": "incremental"})
        for base in (40, 70):
            ext.cache.write_metric("load_metric", columns(names, base))
        assert trace.COUNTERS.get(
            "pas_refresh_warm_total", labels={"path": "incremental"}
        ) == incremental + 2
        rounds = [s for s in obs.ring if s["kind"] == "publish_round"]
        assert len(rounds) == 2
        for sample in rounds:
            self._assert_exhaustive(sample)
            assert {"snapshot", "execute", "readback", "encode"} <= set(sample["stages"])
        assert not [s for s in obs.ring if s["kind"] in ("prioritize_rank", "filter_explain")]

    def test_instrumented_ranking_matches_uninstrumented(self):
        ext, _names = build_extender(32, device=True)
        view = ext.mirror.device_view()
        row = view.metric_index["load_metric"]
        op = OP_IDS["GreaterThan"]
        bare = ext.fastpath._ranking(view, row, op)
        solveobs.enable()
        with ext.fastpath._lock:
            ext.fastpath._rank.clear()
        timed = ext.fastpath._ranking(view, row, op)
        np.testing.assert_array_equal(bare, timed)


@pytest.mark.parametrize("front_end", ["threaded", "async"])
class TestDebugSolveEndpoint:
    def test_404_when_off(self, front_end):
        ext, _names = build_extender(8, device=True)
        server = (
            start_async(ext) if front_end == "async" else start_threaded(ext)
        )
        try:
            status, _, body = get_request(server.port, "/debug/solve")
            assert status == 404
            assert "solve observatory" in json.loads(body)["error"]
        finally:
            server.shutdown()

    def test_payload_after_solves(self, front_end):
        ext, names = build_extender(8, device=True)
        obs = solveobs.enable()
        obs.mirror = ext.mirror
        ext.solveobs = obs
        server = (
            start_async(ext) if front_end == "async" else start_threaded(ext)
        )
        try:
            body = make_bodies(names, "nodenames", count=1)[0]
            status, _, _ = raw_request(
                server.port, post_bytes("/scheduler/prioritize", body)
            )
            assert status == 200
            with ext.fastpath._lock:
                ext.fastpath._rank.clear()
            view = ext.mirror.device_view()
            ext.fastpath._ranking(
                view,
                view.metric_index["load_metric"],
                OP_IDS["GreaterThan"],
            )
            obs.flush_refresh_pass()
            status, headers, payload = get_request(
                server.port, "/debug/solve"
            )
            assert status == 200
            assert headers["content-type"] == "application/json"
            out = json.loads(payload)
            assert out["enabled"] is True
            assert out["samples"] >= 1
            assert set(out["stages"]) <= set(solveobs.STAGES)
            assert out["recent"][-1]["kind"] == "prioritize_rank"
            assert "churn" in out
            assert "prioritize_kernel" in out["compiles"]
            # POST against the GET-only endpoint must 405
            status, _, _ = raw_request(
                server.port, post_bytes("/debug/solve", b"{}")
            )
            assert status == 405
        finally:
            server.shutdown()

    def test_indexed(self, front_end):
        assert "/debug/solve" in {e["path"] for e in DEBUG_ENDPOINTS}


class TestOffPathNeutrality:
    def test_verb_responses_byte_identical_with_and_without_observatory(
        self,
    ):
        """The observatory must never touch a verb response: the same
        request against a disabled and an enabled build returns the
        same status, body, and headers (only X-Request-ID may
        differ)."""
        wire = {}
        for label in ("off", "on"):
            solveobs.ACTIVE = None
            ext, names = build_extender(12, device=True)
            if label == "on":
                obs = solveobs.enable()
                obs.mirror = ext.mirror
                ext.solveobs = obs
            server = start_threaded(ext)
            try:
                body = make_bodies(names, "nodenames", count=1)[0]
                wire[label] = {
                    path: raw_request(
                        server.port, post_bytes(path, body)
                    )
                    for path in (
                        "/scheduler/prioritize",
                        "/scheduler/filter",
                    )
                }
            finally:
                server.shutdown()
                solveobs.ACTIVE = None
        for path, (status, headers, body) in wire["off"].items():
            on_status, on_headers, on_body = wire["on"][path]
            assert status == on_status == 200
            assert body == on_body
            drop = "x-request-id"
            assert {k: v for k, v in headers.items() if k != drop} == {
                k: v for k, v in on_headers.items() if k != drop
            }

    def test_metrics_families_follow_the_observatory(self):
        ext, names = build_extender(8, device=True)
        body = make_bodies(names, "nodenames", count=1)[0]
        ext.prioritize(verb_request("/scheduler/prioritize", body))
        text = ext.metrics_text()
        assert "pas_solve_" not in text
        assert "pas_state_churn_" not in text
        obs = solveobs.enable()
        obs.mirror = ext.mirror
        with ext.fastpath._lock:
            ext.fastpath._rank.clear()
        view = ext.mirror.device_view()
        ext.fastpath._ranking(
            view, view.metric_index["load_metric"], OP_IDS["GreaterThan"]
        )
        # one refresh lands after enabling, so the flush has churn
        ext.cache.write_metric("churn_probe", info(**{names[0]: "1"}))
        obs.flush_refresh_pass()
        text = ext.metrics_text()
        assert 'pas_solve_stage_us_bucket{stage="execute"' in text
        assert "pas_solve_samples_total" in text
        assert "pas_state_churn_passes_total" in text
        # the page must stay a parseable exposition with the extra
        # families mixed in — and every family declared (the same gate
        # trace-lint holds live /metrics to)
        families = trace.parse_prometheus_text(text)
        assert families["pas_solve_stage_us"]["type"] == "histogram"
        assert families["pas_state_churn_fraction"]["type"] == "histogram"
        for family in families:
            assert family in trace.METRICS, f"undeclared {family!r}"


class TestRecompileWatch:
    def test_compile_counter_and_watch_registry(self):
        watches = {w.name for w in trace.JIT_WATCHES}
        assert "prioritize_kernel" in watches
        for watch in trace.JIT_WATCHES:
            assert watch.compile_count >= 0
            assert watch.cache_size() >= 0

    def test_diurnal_twin_zero_recompiles_after_warmup(self):
        """One full diurnal period warms every shape the scenario can
        present; the second identical period must compile NOTHING new
        (pas_xla_compiles_total flat) — the steady-state gate that keeps
        jit cache-key drift from silently re-tracing in production."""
        from platform_aware_scheduling_tpu.testing.twin import TwinCluster

        twin = TwinCluster(num_nodes=8, pods=8, replicas=1)
        obs = solveobs.enable()
        stack = twin.live()[0]
        obs.mirror = stack.mirror
        stack.cache.on_refresh_pass.append(obs.flush_refresh_pass)
        period = 12

        def load_at(t):
            phase = 2.0 * np.pi * (t % period) / period
            return {
                name: int(200 + 150 * np.sin(phase + i))
                for i, name in enumerate(twin.live_node_names())
            }

        for t in range(period):  # warmup: one full period
            twin.set_base_load(load_at(t))
            twin.tick()
        warm = {w.name: w.compile_count for w in trace.JIT_WATCHES}
        for t in range(period):  # identical second period
            twin.set_base_load(load_at(t))
            twin.tick()
        steady = {w.name: w.compile_count for w in trace.JIT_WATCHES}
        assert steady == warm
        # the same run measures the churn distribution the observatory
        # exists to expose: passes landed and the fraction is sane
        churn = obs.churn_summary()
        assert churn["passes"] > 0
        assert churn["world"] == 8
        assert 0.0 <= churn["fraction_mean"] <= 1.0
        assert churn["last_pass"]["total_rows"] >= 0


class TestCausalSpine:
    def test_churn_joins_explain_chain_by_tick(self):
        obs = solveobs.enable()
        JOURNAL.reset()
        saved_source = JOURNAL.tick_source
        JOURNAL.tick_source = lambda: 7
        try:
            JOURNAL.publish("verdict", "filter passed", pod="ns/p1")
            obs._publish_churn(2, 10, 50, 0.1)
            JOURNAL.tick_source = lambda: 8
            obs._publish_churn(1, 3, 50, 0.06)  # other tick: stays out
            out = JOURNAL.explain(pod="ns/p1")
            context = out["context"]
            assert [r["tick"] for r in context] == [7]
            assert context[0]["kind"] == "churn"
            assert context[0]["data"]["rows"] == 10
            assert any(
                "churn" in line for line in out["context_narrative"]
            )
            # churn events carry no entity keys -> never in the chain
            assert all(r["kind"] != "churn" for r in out["events"])
        finally:
            JOURNAL.tick_source = saved_source
            JOURNAL.reset()

    def test_warm_pass_publishes_solve_event(self):
        ext, _names = build_extender(8, device=True)
        JOURNAL.reset()
        try:
            ext.warm_fastpath()  # disabled: no event
            assert not [
                r for r in JOURNAL.snapshot() if r["kind"] == "solve"
            ]
            solveobs.enable()
            ext.warm_fastpath()
            (event,) = [
                r for r in JOURNAL.snapshot() if r["kind"] == "solve"
            ]
            assert event["event"] == "fastpath warmed"
            assert event["data"]["duration_us"] >= 0
            assert "pairs" in event["data"]
        finally:
            JOURNAL.reset()

    def test_flight_export_is_anonymous_and_versioned(self):
        assert FORMAT == "pas-flight-record/4"
        rec = FlightRecorder()
        rec.record_churn(3, 17, 100, 0.0567)
        (event,) = rec.events()
        assert event["kind"] == "churn"
        assert event["metrics"] == 3
        assert event["rows"] == 17
        assert event["world"] == 100
        assert event["fraction"] == pytest.approx(0.0567, abs=1e-4)
        # counts only — a capture never names a metric or node
        assert "load_metric" not in rec.to_jsonl().decode()


class TestAssembly:
    def test_flags_offered_on_both_mains(self):
        from platform_aware_scheduling_tpu.cmd import gas, tas

        for build in (tas.build_arg_parser, gas.build_arg_parser):
            args = build().parse_args([])
            assert args.solveObs == "off"
            args = build().parse_args(
                ["--solveObs", "on", "--solveObsSize", "64"]
            )
            assert args.solveObs == "on"
            assert args.solveObsSize == 64

    def test_build_wires_mirror_flight_and_refresh_hook(self):
        from platform_aware_scheduling_tpu.cmd import common, tas

        ext, _names = build_extender(8, device=True)
        ext.flight = FlightRecorder()
        parser = tas.build_arg_parser()
        args = parser.parse_args([])
        assert common.build_solve_observatory(args, ext) is None
        assert solveobs.ACTIVE is None
        args = parser.parse_args(["--solveObs", "on", "--solveObsSize", "64"])
        obs = common.build_solve_observatory(
            args, ext, cache=ext.cache
        )
        assert solveobs.ACTIVE is obs
        assert ext.solveobs is obs
        assert obs.capacity == 64
        assert obs.mirror is ext.mirror
        assert obs.flight is ext.flight
        assert obs.flush_refresh_pass in ext.cache.on_refresh_pass
        solveobs.disable()
        assert solveobs.ACTIVE is None
