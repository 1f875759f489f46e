"""Interned node-name universes (ISSUE 11): the wire-path repeat-request
floor.

Three layers are pinned here, each against the byte-comparability
discipline (PR-6/PR-7): (1) the C surface — UniverseCache digest+memcmp
keying, second-sighting interning, MRU eviction, and the universe-backed
encoders (``filter_respond`` / ``select_encode_universe``) producing
bytes identical to the per-request encoders; (2) the verb matrix —
warm (interned/spliced) responses byte-equal to the exact Python path
across native/host policies, threaded/async front-ends, gang on/off and
forecast on/off, including invalidation on node add/remove/rename,
metric-state change, and gang-reservation-version change (no
stale-universe splice, ever); (3) the off path — with the universe
cache disabled the wire is byte-identical to the pre-universe paths.

This file also runs under ``make test-wirec`` (ASan+UBSan over the
instrumented extension) — the refcount/ownership coverage for the cache
the C surface grew."""

import json

import numpy as np
import pytest

from benchmarks.http_load import build_extender, make_bodies
from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.gang.group import HeldSet
from platform_aware_scheduling_tpu.native import get_wirec
from platform_aware_scheduling_tpu.tas import telemetryscheduler
from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
from platform_aware_scheduling_tpu.utils import labels as shared_labels
from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity

from wirehelpers import post_bytes, raw_request, start_async, start_threaded

wirec = get_wirec()
pytestmark = pytest.mark.skipif(
    wirec is None or not hasattr(wirec, "UniverseCache"),
    reason="native universe support unavailable (no C toolchain)",
)


def req(body: bytes, path: str = "/scheduler/filter") -> HTTPRequest:
    return HTTPRequest(
        method="POST",
        path=path,
        headers={"Content-Type": "application/json"},
        body=body,
    )


def nn_body(names, pod="p", label="load-pol", namespace="default") -> bytes:
    metadata = {"name": pod, "namespace": namespace}
    if label is not None:
        metadata["labels"] = {"telemetry-policy": label}
    return json.dumps(
        {"Pod": {"metadata": metadata}, "NodeNames": list(names)}
    ).encode()


def exact_bytes(ext, body: bytes, path: str, monkeypatch):
    """(status, body) from the exact Python path — the native scanner
    patched away exactly like the differential fuzzer does."""
    with monkeypatch.context() as m:
        m.setattr(telemetryscheduler, "get_wirec", lambda: None)
        verb = ext.filter if path.endswith("filter") else ext.prioritize
        resp = verb(req(body, path))
    return resp.status, resp.body


def warm(ext, bodies, path: str = "/scheduler/filter", times: int = 4):
    """Drive the same-span bodies until the universe is interned and the
    skeleton seeded (1st sights, 2nd interns + promotes, 3rd splices)."""
    verb = ext.filter if path.endswith("filter") else ext.prioritize
    last = None
    for i in range(times):
        last = verb(req(bodies[i % len(bodies)], path))
    return last


class TestCSurface:
    def _parsed(self, names):
        return wirec.parse_prioritize(nn_body(names))

    def test_second_sighting_interns(self):
        cache = wirec.UniverseCache(capacity=4)
        parsed = self._parsed(["a", "b"])
        assert cache.lookup(parsed, True) is None
        assert cache.note_seen(parsed, True) is False  # first sighting
        assert cache.note_seen(parsed, True) is True  # second: intern now
        universe, evicted = cache.intern(parsed, True)
        assert evicted == 0
        assert universe.num == 2
        hit = cache.lookup(self._parsed(["a", "b"]), True)
        assert hit is not None and hit.uid == universe.uid
        assert cache.occupancy == 1

    def test_same_length_different_content_misses(self):
        """The stale-splice guard: a span of identical LENGTH but
        different bytes (a renamed node) must never hit."""
        cache = wirec.UniverseCache(capacity=4)
        parsed = self._parsed(["node-1", "node-2"])
        cache.note_seen(parsed, True)
        cache.intern(parsed, True)
        assert cache.lookup(self._parsed(["node-1", "node-3"]), True) is None
        assert cache.lookup(self._parsed(["node-1", "node-2"]), True) is not None

    def test_eviction_bound_and_count(self):
        cache = wirec.UniverseCache(capacity=2)
        kept = []
        for i in range(4):
            parsed = self._parsed([f"n{i}", f"m{i}"])
            universe, evicted = cache.intern(parsed, True)
            kept.append(universe)
            assert evicted == (1 if i >= 2 else 0)
        assert cache.occupancy == 2
        # evicted universes stay valid for holders (refcounted, not freed)
        assert kept[0].names() == ("n0", "m0")
        assert [u["names"] for u in cache.universes()] == [2, 2]

    @pytest.mark.parametrize("case", range(4))
    def test_filter_respond_matches_filter_encode(self, case):
        rng = np.random.default_rng(case)
        names = [f"node-{i}" for i in range(40)]
        if case >= 1:
            names[3] = "weird é中"  # non-ASCII: pre-encoded path
            names[7] = 'esc"aped\\name'
        if case >= 2:
            names[9] = names[4]  # duplicate -> FailedNodes dedup
            names[11] = ""  # empty name
        table = wirec.build_table([n for n in names if n != "ghost"][:32])
        body = nn_body(names)
        parsed = wirec.parse_prioritize(body)
        cache = wirec.UniverseCache(capacity=2)
        universe, _ = cache.intern(parsed, True)
        mask = (rng.random(32) < 0.4).astype(np.uint8).tobytes()
        reasons = [
            json.dumps(f"r{i}").encode() if i % 3 == 0 else None
            for i in range(32)
        ]
        for reason_arg in (None, reasons):
            if reason_arg is None:
                want = wirec.filter_encode(parsed, table, mask)
                got = wirec.filter_respond(universe, table, mask)
            else:
                want = wirec.filter_encode(parsed, table, mask, reason_arg)
                got = wirec.filter_respond(universe, table, mask, reason_arg)
            assert got == want  # (bytes, n_failed) both

    def test_select_encode_universe_matches_select_encode(self):
        names = [f"node-{i}" for i in range(30)]
        names[5] = "uniçode"
        table = wirec.build_table(names[:25])
        body = nn_body(names)
        parsed = wirec.parse_prioritize(body)
        universe, _ = wirec.UniverseCache().intern(parsed, True)
        ranked = np.random.default_rng(0).permutation(25).astype(np.int64)
        for planned in (-1, 7):
            want = wirec.select_encode(parsed, table, ranked, planned, True)
            got = wirec.select_encode_universe(universe, table, ranked, planned)
            assert got == want

    @pytest.mark.parametrize("planned,want", [
        (-1, 0),  # no plan
        (27, 0),  # the table does not know the row
        (24, 0),  # known to the table, not among the candidates sent
        (4, 1),  # the ordinal ranking's first candidate already
        (7, 2),  # moved to rank 1 past row 4
    ])
    def test_asked_the_encoders_say_what_the_planned_row_did(self, planned, want):
        names = [f"node-{i}" for i in range(24)]
        table = wirec.build_table(names + ["node-24"])
        parsed = wirec.parse_prioritize(nn_body(names))
        universe, _ = wirec.UniverseCache().intern(parsed, True)
        ranked = np.array([24, 4, 7] + [r for r in range(24) if r not in (4, 7)],
                          dtype=np.int64)
        plain = wirec.select_encode(parsed, table, ranked, planned, True)
        assert isinstance(plain, bytes)  # not asked: the bytes alone, as ever
        assert wirec.select_encode(
            parsed, table, ranked, planned, True, True) == (plain, want)
        assert wirec.select_encode_universe(
            universe, table, ranked, planned, True) == (plain, want)
        first = json.loads(plain)[0]["Host"]
        assert first == ("node-7" if want == 2 else "node-4")

    def test_rows_rebuild_on_table_change(self):
        """Node interning moved (a node joined): the universe's cached
        row map must rebuild against the new table, not splice stale
        rows."""
        names = ["a", "b", "c"]
        parsed = wirec.parse_prioritize(nn_body(names))
        universe, _ = wirec.UniverseCache().intern(parsed, True)
        t1 = wirec.build_table(["a", "b", "c"])
        t2 = wirec.build_table(["z", "a", "b", "c"])  # rows shifted by 1
        mask1 = bytes([1, 0, 0])
        assert wirec.filter_respond(universe, t1, mask1) == (
            wirec.filter_encode(parsed, t1, mask1)
        )
        mask2 = bytes([0, 1, 0, 0])  # "a" violates in t2's numbering
        assert wirec.filter_respond(universe, t2, mask2) == (
            wirec.filter_encode(parsed, t2, mask2)
        )

    def test_filter_respond_rejects_nodes_universe(self):
        body = json.dumps(
            {
                "Pod": {"metadata": {}},
                "Nodes": {"items": [{"metadata": {"name": "a"}}]},
            }
        ).encode()
        parsed = wirec.parse_prioritize(body)
        universe, _ = wirec.UniverseCache().intern(parsed, False)
        table = wirec.build_table(["a"])
        with pytest.raises(ValueError):
            wirec.filter_respond(universe, table, b"\x00")

    def test_names_tuple_matches_materialized_list(self):
        names = ["plain", "", "uniç中", 'q"uote\\x', "plain"]
        parsed = wirec.parse_prioritize(nn_body(names))
        universe, _ = wirec.UniverseCache().intern(parsed, True)
        assert list(universe.names()) == parsed.node_names_list() == names
        assert universe.names() is universe.names()  # built once, shared


class _StubGangs:
    """The tracker surface the Filter cache path consumes, with a
    controllable reservation version — reason strings come from the
    SAME shared helper the real tracker and fastpath.gang_merged use,
    so the exact-path overlay and the cached merge stay byte-equal."""

    def __init__(self):
        self.version = 1
        self.held = {}

    def cache_token(self):
        hosts = {}
        for node, gang_id in self.held.items():
            hosts.setdefault(gang_id, []).append(node)
        return self.version, HeldSet(self.version, tuple(hosts.items()))

    def filter_overlay(self, pod, clean, span=None):
        failed = {
            node: shared_labels.gang_reserved_reason(gang_id)
            for node, gang_id in self.held.items()
            if node in clean
        }
        return failed, {}

    def prioritize_overlay(self, pod, names, span=None):
        return None


class TestVerbParityMatrix:
    NUM = 48

    def _assert_warm_equals_exact(
        self, ext, bodies, path, monkeypatch, times=5
    ):
        status, want = exact_bytes(ext, bodies[0], path, monkeypatch)
        verb = ext.filter if path.endswith("filter") else ext.prioritize
        for i in range(times):
            resp = verb(req(bodies[i % len(bodies)], path))
            assert resp.status == status
            assert resp.body == want, f"request {i} diverged from exact"
        return want

    @pytest.mark.parametrize("path", [
        "/scheduler/filter", "/scheduler/prioritize",
    ])
    def test_warm_equals_exact_device(self, path, monkeypatch):
        ext, names = build_extender(self.NUM, device=True)
        bodies = make_bodies(names, "nodenames")
        before = trace.COUNTERS.get("pas_wire_intern_hits_total")
        self._assert_warm_equals_exact(ext, bodies, path, monkeypatch)
        assert trace.COUNTERS.get("pas_wire_intern_hits_total") > before

    def test_warm_equals_exact_nodes_mode_prioritize(self, monkeypatch):
        ext, names = build_extender(self.NUM, device=True)
        bodies = make_bodies(names, "nodes")
        self._assert_warm_equals_exact(
            ext, bodies, "/scheduler/prioritize", monkeypatch
        )

    def test_warm_equals_exact_host_only(self, monkeypatch):
        """The exact-host fallback: a host-only metric (sub-milli) keeps
        Filter AND Prioritize on exact host semantics; the interned
        universe only replaces the body decode — bytes must match the
        exact path's for both verbs."""
        ext, names = build_extender(self.NUM, device=True)
        ext.cache.write_metric(
            "load_metric",
            {
                n: NodeMetric(value=Quantity("100500u" if i % 2 else "2"))
                for i, n in enumerate(names)
            },
        )
        assert ext.mirror.metric_host_only("load_metric")
        bodies = make_bodies(names, "nodenames")
        for path in ("/scheduler/filter", "/scheduler/prioritize"):
            self._assert_warm_equals_exact(ext, bodies, path, monkeypatch)

    def test_forecast_ranking_parity(self, monkeypatch):
        ext, names = build_extender(self.NUM, device=True, forecast=True)
        bodies = make_bodies(names, "nodenames")
        self._assert_warm_equals_exact(
            ext, bodies, "/scheduler/prioritize", monkeypatch
        )

    def test_gang_version_invalidates_skeleton(self, monkeypatch):
        """A reservation change between byte-identical requests must MISS
        the skeleton (its key carries the reservation version) and serve
        the new exact verdict — never a stale splice."""
        ext, names = build_extender(self.NUM, device=True)
        ext.gangs = _StubGangs()
        bodies = make_bodies(names, "nodenames")
        path = "/scheduler/filter"
        clean = self._assert_warm_equals_exact(
            ext, bodies, path, monkeypatch
        )
        # a reservation lands: same wire bytes in, NEW verdict out
        ext.gangs.held = {names[0]: "gang-a", names[3]: "gang-a"}
        ext.gangs.version = 2
        reserved = self._assert_warm_equals_exact(
            ext, bodies, path, monkeypatch
        )
        assert reserved != clean
        assert names[0].encode() in reserved
        # released: back to the clean bytes (and still exact-equal)
        ext.gangs.held = {}
        ext.gangs.version = 3
        assert self._assert_warm_equals_exact(
            ext, bodies, path, monkeypatch
        ) == clean

    def test_node_add_remove_rename_reinterns(self, monkeypatch):
        """THE mutation pin: node add/remove/rename between requests
        must miss the universe cache and re-intern — each new candidate
        list's warm responses equal ITS exact bytes."""
        ext, names = build_extender(self.NUM, device=True)
        path = "/scheduler/filter"
        streams = [
            names,                                   # baseline
            names + ["node-extra-00001"],            # node added
            names[:-1],                              # node removed
            [n if i != 2 else "node-renamed" for i, n in enumerate(names)],
        ]
        for stream_names in streams:
            bodies = [
                nn_body(stream_names, pod=f"pod-{i}") for i in range(4)
            ]
            misses = trace.COUNTERS.get("pas_wire_intern_misses_total")
            self._assert_warm_equals_exact(ext, bodies, path, monkeypatch)
            assert (
                trace.COUNTERS.get("pas_wire_intern_misses_total") > misses
            ), "a mutated candidate list must miss the universe cache"

    def test_metric_state_change_respected_on_warm_path(self, monkeypatch):
        """Cluster-state mutation: a metric refresh that flips a node
        into violation must flow through warm (interned) requests — the
        skeleton key is the violation-set identity."""
        ext, names = build_extender(self.NUM, device=True)
        bodies = make_bodies(names, "nodenames")
        path = "/scheduler/filter"
        clean = self._assert_warm_equals_exact(ext, bodies, path, monkeypatch)
        assert b"FailedNodes\": {}" in clean
        ext.cache.write_metric(
            "load_metric",
            {
                n: NodeMetric(value=Quantity(10**10 if i == 0 else 5))
                for i, n in enumerate(names)
            },
        )
        violating = self._assert_warm_equals_exact(
            ext, bodies, path, monkeypatch
        )
        assert violating != clean
        assert names[0].encode() in violating.split(b"FailedNodes")[1]

    def test_state_change_skeletons_prewarmed(self, monkeypatch):
        """A metric refresh mints a new violation-set/ranking identity;
        the warm pass must PRE-RENDER the skeletons for every interned
        universe so the first request of the new sync window is still a
        response-cache HIT (spliced), not a re-render."""
        ext, names = build_extender(self.NUM, device=True)
        bodies = make_bodies(names, "nodenames")
        warm(ext, bodies)
        warm(ext, bodies, path="/scheduler/prioritize")
        # the refresh: same topology, shifted values -> new identities
        ext.cache.write_metric(
            "load_metric",
            {n: NodeMetric(value=Quantity(7 + i)) for i, n in enumerate(names)},
        )
        for path, counter in (
            ("/scheduler/filter", "pas_filter_cache_hit_total"),
            ("/scheduler/prioritize", "pas_fastpath_response_hit_total"),
        ):
            hits = trace.COUNTERS.get(counter)
            status, want = exact_bytes(ext, bodies[0], path, monkeypatch)
            verb = ext.filter if path.endswith("filter") else ext.prioritize
            resp = verb(req(bodies[0], path))
            assert (resp.status, resp.body) == (status, want)
            assert trace.COUNTERS.get(counter) == hits + 1, (
                f"{path}: first post-refresh request must splice a "
                f"pre-warmed skeleton"
            )

    def test_disabled_universe_wire_identical(self, monkeypatch):
        """Acceptance: with the universe cache disabled the wire is
        byte-identical to today — same stream, enabled vs disabled
        extender, every response equal."""
        ext_on, names = build_extender(self.NUM, device=True)
        ext_off, _ = build_extender(self.NUM, device=True)
        ext_off.fastpath.UNIVERSE_CACHE_SIZE = 0  # --off analog
        for path in ("/scheduler/filter", "/scheduler/prioritize"):
            bodies = make_bodies(names, "nodenames")
            verb_on = (
                ext_on.filter if path.endswith("filter") else ext_on.prioritize
            )
            verb_off = (
                ext_off.filter
                if path.endswith("filter")
                else ext_off.prioritize
            )
            for i in range(5):
                body = bodies[i % len(bodies)]
                a = verb_on(req(body, path))
                b = verb_off(req(body, path))
                assert (a.status, a.body) == (b.status, b.body)
        assert ext_off.fastpath._universes in (None, False)

    def test_universe_cache_size_env_parsing(self, monkeypatch):
        from platform_aware_scheduling_tpu.tas.fastpath import (
            _universe_cache_size,
        )

        monkeypatch.setenv("PAS_TPU_UNIVERSE_CACHE", "16")
        assert _universe_cache_size() == 16
        monkeypatch.setenv("PAS_TPU_UNIVERSE_CACHE", "0")
        assert _universe_cache_size() == 0
        monkeypatch.setenv("PAS_TPU_UNIVERSE_CACHE", "junk")
        assert _universe_cache_size() == 8
        monkeypatch.setenv("PAS_TPU_UNIVERSE_CACHE", "-3")
        assert _universe_cache_size() == 8


class TestFrontEndParity:
    """Warm (spliced) responses over REAL sockets: threaded and async
    front-ends serve byte-identical bodies for the same stream, equal to
    the exact in-process bytes."""

    @pytest.mark.parametrize("path", [
        "/scheduler/filter", "/scheduler/prioritize",
    ])
    def test_threaded_async_byte_equal(self, path, monkeypatch):
        ext_t, names = build_extender(32, device=True)
        ext_a, _ = build_extender(32, device=True)
        status, want = exact_bytes(
            ext_t, make_bodies(names, "nodenames")[0], path, monkeypatch
        )
        threaded = start_threaded(ext_t)
        async_server = start_async(ext_a)
        try:
            bodies = make_bodies(names, "nodenames")
            for i in range(5):
                body = bodies[i % len(bodies)]
                for server in (threaded, async_server):
                    got_status, _h, got = raw_request(
                        server.port, post_bytes(path, body)
                    )
                    assert got_status == status
                    assert got == want
        finally:
            threaded.shutdown()
            async_server.shutdown()


class TestDebugWire:
    def test_404_without_fastpath(self):
        ext, _names = build_extender(8, device=False)
        server = start_threaded(ext)
        try:
            status, _h, body = raw_request(
                server.port,
                (
                    b"GET /debug/wire HTTP/1.1\r\nHost: t\r\n"
                    b"Connection: close\r\n\r\n"
                ),
            )
            assert status == 404
            assert b"error" in body
        finally:
            server.shutdown()

    def test_payload_reflects_interning(self):
        ext, names = build_extender(8, device=True)
        bodies = make_bodies(names, "nodenames")
        warm(ext, bodies)
        payload = ext.fastpath.wire_debug()
        assert payload["enabled"] is True
        assert payload["occupancy"] == 1
        assert payload["capacity"] >= 1
        assert payload["universes"][0]["kind"] == "nodenames"
        assert payload["universes"][0]["names"] == 8
        assert payload["skeletons"]["filter"], "warm filter must splice"
        assert payload["counters"]["hits"] >= 1
        json.dumps(payload)  # wire-serializable as served by /debug/wire

    def test_405_non_get(self):
        ext, _names = build_extender(8, device=True)
        server = start_threaded(ext)
        try:
            status, _h, _b = raw_request(
                server.port, post_bytes("/debug/wire", b"{}")
            )
            assert status == 405
        finally:
            server.shutdown()
