"""_wirec native wire path: byte parity with the pure-Python paths across
request shapes, and scanner strictness (fallback on any surprise)."""

import json

import numpy as np
import pytest

from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.extender.types import Args, FilterResult
from platform_aware_scheduling_tpu.native import get_wirec
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.testing.builders import make_policy, rule
from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity
from wirehelpers import split_filter_echo

wirec = get_wirec()
pytestmark = pytest.mark.skipif(
    wirec is None, reason="no C toolchain for _wirec"
)


def build_extender(values=None, op="GreaterThan"):
    values = values or {"n1": 100, "n2": 50, "n3": 10, "n4": 70}
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    cache.write_policy(
        "default",
        "pol",
        TASPolicy.from_obj(
            make_policy("pol", strategies={"scheduleonmetric": [rule("m", op, 0)]})
        ),
    )
    cache.write_metric(
        "m", {n: NodeMetric(value=Quantity(str(v))) for n, v in values.items()}
    )
    return MetricsExtender(cache, mirror=mirror)


def request_from(body: bytes) -> HTTPRequest:
    return HTTPRequest(
        method="POST",
        path="/scheduler/prioritize",
        headers={"Content-Type": "application/json"},
        body=body,
    )


def args_body(names, labels=None, pod_extra=None, namespace="default") -> bytes:
    pod = {
        "metadata": {"name": "p", "namespace": namespace},
        "spec": {"containers": [{"name": "c", "resources": {}}]},
    }
    if labels is not None:
        pod["metadata"]["labels"] = labels
    if pod_extra:
        pod.update(pod_extra)
    return json.dumps(
        {
            "Pod": pod,
            "Nodes": {"items": [{"metadata": {"name": n}} for n in names]},
        }
    ).encode()


BODIES = [
    args_body(["n1", "n2", "n3", "n4"], labels={"telemetry-policy": "pol"}),
    args_body(["n3", "n1"], labels={"telemetry-policy": "pol"}),
    args_body(["n1", "ghost", "n4"], labels={"telemetry-policy": "pol"}),
    args_body(["n1"], labels=None),  # no labels at all -> 400 + []
    args_body(["n1"], labels={"other": "x"}),  # label absent -> 400 + []
    args_body(["n1"], labels={"telemetry-policy": "nope"}),  # unknown policy
    args_body([], labels={"telemetry-policy": "pol"}),  # empty items
    args_body(["n1", "n1", "n2"], labels={"telemetry-policy": "pol"}),  # dups
    args_body(["n2"], labels={"telemetry-policy": "pol"}, namespace="other"),
    # extra unknown fields everywhere; nested arrays/objects skipped
    args_body(
        ["n1", "n2"],
        labels={"telemetry-policy": "pol", "zz": "y"},
        pod_extra={"status": {"conditions": [{"a": [1, 2.5, -3e2, True, None]}]}},
    ),
    b'{"Pod": null, "Nodes": {"items": [{"metadata": {"name": "n1"}}]}}',
    b'{"Nodes": {"items": [{"metadata": {"name": "n1"}}]}}',
    b'{"Pod": {}, "Nodes": {"items": [{"spec": {}}]}}',  # node without name
    b'{"Pod": {}, "Nodes": null}',
    b'{"Pod": {}, "Nodes": {"items": null}}',
    b'{"Pod": {}}',
    b"",
    b"not json",
    b'[1, 2, 3]',
    b'{"Pod": {"metadata": {"labels": {"telemetry-policy": "pol"}}}, "NodeNames": ["n1"]}',
]


class TestParityWithPython:
    @pytest.mark.parametrize("body_idx", range(len(BODIES)))
    def test_native_equals_python(self, body_idx, monkeypatch):
        body = BODIES[body_idx]
        ext = build_extender()
        native = ext.prioritize(request_from(body))
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = ext.prioritize(request_from(body))
        assert native.status == python.status, body
        assert native.body == python.body, body

    def test_escaped_and_unicode_names(self, monkeypatch):
        names = ['we"ird\\name', "uniécode", "plain", "tab\tname"]
        values = {n: i + 1 for i, n in enumerate(names)}
        ext = build_extender(values=values)
        body = args_body(names, labels={"telemetry-policy": "pol"})
        native = ext.prioritize(request_from(body))
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = ext.prioritize(request_from(body))
        assert native.body == python.body
        assert json.loads(native.body)[0]["Host"] == "tab\tname"

    def test_parity_at_scale_with_random_subsets(self, monkeypatch):
        rng = np.random.default_rng(11)
        names = [f"node-{i:04d}" for i in range(500)]
        values = {n: int(rng.integers(0, 100)) for n in names}  # many ties
        ext = build_extender(values=values)
        for _ in range(5):
            subset = list(rng.choice(names, size=120, replace=False))
            body = args_body(subset, labels={"telemetry-policy": "pol"})
            native = ext.prioritize(request_from(body))
            monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
            python = ext.prioritize(request_from(body))
            monkeypatch.delenv("PAS_TPU_NO_NATIVE")
            assert native.body == python.body

    def test_planned_promotion_parity(self, monkeypatch):
        ext = build_extender()

        class StubPlanner:
            def planned_node(self, pod):
                return "n3"

        ext.planner = StubPlanner()
        body = args_body(["n1", "n2", "n3"], labels={"telemetry-policy": "pol"})
        native = ext.prioritize(request_from(body))
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = ext.prioritize(request_from(body))
        assert json.loads(native.body)[0]["Host"] == "n3"
        assert native.body == python.body


def build_filter_extender(values=None, target=50, node_cache_capable=True):
    """Extender with a dontschedule policy (GreaterThan target violates)
    over a device mirror, in NodeNames mode."""
    values = values or {"n1": 100, "n2": 50, "n3": 10, "n4": 70}
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    cache.write_policy(
        "default",
        "pol",
        TASPolicy.from_obj(
            make_policy(
                "pol",
                strategies={
                    "scheduleonmetric": [rule("m", "GreaterThan", 0)],
                    "dontschedule": [rule("m", "GreaterThan", target)],
                },
            )
        ),
    )
    cache.write_metric(
        "m", {n: NodeMetric(value=Quantity(str(v))) for n, v in values.items()}
    )
    return MetricsExtender(
        cache, mirror=mirror, node_cache_capable=node_cache_capable
    )


def nn_body(names, policy="pol") -> bytes:
    pod = {"metadata": {"name": "p", "namespace": "default"}}
    if policy is not None:
        pod["metadata"]["labels"] = {"telemetry-policy": policy}
    return json.dumps({"Pod": pod, "NodeNames": names}).encode()


class TestFilterNativeParity:
    """filter_encode (native NodeNames Filter path) must produce the exact
    bytes of the Python path for the same request."""

    # (names, native path expected) — the probe needs a non-empty
    # NodeNames list, so the empty case must take the exact path
    CASES = [
        (["n1", "n2", "n3", "n4"], True),       # mixed violating/passing
        (["n3", "n2"], True),                    # none violating
        (["n1", "n4"], True),                    # all violating
        (["n1", "ghost", "n3"], True),           # unknown name passes
        (["n1", "n1", "n4", "n2", "n1"], True),  # duplicate violators collapse
        ([""], True),                            # empty-string name
        ([], False),                             # empty list -> exact path
    ]

    @staticmethod
    def _spy_filter_encode(monkeypatch):
        """Count filter_encode invocations — the parity assertions are
        vacuous if a wiring bug silently degrades every request to the
        exact path (the probe's broad except would eat the error)."""
        calls = []
        real = wirec.filter_encode

        def spy(*args):
            calls.append(args)
            return real(*args)

        monkeypatch.setattr(wirec, "filter_encode", spy)
        return calls

    @pytest.mark.parametrize("case_idx", range(len(CASES)))
    def test_filter_nodenames_parity(self, case_idx, monkeypatch):
        names, native_expected = self.CASES[case_idx]
        body = nn_body(names)
        request = request_from(body)
        calls = self._spy_filter_encode(monkeypatch)
        native = build_filter_extender().filter(request)
        assert len(calls) == (1 if native_expected else 0), names
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = build_filter_extender().filter(request)
        assert native.status == python.status, names
        assert native.body == python.body, names

    def test_filter_escaped_unicode_names(self, monkeypatch):
        names = ['we"ird\\name', "uniécode", "plain", "tab\tname", "\x7f"]
        values = {n: (100 if i % 2 == 0 else 1) for i, n in enumerate(names)}
        body = nn_body(names + ["uniécode", 'we"ird\\name'])
        request = request_from(body)
        calls = self._spy_filter_encode(monkeypatch)
        native = build_filter_extender(values=values).filter(request)
        assert len(calls) == 1
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = build_filter_extender(values=values).filter(request)
        assert native.body == python.body
        assert b"FailedNodes" in native.body

    def test_filter_parity_at_scale(self, monkeypatch):
        rng = np.random.default_rng(7)
        names = [f"node-{i:04d}" for i in range(400)]
        values = {n: int(rng.integers(0, 100)) for n in names}
        calls = self._spy_filter_encode(monkeypatch)
        for trial in range(4):
            subset = list(rng.choice(names, size=150, replace=False))
            body = nn_body(subset)
            request = request_from(body)
            native = build_filter_extender(values=values).filter(request)
            assert len(calls) == trial + 1
            monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
            python = build_filter_extender(values=values).filter(request)
            monkeypatch.delenv("PAS_TPU_NO_NATIVE")
            assert native.body == python.body

    def test_filter_miss_then_hit_same_bytes(self, monkeypatch):
        """Two identical requests: first builds natively (miss), second is
        served from the span cache — byte-identical, one native encode."""
        calls = self._spy_filter_encode(monkeypatch)
        ext = build_filter_extender()
        request = request_from(nn_body(["n1", "n2", "n3"]))
        first = ext.filter(request)
        second = ext.filter(request)
        assert len(calls) == 1  # the second request was a span-cache hit
        assert first.body == second.body
        assert first.status == second.status == 200

    def test_filter_encode_mask_shorter_than_table_raises(self):
        parsed = wirec.parse_prioritize(nn_body(["n1"]))
        table = wirec.build_table(["n1", "n2"])
        with pytest.raises(ValueError):
            wirec.filter_encode(parsed, table, b"\x01")


POD_JSON = (
    b'{"metadata": {"name": "p", "namespace": "default", '
    b'"labels": {"telemetry-policy": "pol"}}}'
)


def nodes_wire_body(items, join=b", ", head=b'{"Pod": ', nodes_key=b'"Nodes"'):
    """A Nodes-wire Args body whose items are written exactly as given:
    bytes stay as they are, anything else goes through json.dumps."""
    raw = [
        i if isinstance(i, bytes) else json.dumps(i).encode() for i in items
    ]
    return b"".join(
        [head, POD_JSON, b", ", nodes_key, b': {"items": [', join.join(raw),
         b"]}}"]
    )


def node_item(name, **extra):
    return {"metadata": {"name": name}, **extra}


ODD_NAMES = ['we"ird\\name', "uni\u00e9code", "tab\tname", "\u8282\u70b9", "\x7f"]
ODD_VALUES = {n: (100 if i % 2 == 0 else 1) for i, n in enumerate(ODD_NAMES)}
TRICKY = {
    "status": {
        "images": [{"names": ["a}", "{b", '}], "NodeNames": ["x"]'], "n": -1.5e3}],
        "note": 'quote \\" and brace } and "NodeNames": [',
    },
    "spec": {"taints": [], "deep": {"er": {"est": [None, True, {}]}}},
}


def native_filters(wire="nodes"):
    return trace.COUNTERS.get("pas_filter_native_total", labels={"wire": wire})


class TestFilterNodesWireParity:
    """filter_encode_nodes (the native answer to a Filter that carried
    ``Nodes``) against the exact path: the whole answer equal once parsed,
    the frame byte-equal, every echoed item the request's own bytes."""

    # values n1=100 n2=50 n3=10 n4=70, dontschedule m > 50: n1 and n4 violate
    NATIVE = {
        "some violating": nodes_wire_body(
            [node_item(n) for n in ("n1", "n2", "n3", "n4")]),
        "none violating": nodes_wire_body([node_item("n3"), node_item("n2")]),
        "all violating": nodes_wire_body([node_item("n1"), node_item("n4")]),
        "duplicate names": nodes_wire_body(
            [node_item(n) for n in ("n1", "n1", "n2", "n2", "n4", "n1")]),
        "name absent from the table": nodes_wire_body(
            [node_item("n1"), node_item("ghost"), node_item("n3")]),
        "compact as Go marshals": nodes_wire_body(
            [json.dumps(node_item(n, **TRICKY), separators=(",", ":")).encode()
             for n in ("n1", "n2", "n3")], join=b","),
        "whitespace between tokens": nodes_wire_body(
            [b"\n\t" + json.dumps(node_item(n, **TRICKY), indent=2).encode()
             + b" \r\n" for n in ("n4", "n3", "n2")], join=b" ,\n"),
        "nested objects and tricky strings": nodes_wire_body(
            [node_item(n, **TRICKY) for n in ("n2", "n1", "n3")]),
        "upstream's lowercase keys": nodes_wire_body(
            [node_item("n1"), node_item("n2")], head=b'{"pod": ',
            nodes_key=b'"nodes"'),
        "metadata twice, the last wins": nodes_wire_body(
            [b'{"metadata": {"name": "n1"}, "metadata": {"name": "n2"}}',
             node_item("n4")]),
        "NodeNames beside Nodes": nodes_wire_body(
            [node_item("n1"), node_item("n3")])[:-1] + b', "NodeNames": ["n4"]}',
    }
    # the exact path answers these; the native encoder must not
    FALL_BACK = {
        "name with a space": nodes_wire_body(
            [node_item("n2"), node_item("n 3")]),
        "empty name": nodes_wire_body([node_item("n2"), node_item("")]),
        "no name at all": nodes_wire_body([node_item("n2"), {"spec": {}}]),
        "null metadata": nodes_wire_body([node_item("n2"), {"metadata": None}]),
        "escaped space in a name": nodes_wire_body(
            [node_item("n2"), b'{"metadata": {"name": "n\\u00203"}}']),
        "null item": nodes_wire_body([node_item("n2"), b"null"]),
        "item that is no object": nodes_wire_body([node_item("n2"), b"7"]),
        "escaped key": nodes_wire_body(
            [b'{"met\\u0061data": {"name": "n2"}}']),
        "items empty": nodes_wire_body([]),
    }

    @staticmethod
    def _both(body, monkeypatch, **kwargs):
        request = request_from(body)
        before = native_filters()
        native = build_filter_extender(**kwargs).filter(request)
        moved = native_filters() - before
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = build_filter_extender(**kwargs).filter(request)
        monkeypatch.delenv("PAS_TPU_NO_NATIVE")
        return native, python, moved

    @staticmethod
    def _assert_echo_parity(native, python, body):
        assert native.status == python.status == 200
        assert json.loads(native.body) == json.loads(python.body)
        frame, items = split_filter_echo(native.body)
        assert (frame, items) == split_filter_echo(python.body)
        # what is echoed is the request's own bytes, not a re-encoding
        sent = json.loads(body)
        sent = sent.get("Nodes") or sent.get("nodes")
        assert items == [
            i for i in sent["items"]
            if i["metadata"]["name"] in json.loads(native.body)["NodeNames"]
        ]

    @pytest.mark.parametrize("node_cache_capable", [True, False])
    @pytest.mark.parametrize("case", sorted(NATIVE))
    def test_native_answer_equals_the_exact_path(
        self, case, node_cache_capable, monkeypatch
    ):
        body = self.NATIVE[case]
        native, python, moved = self._both(
            body, monkeypatch, node_cache_capable=node_cache_capable)
        assert moved == 1, case
        self._assert_echo_parity(native, python, body)
        if case == "all violating":
            assert native.body.startswith(
                b'{"Nodes": {"metadata": {}, "items": null}, "NodeNames": [""]')
            assert native.body == python.body

    @pytest.mark.parametrize("ensure_ascii", [True, False])
    def test_escaped_and_non_ascii_names(self, ensure_ascii, monkeypatch):
        names = ODD_NAMES + ["uni\u00e9code", 'we"ird\\name', "plain"]
        body = nodes_wire_body(
            [json.dumps(node_item(n), ensure_ascii=ensure_ascii).encode()
             for n in names])
        native, python, moved = self._both(
            body, monkeypatch, values=ODD_VALUES)
        assert moved == 1
        self._assert_echo_parity(native, python, body)
        assert json.loads(native.body)["FailedNodes"]
        if ensure_ascii:  # json.dumps' own form on the way in: same bytes
            assert native.body == python.body

    @pytest.mark.parametrize("case", sorted(FALL_BACK))
    def test_what_the_encoder_will_not_vouch_for_takes_the_exact_path(
        self, case, monkeypatch
    ):
        native, python, moved = self._both(self.FALL_BACK[case], monkeypatch)
        assert moved == 0, case
        assert native.status == python.status
        assert native.body == python.body

    def test_items_are_slices_of_the_request(self):
        items = [
            json.dumps(node_item(n, **TRICKY), separators=(",", ":")).encode()
            for n in ("n1", "n2", "n3", "n4")
        ]
        response = build_filter_extender().filter(
            request_from(nodes_wire_body(items, join=b",")))
        assert response.body.startswith(
            b'{"Nodes": {"metadata": {}, "items": ['
            + items[1] + b", " + items[2] + b']}, "NodeNames": ["n2", "n3", ""]'
        )

    def test_per_rule_reasons_and_the_default(self):
        """With a reason table the FailedNodes values are its bytes; a
        violating row without one gets the reference's "Node violates"."""
        body = nodes_wire_body([node_item(n) for n in ("n1", "n2", "n4", "n1")])
        parsed = wirec.parse_prioritize(body)
        table = wirec.build_table(["n1", "n2", "n3", "n4"])
        args = Args.from_json(body)
        passing = [n for n in args.nodes if n.name == "n2"]
        for reasons, want in (
            (None, {"n1": "Node violates", "n4": "Node violates"}),
            ([b'"r\\u00e9ason one"', None, None, None],
             {"n1": "r\u00e9ason one", "n4": "Node violates"}),
        ):
            got, n_failed = wirec.filter_encode_nodes(
                parsed, table, b"\x01\x00\x00\x01", reasons)
            assert n_failed == 2
            assert got == FilterResult(
                nodes=passing, node_names=["n2", ""], failed_nodes=want
            ).to_json()

    def test_miss_then_hit_same_bytes(self):
        ext = build_filter_extender()
        request = request_from(self.NATIVE["some violating"])
        before = native_filters()
        first = ext.filter(request)
        second = ext.filter(request)
        assert native_filters() - before == 1  # the second was a cache hit
        assert first.body == second.body and first.status == 200

    def test_the_scan_keeps_what_prioritize_reads(self):
        """The spans ride beside the names: same names, same candidate
        span, and a names-wire body carries none."""
        body = self.NATIVE["whitespace between tokens"]
        parsed = wirec.parse_prioritize(body)
        assert parsed.node_names() == ["n4", "n3", "n2"]
        assert parsed.nodes_span() == body[body.index(b'{"items"'):-1]
        with pytest.raises(ValueError):
            wirec.filter_encode_nodes(
                wirec.parse_prioritize(nn_body(["n1"])),
                wirec.build_table(["n1"]), b"\x00")


class TestEncoderPoolConcurrency:
    """The process-wide buffer pool behind select_encode/filter_encode/
    filter_encode_nodes: many threads hammering the encoders (GIL-free
    sections overlap for real) must produce byte-correct output — a pooled buffer handed to
    two requests at once, or stale mask bytes surviving reuse, would
    corrupt responses."""

    def test_parallel_encoders_byte_correct(self):
        import threading

        rng = np.random.default_rng(3)
        n = 600
        names = [f"node-{i:04d}" for i in range(n)]
        table = wirec.build_table(names)
        ranked = np.argsort(
            rng.permutation(n), kind="stable"
        ).astype(np.int64)
        masks = [
            (rng.random(n) < p).astype(np.uint8).tobytes()
            for p in (0.0, 0.3, 0.9)
        ]
        subsets = []
        for _ in range(6):
            chosen = sorted(rng.choice(n, size=200, replace=False))
            body = json.dumps(
                {
                    "Pod": {"metadata": {"name": "p"}},
                    "NodeNames": [names[i] for i in chosen],
                }
            ).encode()
            subsets.append(body)
        # the same candidates on the Nodes wire, 1 KB an object
        node_bodies = [
            nodes_wire_body(
                [node_item(name, pad="x" * 1000)
                 for name in json.loads(body)["NodeNames"]])
            for body in subsets
        ]
        # per-workload expected bytes computed single-threaded first
        expected = {}
        for bi, body in enumerate(subsets):
            parsed = wirec.parse_prioritize(body)
            expected[("sel", bi)] = wirec.select_encode(
                parsed, table, ranked, -1, True
            )
            for mi, mask in enumerate(masks):
                expected[("fil", bi, mi)] = wirec.filter_encode(
                    parsed, table, mask
                )
                expected[("echo", bi, mi)] = wirec.filter_encode_nodes(
                    wirec.parse_prioritize(node_bodies[bi]), table, mask
                )
        errors = []

        def worker(seed):
            try:
                r = np.random.default_rng(seed)
                for _ in range(120):
                    bi = int(r.integers(len(subsets)))
                    parsed = wirec.parse_prioritize(subsets[bi])
                    if r.random() < 0.25:
                        mi = int(r.integers(len(masks)))
                        got = wirec.filter_encode_nodes(
                            wirec.parse_prioritize(node_bodies[bi]), table,
                            masks[mi],
                        )
                        want = expected[("echo", bi, mi)]
                    elif r.random() < 0.5:
                        got = wirec.select_encode(
                            parsed, table, ranked, -1, True
                        )
                        want = expected[("sel", bi)]
                    else:
                        mi = int(r.integers(len(masks)))
                        got = wirec.filter_encode(parsed, table, masks[mi])
                        want = expected[("fil", bi, mi)]
                    if got != want:
                        errors.append((seed, bi))
                        return
            except Exception as exc:  # a dying thread must fail the test
                errors.append((seed, repr(exc)))

        threads = [
            threading.Thread(target=worker, args=(s,)) for s in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors


class TestScannerStrictness:
    @pytest.mark.parametrize(
        "bad",
        [
            b'{"Pod": {,}}',
            b'{"Pod": {}} trailing',
            b'{"Pod": {"metadata": {"labels": {"telemetry-policy": 5}}}, "Nodes": {"items": []}}',
            b'{"Nodes": {"items": [{}',
            b'{"Nodes": {"items": 7}}',
            b'{"a": 01}',
            b'{"a": truthy}',
            b'{"a": "\x01"}',
        ],
    )
    def test_surprises_raise(self, bad):
        with pytest.raises((ValueError, TypeError)):
            wirec.parse_prioritize(bad)

    def test_whitespace_tolerated(self):
        body = b' \n\t{ "Pod" : { "metadata" : { "name" : "p" } } , "Nodes" : { "items" : [ { "metadata" : { "name" : "n1" } } ] } } \n'
        parsed = wirec.parse_prioritize(body)
        assert parsed.pod_name == "p"
        assert parsed.node_names() == ["n1"]

    def test_last_duplicate_key_wins(self):
        body = (
            b'{"Nodes": {"items": [{"metadata": {"name": "a"}}]},'
            b' "Nodes": {"items": [{"metadata": {"name": "b"}}]}}'
        )
        parsed = wirec.parse_prioritize(body)
        assert parsed.node_names() == ["b"]

    def test_select_encode_empty_selection(self):
        parsed = wirec.parse_prioritize(
            b'{"Nodes": {"items": [{"metadata": {"name": "ghost"}}]}}'
        )
        table = wirec.build_table(["n1", "n2"])
        ranked = np.array([0, 1], dtype=np.int64)
        assert wirec.select_encode(parsed, table, ranked) == b"[]\n"


class TestAdvisorFindings:
    """Round-2 advisor findings: malformed-string fallback, duplicate-key
    last-wins for Pod/metadata/labels, allocator hygiene."""

    @pytest.mark.parametrize(
        "body",
        [
            # invalid JSON escape inside the policy label value
            b'{"Pod": {"metadata": {"namespace": "default", "labels": '
            b'{"telemetry-policy": "\\q"}}}, '
            b'"Nodes": {"items": [{"metadata": {"name": "n1"}}]}}',
            # invalid UTF-8 inside the policy label value
            b'{"Pod": {"metadata": {"namespace": "default", "labels": '
            b'{"telemetry-policy": "\xff\xfe"}}}, '
            b'"Nodes": {"items": [{"metadata": {"name": "n1"}}]}}',
            # invalid UTF-8 inside a node name
            b'{"Pod": {"metadata": {"namespace": "default", "labels": '
            b'{"telemetry-policy": "pol"}}}, '
            b'"Nodes": {"items": [{"metadata": {"name": "n\xff1"}}]}}',
            # invalid escape inside the pod namespace
            b'{"Pod": {"metadata": {"namespace": "\\z", "labels": '
            b'{"telemetry-policy": "pol"}}}, '
            b'"Nodes": {"items": [{"metadata": {"name": "n1"}}]}}',
        ],
    )
    def test_malformed_string_bodies_answer_like_python(self, body, monkeypatch):
        # the verb must produce the same response as the exact Python path
        # (json.loads rejects these bodies -> empty 200), never an unhandled
        # exception / dropped connection
        ext = build_extender()
        native = ext.prioritize(request_from(body))
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = ext.prioritize(request_from(body))
        assert native.status == python.status
        assert native.body == python.body

    def test_duplicate_pod_key_last_wins(self):
        body = (
            b'{"Pod": {"metadata": {"name": "first", "namespace": "ns1", '
            b'"labels": {"telemetry-policy": "pol"}}}, '
            b'"Pod": {"metadata": {"name": "second"}}, '
            b'"Nodes": {"items": []}}'
        )
        parsed = wirec.parse_prioritize(body)
        obj = json.loads(body)  # python dict building is also last-wins
        assert parsed.pod_name == obj["Pod"]["metadata"]["name"] == "second"
        assert parsed.pod_namespace is None
        assert parsed.policy_label is None

    def test_duplicate_metadata_key_last_wins(self):
        body = (
            b'{"Pod": {"metadata": {"name": "first", '
            b'"labels": {"telemetry-policy": "pol"}}, '
            b'"metadata": {"namespace": "ns2"}}, "Nodes": {"items": []}}'
        )
        parsed = wirec.parse_prioritize(body)
        assert parsed.pod_name is None
        assert parsed.pod_namespace == "ns2"
        assert parsed.policy_label is None

    def test_duplicate_labels_key_last_wins(self):
        body = (
            b'{"Pod": {"metadata": {"labels": {"telemetry-policy": "old"}, '
            b'"labels": {"other": "x"}}}, "Nodes": {"items": []}}'
        )
        parsed = wirec.parse_prioritize(body)
        assert parsed.policy_label is None
        body2 = (
            b'{"Pod": {"metadata": {"labels": {"other": "x"}, '
            b'"labels": {"telemetry-policy": "new"}}}, "Nodes": {"items": []}}'
        )
        assert wirec.parse_prioritize(body2).policy_label == "new"

    def test_pod_null_after_object_has_no_effect(self):
        """Go decodes null into a VALUE struct (the reference's Args.Pod
        is v1.Pod by value) as "no effect" — fields captured from the
        earlier occurrence survive; the Python fold (_fold_keys nullable
        handling) and the native scanner agree."""
        body = (
            b'{"Pod": {"metadata": {"name": "first", '
            b'"labels": {"telemetry-policy": "pol"}}}, '
            b'"Pod": null, "Nodes": {"items": []}}'
        )
        parsed = wirec.parse_prioritize(body)
        assert parsed.pod_name == "first"
        assert parsed.policy_label == "pol"
        from platform_aware_scheduling_tpu.extender.types import Args

        args = Args.from_json(body)
        assert args.pod.name == "first"
        assert args.pod.get_labels()["telemetry-policy"] == "pol"

    def test_nodes_null_after_object_assigns_nil(self):
        """Pointer-typed Nodes/NodeNames DO take null (Go assigns nil)."""
        body = (
            b'{"NodeNames": ["n1"], "NodeNames": null, '
            b'"Pod": {"metadata": {"name": "p"}}}'
        )
        parsed = wirec.parse_prioritize(body)
        assert parsed.node_names_present == 0
        from platform_aware_scheduling_tpu.extender.types import Args

        args = Args.from_json(body)
        assert args.node_names is None

    def test_allocator_hygiene_under_debug_malloc(self):
        # NameTable mixes Buf (malloc) and PyMem storage; the dealloc must
        # free each with the matching allocator or PYTHONMALLOC=debug aborts
        import os
        import subprocess
        import sys

        code = (
            "from platform_aware_scheduling_tpu.native import get_wirec\n"
            "w = get_wirec()\n"
            "assert w is not None\n"
            "import numpy as np\n"
            "for _ in range(3):\n"
            "    t = w.build_table(['n%d' % i for i in range(500)])\n"
            "    p = w.parse_prioritize(b'{\"Nodes\": {\"items\": "
            "[{\"metadata\": {\"name\": \"n1\"}}]}}')\n"
            "    w.select_encode(p, t, np.arange(500, dtype=np.int64))\n"
            "    del t, p\n"
            "print('OK')\n"
        )
        env = dict(os.environ, PYTHONMALLOC="debug")
        env.pop("PAS_TPU_NO_NATIVE", None)
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=120,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert proc.returncode == 0, proc.stderr
        assert "OK" in proc.stdout

    def test_items_null_after_array_last_wins(self, monkeypatch):
        # {"items": [...], "items": null} -> json.loads keeps null; the
        # native parse must agree (and the verb must match the exact path)
        body = (
            b'{"Pod": {"metadata": {"namespace": "default", "labels": '
            b'{"telemetry-policy": "pol"}}}, '
            b'"Nodes": {"items": [{"metadata": {"name": "n1"}}], '
            b'"items": null}}'
        )
        parsed = wirec.parse_prioritize(body)
        assert parsed.num_nodes == 0
        ext = build_extender()
        native = ext.prioritize(request_from(body))
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = ext.prioritize(request_from(body))
        assert native.status == python.status
        assert native.body == python.body

    @pytest.mark.parametrize(
        "body",
        [
            # \u-escaped "Pod" alias: last-wins would pick the second, the
            # scanner cannot see that -> must fail and fall back
            b'{"Pod": {"metadata": {"name": "a"}}, '
            b'"\\u0050od": {"metadata": {"name": "b"}}, "Nodes": {"items": []}}',
            # escaped "metadata" inside Pod
            b'{"Pod": {"\\u006detadata": {"name": "x"}}, "Nodes": {"items": []}}',
            # escaped "items" inside Nodes
            b'{"Nodes": {"\\u0069tems": [{"metadata": {"name": "n"}}]}}',
        ],
    )
    def test_escaped_keys_fail_parse(self, body):
        with pytest.raises(ValueError):
            wirec.parse_prioritize(body)

    def test_scalar_key_last_wins_non_string(self, monkeypatch):
        # {"namespace": "default", "namespace": null}: json.loads keeps
        # null; the native parse must clear the earlier slice (and the verb
        # must answer exactly like the Python path, which misses the policy)
        body = (
            b'{"Pod": {"metadata": {"namespace": "default", "namespace": null, '
            b'"labels": {"telemetry-policy": "pol"}}}, '
            b'"Nodes": {"items": [{"metadata": {"name": "n1"}}]}}'
        )
        assert wirec.parse_prioritize(body).pod_namespace is None
        ext = build_extender()
        native = ext.prioritize(request_from(body))
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = ext.prioritize(request_from(body))
        assert native.status == python.status
        assert native.body == python.body

    def test_duplicate_node_metadata_last_wins(self):
        body = (
            b'{"Nodes": {"items": [{"metadata": {"name": "n1"}, '
            b'"metadata": {}}]}}'
        )
        parsed = wirec.parse_prioritize(body)
        # last-wins: the second metadata object has no name, which is the
        # Go zero value "" — exactly what the Python decode yields
        # (Node({}).name == ""); the round-5 differential fuzzer caught
        # the earlier drop-the-candidate behavior diverging
        assert parsed.node_names() == [""]

    def test_missing_name_is_empty_string_candidate(self):
        """A {} node item (or null metadata / null name) participates as
        the empty-named candidate on both paths — the Go zero value
        (fuzzer-found divergence, fixed in scan_node_item)."""
        parsed = wirec.parse_prioritize(
            b'{"Nodes": {"items": [{}, {"metadata": null}, '
            b'{"metadata": {"name": null}}]}}'
        )
        assert parsed.node_names() == ["", "", ""]

    def test_type_mismatches_fail_parse_like_go(self):
        """Go's json.Unmarshal fails the whole decode on type-mismatched
        fields; the scanner rejects identically so the exact path (whose
        from_json raises DecodeError -> the empty-200 quirk) owns the
        response on both runs."""
        import pytest

        for body in (
            b'{"Nodes": {"items": [{"metadata": {"name": 3}}]}}',
            b'{"Nodes": {"items": [{"metadata": 3}]}}',
            b'{"Pod": {"metadata": {"name": 3}}, "NodeNames": ["a"]}',
            b'{"Pod": {"metadata": {"namespace": []}}, "NodeNames": ["a"]}',
            b'{"Pod": {"metadata": {"labels": 3}}, "NodeNames": ["a"]}',
            b'{"Pod": {"metadata": {"labels": {"x": 3}}}, "NodeNames": ["a"]}',
        ):
            with pytest.raises(ValueError):
                wirec.parse_prioritize(body)

    @pytest.mark.parametrize(
        "bad",
        [
            b'{"a": "\\q"}',          # invalid escape
            b'{"a": "\\u12zz"}',      # bad \u hex
            b'{"a": "\xff"}',         # invalid UTF-8 lead byte
            b'{"a": "\xc0\xaf"}',     # overlong encoding
            b'{"a": "\xf5\x80\x80\x80"}',  # > U+10FFFF
            b'{"a": "\xc3"}',         # truncated sequence at end of string
        ],
    )
    def test_strings_validated_like_json_loads(self, bad):
        # every body here is also rejected by json.loads on bytes
        with pytest.raises(ValueError):
            json.loads(bad)
        with pytest.raises(ValueError):
            wirec.parse_prioritize(bad)

    def test_valid_unicode_zero_copy(self):
        # valid non-ASCII stays on the zero-copy path (escaped=0) and
        # round-trips through name lookup byte-exactly
        name = "nodé-ü"
        body = json.dumps(
            {"Nodes": {"items": [{"metadata": {"name": name}}]}},
            ensure_ascii=False,
        ).encode()
        parsed = wirec.parse_prioritize(body)
        assert parsed.node_names() == [name]
        table = wirec.build_table([name])
        out = wirec.select_encode(parsed, table, np.array([0], dtype=np.int64))
        assert json.loads(out) == [{"Host": name, "Score": 10}]

    def test_surrogate_bytes_fall_back_with_parity(self, monkeypatch):
        # json.loads(bytes) decodes with surrogatepass, so a UTF-8-encoded
        # lone surrogate is ACCEPTED by the Python path; the scanner
        # rejects it (-> fallback), which is parity-safe because the exact
        # path then owns the whole answer
        body = (
            b'{"Pod": {"metadata": {"namespace": "default", "labels": '
            b'{"telemetry-policy": "pol"}}}, '
            b'"Nodes": {"items": [{"metadata": {"name": "n1"}}, '
            b'{"metadata": {"name": "s\xed\xa0\x80x"}}]}}'
        )
        json.loads(body)  # accepted by the Python decoder
        with pytest.raises(ValueError):
            wirec.parse_prioritize(body)
        ext = build_extender()
        native = ext.prioritize(request_from(body))
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = ext.prioritize(request_from(body))
        assert native.status == python.status
        assert native.body == python.body


# ---------------------------------------------------------------------------
# what a gang member's native Filter reads: the labels span, the candidate
# rows, and the encoder's default reason
# ---------------------------------------------------------------------------


def labels_body(metadata: bytes, tail: bytes = b"") -> bytes:
    return (b'{"Pod": {"metadata": ' + metadata + b"}" + tail
            + b', "NodeNames": ["n1", "n2"]}')


class TestPodLabelsSpan:
    """``ParsedArgs.pod_labels_span`` against the exact decode's labels:
    JSON-equal (a null value read as Go's ""), on every shape the two
    agree on."""

    CASES = {
        "escapes": labels_body(json.dumps({
            "name": "p", "labels": {
                "telemetry-policy": "pol",
                "note": 'q"uo\\te é ☃ \t',
                "pas-gang-size": "4"}}).encode()),
        "escapes kept as sent": labels_body(json.dumps({
            "name": "p", "labels": {"telemetry-policy": "pol",
                                    "note": "é☃"}},
            ensure_ascii=False).encode()),
        "a repeated labels key, the last wins": labels_body(
            b'{"name": "p", "labels": {"a": "1", "telemetry-policy": "x"}, '
            b'"labels": {"telemetry-policy": "pol", "b": "2"}}'),
        "labels null": labels_body(b'{"name": "p", "labels": null}'),
        "labels null after an object": labels_body(
            b'{"name": "p", "labels": {"a": "1"}, "labels": null}'),
        "no labels": labels_body(b'{"name": "p", "namespace": "ns"}'),
        "empty labels": labels_body(b'{"name": "p", "labels": {}}'),
        "a null label value": labels_body(
            b'{"name": "p", "labels": {"telemetry-policy": "pol", "x": null}}'),
        "metadata twice, the last has none": labels_body(
            b'{"name": "p", "labels": {"a": "1"}}, "metadata": {"name": "q"}'),
        "Pod null after an object": labels_body(
            b'{"name": "p", "labels": {"a": "1"}}', b', "Pod": null'),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_the_span_is_the_exact_decodes_labels(self, case):
        body = self.CASES[case]
        span = wirec.parse_prioritize(body).pod_labels_span
        got = {} if span is None else {
            key: "" if value is None else value
            for key, value in json.loads(span).items()
        }
        assert got == Args.from_json(body).pod.get_labels()
        if case.startswith(("labels null", "no labels", "metadata twice")):
            assert span is None
        else:
            assert span is not None and span in body  # a slice, as sent


class TestMemberFilterPieces:
    NAMES = ["n1", "n2", "n3", "n4"]

    def test_candidate_rows(self):
        table = wirec.build_table(self.NAMES)
        parsed = wirec.parse_prioritize(nn_body(["n3", "ghost", "n1", "n3"]))
        rows = np.frombuffer(wirec.candidate_rows(parsed, table), np.int32)
        assert rows.tolist() == [2, -1, 0, 2]
        empty = wirec.parse_prioritize(nn_body([]))
        assert wirec.candidate_rows(empty, table) == b""

    @pytest.mark.parametrize("name", ["", "n 1", "éscaped"])
    def test_candidate_rows_will_not_vouch(self, name):
        table = wirec.build_table(self.NAMES + [name])
        parsed = wirec.parse_prioritize(nn_body(["n1", name]))
        assert wirec.candidate_rows(parsed, table) is None

    def test_a_default_reason_fills_rows_without_one(self):
        table = wirec.build_table(self.NAMES)
        parsed = wirec.parse_prioritize(nn_body(["n1", "n2", "n4", "n1", "n3"]))
        own = "gang default/g: node outside reserved 2x2 slice"
        body, n_failed = wirec.filter_encode(
            parsed, table, b"\x01\x00\x01\x01", [b'"r1"', None, None, None],
            json.dumps(own).encode())
        assert n_failed == 3
        assert body == FilterResult(
            nodes=None, node_names=["n2"],
            failed_nodes={"n1": "r1", "n4": own, "n3": own},
        ).to_json()
        with pytest.raises(TypeError):
            wirec.filter_encode(parsed, table, b"\x00" * 4, None, own)

    @pytest.mark.parametrize(
        "case_idx",
        [i for i, (_n, native) in enumerate(TestFilterNativeParity.CASES)
         if native])
    def test_the_default_argument_keeps_todays_bytes(
        self, case_idx, monkeypatch
    ):
        """Every names-wire differential case: the encoder's bytes with no
        default, an explicit None and the reference literal are the exact
        path's."""
        names, _native = TestFilterNativeParity.CASES[case_idx]
        real = wirec.filter_encode
        calls = TestFilterNativeParity._spy_filter_encode(monkeypatch)
        build_filter_extender().filter(request_from(nn_body(names)))
        (args,) = calls
        monkeypatch.setenv("PAS_TPU_NO_NATIVE", "1")
        python = build_filter_extender().filter(request_from(nn_body(names)))
        for extra in ((), (None,), (b'"Node violates"',)):
            body, _n_failed = real(*args, *extra)
            assert body == python.body, (names, extra)

    @pytest.mark.parametrize("case", sorted(TestFilterNodesWireParity.NATIVE))
    def test_the_nodes_wire_keeps_todays_bytes(self, case):
        body = TestFilterNodesWireParity.NATIVE[case]
        parsed = wirec.parse_prioritize(body)
        table = wirec.build_table(self.NAMES)
        mask = b"\x01\x00\x00\x01"
        reasons = [b'"r1"', None, None, None]
        today = wirec.filter_encode_nodes(parsed, table, mask, reasons)
        for extra in ((None,), (b'"Node violates"',)):
            assert wirec.filter_encode_nodes(
                parsed, table, mask, reasons, *extra) == today, case
