"""Differential wire fuzzing: the native scanner path and the exact
Python path must produce IDENTICAL responses for every body (VERDICT r4
missing #2 / task #3).

Oracle: one MetricsExtender over one seeded cache+mirror; each fuzz body
is served twice through the REAL verb handlers — once with the native
scanner available, once with ``get_wirec`` patched to None (the exact
path that owns every decode-failure/empty-list wire quirk,
telemetryscheduler.py module doc).  Status and body bytes must match
exactly, for Prioritize and Filter, in both nodeCacheCapable modes —
with one sanctioned difference since PR 30: a Filter that carried
``Nodes`` and is answered natively echoes each passing ``v1.Node`` as
the slice of the request it arrived in, so there the answer's frame
(everything outside the items) must be byte-equal and each item
JSON-equal (``wirehelpers.split_filter_echo``).
A body the scanner rejects (strict parse) must therefore produce the
exact path's answer on BOTH runs — so any scanner-vs-Python divergence
in acceptance, field resolution, case folding, escape handling, or
response assembly shows up as a byte diff.

Corpus: >=10,000 cases from a FIXED seed —
  * structured generator over the wire grammar: upstream + reference key
    spellings and case variants, duplicate/null fields in document order,
    Nodes/NodeNames/both/neither, escaped + non-ASCII + empty + duplicate
    node names, pods with/without the telemetry-policy label, unknown
    policies, extra unknown fields, nested metadata oddities, node
    objects whose strings hold ``}`` ``"NodeNames"`` and ``\\"``, values
    written compact, spaced or indented;
  * byte-level mutations (truncate / flip / insert / delete / splice) of
    the golden request fixtures (tests/golden/*.json) and of generated
    valid bodies — mostly-invalid inputs that must fail IDENTICALLY.

Divergence log (kept per the task's done-criterion):
  * **REAL divergence found by this harness on its first run** (round 5,
    generated case #1756): a ``Nodes.items`` entry with NO
    ``metadata.name`` (``{}``) was DROPPED from the candidate set by the
    native scanner but scored as the empty-named node ``""`` by the
    Python path (``Node({}).name == ""`` — the Go zero value, which is
    what the reference's decode produces).  Fixed in wirec.c
    ``scan_node_item``: a missing name is now a present empty slice; a
    NON-string name stays a no-match on both paths; non-object node
    metadata fails the native parse (Go decode error) so the exact path
    owns it.  Pinned by test_wirec.py
    ``test_missing_name_is_empty_string_candidate``.
  * same sweep hardened ``KubeObject.metadata`` against JSON null
    (Go: null into a struct "has no effect"; the Python property used to
    raise on ``metadata: null`` bodies).
  * a second divergence class was closed while building the harness:
    ``str.lower()`` key folding on the Python path folds non-ASCII
    spellings into ASCII the native byte tables never match — fixed in
    extender/types.py (A-Z-only fold, r4 advisor finding); the generator
    keeps emitting such keys (``_exotic_key``) so a regression reopens
    as a byte diff here.
  * after the fixes: the full >=10k corpus passes with zero divergence.
"""

from __future__ import annotations

import json
import os
import random

import numpy as np
import pytest

from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.native import get_wirec
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas import telemetryscheduler
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity
from wirehelpers import split_filter_echo

pytestmark = pytest.mark.skipif(
    get_wirec() is None, reason="native scanner unavailable (no compiler)"
)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
NUM_NODES = 64
CASES_GENERATED = 6_000
CASES_MUTATED = 4_500
CASES_ECHO = 1_200


def _policy_obj(name):
    return {
        "metadata": {"name": name, "namespace": "default"},
        "spec": {
            "strategies": {
                "scheduleonmetric": {
                    "rules": [
                        {
                            "metricname": "fuzz_metric",
                            "operator": "GreaterThan",
                            "target": 0,
                        }
                    ]
                },
                "dontschedule": {
                    "rules": [
                        {
                            "metricname": "fuzz_metric",
                            "operator": "GreaterThan",
                            "target": 700_000,
                        }
                    ]
                },
            }
        },
    }


# name alphabet stresses every encoder branch: escapes, non-ASCII,
# multibyte UTF-8, JSON-meta characters
NAME_POOL = (
    [f"node-{i:03d}" for i in range(40)]
    + ['no"de-q', "no\\de-b", "node\t-t", "nöde-ü", "节点-一", "n💡de"]
    + ["", " ", "trailing ", "x" * 300]
)


@pytest.fixture(scope="module", params=[True, False], ids=["ncc", "legacy"])
def service(request):
    """(extender, known node names) over a seeded cache+mirror; half the
    NAME_POOL is interned with metric values so requests mix known and
    unknown candidates.  Parametrized over BOTH nodeCacheCapable modes
    (the False mode exercises the NodeNames-ignoring legacy quirks); the
    legacy mode runs a reduced slice of the corpus — the mode only
    changes candidate-carrier selection, not parse/encode shapes."""
    rng = np.random.default_rng(7)
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    cache.write_policy(
        "default", "fuzz-pol", TASPolicy.from_obj(_policy_obj("fuzz-pol"))
    )
    known = NAME_POOL[: len(NAME_POOL) // 2 * 2 : 2] + [
        f"node-{i:03d}" for i in range(40)
    ]
    values = rng.integers(0, 1_000_000, size=len(known))
    cache.write_metric(
        "fuzz_metric",
        {
            n: NodeMetric(value=Quantity(int(v)))
            for n, v in zip(known, values)
        },
    )
    ext = MetricsExtender(
        cache, mirror=mirror, node_cache_capable=request.param
    )
    return ext, known


def _case_counts(ext) -> tuple:
    """(generated, mutated) case counts: the primary ncc mode runs the
    full >=10k corpus; the legacy mode a reduced slice."""
    if ext.node_cache_capable:
        return CASES_GENERATED, CASES_MUTATED
    return 2_000, 1_500


def _request(body: bytes, path: str) -> HTTPRequest:
    return HTTPRequest(
        method="POST",
        path=path,
        headers={"Content-Type": "application/json"},
        body=body,
    )


def _serve_both(ext, body: bytes, verb: str, monkeypatch):
    """(native response, exact-path response) through the real verb."""
    handler = getattr(ext, verb)
    path = f"/scheduler/{verb}"
    native = handler(_request(body, path))
    with monkeypatch.context() as m:
        m.setattr(telemetryscheduler, "get_wirec", lambda: None)
        exact = handler(_request(body, path))
    return native, exact


def _exotic_key(rng: random.Random, base: str) -> str:
    """Key spellings around the ASCII-fold contract: plain case variants
    plus non-ASCII lookalikes (Kelvin sign K, long s ſ) that Go's
    EqualFold would accept but BOTH paths here must drop identically."""
    roll = rng.random()
    if roll < 0.4:
        return "".join(
            c.upper() if rng.random() < 0.5 else c.lower() for c in base
        )
    if roll < 0.5 and "k" in base.lower():
        return base.lower().replace("k", "K", 1)  # KELVIN SIGN
    if roll < 0.6 and "s" in base.lower():
        return base.lower().replace("s", "ſ", 1)  # LONG S
    return base


def _rand_name(rng: random.Random) -> str:
    if rng.random() < 0.7:
        return rng.choice(NAME_POOL)
    return "".join(
        rng.choice('abz-09 "\\\té一\U0001f4a1')
        for _ in range(rng.randrange(0, 12))
    )


def _gen_body(rng: random.Random) -> bytes:
    """One structured body over the wire grammar (module doc)."""
    parts = []
    # Pod
    if rng.random() < 0.9:
        labels = {}
        if rng.random() < 0.8:
            label_key = (
                "telemetry-policy"
                if rng.random() < 0.9
                else rng.choice(["telemetry-Policy", "policy", ""])
            )
            labels[label_key] = rng.choice(
                ["fuzz-pol", "no-such-pol", "", 'p"ol', "pöl"]
            )
        pod = {
            "metadata": {
                "name": rng.choice(["p", "", 'p"od', "p二"]),
                "namespace": rng.choice(["default", "", "other", "déf"]),
                "labels": labels,
            }
        }
        if rng.random() < 0.1:
            pod["spec"] = {"nodeName": "x", "containers": []}
        if rng.random() < 0.1:
            pod["metadata"]["extra"] = [1, {"deep": None}]
        parts.append((_exotic_key(rng, "Pod"), pod))
    # candidate carriers: Nodes / NodeNames / both / neither, null forms
    names = [_rand_name(rng) for _ in range(rng.randrange(0, 14))]
    if rng.random() < 0.15:
        names = names + names  # duplicates
    carrier = rng.random()
    if carrier < 0.45:
        # half the Nodes bodies carry only names the native Filter echo
        # vouches for (none empty, none with a space: the reference's
        # split(" ") quirk sends the rest to the exact path), each on an
        # object that has one — or that path would hardly be reached
        echoable = rng.random() < 0.5
        if echoable:
            names = [n for n in names if n and " " not in n]
        named = [
            lambda n: {"metadata": {"name": n, "labels": {"a": "b"}}},
            lambda n: {
                "metadata": {
                    "name": n,
                    "annotations": {"k}": '}], "NodeNames": ["x\\"]'},
                },
                "status": {"images": [{"names": ["a}", "{b\""]}, 1.5]},
            },
        ]
        nameless = [{}, {"metadata": {}}, {"status": {"phase": "Ready"}}]
        items = [
            {"metadata": {"name": n}}
            if rng.random() < 0.85
            else rng.choice(named)(n)
            if echoable or rng.random() < 0.4
            else rng.choice(nameless)
            for n in names
        ]
        nodes = (
            None
            if rng.random() < 0.1
            else {"items": items if rng.random() < 0.9 else None}
        )
        parts.append((_exotic_key(rng, "Nodes"), nodes))
    elif carrier < 0.85:
        value = None if rng.random() < 0.1 else names
        parts.append((_exotic_key(rng, "NodeNames"), value))
    elif carrier < 0.95:
        parts.append((_exotic_key(rng, "Nodes"), {"items": []}))
        parts.append((_exotic_key(rng, "NodeNames"), names))
    # (else: neither carrier)
    if rng.random() < 0.15:  # duplicate field, later wins in Go order
        key, value = rng.choice(parts) if parts else ("Pod", {})
        parts.append((_exotic_key(rng, key), value))
    if rng.random() < 0.1:
        parts.append(("Unknown" + str(rng.randrange(3)), [None, 1, "x"]))
    rng.shuffle(parts)
    # how the values are written: json.dumps' own separators, compact as
    # Go marshals, or indented (whitespace between every token)
    layout = rng.choice(
        [{}, {}, {"separators": (",", ":")}, {"indent": 1}]
    )
    obj = "{" + ", ".join(
        json.dumps(k, ensure_ascii=rng.random() < 0.5)
        + ": "
        + json.dumps(v, ensure_ascii=rng.random() < 0.5, **layout)
        for k, v in parts
    ) + "}"
    return obj.encode()


def _rand_json(rng: random.Random, depth: int = 0):
    """A random JSON value; strings lean on what a byte-slicing echo
    could trip over (braces, brackets, quotes, backslashes, key names)."""
    roll = rng.random()
    if depth > 3 or roll < 0.35:
        return "".join(
            rng.choice(['}', '{', ']', '"', "\\", ",", ":", " ", "a", "é",
                        '"NodeNames"', '"items"', "\n", "\U0001f4a1"])
            for _ in range(rng.randrange(0, 6))
        )
    if roll < 0.5:
        return rng.choice([None, True, False, 0, -7, 2.5, 1e21, -3e-7])
    if roll < 0.75:
        return [_rand_json(rng, depth + 1) for _ in range(rng.randrange(0, 4))]
    return {
        _rand_json(rng, 4): _rand_json(rng, depth + 1)
        for _ in range(rng.randrange(0, 4))
    }


def _gen_echo_body(rng: random.Random) -> bytes:
    names = [
        n for n in (_rand_name(rng) for _ in range(rng.randrange(1, 14)))
        if n and " " not in n
    ] or ["node-001"]
    if rng.random() < 0.15:
        names = names + names[: rng.randrange(1, 4)]
    if rng.random() < 0.2:  # a name the encoder will not vouch for
        names.insert(rng.randrange(len(names) + 1), rng.choice(["", "a b"]))
    items = []
    for name in names:
        item = {"metadata": {"name": name}}
        if rng.random() < 0.5:
            item["metadata"]["labels"] = {"k": _rand_json(rng, 4)}
        # (an escaped key at an item's top level is the scanner's to
        # refuse, so these stay plain; nested keys are anything)
        for key in ("spec", "status", "x}", "a,b:"):
            if rng.random() < 0.3:
                item[key] = _rand_json(rng)
        items.append(dict(rng.sample(list(item.items()), len(item))))
    pod = {"metadata": {"name": "p", "namespace": "default",
                        "labels": {"telemetry-policy": "fuzz-pol"}}}
    layout = rng.choice([{}, {"separators": (",", ":")}, {"indent": 1}])
    return json.dumps(
        {"Pod": pod, "Nodes": {"items": items}},
        ensure_ascii=rng.random() < 0.5, **layout,
    ).encode()


def _mutate(rng: random.Random, body: bytes) -> bytes:
    data = bytearray(body)
    for _ in range(rng.randrange(1, 4)):
        if not data:
            break
        op = rng.random()
        pos = rng.randrange(len(data))
        if op < 0.3:  # truncate
            del data[pos:]
        elif op < 0.5:  # byte flip
            data[pos] = rng.randrange(256)
        elif op < 0.7:  # insert json-meta byte
            data.insert(pos, ord(rng.choice('{}[]",:\\ ')))
        elif op < 0.85:  # delete a span
            del data[pos : pos + rng.randrange(1, 6)]
        else:  # splice a fragment from elsewhere in the body
            frag = bytes(data[pos : pos + 8])
            at = rng.randrange(len(data) + 1)
            data[at:at] = frag
    return bytes(data)


def _native_filters(wire: str) -> float:
    return trace.COUNTERS.get("pas_filter_native_total", labels={"wire": wire})


def _assert_same(native, exact, body: bytes, verb: str):
    same = native.body == exact.body
    if verb == "filter" and not same:
        # the one sanctioned difference: a Nodes-wire Filter answered
        # natively echoes each passing node as the request's own bytes —
        # the frame byte-equal, each item JSON-equal (wirehelpers)
        same = split_filter_echo(native.body) == split_filter_echo(exact.body)
    assert native.status == exact.status and same, (
        f"{verb} divergence on {body[:200]!r}...: "
        f"native {native.status}/{native.body[:120]!r} vs "
        f"exact {exact.status}/{exact.body[:120]!r}"
    )


class TestDifferentialWireFuzz:
    def test_generated_corpus(self, service, monkeypatch):
        ext, _ = service
        count, _ = _case_counts(ext)
        rng = random.Random(0xC0FFEE)
        for i in range(count):
            body = _gen_body(rng)
            verb = "prioritize" if i % 2 == 0 else "filter"
            native, exact = _serve_both(ext, body, verb, monkeypatch)
            _assert_same(native, exact, body, verb)

    def test_nodes_wire_echo_corpus(self, service, monkeypatch):
        """Filters the policy resolves for, every one carrying ``Nodes``:
        the general corpus mostly probes the failure paths (one body in
        thirty names the policy), so the native echo gets a corpus of
        its own — random ``v1.Node``-shaped objects, written compact,
        spaced or indented, one body in five with a name the encoder
        must hand to the exact path."""
        ext, _ = service
        rng = random.Random(0xEC40)
        echoed = _native_filters("nodes")
        for _ in range(CASES_ECHO):
            body = _gen_echo_body(rng)
            native, exact = _serve_both(ext, body, "filter", monkeypatch)
            _assert_same(native, exact, body, "filter")
        # not held by the exact path answering both times
        assert _native_filters("nodes") - echoed >= CASES_ECHO // 2

    def test_mutated_corpus(self, service, monkeypatch):
        ext, _ = service
        _, count = _case_counts(ext)
        rng = random.Random(0xFEED)
        goldens = [
            open(os.path.join(GOLDEN_DIR, f), "rb").read()
            for f in sorted(os.listdir(GOLDEN_DIR))
            if f.endswith(".json")
        ]
        assert goldens, "golden request fixtures missing"
        seeds = goldens + [_gen_body(rng) for _ in range(40)]
        for i in range(count):
            body = _mutate(rng, rng.choice(seeds))
            verb = "prioritize" if i % 2 == 0 else "filter"
            native, exact = _serve_both(ext, body, verb, monkeypatch)
            _assert_same(native, exact, body, verb)

    def test_corpus_size_documented(self):
        assert CASES_GENERATED + CASES_MUTATED >= 10_000

    def test_exotic_fold_key_dropped_identically(self, service, monkeypatch):
        """The ASCII-fold contract pinned explicitly: a LONG-S spelling
        of NodeNames (``NodeName\u017f``, which Go's EqualFold would
        accept as the field) is NOT this field on either path here, so
        the body has no candidate carrier and both paths answer with the
        empty-200 quirk."""
        ext, known = service
        body = json.dumps(
            {
                "Pod": {
                    "metadata": {
                        "name": "p",
                        "namespace": "default",
                        "labels": {"telemetry-policy": "fuzz-pol"},
                    }
                },
                "NodeName\u017f": [known[0]],
            }
        ).encode()
        native, exact = _serve_both(ext, body, "prioritize", monkeypatch)
        _assert_same(native, exact, body, "prioritize")
        # no recognized candidate carrier -> the empty-200 quirk
        assert native.status == 200 and native.body == b""
