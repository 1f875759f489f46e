"""TensorStateMirror: cache-hook sync, interning, capacity growth,
policy compilation, host-only fallback marking."""

import numpy as np
import pytest

from platform_aware_scheduling_tpu.ops import i64, solveobs
from platform_aware_scheduling_tpu.ops.rules import (
    OP_GREATER_THAN,
    OP_LESS_THAN,
)
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import (
    MetricColumns,
    NodeMetric,
    wrap_metrics,
)
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.testing.builders import make_policy, rule
from platform_aware_scheduling_tpu.utils import trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity


def info(**kv):
    return {node: NodeMetric(value=Quantity(v)) for node, v in kv.items()}


def attach_pair():
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror(node_capacity=4, metric_capacity=2)
    mirror.attach(cache)
    return cache, mirror


def test_metric_write_lands_in_matrix():
    cache, mirror = attach_pair()
    cache.write_metric("health", info(node1="10", node2="3500m"))
    view = mirror.device_view()
    row = 0
    i1, i2 = view.node_index["node1"], view.node_index["node2"]
    vals = i64.to_int64_np(view.values)
    assert vals[row, i1] == 10_000  # milli-units
    assert vals[row, i2] == 3500
    present = np.asarray(view.present)
    assert present[row, i1] and present[row, i2]
    assert not present[row].sum() > 2


def test_view_memoized_until_mutation():
    cache, mirror = attach_pair()
    cache.write_metric("m", info(a="1"))
    v1 = mirror.device_view()
    assert mirror.device_view() is v1
    cache.write_metric("m", info(a="2"))
    v2 = mirror.device_view()
    assert v2 is not v1
    # old snapshot untouched (copy-on-write)
    assert i64.to_int64_np(v1.values)[0, v1.node_index["a"]] == 1000


def test_node_capacity_growth():
    cache, mirror = attach_pair()
    cache.write_metric("m", info(**{f"n{i}": str(i) for i in range(20)}))
    view = mirror.device_view()
    assert view.node_capacity >= 20
    vals = i64.to_int64_np(view.values)
    for i in range(20):
        assert vals[0, view.node_index[f"n{i}"]] == i * 1000


def test_metric_capacity_growth_and_row_reuse():
    cache, mirror = attach_pair()
    for m in ["m0", "m1", "m2", "m3", "m4"]:
        cache.write_metric(m, info(a="1"))
    # register (refcount) then delete m2 -> its row is freed and reused
    cache.write_metric("m2")
    cache.delete_metric("m2")
    cache.write_metric("m9", info(a="9"))
    view = mirror.device_view()
    vals = i64.to_int64_np(view.values)
    present = np.asarray(view.present)
    col = view.node_index["a"]
    live_rows = present[:, col].sum()
    assert live_rows == 5  # m0,m1,m3,m4,m9
    assert 9000 in vals[:, col]


def test_candidate_mask_and_unknown_nodes():
    cache, mirror = attach_pair()
    cache.write_metric("m", info(a="1", b="2"))
    view = mirror.device_view()
    mask, unknown = view.candidate_mask(["a", "ghost", "b"])
    assert unknown == ["ghost"]
    m = np.asarray(mask)
    assert m[view.node_index["a"]] and m[view.node_index["b"]]
    assert m.sum() == 2


def test_policy_compilation():
    cache, mirror = attach_pair()
    cache.write_metric("cpu", info(a="1"))
    policy = TASPolicy.from_obj(
        make_policy(
            "p1",
            strategies={
                "dontschedule": [rule("cpu", "GreaterThan", 80)],
                "scheduleonmetric": [rule("mem", "LessThan", 0)],
            },
        )
    )
    cache.write_policy("default", "p1", policy)
    compiled = mirror.policy("default", "p1")
    assert compiled is not None
    rs = compiled.device_rules("dontschedule")
    assert rs is not None
    assert int(rs.op_id[0]) == OP_GREATER_THAN
    assert i64.to_int64_np(rs.target)[0] == 80_000
    assert bool(rs.active[0]) and not bool(rs.active[1])
    assert compiled.scheduleonmetric_op == OP_LESS_THAN
    # the scheduleonmetric metric got interned even before any values
    view = mirror.device_view()
    assert compiled.scheduleonmetric_row >= 0


def test_unknown_operator_marks_host_only():
    cache, mirror = attach_pair()
    policy = TASPolicy.from_obj(
        make_policy("p", strategies={"dontschedule": [rule("m", "Weird", 1)]})
    )
    cache.write_policy("default", "p", policy)
    compiled = mirror.policy("default", "p")
    assert compiled.dontschedule.host_only
    assert compiled.device_rules("dontschedule") is None


def test_inexact_quantity_marks_metric_host_only():
    cache, mirror = attach_pair()
    # 1/3000 has no exact milli representation
    cache.write_metric("m", {"a": NodeMetric(value=Quantity("333333n"))})
    assert mirror.metric_host_only("m")
    cache.write_metric("m", info(a="5"))
    assert not mirror.metric_host_only("m")


def test_policy_delete_removes_compiled():
    cache, mirror = attach_pair()
    policy = TASPolicy.from_obj(
        make_policy("p", strategies={"dontschedule": [rule("m", "LessThan", 1)]})
    )
    cache.write_policy("default", "p", policy)
    assert mirror.policy("default", "p") is not None
    cache.delete_policy("default", "p")
    assert mirror.policy("default", "p") is None


class TestDescheduleDevicePath:
    def _setup(self, rules_list):
        from platform_aware_scheduling_tpu.tas.strategies import deschedule

        cache, mirror = attach_pair()
        policy = TASPolicy.from_obj(
            make_policy("desched-pol", strategies={"deschedule": rules_list})
        )
        cache.write_policy("default", "desched-pol", policy)
        strat = deschedule.Strategy.from_policy_strategy(
            policy.strategies["deschedule"]
        )
        strat.set_policy_name("desched-pol")
        return cache, mirror, strat

    def test_device_matches_host(self):
        import numpy as np

        rng = np.random.default_rng(11)
        cache, mirror, strat = self._setup(
            [rule("mem", "GreaterThan", 90), rule("disk", "LessThan", 10)]
        )
        names = [f"n{i}" for i in range(40)]
        cache.write_metric(
            "mem", info(**{n: str(int(rng.integers(0, 120))) for n in names})
        )
        cache.write_metric(
            "disk",
            info(**{n: str(int(rng.integers(0, 30))) for n in names[5:]}),
        )
        host = strat.violated(cache)
        device = strat.violated_device(mirror)
        assert device is not None
        assert set(device) == set(host)

    def test_mismatched_rules_fall_back(self):
        from platform_aware_scheduling_tpu.tas.strategies import deschedule

        cache, mirror, strat = self._setup([rule("mem", "GreaterThan", 90)])
        # a stale strategy instance with different rules must refuse device
        stale = deschedule.Strategy(
            policy_name="desched-pol",
            rules=[TASPolicy.from_obj(
                make_policy("x", strategies={"deschedule": [
                    rule("mem", "GreaterThan", 50)]})
            ).strategies["deschedule"].rules[0]],
        )
        assert stale.violated_device(mirror) is None

    def test_unknown_policy_falls_back(self):
        from platform_aware_scheduling_tpu.tas.strategies import deschedule

        _, mirror = attach_pair()
        strat = deschedule.Strategy(policy_name="ghost")
        assert strat.violated_device(mirror) is None


def test_unchanged_metric_rewrite_keeps_version():
    """Periodic refresh with identical values must not invalidate the
    snapshot (plans/device buffers stay valid in steady state)."""
    cache, mirror = attach_pair()
    cache.write_metric("m", info(a="1", b="2"))
    v1 = mirror.device_view()
    cache.write_metric("m", info(a="1", b="2"))  # same values, new objects
    assert mirror.device_view() is v1
    cache.write_metric("m", info(a="1"))  # b vanished -> real change
    assert mirror.device_view() is not v1


# -- ISSUE 32: a fetched round published as a scatter ---------------------------


def value_list(*pairs):
    return {"items": [{"describedObject": {"name": node}, "value": value}
                      for node, value in pairs]}


def mirror_state(mirror, metric="m"):
    """Everything a metric write may move, by node name."""
    row = mirror._metric_index[metric]
    cols = range(len(mirror._node_names))
    return {
        "values": {mirror._node_names[c]: int(mirror._values[row, c]) for c in cols},
        "present": {mirror._node_names[c]: bool(mirror._present[row, c]) for c in cols},
        "host_only": mirror._host_only_metrics[metric],
        "version": mirror._version,
        "row_version": mirror._row_versions[row],
        "intern_version": mirror._intern_version,
        "shape": mirror._values.shape,
    }


def ingest_counts():
    return {path: trace.COUNTERS.get("pas_refresh_ingest_total", labels={"path": path})
            for path in ("columnar", "items")}


def publish_both(rounds):
    """The same rounds through both input forms, each into a cache and
    mirror of its own; the states after every round."""
    states = {}
    for form in (MetricColumns, wrap_metrics):
        cache, mirror = attach_pair()
        cache.write_metric("m")
        states[form] = []
        for pairs in rounds:
            cache.write_metric("m", form(value_list(*pairs)))
            states[form].append(mirror_state(mirror))
    return states[MetricColumns], states[wrap_metrics]


@pytest.mark.parametrize("value", [
    "0", "97", "-5", "100m", "1Ki", "1.5", "1e3", "333333n",
    "9223372036854775", "9223372036854775807", "-9223372036854775808", 97, 1.5,
])
def test_columnar_round_lands_as_the_items_do(value):
    first = [("a", "1"), ("b", "2")]
    columnar, items = publish_both([first, [("a", value), ("b", "3")], first])
    assert columnar == items
    exact = Quantity(str(value)).milli_value_exact()[1]
    assert [s["host_only"] for s in columnar] == [False, not exact, False]
    assert [s["version"] for s in columnar] == [1, 2, 3]


@pytest.mark.parametrize("second", [
    [("a", "1"), ("b", "2"), ("c", "3")],            # the same round again
    [("a", "1"), ("b", "2"), ("c", "3"), ("d", "4")],  # one added
    [("a", "1"), ("c", "3")],                        # one gone
    [("c", "3"), ("a", "1"), ("b", "2")],            # a reorder
    [("c", "9"), ("e", "5"), ("a", "1")],            # all three at once
    [("a", "1"), ("b", "2"), ("c", "3"), ("d", "4"), ("e", "5"), ("f", "6")],  # grows the matrix
    [("a", "7"), ("b", "2"), ("a", "1"), ("c", "3")],  # a duplicate: the last wins
    [("a", "1"), ("b", "100n"), ("c", "3"), ("b", "2")],  # ... its exactness too
], ids=["same", "added", "gone", "reorder", "mixed", "grown", "dup", "dup-inexact"])
def test_columnar_node_set_changes_as_the_items_do(second):
    first = [("a", "1"), ("b", "2"), ("c", "3")]
    columnar, items = publish_both([first, second, first])
    assert columnar == items
    same = dict(second) == dict(first)
    assert columnar[1]["version"] == (1 if same else 2)
    assert columnar[1]["row_version"] - columnar[0]["row_version"] == (not same)
    gone = set(dict(first)) - set(dict(second))
    assert all(not columnar[1]["present"][node] for node in gone)
    assert not columnar[1]["host_only"]


def test_columnar_unchanged_round_keeps_the_view_and_reuses_the_columns():
    cache, mirror = attach_pair()
    pairs = [("a", "1"), ("b", "2")]
    cache.write_metric("m", MetricColumns(value_list(*pairs)))
    view, cols = mirror.device_view(), mirror._round_cols["m"][1]
    cache.write_metric("m", MetricColumns(value_list(*pairs)))  # new objects
    assert mirror.device_view() is view
    assert mirror._round_cols["m"][1] is cols
    cache.write_metric("m", MetricColumns(value_list(("b", "2"), ("a", "1"))))
    assert mirror.device_view() is view  # a reorder moves no value
    assert mirror._round_cols["m"][1].tolist() == cols.tolist()[::-1]


def test_a_kept_round_may_be_written_again():
    """perfbench's ``--fault stale-round`` hands an old round back."""
    cache, mirror = attach_pair()
    old = MetricColumns(value_list(("a", "1"), ("b", "2")))
    cache.write_metric("m", old)
    was = mirror_state(mirror)
    cache.write_metric("m", MetricColumns(value_list(("a", "5"), ("c", "2"))))
    cache.write_metric("m", old)
    now = mirror_state(mirror)
    assert now["values"] == {**was["values"], "c": 0}
    assert now["present"] == {**was["present"], "c": False}
    assert cache.read_metric("m") is old


def test_deleted_metric_forgets_its_columns():
    cache, mirror = attach_pair()
    cache.write_metric("m")
    cache.write_metric("m", MetricColumns(value_list(("a", "1"))))
    assert "m" in mirror._round_cols
    cache.delete_metric("m")
    assert "m" not in mirror._round_cols


class _Halves:
    """A two-partition map by the node's last letter."""

    def partition_of(self, node_name):
        return ord(node_name[-1]) % 2


@pytest.mark.parametrize("scoped", [False, True])
@pytest.mark.parametrize("form", [MetricColumns, wrap_metrics])
def test_ingest_path_follows_the_input(form, scoped):
    """Columns are scattered; a plain dict, and any round while the mirror
    is partition-scoped, is staged item by item (and kept to the owned
    partitions)."""
    cache, mirror = attach_pair()
    if scoped:
        mirror.set_partition_scope(_Halves(), lambda: {0})
    before = ingest_counts()
    cache.write_metric("m")  # a registration is no round
    cache.write_metric("m", form(value_list(("nb", "1"), ("nc", "2"), ("nd", "3"))))
    after = ingest_counts()
    columnar = form is MetricColumns and not scoped
    assert after["columnar"] - before["columnar"] == columnar
    assert after["items"] - before["items"] == (not columnar)
    state = mirror_state(mirror)
    kept = {"nb": 1000, "nd": 3000} if scoped else {"nb": 1000, "nc": 2000, "nd": 3000}
    assert state["values"] == kept
    assert ("m" in mirror._round_cols) == columnar


def test_columnar_churn_counts_the_columns_that_moved(monkeypatch):
    monkeypatch.setattr(solveobs, "ACTIVE", object())
    cache, mirror = attach_pair()
    cache.write_metric("m", MetricColumns(value_list(("a", "1"), ("b", "2"), ("c", "3"))))
    assert mirror._churn_pending["m"][0] == 3
    cache.write_metric("m", MetricColumns(value_list(("a", "1"), ("b", "5"))))
    assert mirror._churn_pending["m"][0] == 3 + 2  # b moved, c left


# -- ISSUE 36: a one-row publish is applied to the resident state ---------------


def warm_paths():
    return {path: trace.COUNTERS.get("pas_refresh_warm_total", labels={"path": path})
            for path in ("incremental", "full")}


def published_pair(first, second, scoped=False, form=MetricColumns):
    """A mirror with a policy on ``m``, ``first`` published and viewed,
    then ``second``: (mirror, the view before, the view after, the warm
    paths ``second`` took)."""
    cache, mirror = attach_pair()
    if scoped:
        mirror.set_partition_scope(_Halves(), lambda: {0, 1})
    cache.write_policy("default", "pol", TASPolicy.from_obj(make_policy(
        "pol", strategies={"scheduleonmetric": [rule("m", "GreaterThan", 0)],
                           "dontschedule": [rule("m", "GreaterThan", 2)]})))
    cache.write_metric("m", form(value_list(*first)))
    before = mirror.device_view()
    paths = warm_paths()
    cache.write_metric("m", form(value_list(*second)))
    took = {p: n - paths[p] for p, n in warm_paths().items() if n != paths[p]}
    return mirror, before, mirror.device_view(), took


ROUND = [("na", "1"), ("nb", "2"), ("nc", "3")]


@pytest.mark.parametrize("second,scoped,form,path", [
    ([("na", "5"), ("nb", "2"), ("nc", "9")], False, MetricColumns, "incremental"),
    ([("na", "5"), ("nc", "9")], False, MetricColumns, "incremental"),  # a node left the round
    ([("na", "5"), ("nb", "2"), ("nc", "9"), ("nd", "4")], False, MetricColumns, "full"),  # interned
    ([("na", "5"), ("nb", "2"), ("nc", "9")], True, MetricColumns, "full"),  # partition scope
    ([("na", "5"), ("nb", "2"), ("nc", "9")], False, wrap_metrics, "full"),  # a plain dict
], ids=["values", "node-left", "node-interned", "scoped", "dict"])
def test_a_publish_takes_the_path_its_input_calls_for(second, scoped, form, path):
    mirror, before, after, took = published_pair(ROUND, second, scoped, form)
    assert took == {path: 1}
    assert after.version == before.version + 1
    row = after.metric_index["m"]
    expected = {name: int(value) * 1000 for name, value in second}
    for name, col in after.node_index.items():
        assert bool(np.asarray(after.present)[row, col]) == (name in expected)
        if name in expected:
            assert i64.to_int64_np(after.values)[row, col] == expected[name]
            assert after.values_milli[row, col] == expected[name]
    # not donated: whoever holds the view before still reads its round
    was = {name: int(value) * 1000 for name, value in ROUND}
    assert {n: int(i64.to_int64_np(before.values)[row, c]) for n, c in before.node_index.items()
            if np.asarray(before.present)[row, c]} == was
    assert {n: int(before.values_milli[row, before.node_index[n]]) for n in was} == was
    # copy on intern, not on publish
    interned = len(after.node_names) != len(before.node_names)
    assert (after.node_names is before.node_names) == (not interned)
    assert (after.node_index is before.node_index) == (not interned)
    assert after.metric_index is before.metric_index
    assert (after.round is not None) == (path == "incremental")


def test_readers_keep_the_published_view_until_the_round_is_installed():
    """Between the locked write and the swap a reader is given the
    previous view whole; the subscriber is first handed the staged view
    (its answers attached), then called again once it is the published
    one."""
    cache, mirror = attach_pair()
    cache.write_policy("default", "pol", TASPolicy.from_obj(make_policy(
        "pol", strategies={"scheduleonmetric": [rule("m", "GreaterThan", 0)]})))
    cache.write_metric("m", MetricColumns(value_list(*ROUND)))
    before = mirror.device_view()
    seen = []

    def subscriber(staged=None):
        seen.append((staged, mirror.device_view(), mirror.policy_with_view("default", "pol")[1]))

    mirror.on_state_change.append(subscriber)
    cache.write_metric("m", MetricColumns(value_list(("na", "7"), ("nb", "2"), ("nc", "3"))))
    (staged, read, read_with_policy), (after_swap, published, _) = seen
    assert read is before and read_with_policy is before
    assert staged.version == before.version + 1
    assert staged.round.asks is mirror.warm_snapshot().asks
    assert after_swap is None and published is staged
    assert mirror.device_view() is staged


def test_a_failed_round_trip_falls_back_to_the_full_restage(monkeypatch):
    import platform_aware_scheduling_tpu.ops.state as state_mod

    cache, mirror = attach_pair()
    cache.write_metric("m", MetricColumns(value_list(*ROUND)))
    before = mirror.device_view()

    def boom(*args, **kwargs):
        raise RuntimeError("device lost")

    monkeypatch.setattr(state_mod, "round_kernel", boom)
    paths = warm_paths()
    errors = trace.COUNTERS.get("pas_device_path_errors_total", labels={"site": "publish_round"})
    cache.write_metric("m", MetricColumns(value_list(("na", "7"), ("nb", "2"), ("nc", "3"))))
    assert warm_paths()["full"] - paths["full"] == 1
    assert warm_paths()["incremental"] == paths["incremental"]
    assert trace.COUNTERS.get(
        "pas_device_path_errors_total", labels={"site": "publish_round"}) == errors + 1
    # the process's counter: a failure made on purpose is taken back, for
    # the tests on this worker that hold it at 0 (tests/test_fastpath.py)
    trace.COUNTERS.inc(
        "pas_device_path_errors_total", -1, labels={"site": "publish_round"})
    after = mirror.device_view()
    assert after is not before and after.round is None
    assert after.values_milli[after.metric_index["m"], after.node_index["na"]] == 7000


def test_the_hand_off_is_in_the_pass_and_in_none_of_its_parts(monkeypatch):
    """After a one-row publish the publishing thread stands aside; the
    seconds go to their own counter and ``tas/cache.py`` keeps them (and
    the warm) out of the publish."""
    import platform_aware_scheduling_tpu.ops.state as state_mod

    monkeypatch.setattr(state_mod, "PUBLISH_HANDOFF_S", 0.03)
    cache, mirror = attach_pair()
    rounds = iter([MetricColumns(value_list(*ROUND)),
                   MetricColumns(value_list(("na", "7"), ("nb", "2"), ("nc", "3")))])

    class Client:
        def get_node_metric(self, name):
            return next(rounds)

    cache.write_metric("m")
    cache.update_all_metrics(Client())  # nodes interned: the full restage, no hand-off
    mirror.device_view()
    before = {name: trace.COUNTERS.get(name) + cache.counters.get(name) for name in (
        "pas_refresh_handoff_seconds_total", "pas_refresh_publish_seconds_total",
        "pas_refresh_pass_seconds_total")}
    cache.update_all_metrics(Client())
    took = {name: trace.COUNTERS.get(name) + cache.counters.get(name) - was
            for name, was in before.items()}
    assert 0.03 <= took["pas_refresh_handoff_seconds_total"] < 0.2
    assert took["pas_refresh_publish_seconds_total"] < 0.02
    assert took["pas_refresh_pass_seconds_total"] >= took["pas_refresh_handoff_seconds_total"]
