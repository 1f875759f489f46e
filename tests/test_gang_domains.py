"""Gang slices over many ICI domains, and the tracker's pod feed.

  * the one-program-per-orientation solve over a ``[D, M, N]`` free mask
    (ops/topology.py ``_domains_best_anchor``) against its host mirror and
    against an independent loop over every anchor, for every shape of a
    TPU v5e fleet's job mix in both orientations: no anchor lies in a
    padding domain or leaves its own, and one domain reads exactly what
    the single-mesh kernel reads;
  * ``MeshView`` keyed by the ``pas-tpu-domain`` label, with the
    coordinate bound that keeps ``D x M x N`` within one mesh's cells;
  * a served run over six domains of 8 x 8 hosts: every Filter and
    Prioritize answer and every admitted slice equals the slice rule of
    the benchmark's plain reference (``perfbench/gang_world.py``), with
    every binding learned from the cluster's pods — no Bind verb;
  * a device failure counted in ``pas_device_path_errors_total``;
  * a member's Filter answered by the native encoder from the tracker's
    compact verdict, byte for byte the exact path's on the same tracker
    state, with the same side effects and decision counts, and the
    requests it leaves to the exact path.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

from benchmarks.gang_load import POLICY, _gang_pod_obj, _policy_obj, _post
from platform_aware_scheduling_tpu.gang import GangTracker
from platform_aware_scheduling_tpu.ops import topology
from platform_aware_scheduling_tpu.ops.state import TensorStateMirror
from platform_aware_scheduling_tpu.tas.cache import AutoUpdatingCache
from platform_aware_scheduling_tpu.tas.metrics import NodeMetric
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.tas import degraded as degraded_mode
from platform_aware_scheduling_tpu.tas import telemetryscheduler
from platform_aware_scheduling_tpu.tas.telemetryscheduler import MetricsExtender
from platform_aware_scheduling_tpu.testing.builders import (
    make_gang_pod,
    make_mesh_nodes,
    make_node,
)
from platform_aware_scheduling_tpu.testing.fake_kube import FakeKubeClient
from platform_aware_scheduling_tpu.utils import decisions, labels, trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "perfbench")
)
import gang_world  # noqa: E402  (the benchmark's plain slice rule)

#: the v5e fleet's gang shapes in hosts, both orientations of each
SHAPES = [(2, 2), (2, 4), (4, 2), (4, 4), (4, 8), (8, 4), (8, 8), (1, 1), (3, 5)]


def loop_anchor(free: np.ndarray, h: int, w: int):
    """(score, domain, row, col) of the best anchor, one anchor at a time:
    the window's cells all free, fewest free cells in the ring around it
    inside its domain, ties to the lowest (domain, row, col)."""
    d_count, rows, cols = free.shape
    best = None
    for d in range(d_count):
        for i in range(rows - h + 1):
            for j in range(cols - w + 1):
                if not free[d, i: i + h, j: j + w].all():
                    continue
                ring = 0
                for a in range(i - 1, i + h + 1):
                    for b in range(j - 1, j + w + 1):
                        inside = i <= a < i + h and j <= b < j + w
                        if (not inside and 0 <= a < rows and 0 <= b < cols
                                and free[d, a, b]):
                            ring += 1
                if best is None or ring < best[0]:
                    best = (ring, d, i, j)
    return best


def random_masks(seed: int, domains: int, rows: int = 8, cols: int = 8):
    gen = np.random.default_rng(seed)
    padded = topology.padded_domains(domains)
    free = np.zeros((padded, rows, cols), dtype=bool)
    free[:domains] = gen.random((domains, rows, cols)) < gen.uniform(0.55, 0.95)
    return free


class TestDomainKernel:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_device_host_and_loop_agree_on_every_shape(self, shape):
        h, w = shape
        for seed in range(6):
            for domains in (1, 3, 6):
                free = random_masks(seed * 10 + domains, domains)
                device = topology.domains_anchor_device(free, h, w)
                host = topology.domains_anchor_host(free, h, w)
                assert device == host == loop_anchor(free, h, w)
                if device is not None:
                    _score, d, i, j = device
                    assert d < domains  # never a padding domain
                    assert i + h <= free.shape[1] and j + w <= free.shape[2]
                    assert free[d, i: i + h, j: j + w].all()

    @pytest.mark.parametrize("shape", [(2, 2), (2, 4), (4, 8), (8, 8)])
    def test_one_domain_reads_what_the_single_mesh_kernel_reads(self, shape):
        import jax
        import jax.numpy as jnp

        h, w = shape
        scores = jax.jit(
            lambda f: topology._anchor_grid(jnp, f.astype(jnp.int32), h, w)[1]
        )
        for seed in range(8):
            free = random_masks(seed, 1)
            _ok, anchor_score, _node = topology._topology_kernel(
                jnp.asarray(free[0]), h, w
            )
            cropped = np.asarray(anchor_score)[: 8 - h + 1, : 8 - w + 1]
            batched = np.asarray(scores(jnp.asarray(free)))[0]
            assert batched.dtype == cropped.dtype
            assert np.array_equal(batched, cropped)
            single = topology.best_anchor(
                topology.topology_feasibility_device(free[0], h, w)
            )
            found = topology.domains_anchor_device(free, h, w)
            assert (found is None) == (single is None)
            if found is not None:
                assert found == (single[2], 0, single[0], single[1])

    def test_orientations_are_ranked_by_score_then_order(self):
        free = np.zeros((2, 8, 8), dtype=bool)
        free[1, :2, :4] = True  # a 2x4 that strands nothing, in domain 1
        free[0, :4, :6] = True  # a 4x2 window there strands free cells
        found = topology.best_domain_anchor(free, [(4, 2), (2, 4)])
        assert found == (2, 4, 1, 0, 0)
        assert topology.best_domain_anchor(free, [(8, 8)]) is None

    def test_a_device_failure_is_counted_and_served_by_the_mirror(
        self, monkeypatch
    ):
        def broken(*_args, **_kwargs):
            raise RuntimeError("device lost")

        free = random_masks(3, 4)
        want = topology.best_domain_anchor(free, [(2, 4), (4, 2)])
        before = trace.COUNTERS.get(
            "pas_device_path_errors_total", labels={"site": "gang_topology"}
        )
        monkeypatch.setattr(topology, "_domains_best_anchor", broken)
        assert topology.best_domain_anchor(free, [(2, 4), (4, 2)]) == want
        after = trace.COUNTERS.get(
            "pas_device_path_errors_total", labels={"site": "gang_topology"}
        )
        assert after - before == 1


def fleet_nodes(domains: int, rows: int = 8, cols: int = 8, extra=()):
    nodes = []
    for d in range(domains):
        for r in range(rows):
            for c in range(cols):
                nodes.append(make_node(
                    f"host-{(d * rows + r) * cols + c:05d}",
                    labels={
                        labels.TPU_DOMAIN_LABEL: f"pod-{d:03d}",
                        labels.TPU_COORD_LABEL: labels.format_coord(r, c),
                    },
                ))
    return nodes + list(extra)


class TestMeshViewDomains:
    def test_domains_are_indexed_in_label_order(self):
        mesh = topology.MeshView(fleet_nodes(3))
        assert mesh.domains == ["pod-000", "pod-001", "pod-002"]
        assert (mesh.rows, mesh.cols, mesh.padded_domains) == (8, 8, 4)
        assert len(mesh) == 192
        assert mesh.coord_of["host-00130"] == (0, 2)
        assert mesh.domain_of["host-00130"] == 2
        masks = mesh.free_masks({"host-00130", "host-00000", "stranger"})
        assert masks.shape == (4, 8, 8) and masks.sum() == 2
        assert masks[2, 0, 2] and masks[0, 0, 0]
        assert mesh.names_for([(0, 2), (0, 3)], domain=2) == [
            "host-00130", "host-00131"]
        with pytest.raises(ValueError):
            mesh.free_mask({"host-00000"})

    def test_unlabeled_nodes_are_one_domain_as_before(self):
        mesh = topology.MeshView(make_mesh_nodes(3, 4))
        assert mesh.domains == [""] and mesh.padded_domains == 1
        assert mesh.free_mask({"mesh-2-3"}).tolist()[2] == [
            False, False, False, True]
        assert labels.mesh_dim_limit(1) == labels.MAX_MESH_DIM

    def test_one_mislabeled_node_cannot_size_every_domain(self):
        rogue = make_node("rogue", labels={
            labels.TPU_DOMAIN_LABEL: "pod-000",
            labels.TPU_COORD_LABEL: "900,900"})
        mesh = topology.MeshView(fleet_nodes(199, extra=[rogue]))
        assert mesh.padded_domains == 256
        assert labels.mesh_dim_limit(256) == 64
        assert (mesh.rows, mesh.cols) == (8, 8) and "rogue" not in mesh.coord_of
        cells = mesh.padded_domains * mesh.rows * mesh.cols
        assert cells <= labels.MAX_MESH_DIM ** 2

    def test_a_slice_never_spans_two_domains(self):
        # the two halves of a 4x4 lie in two domains, side by side as one
        # global mesh would lay them: no slice is found
        nodes = fleet_nodes(2)
        mesh = topology.MeshView(nodes)
        free = {n.name for n in nodes
                if (mesh.domain_of[n.name] == 0 and mesh.coord_of[n.name][1] >= 6
                    and mesh.coord_of[n.name][0] < 4)
                or (mesh.domain_of[n.name] == 1 and mesh.coord_of[n.name][1] < 2
                    and mesh.coord_of[n.name][0] < 4)}
        assert topology.best_slice(mesh, free, (4, 4)) is None
        names, anchor, domain = topology.best_slice(mesh, free, (2, 4))
        assert anchor[2:] == (4, 2) and domain in ("pod-000", "pod-001")
        assert len({mesh.domain_of[n] for n in names}) == 1


# ---------------------------------------------------------------------------
# a served run: the verbs against the slice rule, bindings from the pods
# ---------------------------------------------------------------------------


def fleet_service(domains: int = 6):
    """(extender, tracker, fake kube, host names) over ``domains`` 8x8 ICI
    domains with clean telemetry; the tracker follows the kube's pods."""
    kube = FakeKubeClient()
    for node in fleet_nodes(domains):
        kube.add_node(node)
    names = sorted(n.name for n in kube.list_nodes())
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    cache.write_policy("default", POLICY, TASPolicy.from_obj(_policy_obj()))
    cache.write_metric("mesh_metric", {
        name: NodeMetric(value=Quantity(len(names) - i))
        for i, name in enumerate(names)
    })
    extender = MetricsExtender(cache, mirror=mirror, node_cache_capable=True)
    tracker = GangTracker(
        nodes_provider=kube.list_nodes, pods_provider=kube.list_pods)
    extender.gangs = tracker
    feed = tracker.watch(kube)
    return extender, tracker, kube, names, feed


def wait_for(condition, limit_s: float = 10.0) -> bool:
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline:
        if condition():
            return True
        time.sleep(0.005)
    return False


class TestServedFleet:
    def test_every_answer_and_slice_equals_the_reference_rule(self):
        extender, tracker, kube, names, feed = fleet_service(6)
        try:
            gen = np.random.default_rng(41)
            free = gen.random(len(names)) < 0.7
            config = {"domains": 6, "domain_rows": 8, "domain_cols": 8}
            shapes = [(2, 2), (2, 4), (4, 4), (4, 8), (2, 2), (8, 8), (2, 4)]
            running = []
            admitted0 = trace.COUNTERS.get("pas_gang_admitted_total")
            binds0 = trace.COUNTERS.get("pas_gang_member_binds_total")
            admitted = learned = 0
            for number, shape in enumerate(shapes * 3):
                group, size = f"job-{number:03d}", shape[0] * shape[1]
                topo = f"{shape[0]}x{shape[1]}"
                found = gang_world.place(
                    gang_world.free_mask(config, free.copy()), shape)
                want = ([names[i] for i in gang_world.slice_hosts(config, found)]
                        if found is not None else [])
                members = [f"{group}-{m:02d}" for m in range(size)]
                for pod in members:
                    kube.add_pod(make_gang_pod(pod, group, size, topo,
                                               policy=POLICY))
                for m, pod in enumerate(members):
                    candidates = [names[i] for i in np.flatnonzero(free)]
                    obj = _gang_pod_obj(pod, group, size, topo)
                    answer = json.loads(_post(extender, "filter", {
                        "Pod": obj, "NodeNames": candidates}).body)
                    passed = answer["NodeNames"] or []
                    assert passed == want[m:], (group, m)
                    if not passed:
                        break
                    ranked = json.loads(_post(extender, "prioritize", {
                        "Pod": obj, "NodeNames": passed}).body)
                    assert [e["Host"] for e in ranked] == want[m:]
                    assert [e["Score"] for e in ranked] == [
                        10 - k for k in range(len(want) - m)]
                    # the binding goes to the API alone: no Bind verb
                    kube.bind_pod("default", pod, f"uid-{pod}", ranked[0]["Host"])
                    free[names.index(ranked[0]["Host"])] = False
                if want:
                    admitted += 1
                    learned += size
                    assert wait_for(lambda: tracker.gang_state(
                        f"default/{group}") == "bound"), group
                    running.append((group, members, want))
                if len(running) > 3:  # a job finishes: its slice comes back
                    done, pods, hosts = running.pop(int(gen.integers(0, 3)))
                    for pod in pods:
                        kube.delete_pod("default", pod)
                    assert wait_for(lambda: tracker.gang_state(
                        f"default/{done}") is None), done
                    for host in hosts:
                        free[names.index(host)] = True
            assert admitted >= 12
            assert trace.COUNTERS.get("pas_gang_admitted_total") - admitted0 == admitted
            assert trace.COUNTERS.get(
                "pas_gang_member_binds_total") - binds0 == learned
            assert trace.COUNTERS.get("pas_gang_domains") == 6.0
        finally:
            feed.stop()

    def test_a_gang_admitted_from_the_pods_is_released_when_they_go(self):
        extender, tracker, kube, names, feed = fleet_service(2)
        try:
            members = [f"solo-{m}" for m in range(4)]
            for pod in members:
                kube.add_pod(make_gang_pod(pod, "solo", 4, "2x2", policy=POLICY))
            for pod in members:
                obj = _gang_pod_obj(pod, "solo", 4, "2x2")
                passed = json.loads(_post(extender, "filter", {
                    "Pod": obj, "NodeNames": names}).body)["NodeNames"]
                kube.bind_pod("default", pod, f"uid-{pod}", passed[0])
                names = [n for n in names if n != passed[0]]
            assert wait_for(lambda: tracker.gang_state("default/solo") == "bound")
            held = set(tracker.reserved_nodes())
            assert len(held) == 4
            # the periodic pod LIST is not needed while the feed runs
            assert tracker._feed is not None
            kube.delete_pod("default", members[0])
            time.sleep(0.05)
            assert tracker.gang_state("default/solo") == "bound"
            for pod in members[1:]:
                kube.delete_pod("default", pod)
            assert wait_for(lambda: tracker.gang_state("default/solo") is None)
            assert tracker.reserved_nodes() == {}
        finally:
            feed.stop()

    def test_a_binding_seen_twice_counts_once(self):
        tracker = GangTracker(nodes_provider=lambda: fleet_nodes(1))
        pod = make_gang_pod("m-0", "dup", 1, "1x1")
        failed, _ = tracker.filter_overlay(pod, [f"host-{i:05d}" for i in range(64)])
        node = next(f"host-{i:05d}" for i in range(64)
                    if f"host-{i:05d}" not in failed)
        before = trace.COUNTERS.get("pas_gang_member_binds_total")
        tracker.observe_bind("default", "m-0", node)  # the pod feed
        tracker.observe_bind("default", "m-0", node)  # a Bind verb
        assert trace.COUNTERS.get("pas_gang_member_binds_total") - before == 1
        assert tracker.gang_state("default/dup") == "bound"

    def test_a_member_that_leaves_is_journaled_at_once(self):
        class Journal:
            def __init__(self):
                self.saved = []

            def save(self, snapshot):
                self.saved.append(snapshot)
                return True

        names = [f"host-{i:05d}" for i in range(64)]
        tracker = GangTracker(nodes_provider=lambda: fleet_nodes(1))
        tracker.journal = journal = Journal()
        members = [f"p-{m}" for m in range(4)]
        for pod in members:
            failed, _ = tracker.filter_overlay(
                make_gang_pod(pod, "part", 4, "2x2"), names)
            node = next(n for n in names if n not in failed)
            tracker.observe_bind("default", pod, node)
            names.remove(node)
        assert tracker.gang_state("default/part") == "bound"
        tracker.observe_gone("default", members[0])
        (entry,) = journal.saved[-1]["gangs"]
        assert sorted(entry["bound"]) == [f"default/{p}" for p in members[1:]]
        assert tracker.gang_state("default/part") == "bound"
        for pod in members[1:]:
            tracker.observe_gone("default", pod)
        assert journal.saved[-1]["gangs"] == []
        assert tracker.reserved_nodes() == {}

    def test_without_a_feed_the_sweep_follows_the_feeds_rule(self):
        pods = []
        clock = [0.0]
        tracker = GangTracker(
            nodes_provider=lambda: fleet_nodes(1),
            pods_provider=lambda: list(pods),
            mesh_max_age_s=5.0,
            clock=lambda: clock[0],
        )
        names = [f"host-{i:05d}" for i in range(64)]
        members = [f"s-{m}" for m in range(4)]
        for pod in members:
            obj = make_gang_pod(pod, "swept", 4, "2x2")
            pods.append(obj)
            failed, _ = tracker.filter_overlay(obj, names)
            node = next(n for n in names if n not in failed)
            tracker.observe_bind("default", pod, node)
            names.remove(node)
        pods.pop(0)  # one member leaves: the gang keeps its slice
        clock[0] = 10.0
        tracker.prune()
        assert tracker.gang_state("default/swept") == "bound"
        assert len(tracker.reserved_nodes()) == 4
        pods.clear()
        clock[0] = 20.0
        tracker.prune()
        assert tracker.gang_state("default/swept") is None


def test_the_assembly_starts_the_pod_feed():
    from platform_aware_scheduling_tpu.cmd.tas import assemble
    from platform_aware_scheduling_tpu.tas.metrics import CustomMetricsClient

    kube = FakeKubeClient()
    for node in fleet_nodes(1):
        kube.add_node(node)
    tracker = GangTracker(nodes_provider=kube.list_nodes)
    _cache, _mirror, extender, _ctl, _enf, stop = assemble(
        kube, CustomMetricsClient(kube), 3600.0, gang_tracker=tracker)
    try:
        assert extender.gangs is tracker and tracker._feed is not None
    finally:
        stop.set()


# ---------------------------------------------------------------------------
# a member's Filter: the native encoder against the exact path
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t


def member_service(clock, domains: int = 2, mesh: bool = True):
    """(extender, host names) over ``domains`` 8x8 ICI domains, the gang
    tracker on ``clock`` and fed no pods (bindings are told it); without
    ``mesh`` the tracker sees no coordinates."""
    kube = FakeKubeClient()
    for node in fleet_nodes(domains):
        kube.add_node(node)
    names = sorted(n.name for n in kube.list_nodes())
    cache = AutoUpdatingCache()
    mirror = TensorStateMirror()
    mirror.attach(cache)
    cache.write_policy("default", POLICY, TASPolicy.from_obj(_policy_obj()))
    extender = MetricsExtender(cache, mirror=mirror, node_cache_capable=True)
    extender.gangs = GangTracker(
        nodes_provider=kube.list_nodes if mesh else list, clock=clock)
    set_hot(extender, names, ())
    return extender, names


def set_hot(extender, names, hot) -> None:
    """Telemetry under which the ``hot`` hosts violate the policy."""
    extender.cache.write_metric("mesh_metric", {
        name: NodeMetric(value=Quantity(
            2 * 10**9 if name in hot else len(names) - i))
        for i, name in enumerate(names)
    })


def member_obj(name, group, size, topo=None):
    obj = _gang_pod_obj(name, group, size, topo or "1x1")
    if topo is None:
        del obj["metadata"]["labels"][labels.GANG_TOPOLOGY_LABEL]
    return obj


def ledger(tracker):
    """What a member's Filter may change in the tracker."""
    with tracker._lock:
        gangs = {
            gid: (g.state, sorted(g.members), g.expires_at,
                  list(g.reserved_nodes), dict(g.bound))
            for gid, g in tracker._gangs.items()
        }
        return gangs, dict(tracker._member_gang), tracker._reservation_version


REASONS = ("rule_violation", "gang_reserved", "gang_infeasible")
COUNTED = ("pas_gang_filter_native_total", "pas_filter_cache_bypass_total",
           "pas_filter_cache_miss_total")


def counters():
    out = {name: trace.COUNTERS.get(name, kind="counter") for name in COUNTED}
    for reason in REASONS:
        out[reason] = trace.COUNTERS.get(
            "pas_decision_filtered_nodes_total", kind="counter",
            labels={"reason": reason})
    return out


def moved(before):
    after = counters()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def filter_both(twins, obj, candidates, monkeypatch):
    """One Filter on each twin: the first as served, the second with the
    native wire module gone (the exact path).  Returns both answers and
    what each moved, after checking the trackers agree."""
    (native, _), (exact, _) = twins
    body = {"Pod": obj, "NodeNames": candidates}
    before = counters()
    got = _post(native, "filter", body)
    got_moved = moved(before)
    got_record = last_record()
    before = counters()
    with monkeypatch.context() as patch:
        patch.setattr(telemetryscheduler, "get_wirec", lambda: None)
        want = _post(exact, "filter", body)
    want_moved = moved(before)
    assert ledger(native.gangs) == ledger(exact.gangs)
    assert native.gangs.reserved_nodes() == exact.gangs.reserved_nodes()
    if decisions.DECISIONS.enabled:
        want_record = last_record()
        for record in (got_record, want_record):
            for key in ("seq", "ts", "request_id", "path"):
                record.pop(key)
        assert got_record == want_record
    return got, want, got_moved, want_moved


def last_record():
    return decisions.DECISIONS.snapshot(verb="filter", limit=1)["records"][0]


def assert_native(got, want, got_moved, want_moved):
    assert got.status == want.status == 200
    assert got.body == want.body
    assert got_moved.pop("pas_gang_filter_native_total") == 1
    assert got_moved.pop("pas_filter_cache_miss_total") == 1
    assert want_moved.pop("pas_filter_cache_bypass_total") == 1
    assert got_moved == want_moved  # the same count under every reason
    return json.loads(got.body)


class TestMemberFilterNative:
    """Every member state, each with violating hosts among the candidates
    and hosts another gang holds; where the member's gang holds a slice,
    a host of it violates too."""

    @staticmethod
    def twins(mesh=True):
        clock = _Clock()
        return (member_service(clock, mesh=mesh),
                member_service(clock, mesh=mesh)), clock

    @staticmethod
    def hot_everywhere(twins, hot):
        for extender, names in twins:
            set_hot(extender, names, hot)

    def held_by_other(self, twins, monkeypatch, topo="2x2"):
        """Gang ``other`` holds four hosts, and three hosts violate."""
        names = twins[0][1]
        self.hot_everywhere(twins, {names[3], names[77], names[100]})
        obj = member_obj("o-0", "other", 4, topo)
        got = assert_native(*filter_both(twins, obj, names, monkeypatch))
        assert len(got["NodeNames"]) == 4
        return set(got["NodeNames"])

    def test_the_reserving_call(self, monkeypatch):
        twins, _clock = self.twins()
        names = twins[0][1]
        other = self.held_by_other(twins, monkeypatch)
        got = assert_native(*filter_both(
            twins, member_obj("j-0", "job", 8, "2x4"), names, monkeypatch))
        assert len(got["NodeNames"]) == 8 and not other & set(got["NodeNames"])
        reasons = set(got["FailedNodes"].values())
        assert "gang: node reserved by gang default/other" in reasons
        assert any("threshold" in r for r in reasons)

    def test_a_reserved_member(self, monkeypatch):
        twins, clock = self.twins()
        names = twins[0][1]
        self.held_by_other(twins, monkeypatch)
        first = assert_native(*filter_both(
            twins, member_obj("j-0", "job", 8, "2x4"), names, monkeypatch))
        slice_hosts = first["NodeNames"]
        self.hot_everywhere(
            twins, {names[3], names[77], names[100], slice_hosts[2]})
        clock.t += 5.0  # the member's Filter refreshes the TTL
        got = assert_native(*filter_both(
            twins, member_obj("j-1", "job", 8, "2x4"), names, monkeypatch))
        assert got["NodeNames"] == [h for h in slice_hosts if h != slice_hosts[2]]
        assert "threshold" in got["FailedNodes"][slice_hosts[2]]

    def test_a_bound_member(self, monkeypatch):
        twins, _clock = self.twins()
        names = twins[0][1]
        self.held_by_other(twins, monkeypatch)
        members = [f"b-{m}" for m in range(4)]
        slice_hosts = None
        for pod in members:
            got = assert_native(*filter_both(
                twins, member_obj(pod, "bound", 4, "2x2"), names, monkeypatch))
            slice_hosts = slice_hosts or got["NodeNames"]
        for pod, host in zip(members, slice_hosts):
            for extender, _names in twins:
                extender.gangs.observe_bind("default", pod, host)
        assert twins[0][0].gangs.gang_state("default/bound") == "bound"
        self.hot_everywhere(
            twins, {names[3], names[77], names[100], slice_hosts[1]})
        got = assert_native(*filter_both(
            twins, member_obj("b-0", "bound", 4, "2x2"), names, monkeypatch))
        assert set(got["NodeNames"]) == set(slice_hosts) - {slice_hosts[1]}

    def test_an_infeasible_gang(self, monkeypatch):
        twins, _clock = self.twins()
        names = twins[0][1]
        self.held_by_other(twins, monkeypatch)
        got = assert_native(*filter_both(
            twins, member_obj("w-0", "wide", 256, "16x16"), names,
            monkeypatch))
        assert got["NodeNames"] == []
        assert set(got["FailedNodes"]) == set(names)
        assert "gang default/wide: no feasible 16x16 slice" in set(
            got["FailedNodes"].values())

    def test_no_mesh(self, monkeypatch):
        twins, _clock = self.twins(mesh=False)
        names = twins[0][1]
        self.held_by_other(twins, monkeypatch, topo=None)  # no mesh needed
        got = assert_native(*filter_both(
            twins, member_obj("n-0", "nomesh", 4, "2x2"), names, monkeypatch))
        assert got["NodeNames"] == []
        values = set(got["FailedNodes"].values())
        assert "gang default/nomesh: no mesh coordinates available" in values
        assert any("threshold" in r for r in values)

    def test_a_size_only_gang(self, monkeypatch):
        twins, _clock = self.twins()
        names = twins[0][1]
        other = self.held_by_other(twins, monkeypatch)
        got = assert_native(*filter_both(
            twins, member_obj("s-0", "sized", 3), names, monkeypatch))
        assert len(got["NodeNames"]) == 3 and not other & set(got["NodeNames"])
        self.hot_everywhere(
            twins, {names[3], names[77], names[100], got["NodeNames"][0]})
        again = assert_native(*filter_both(
            twins, member_obj("s-1", "sized", 3), names, monkeypatch))
        assert again["NodeNames"] == got["NodeNames"][1:]


class TestMemberFilterFallback:
    """What the native member path will not vouch for takes the exact
    path, and ``pas_gang_filter_native_total`` does not move."""

    class _Degraded:
        def __init__(self, action):
            self.action = action

        def filter_decision(self):
            return self.action, "telemetry stale"

    class _Admission:
        def review(self, *_args, **_kwargs):
            return None

    @pytest.mark.parametrize("case", [
        "unknown to the mirror", "an empty name", "a name with a space",
        "degraded fail-open", "degraded fail-closed", "an admission plane",
    ])
    def test_the_exact_path_answers(self, case, monkeypatch):
        twins, _clock = TestMemberFilterNative.twins()
        names = twins[0][1]
        candidates = list(names)
        extra = {"unknown to the mirror": "ghost-host",
                 "an empty name": "", "a name with a space": "host 00001"}
        if case in extra:
            candidates.insert(5, extra[case])
        for extender, _names in twins:
            if case.startswith("degraded"):
                extender.degraded = self._Degraded(
                    degraded_mode.ACTION_FAIL_OPEN if case.endswith("open")
                    else degraded_mode.ACTION_FAIL_CLOSED)
            elif case == "an admission plane":
                extender.admission = self._Admission()
        got, want, got_moved, want_moved = filter_both(
            twins, member_obj("f-0", "fall", 4, "2x2"), candidates,
            monkeypatch)
        assert got.status == want.status == 200
        assert got.body == want.body
        assert "pas_gang_filter_native_total" not in got_moved
        assert "pas_filter_cache_miss_total" not in got_moved
        if not case.startswith("degraded"):
            assert got_moved.get("pas_filter_cache_bypass_total") == 1
        assert got_moved == want_moved
