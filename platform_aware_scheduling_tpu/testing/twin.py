"""Cluster-scale digital twin: replayable scenario programs over the
fully assembled scheduling stack, judged by the SLO engine
(docs/observability.md "SLOs & error budgets"; ROADMAP item 5).

The fault plan (testing/faults.py), the chaos scenario
(benchmarks/chaos_load.ChaosScenario), the churn harness
(benchmarks/rebalance_load.ChurnHarness) and the HA fleet (testing/ha.py)
each proved one slice of the system on fakes.  This module generalizes
them into ONE replayable simulator:

  * :class:`TwinCluster` — an :class:`~platform_aware_scheduling_tpu.
    testing.ha.HAHarness` fleet (N fully assembled TAS replicas: cache +
    mirror + extender + enforcer + rebalancer + breakers + elector, one
    shared FakeKubeClient/FakeClock/FaultPlan) grown with: pods SPREAD
    across a configurable node count (up to 100k nodes / 1M pods — every
    structure is dict/ring-bounded, scale is a constructor argument, not
    a code path), a scenario-controlled per-node base-load model on top
    of placement-derived load, synthetic verb traffic driven through the
    REAL Prioritize/Filter handlers each tick (so the latency histograms
    and availability counters the SLOs read are measurements, not
    mocks), a GAS extender lane over the same fake cluster, and an
    :class:`~platform_aware_scheduling_tpu.utils.slo.SLOEngine` ticking
    on the same fake clock;
  * :class:`Scenario` programs — diurnal load, deployment wave,
    node-failure wave, metric storm, the leader-kill composite, and a
    gang deployment wave — each builds its own twin, steps it tick by
    tick, and renders a verdict whose checks are EXACTLY the SLO
    engine's judgment (plus scenario-specific invariants like "zero
    evictions while telemetry was stale");
  * :func:`run_matrix` — the scenario matrix the bench's ``twin``
    section reports (benchmarks/twin_load.py): every future PR's
    BENCH_DETAIL shows the regression surface per scenario.

Everything is deterministic: one fake clock, seeded fault plans, no real
sleeping.  Heavy imports (jax via the mirror) stay lazy so this module
remains importable without jax, like the rest of testing/.
"""

from __future__ import annotations

import json
import math
from typing import Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from platform_aware_scheduling_tpu.extender.server import (
    HTTPRequest,
    HTTPResponse,
)
from platform_aware_scheduling_tpu.testing.builders import make_pod
from platform_aware_scheduling_tpu.testing.faults import int_node_metric
from platform_aware_scheduling_tpu.testing.ha import (
    HAHarness,
    METRIC,
    POD_LOAD,
    POLICY_NAME,
    THRESHOLD,
)
from platform_aware_scheduling_tpu.utils import events, trace
from platform_aware_scheduling_tpu.utils import labels as shared_labels
from platform_aware_scheduling_tpu.utils.slo import (
    ALERT_PAGE,
    SLO,
    SLOEngine,
    _counter_specs,
    default_slos,
)
from platform_aware_scheduling_tpu.utils.tracing import CounterSet

GAS_NODES = 4  # the GAS lane's GPU nodes, constant across scales


class AdmissionQueue:
    """The twin's stand-in for AsyncServer's bounded admission queue
    (serving/dispatcher.py), with the two failure modes a real queue
    has and the legacy per-tick ``serving_capacity`` shed model lacks:

      * **early shed**: past ``max_queue_depth`` (live-read — this is
        the budget controller's admission knob), a request is rejected
        the tick it arrives — the cheap 503 + Retry-After path; the
        client backs off, so a shed never re-enters demand;
      * **queue timeout**: a request that waits more than
        ``timeout_ticks`` without being served expires client-side —
        and with ``retry_storm`` on, each first-time timeout RETRIES
        once next tick, the metastable amplification that makes a deep
        queue under sustained overload strictly worse than shedding
        (both outcomes count into ``pas_serving_rejected_total``, so
        the availability SLO sees them identically — the ledger
        difference is purely how MANY each policy produces).
    """

    def __init__(
        self,
        max_queue_depth: int = 64,
        timeout_ticks: int = 2,
        retry_storm: bool = False,
    ):
        self.max_queue_depth = max(1, int(max_queue_depth))
        self.timeout_ticks = max(1, int(timeout_ticks))
        self.retry_storm = bool(retry_storm)
        #: queued entries: [age_ticks, verb, body, is_retry]
        self.backlog: List[List] = []
        #: timeouts carried into the next tick's demand (retry storm)
        self.retries: List[Tuple[str, bytes]] = []
        self.timeouts = 0
        self.sheds = 0


def _prioritize_body(pod_name: str, names: List[str]) -> bytes:
    return json.dumps(
        {
            "Pod": {
                "metadata": {
                    "name": pod_name,
                    "namespace": "default",
                    "labels": {"telemetry-policy": POLICY_NAME},
                }
            },
            "NodeNames": names,
        }
    ).encode()


def _gas_filter_body(pod_name: str, names: List[str]) -> bytes:
    return json.dumps(
        {
            "Pod": {
                "metadata": {"name": pod_name, "namespace": "default"},
                "spec": {
                    "containers": [
                        {
                            "resources": {
                                "requests": {
                                    "gpu.intel.com/i915": "1",
                                    "gpu.intel.com/millicores": "100",
                                }
                            }
                        }
                    ]
                },
            },
            "NodeNames": names,
        }
    ).encode()


def _request(path: str, body: bytes) -> HTTPRequest:
    return HTTPRequest(
        method="POST",
        path=path,
        headers={"Content-Type": "application/json"},
        body=body,
    )


class TwinCluster(HAHarness):
    """The digital twin: an HA fleet with a scenario-controlled load
    model, synthetic verb traffic, a GAS lane, and the SLO engine —
    everything on the shared fake clock.

    ``num_nodes``/``pods`` set the scale (pods spread round-robin);
    ``base_load`` is the scenario's knob (published ON TOP of the
    placement-derived pod load, so rebalancing remains visible in the
    telemetry the way it is in production); ``fail_nodes`` models a
    node-failure wave (telemetry source dies, pods reschedule onto
    survivors, verb traffic stops naming the dead nodes)."""

    def __init__(
        self,
        num_nodes: int = 16,
        pods: Optional[int] = None,
        replicas: int = 1,
        period_s: float = 5.0,
        requests_per_tick: int = 2,
        latency_threshold_ms: float = 25.0,
        wire_slo_us: float = 500.0,
        hysteresis_cycles: int = 2,
        max_moves: int = 8,
        groups: int = 8,
        gas: bool = True,
        slo: bool = True,
        slo_windows: Optional[Dict[str, float]] = None,
        seed: int = 7,
        gang: bool = False,
        mesh: Optional[Tuple[int, int]] = None,
        lease_duration_s: float = 15.0,
        serving_capacity: Optional[int] = None,
        vectorized: bool = True,
        admission_depth: Optional[int] = None,
        admission_timeout_ticks: int = 2,
        retry_storm: bool = False,
        control: bool = False,
        admission_plane: bool = False,
        preemption: bool = False,
        preemption_max_victims: int = 8,
        admission_starve_consults: int = 16,
        shard_partitions: int = 0,
        eviction_cooldown_s: Optional[float] = None,
    ):
        # production runs the actuator's per-pod eviction cooldown
        # (rebalance/actuator.DEFAULT_COOLDOWN_S) so no workload can be
        # bounced every cycle; the twin arms the same gate scaled to its
        # tick period.  Found by the fuzzer: with the gate off, a
        # globally saturated timeline re-evicts ONE pod every tick — a
        # zero-progress loop the preemption_progress oracle calls
        # (tests/scenarios/eviction_pingpong.json)
        if eviction_cooldown_s is None:
            eviction_cooldown_s = 3.0 * period_s
        super().__init__(
            replicas=replicas,
            num_nodes=num_nodes,
            hot_pods=0,  # the twin spreads its own pods below
            period_s=period_s,
            hysteresis_cycles=hysteresis_cycles,
            max_moves=max_moves,
            lease_duration_s=lease_duration_s,
            rebalance_mode="active",
            seed=seed,
            gang=gang,
            mesh=mesh,
            # the PRIORITY admission plane (admission/plane.py), built
            # per replica by ReplicaStack — distinct from
            # ``admission_depth`` above, which models the SERVING-layer
            # request queue (self.admission, an AdmissionQueue)
            admission_plane=admission_plane,
            preemption=preemption,
            preemption_max_victims=preemption_max_victims,
            admission_starve_consults=admission_starve_consults,
            # the partition plane (shard/): > 0 gives every replica a
            # ShardPlane over the shared journal, with in-process gossip
            shard_partitions=shard_partitions,
            eviction_cooldown_s=eviction_cooldown_s,
            # capacity below the violation threshold (4 x POD_LOAD=400
            # <= THRESHOLD=450): a capacity-legal rebalance plan can
            # never manufacture the next violating node, so scenarios
            # converge instead of thrashing — the sizing relation real
            # clusters are operated under
            node_cap=4,
        )
        self.requests_per_tick = requests_per_tick
        self.base_load: Dict[str, int] = {}
        self.failed_nodes: Set[str] = set()
        self._pod_labels: Dict[str, Dict[str, str]] = {}
        self._seen_evictions = 0
        self._bodies: Optional[List[bytes]] = None
        self.traffic = {"requests": 0, "errors": 0}
        self.storm_evictions: Optional[int] = None
        #: per-tick verb admission budget (None = unlimited): requests
        #: past it are SHED the way AsyncServer sheds past its queue bound —
        #: counted into pas_serving_rejected_total (the twin-local
        #: CounterSet below, wired into the engine's sources), never
        #: reaching a verb handler, so verb_availability degrades under
        #: a what-if load multiplier exactly as production would
        self.serving_capacity = serving_capacity
        self.serving_counters = CounterSet()
        #: opt-in queued-admission model (None = the legacy capacity
        #: shed path above, byte-identical for every existing scenario):
        #: a bounded backlog with queue timeouts and optional retry
        #: amplification, serving ``serving_capacity`` requests per tick
        #: through the real handlers — the surface the budget
        #: controller's admission knob actuates in the head-to-heads
        self.admission: Optional[AdmissionQueue] = None
        if admission_depth is not None:
            self.admission = AdmissionQueue(
                max_queue_depth=admission_depth,
                timeout_ticks=admission_timeout_ticks,
                retry_storm=retry_storm,
            )
        #: vectorized per-tick load model (numpy bincount over interned
        #: node ordinals + memoized NodeMetric publication); the legacy
        #: dict path stays selectable so benchmarks/twin_load.py can
        #: report the before/after ticks-per-second honestly
        self.vectorized = vectorized
        self._node_ordinal: Dict[str, int] = (
            {} if gang else {f"node-{i}": i for i in range(num_nodes)}
        )
        self._base_vector = np.zeros(num_nodes, dtype=np.int64)
        self._live_cache: Optional[List[str]] = None
        if not gang and pods:
            for i in range(pods):
                name = f"pod-{i}"
                labels = {
                    "telemetry-policy": POLICY_NAME,
                    shared_labels.GROUP_LABEL: f"g-{i % groups}",
                }
                self._pod_labels[name] = labels
                self.fake.add_pod(
                    make_pod(
                        name,
                        labels=labels,
                        node_name=f"node-{i % num_nodes}",
                        phase="Running",
                    )
                )
        # -- GAS lane: a small GPU pool on the same fake cluster, its
        # informer-fed cache serving the real gas_filter verb
        self.gas = None
        self._gas_names: List[str] = []
        if gas:
            from platform_aware_scheduling_tpu.gas.cache import Cache
            from platform_aware_scheduling_tpu.gas.scheduler import (
                GASExtender,
            )
            from platform_aware_scheduling_tpu.testing.builders import (
                make_node,
            )

            for i in range(GAS_NODES):
                name = f"gpu-node-{i}"
                self._gas_names.append(name)
                self.fake.add_node(
                    make_node(
                        name,
                        labels={"gpu.intel.com/cards": "card0.card1"},
                        allocatable={
                            "gpu.intel.com/i915": "2",
                            "gpu.intel.com/millicores": "2000",
                            "gpu.intel.com/memory.max": "8000000000",
                        },
                    )
                )
            gas_cache = Cache(self.fake, start=False)
            self.gas = GASExtender(
                self.fake, cache=gas_cache, use_device=False
            )
            gas_cache.start()
            gas_cache.wait_settled()
        # -- the SLO engine, on the same fake clock; attached to every
        # replica's extender so any mounted front-end serves /debug/slo
        self.engine: Optional[SLOEngine] = None
        if slo:
            slos = default_slos(
                tas=True,
                prioritize_p99_ms=latency_threshold_ms,
                filter_p99_ms=latency_threshold_ms,
            )
            if self.gas is not None:
                slos.append(
                    SLO(
                        name="gas_filter_p99",
                        sli="latency",
                        objective=0.99,
                        description="GAS Filter latency through the twin",
                        verbs=("gas_filter",),
                        threshold_s=latency_threshold_ms / 1e3,
                    )
                )
            if wire_slo_us > 0:
                # the wire-path floor gate (ISSUE 11): the PR-10 sub-ms
                # histogram bounds resolve 250/500/750 us, so a Filter
                # verb regressing past the interned-universe floor fails
                # the diurnal scenario (DiurnalLoad gates compliance on
                # these).  Objective 0.9, not 0.99: in-process twin
                # verbs jitter under test-runner load, and at 0.9 the
                # page tier is unreachable (burn 14.4 x 0.1 > 1), so
                # only the diurnal compliance gate — never a paging
                # false alarm in other scenarios — enforces the floor.
                slos.append(
                    SLO(
                        name="filter_wire",
                        sli="latency",
                        objective=0.9,
                        description=(
                            f"Filter wire floor: p90 under "
                            f"{wire_slo_us:g} us"
                        ),
                        verbs=("filter",),
                        threshold_s=wire_slo_us / 1e6,
                    )
                )
                if self.gas is not None:
                    # a MEDIAN gate (objective 0.5), not p90: the GAS
                    # lane's host-loop verb idles at 250-400 us — within
                    # a CPU-contended test runner's jitter of the 500 us
                    # threshold (a full-suite tier-1 run measured p90
                    # grazing it on a healthy build).  Tail noise cannot
                    # move a median; a real wire-path regression shifts
                    # the whole distribution past the threshold and
                    # still fails.  The TAS filter_wire gate above keeps
                    # p90 — the interned floor leaves it 3-5x headroom.
                    slos.append(
                        SLO(
                            name="gas_filter_wire",
                            sli="latency",
                            objective=0.5,
                            description=(
                                f"GAS Filter wire floor: median under "
                                f"{wire_slo_us:g} us"
                            ),
                            verbs=("gas_filter",),
                            threshold_s=wire_slo_us / 1e6,
                        )
                    )
            plane = self.priority_plane()
            if plane is not None:
                # per-class admission availability (docs/admission.md):
                # admitted consults are the good events, starvation
                # events (consults past the plane's threshold) the bad.
                # One SLO per configured class, so the preemption
                # head-to-head can compare the HIGH class's error-budget
                # ledger while watching the victim classes' cost.  An
                # idle class measures compliance 1.0 (no events, no
                # errors), so armed-but-quiet scenarios stay green.
                for klass in plane.classes:
                    slos.append(
                        SLO(
                            name=f"class_availability_{klass}",
                            sli="counter_ratio",
                            objective=0.9,
                            description=(
                                f"admission outcomes for priority class "
                                f"{klass!r}: admitted vs starved consults"
                            ),
                            good=_counter_specs([{
                                "name": "pas_admission_admitted_total",
                                "labels": {"class": klass},
                            }]),
                            bad=_counter_specs([{
                                "name": "pas_admission_starved_total",
                                "labels": {"class": klass},
                            }]),
                        )
                    )
            recorders = [s.extender.recorder for s in self.replicas if s]
            if self.gas is not None:
                recorders.append(self.gas.recorder)
            self.engine = SLOEngine(
                slos,
                recorders=recorders,
                # the plane's CounterSet joins the engine's sources the
                # same single-replica way the controller attaches knobs:
                # the head-to-heads run one replica, and that replica's
                # pas_admission_* families are the class SLOs' events
                counter_sets=[self.serving_counters]
                + ([plane.counters] if plane is not None else []),
                freshness=self._freshness,
                clock=self.clock.now,
                windows=slo_windows,
            )
            for stack in self.replicas:
                if stack is not None:
                    stack.extender.slo = self.engine
            if self.gas is not None:
                self.gas.slo = self.engine
        # -- the budget controller (utils/control.py): subscribed to the
        # engine, actuating the admission queue plus the FIRST replica's
        # rebalancer/degraded knobs (single-replica head-to-heads; a
        # restarted replica's fresh stack is not re-attached).  control
        # defaults off so every pre-existing scenario runs the identical
        # uncontrolled program
        self.controller = None
        if control:
            if self.engine is None:
                raise ValueError("control=True requires slo=True")
            from platform_aware_scheduling_tpu.utils.control import (
                BudgetController,
            )
            from platform_aware_scheduling_tpu.utils.decisions import (
                DecisionLog,
            )

            self.controller = BudgetController(
                self.engine, decision_log=DecisionLog()
            )
            if self.admission is not None:
                # the floor is the per-tick drain rate: a queue shorter
                # than what the server can serve each tick would starve
                # a fully-loaded server — shedding must cap WAITING,
                # never throughput
                self.controller.attach_admission(
                    self.admission,
                    floor=max(2, self.serving_capacity or 2),
                )
            stack = next(s for s in self.replicas if s is not None)
            self.controller.attach_rebalancer(stack.rebalancer)
            self.controller.attach_degraded(stack.degraded)
            if (
                stack.admission is not None
                and stack.admission.preemption is not None
            ):
                # the victim classes pay for planner aggressiveness:
                # sustained burn on the LOWEST class's availability
                # ledger steps the max_victims ceiling down
                self.controller.attach_preemption(
                    stack.admission.preemption,
                    slo=(
                        f"class_availability_"
                        f"{stack.admission.classes[-1]}"
                    ),
                )
            for stack in self.replicas:
                if stack is not None:
                    stack.extender.control = self.controller
        # -- the causal event spine rides the twin tick: journal events
        # carry the engine tick (not just wall time) so /debug/explain
        # narratives read in scheduler time.  The PREVIOUS source is
        # saved and restored in close(): a what-if replay builds a
        # TwinCluster inside a live server's request and must not leave
        # a dead lambda (or a cleared slot) on the process-wide journal.
        self.tick_no = 0
        self._prev_tick_source = events.JOURNAL.tick_source
        self._prev_journal_flight = events.JOURNAL.flight
        events.JOURNAL.tick_source = lambda: self.tick_no

    # -- signal plumbing -------------------------------------------------------

    def priority_plane(self):
        """The first replica's admission plane (admission/plane.py), or
        None — the plane the engine's class SLOs and the controller's
        preemption knob watch.  NOT ``self.admission``: that name is the
        serving-layer :class:`AdmissionQueue` model."""
        for stack in self.replicas:
            if stack is not None and stack.admission is not None:
                return stack.admission
        return None

    def _freshness(self) -> Tuple[bool, str]:
        """The fleet's telemetry-freshness signal: the first LIVE
        replica's cache (the replica a Service would be routing to)."""
        live = self.live()
        if not live:
            return False, "no live replicas"
        return live[0].cache.telemetry_freshness()

    def live_node_names(self) -> List[str]:
        if self.gang:
            return [n for n in self.mesh_nodes if n not in self.failed_nodes]
        # memoized: node names are fixed for the twin's lifetime and the
        # failed set only changes through fail_nodes(), which invalidates
        cached = self._live_cache
        if cached is None:
            cached = [
                f"node-{i}"
                for i in range(self.num_nodes)
                if f"node-{i}" not in self.failed_nodes
            ]
            self._live_cache = cached
        return cached

    def pod_counts(self, live: Optional[List[str]] = None) -> Dict[str, int]:
        """Running pods per live node — the ONE counting rule
        (Succeeded/Failed excluded) shared by telemetry publication,
        eviction rebinding, and failure-wave rescheduling, so the three
        consumers can never drift on what 'load' means."""
        nodes = live if live is not None else self.live_node_names()
        if self.vectorized and self._node_ordinal:
            vec = self._count_vector().tolist()
            ordinal = self._node_ordinal
            return {n: vec[ordinal[n]] for n in nodes if n in ordinal}
        counts: Dict[str, int] = {n: 0 for n in nodes}
        with self.fake._lock:
            for raw in self.fake._pods.values():
                if (raw.get("status") or {}).get("phase") in (
                    "Succeeded",
                    "Failed",
                ):
                    continue
                node = (raw.get("spec") or {}).get("nodeName", "")
                if node in counts:
                    counts[node] += 1
        return counts

    def _count_vector(self) -> "np.ndarray":
        """Running pods per node ordinal as ONE bincount: the pod scan
        appends interned node indices and numpy folds them — replacing
        a dict increment per pod and a per-node dict comprehension on
        the tick's hottest loop (100k nodes x every tick)."""
        idx: List[int] = []
        append = idx.append
        ordinal_get = self._node_ordinal.get
        with self.fake._lock:
            for raw in self.fake._pods.values():
                status = raw.get("status")
                if status is not None and status.get("phase") in (
                    "Succeeded",
                    "Failed",
                ):
                    continue
                spec = raw.get("spec")
                if spec is None:
                    continue
                j = ordinal_get(spec.get("nodeName", ""))
                if j is not None:
                    append(j)
        if not idx:
            return np.zeros(self.num_nodes, dtype=np.int64)
        return np.bincount(
            np.asarray(idx, dtype=np.int64), minlength=self.num_nodes
        )

    def publish_loads(self) -> None:
        """Scenario-aware telemetry publication: placement-derived pod
        load + the scenario's base load, for live nodes only (a failed
        node's telemetry source dies with it).  Gang-mode meshes publish
        a flat zero surface so freshness stays green while reservations
        are the scenario's subject."""
        live = self.live_node_names()
        if self.gang:
            self.metrics.set_all(METRIC, {n: 0 for n in live})
            return
        if not self.vectorized:
            counts = self.pod_counts(live)
            self.metrics.set_all(
                METRIC,
                {
                    n: counts[n] * POD_LOAD + self.base_load.get(n, 0)
                    for n in live
                },
            )
            return
        # vectorized: one bincount + one fused numpy expression for the
        # whole load surface, published as SHARED per-value NodeMetric
        # objects (int_node_metric) instead of a Quantity parse per node
        loads = (
            self._count_vector() * POD_LOAD + self._base_vector
        ).tolist()
        metric_for = int_node_metric
        if not self.failed_nodes:
            # healthy fleet: live is exactly node-0..N-1 in ordinal order,
            # so the payload zips straight off the load vector
            payload = dict(zip(live, map(metric_for, loads)))
        else:
            ordinal = self._node_ordinal
            payload = {n: metric_for(loads[ordinal[n]]) for n in live}
        self.metrics.set_all_metrics(METRIC, payload)

    # -- the tick --------------------------------------------------------------

    def tick(self) -> None:
        """One twin tick: the fleet tick (clock + telemetry + election +
        enforcement + rebalance), then the world's reaction (evicted
        pods reschedule), then synthetic verb traffic through the real
        handlers, then one SLO evaluation."""
        self.tick_no += 1
        super().tick()
        self._rebind_evicted()
        self._drive_traffic()
        if self.engine is not None:
            self.engine.tick()

    def _rebind_evicted(self) -> None:
        """The kube-controller + scheduler stand-in: an evicted pod is
        re-created and lands on its planned target when the leader's
        last plan names one, else on the least-loaded live node."""
        new = self.fake.evictions[self._seen_evictions:]
        if not new:
            return
        self._seen_evictions = len(self.fake.evictions)
        if self.gang:
            # the mesh world belongs to the scenario: a preempted gang
            # member silently re-created on the least-loaded node would
            # keep its old gang's member key alive (the draining slice
            # could never release) and would bypass the scheduler
            # entirely.  Re-admission goes back through the verbs —
            # which is exactly what the preemption cascade measures.
            return
        targets: Dict[str, str] = {}
        for stack in self.live():
            record = stack.rebalancer.status().get("last_plan") or {}
            for move in record.get("moves", []):
                targets[move["pod_key"]] = move["to_node"]
        live = self.live_node_names()
        if not live:
            return
        counts = self.pod_counts(live)
        for eviction in new:
            key = f"{eviction['namespace']}&{eviction['pod']}"
            target = targets.get(key)
            if target is None or target not in counts:
                target = min(counts, key=lambda n: (counts[n], n))
            counts[target] += 1
            self.fake.add_pod(
                make_pod(
                    eviction["pod"],
                    namespace=eviction["namespace"],
                    labels=self._pod_labels.get(
                        eviction["pod"],
                        {"telemetry-policy": POLICY_NAME},
                    ),
                    node_name=target,
                    phase="Running",
                )
            )

    def _drive_traffic(self) -> None:
        """``requests_per_tick`` Prioritize + Filter pairs through the
        first live replica's REAL verb handlers (what a Service would
        route), plus one gas_filter when the GAS lane is on — the
        latency/availability numbers the SLOs judge are measured off
        these, end to end through decode/kernel/encode."""
        live = self.live()
        if not live or self.gang:
            return
        if self.admission is not None:
            self._drive_queued_traffic(live[0].extender)
            return
        if self._bodies is None:
            names = self.live_node_names()
            self._bodies = [
                _prioritize_body(f"twin-pod-{i}", names)
                for i in range(max(1, self.requests_per_tick))
            ]
        extender = live[0].extender
        capacity = self.serving_capacity
        issued = 0
        for i in range(self.requests_per_tick):
            body = self._bodies[i % len(self._bodies)]
            for verb, path in (
                ("prioritize", "/scheduler/prioritize"),
                ("filter", "/scheduler/filter"),
            ):
                self.traffic["requests"] += 1
                if capacity is not None and issued >= capacity:
                    # admission queue full: shed without touching a verb
                    # handler (no histogram sample), counted bad in the
                    # family verb_availability's SLI reads
                    self.traffic["errors"] += 1
                    self.serving_counters.inc(
                        "pas_serving_rejected_total"
                    )
                    continue
                issued += 1
                try:
                    response = getattr(extender, verb)(
                        _request(path, body)
                    )
                    if response.status != 200:
                        self.traffic["errors"] += 1
                except Exception:
                    self.traffic["errors"] += 1
        if self.gas is not None:
            self.traffic["requests"] += 1
            try:
                response = self.gas.filter(
                    _request(
                        "/scheduler/filter",
                        _gas_filter_body("twin-gas-pod", self._gas_names),
                    )
                )
                if response.status != 200:
                    self.traffic["errors"] += 1
            except Exception:
                self.traffic["errors"] += 1

    def _drive_queued_traffic(self, extender) -> None:
        """The queued-admission tick: age -> timeout -> admit -> serve.
        Serving still goes through the REAL verb handlers (those are the
        good events the availability SLO counts); sheds and timeouts
        both land on ``pas_serving_rejected_total``.  The GAS lane is
        not modeled here — the head-to-head scenarios run gas=False."""
        q = self.admission
        # 1. everything queued last tick has now waited one tick longer
        for entry in q.backlog:
            entry[0] += 1
        # 2. queue timeouts: the client's deadline expired while the
        # request sat unserved — a bad event that (retry storm) also
        # re-enters demand once, the amplification a deep queue invites
        still: List[List] = []
        retry_next: List[Tuple[str, bytes]] = []
        for age, verb, body, is_retry in q.backlog:
            if age > q.timeout_ticks:
                q.timeouts += 1
                self.traffic["errors"] += 1
                self.serving_counters.inc("pas_serving_rejected_total")
                if q.retry_storm and not is_retry:
                    retry_next.append((verb, body))
            else:
                still.append([age, verb, body, is_retry])
        q.backlog = still
        # 3. admit: last tick's retries, then this tick's fresh demand.
        # A full queue sheds instantly (503 + Retry-After — the client
        # backs off, so a shed never retries)
        demand: List[Tuple[str, bytes, bool]] = [
            (verb, body, True) for verb, body in q.retries
        ]
        q.retries = retry_next
        if self._bodies is None:
            names = self.live_node_names()
            self._bodies = [
                _prioritize_body(f"twin-pod-{i}", names)
                for i in range(max(1, self.requests_per_tick))
            ]
        for i in range(self.requests_per_tick):
            body = self._bodies[i % len(self._bodies)]
            demand.append(("prioritize", body, False))
            demand.append(("filter", body, False))
        for verb, body, is_retry in demand:
            self.traffic["requests"] += 1
            if len(q.backlog) >= q.max_queue_depth:
                q.sheds += 1
                self.traffic["errors"] += 1
                self.serving_counters.inc("pas_serving_rejected_total")
                continue
            q.backlog.append([0, verb, body, is_retry])
        # 4. serve the oldest up to capacity through the real handlers
        capacity = (
            self.serving_capacity
            if self.serving_capacity is not None
            else len(q.backlog)
        )
        served = 0
        while q.backlog and served < capacity:
            _age, verb, body, _is_retry = q.backlog.pop(0)
            served += 1
            path = (
                "/scheduler/prioritize"
                if verb == "prioritize"
                else "/scheduler/filter"
            )
            try:
                response = getattr(extender, verb)(_request(path, body))
                if response.status != 200:
                    self.traffic["errors"] += 1
            except Exception:
                self.traffic["errors"] += 1

    # -- scenario verbs --------------------------------------------------------

    def set_base_load(self, loads: Dict[str, int]) -> None:
        self.base_load = dict(loads)
        if self._node_ordinal:
            vec = np.zeros(self.num_nodes, dtype=np.int64)
            ordinal = self._node_ordinal
            for name, value in self.base_load.items():
                j = ordinal.get(name)
                if j is not None:
                    vec[j] = int(value)
            self._base_vector = vec

    def set_base_load_vector(self, vector) -> None:
        """The replay loader's base-load knob: index i loads node-i
        directly from an array (its per-tick targets come out of numpy
        interpolation already), keeping the legacy dict view in sync so
        ``vectorized=False`` replays publish the same surface."""
        vec = np.zeros(self.num_nodes, dtype=np.int64)
        arr = np.asarray(vector, dtype=np.int64)
        span = min(arr.shape[0], self.num_nodes)
        vec[:span] = np.maximum(arr[:span], 0)
        self._base_vector = vec
        values = vec.tolist()
        self.base_load = {
            f"node-{i}": values[i] for i in range(self.num_nodes)
        }

    def fail_nodes(self, names: List[str]) -> None:
        """A node-failure wave: the named nodes' telemetry sources die
        and their pods are rescheduled onto the least-loaded survivors
        (the controller re-create path, like an eviction's)."""
        self.failed_nodes.update(names)
        self._bodies = None  # verb traffic stops naming dead nodes
        self._live_cache = None
        doomed: List[Tuple[str, str, str]] = []
        with self.fake._lock:
            for raw in self.fake._pods.values():
                node = (raw.get("spec") or {}).get("nodeName", "")
                if node in self.failed_nodes:
                    meta = raw.get("metadata") or {}
                    doomed.append(
                        (meta.get("namespace", "default"), meta["name"], node)
                    )
        counts = self.pod_counts()
        # round-robin over survivors ordered coldest-first: O(pods), not
        # O(pods x nodes) — a 5%-of-100k failure wave reschedules 5k
        # pods and a per-pod min() over 95k survivors would dwarf the
        # simulated cluster's own work
        order = sorted(counts, key=lambda n: (counts[n], n))
        for i, (namespace, pod, _node) in enumerate(doomed):
            self.fake.delete_pod(namespace, pod)
            target = order[i % len(order)]
            self.fake.add_pod(
                make_pod(
                    pod,
                    namespace=namespace,
                    labels=self._pod_labels.get(
                        pod, {"telemetry-policy": POLICY_NAME}
                    ),
                    node_name=target,
                    phase="Running",
                )
            )

    def restart(self, index: int):
        """Rebuild a replica (HAHarness semantics) and re-wire it into
        the observability plane: the fresh extender's recorder joins the
        engine's sources and /debug/slo serves on it — without this a
        restarted replica's traffic would be invisible to the SLOs and
        they would pass their gates on zero judged events."""
        stack = super().restart(index)
        if self.engine is not None:
            self.engine.recorders.append(stack.extender.recorder)
            stack.extender.slo = self.engine
        return stack

    def mark_storm(self) -> None:
        """Remember the eviction count at storm start: the suspension
        gate asserts it never moves until recovery."""
        self.storm_evictions = len(self.fake.evictions)

    def attach_flight(self, recorder) -> None:
        """Wire a FlightRecorder exactly the way cmd/common.py does in
        production: verb hooks on the first live replica's extender plus
        ONE telemetry subscription on its cache's refresh pass — so a
        twin-recorded capture and a production capture come off the same
        code paths (testing/replay.py round-trips the former)."""
        stack = self.live()[0]
        stack.extender.flight = recorder
        stack.cache.on_refresh_pass.append(
            lambda: recorder.observe_cache(stack.cache)
        )
        # the causal spine exports through the same capture, exactly as
        # cmd/common.build_flight_recorder wires it in production
        events.JOURNAL.flight = recorder

    def serve(self, serving: str = "threaded"):
        """Mount the first live replica's extender behind a REAL HTTP
        front-end (threaded or async) on an ephemeral port — the
        acceptance tests curl /debug/slo and /metrics while the twin
        ticks on the fake clock.  Caller shuts the server down."""
        extender = self.live()[0].extender
        if serving == "async":
            from platform_aware_scheduling_tpu.serving import AsyncServer

            server = AsyncServer(extender)
        else:
            from platform_aware_scheduling_tpu.extender.server import Server

            server = Server(extender, metrics_provider=extender.metrics_text)
        server.start_server(
            port="0", unsafe=True, host="127.0.0.1", block=False
        )
        server.wait_ready()
        return server

    def close(self) -> None:
        if self.gas is not None:
            self.gas.cache.stop()
        events.JOURNAL.tick_source = self._prev_tick_source
        events.JOURNAL.flight = self._prev_journal_flight

    # -- judgment --------------------------------------------------------------

    def violating_nodes(self) -> List[str]:
        """The leader's latest view of violating nodes (convergence
        gates read this)."""
        for stack in self.live():
            record = stack.rebalancer.status().get("last_plan") or {}
            nodes = record.get("violating_nodes")
            if nodes is not None:
                return list(nodes)
        return []

    def judgment(self) -> Dict[str, Dict]:
        return self.engine.judge() if self.engine is not None else {}


# ---------------------------------------------------------------------------
# scenario programs
# ---------------------------------------------------------------------------


class Scenario:
    """One replayable scenario program.  ``run(scale)`` builds its own
    twin, applies the program tick by tick, and returns a verdict whose
    ``checks`` are the SLO engine's judgment plus scenario invariants.
    ``build``/``ticks``/``apply`` are public so tests can drive the
    identical program manually (e.g. with a live front-end mounted)."""

    name = "scenario"

    def build(self, scale: Dict) -> TwinCluster:
        return TwinCluster(**scale)

    def ticks(self, scale: Dict) -> int:
        raise NotImplementedError

    def apply(self, twin: TwinCluster, t: int) -> None:
        pass

    def checks(self, twin: TwinCluster) -> List[Dict]:
        raise NotImplementedError

    # -- shared gate helpers ---------------------------------------------------

    @staticmethod
    def _check(name: str, ok: bool, detail: str = "") -> Dict:
        return {"check": name, "ok": bool(ok), "detail": detail}

    def slo_gates(
        self,
        twin: TwinCluster,
        compliant: Tuple[str, ...] = (),
        no_page: bool = True,
    ) -> List[Dict]:
        """The SLO engine's judgment as verdict checks: the named SLOs
        must meet their objective over the budget window, and (default)
        no SLO may sit in the page tier at scenario end."""
        judgment = twin.judgment()
        checks: List[Dict] = []
        for name in compliant:
            entry = judgment.get(name) or {}
            objective = twin.engine.slos[name].objective
            value = entry.get("compliance")
            checks.append(
                self._check(
                    f"slo:{name}",
                    value is not None and value >= objective,
                    f"compliance {value} vs objective {objective}",
                )
            )
        if no_page:
            paging = sorted(
                name
                for name, entry in judgment.items()
                if entry.get("alert") == ALERT_PAGE
            )
            checks.append(
                self._check(
                    "slo:no_page_tier",
                    not paging,
                    f"paging: {paging}" if paging else "no SLO paging",
                )
            )
        return checks

    def expect_chain(
        self,
        twin: TwinCluster,
        expected: List[Tuple[str, str]],
        **query: str,
    ) -> Dict:
        """Prove a causal story through the REAL debug surface: issue
        ``GET /debug/explain`` against a front-end mounted on the twin's
        leader (routed directly — no socket) and assert ``expected``,
        ordered ``(kind, event-prefix)`` pairs, appears as a subsequence
        of the returned chain.  Query kwargs are the endpoint's own
        filters (``pod=``/``gang=``/``request_id=``/``node=``)."""
        from platform_aware_scheduling_tpu.extender.server import Server

        extender = twin.live()[0].extender
        server = Server(extender, metrics_provider=extender.metrics_text)
        qs = "&".join(f"{k}={v}" for k, v in query.items() if v)
        response = server.route(
            HTTPRequest(
                method="GET",
                path=f"/debug/explain?{qs}",
                headers={},
                body=b"",
            )
        )
        if response.status != 200:
            return self._check(
                "explain:chain",
                False,
                f"/debug/explain?{qs} -> {response.status}",
            )
        chain = json.loads(response.body).get("events") or []
        walker = iter(chain)
        missing: List[str] = []
        for kind, event in expected:
            for record in walker:
                if record["kind"] == kind and record["event"].startswith(
                    event
                ):
                    break
            else:
                # once one link is missing, order past it is unprovable
                missing.append(f"{kind}:{event}")
                walker = iter(())
        return self._check(
            "explain:chain",
            not missing,
            f"missing (in order) {missing} in {len(chain)} events"
            if missing
            else f"full causal chain present ({len(chain)} events)",
        )

    def run(self, scale: Optional[Dict] = None) -> Dict:
        scale = dict(scale or {})
        twin = self.build(scale)
        try:
            total = self.ticks(scale)
            for t in range(total):
                self.apply(twin, t)
                twin.tick()
            checks = self.checks(twin)
            result = {
                "name": self.name,
                "passed": all(c["ok"] for c in checks),
                "ticks": total,
                "num_nodes": twin.num_nodes,
                "traffic": dict(twin.traffic),
                "checks": checks,
                "judgment": twin.judgment(),
                "actuations": (
                    twin.controller.actuation_count()
                    if getattr(twin, "controller", None) is not None
                    else 0
                ),
            }
            if twin.admission is not None:
                result["admission"] = {
                    "sheds": twin.admission.sheds,
                    "timeouts": twin.admission.timeouts,
                    "final_depth": twin.admission.max_queue_depth,
                }
            plane = twin.priority_plane()
            if plane is not None:
                result["admission_plane"] = plane.snapshot()
            return result
        finally:
            twin.close()


_CORE_SLOS = (
    "verb_availability",
    "prioritize_p99",
    "filter_p99",
    "telemetry_freshness",
    "eviction_safety",
)


class DiurnalLoad(Scenario):
    """A day/night load curve: every node's base load swings
    sinusoidally (phase-shifted across the cluster) while staying under
    the deschedule threshold.  The null hypothesis scenario: nothing
    should page, nothing should evict, every SLO should hold."""

    name = "diurnal"
    period_ticks = 24

    def ticks(self, scale: Dict) -> int:
        return 2 * self.period_ticks

    def apply(self, twin: TwinCluster, t: int) -> None:
        amplitude = max(1, THRESHOLD - POD_LOAD * 2 - 50)
        loads = {}
        for i, node in enumerate(twin.live_node_names()):
            phase = 2.0 * math.pi * (
                t / self.period_ticks + i / max(1, twin.num_nodes)
            )
            loads[node] = int(amplitude * 0.5 * (1.0 + math.sin(phase)))
        twin.set_base_load(loads)

    def checks(self, twin: TwinCluster) -> List[Dict]:
        # the wire-path floor SLOs gate HERE, in the null-hypothesis
        # scenario: a healthy cluster's Filter verbs must sit under the
        # interned-universe floor (500 us default), so a wire-path
        # regression fails run_matrix() even when every other SLO holds
        wire = tuple(
            name
            for name in ("filter_wire", "gas_filter_wire")
            if twin.engine is not None and name in twin.engine.slos
        )
        checks = self.slo_gates(twin, compliant=_CORE_SLOS + wire)
        checks.append(
            self._check(
                "zero_evictions",
                len(twin.evictions()) == 0,
                f"{len(twin.evictions())} evictions under a healthy "
                f"sub-threshold curve",
            )
        )
        return checks


class DeploymentWave(Scenario):
    """A deployment lands on a narrow set of nodes and its workload's
    load ramps up underneath them, pushing them over threshold; the
    rebalancer must move pods off the hot nodes within the scenario
    while the serving SLOs hold."""

    name = "deployment_wave"
    wave_start = 4
    ramp_ticks = 6
    peak_base = 350  # + 2 pods x POD_LOAD = 550 > THRESHOLD on hot nodes

    def ticks(self, scale: Dict) -> int:
        return 36

    def _hot(self, twin: TwinCluster) -> List[str]:
        # capped at 16 landing nodes: the wave must be drainable within
        # the scenario under the actuator's churn budget (max_moves per
        # cycle) — an uncapped width at 100k nodes would need thousands
        # of moves and "fail" convergence for a reason that is a knob,
        # not a regression
        width = min(16, max(1, twin.num_nodes // 8))
        return [f"node-{j}" for j in range(width)]

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t == self.wave_start:
            # the deployment: one new pod per landing node
            for j, node in enumerate(self._hot(twin)):
                name = f"wave-{j}"
                labels = {
                    "telemetry-policy": POLICY_NAME,
                    shared_labels.GROUP_LABEL: f"wave-{j}",
                }
                twin._pod_labels[name] = labels
                twin.fake.add_pod(
                    make_pod(
                        name, labels=labels, node_name=node, phase="Running"
                    )
                )
        if t >= self.wave_start:
            # its workload ramps to steady state over ramp_ticks
            ramp = min(1.0, (t - self.wave_start + 1) / self.ramp_ticks)
            twin.set_base_load(
                {node: int(self.peak_base * ramp) for node in self._hot(twin)}
            )

    def checks(self, twin: TwinCluster) -> List[Dict]:
        checks = self.slo_gates(twin, compliant=_CORE_SLOS)
        residual = twin.violating_nodes()
        checks.append(
            self._check(
                "wave_converged",
                not residual,
                f"violating nodes at end: {residual}",
            )
        )
        checks.append(
            self._check(
                "rebalancer_engaged",
                len(twin.evictions()) > 0,
                f"{len(twin.evictions())} evictions spread the wave",
            )
        )
        return checks


class NodeFailureWave(Scenario):
    """A rack dies: a slice of nodes stops reporting telemetry and its
    pods reschedule onto the survivors.  The survivors absorb the load
    (rebalancing if pushed over threshold) and the serving SLOs hold —
    a dead rack is capacity loss, not a scheduler outage."""

    name = "node_failure_wave"
    fail_at = 8

    def ticks(self, scale: Dict) -> int:
        return 36

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t == self.fail_at:
            width = max(1, twin.num_nodes // 20)
            doomed = [
                f"node-{twin.num_nodes - 1 - i}" for i in range(width)
            ]
            twin.fail_nodes(doomed)

    def checks(self, twin: TwinCluster) -> List[Dict]:
        checks = self.slo_gates(twin, compliant=_CORE_SLOS)
        residual = twin.violating_nodes()
        checks.append(
            self._check(
                "absorbed_failures",
                not residual,
                f"violating nodes at end: {residual}",
            )
        )
        orphaned = 0
        with twin.fake._lock:
            for raw in twin.fake._pods.values():
                node = (raw.get("spec") or {}).get("nodeName", "")
                if node in twin.failed_nodes:
                    orphaned += 1
        checks.append(
            self._check(
                "no_orphaned_pods",
                orphaned == 0,
                f"{orphaned} pods still bound to failed nodes",
            )
        )
        return checks


class MetricStorm(Scenario):
    """The acceptance scenario: the metrics API hard-fails for a
    stretch.  Telemetry goes stale, the freshness SLO burns through the
    page tier (breach counted, /debug/slo names it), evictions stay
    suspended for the whole storm, and after the API recovers the fast
    windows drain, the page clears, and the error budget ledger shows
    exactly the storm's seconds — consistent to the fake clock."""

    name = "metric_storm"
    healthy_ticks = 6
    storm_ticks = 8

    def ticks(self, scale: Dict) -> int:
        # enough post-storm ticks to drain the 5m page window: the page
        # must CLEAR, not just fire
        twin_period = float(scale.get("period_s", 5.0))
        drain = int(300.0 / twin_period) + 4
        return self.healthy_ticks + self.storm_ticks + drain

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t == self.healthy_ticks:
            twin.mark_storm()
            twin.plan.outage("get_node_metric", status=503)
        if t == self.healthy_ticks + self.storm_ticks:
            twin.plan.clear("get_node_metric")

    def checks(self, twin: TwinCluster) -> List[Dict]:
        judgment = twin.judgment()
        fresh = judgment.get("telemetry_freshness") or {}
        breaches = fresh.get("breaches") or {}
        checks = [
            self._check(
                "freshness_paged",
                breaches.get("page", 0) == 1,
                f"page breaches {breaches.get('page')} (exactly one "
                f"storm, exactly one page entry)",
            ),
            self._check(
                # the page tier must CLEAR once the fast 5m window drains;
                # the slow 6h/3d warn tier legitimately stays open — the
                # storm really did eat a chunk of the long-window budget
                "page_recovered",
                fresh.get("alert") != ALERT_PAGE,
                f"final alert {fresh.get('alert')!r} (warn acceptable: the "
                f"slow windows still remember the storm)",
            ),
        ]
        # eviction suspension: the count at storm start never moved
        # while telemetry was stale (the degraded controller's HARD
        # invariant, observed through the twin)
        checks.append(
            self._check(
                "evictions_suspended_in_storm",
                twin.storm_evictions is not None
                and len(twin.evictions()) == twin.storm_evictions,
                f"evictions {twin.storm_evictions} -> "
                f"{len(twin.evictions())}",
            )
        )
        # budget ledger consistency, on the fake clock: bad seconds ==
        # storm wall time, within the staleness-detection lag (the
        # freshness bound) and one recovery tick
        state = None
        if twin.engine is not None:
            for row in twin.engine.snapshot()["slos"]:
                if row["name"] == "telemetry_freshness":
                    state = row
        if state is None:
            checks.append(self._check("budget_ledger", False, "no slo row"))
        else:
            bad_s = state["cumulative"]["total"] - state["cumulative"]["good"]
            storm_s = self.storm_ticks * twin.period_s
            bound_s = 3.0 * twin.period_s  # the cache freshness bound
            ok = (
                storm_s - bound_s - twin.period_s
                <= bad_s
                <= storm_s + 2 * twin.period_s
            )
            checks.append(
                self._check(
                    "budget_ledger",
                    ok,
                    f"{bad_s:.1f}s of staleness for a {storm_s:.0f}s storm "
                    f"(detection lag {bound_s:.0f}s)",
                )
            )
            checks.append(
                self._check(
                    "budget_spent",
                    state["error_budget_remaining"] < 1.0,
                    f"error budget remaining "
                    f"{state['error_budget_remaining']}",
                )
            )
        # the serving SLOs must have stayed healthy THROUGH the storm —
        # degraded mode exists so staleness never becomes unavailability
        checks += self.slo_gates(
            twin,
            compliant=("verb_availability", "prioritize_p99", "filter_p99"),
            no_page=False,
        )
        return checks


class LeaderKillComposite(Scenario):
    """The composite: a 3-replica fleet takes a diurnal curve AND loses
    its leader mid-run.  Failover happens within the lease duration,
    no eviction is duplicated, and the serving SLOs never notice."""

    name = "leader_kill"
    kill_at = 6

    def build(self, scale: Dict) -> TwinCluster:
        scale = dict(scale)
        scale["replicas"] = 3
        return TwinCluster(**scale)

    def ticks(self, scale: Dict) -> int:
        return 24

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t == self.kill_at:
            leader = next(
                (
                    i
                    for i, s in enumerate(twin.replicas)
                    if s is not None and s.is_leader()
                ),
                0,
            )
            twin.crash(leader)
            self.killed_tick = t
        # a gentle diurnal curve keeps the telemetry moving
        loads = {
            node: 50 + 20 * ((t + i) % 5)
            for i, node in enumerate(twin.live_node_names())
        }
        twin.set_base_load(loads)

    def checks(self, twin: TwinCluster) -> List[Dict]:
        checks = self.slo_gates(
            twin,
            compliant=(
                "verb_availability",
                "prioritize_p99",
                "filter_p99",
                "eviction_safety",
            ),
        )
        lease_ticks = int(twin.lease_duration_s / twin.period_s) + 1
        checks.append(
            self._check(
                "failover_within_lease",
                len(twin.leaders()) == 1,
                f"leaders at end: {twin.leaders()} (lease bound "
                f"{lease_ticks} ticks)",
            )
        )
        duplicates = twin.duplicate_evictions()
        checks.append(
            self._check(
                "zero_duplicate_evictions",
                not duplicates,
                f"duplicates: {duplicates}",
            )
        )
        return checks


class PartitionHandoff(Scenario):
    """Partition ownership moves mid-traffic (docs/sharding.md): a
    3-replica sharded fleet serves scatter/gather verbs over 4
    partitions while a partition OWNER is killed cold.  Its membership
    heartbeat ages out, the coordinator hands its partitions to
    survivors under bumped fencing epochs, gossip re-converges, and the
    serving SLOs never notice.  The fencing audit is double: no live
    replica's store may still SERVE a moved partition under the dead
    owner's epoch, and the causal spine must carry the handoff as
    queryable context next to the verdicts that rode through it."""

    name = "partition_handoff"
    kill_at = 8
    partitions = 4

    def build(self, scale: Dict) -> TwinCluster:
        scale = dict(scale)
        scale["replicas"] = 3
        scale["shard_partitions"] = self.partitions
        scale["gas"] = False
        # one causal story per run, as the admission scenarios do
        events.JOURNAL.reset()
        self.victim: Optional[str] = None
        self.victim_owned: List[int] = []
        self.pre_epochs: Dict[int, int] = {}
        return TwinCluster(**scale)

    def ticks(self, scale: Dict) -> int:
        return 24

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t == self.kill_at:
            # kill a partition owner that is NOT serving traffic: the
            # handoff story is this scenario's subject — the serving
            # replica's failover story is LeaderKillComposite's
            serving = twin.live()[0].index
            victim_idx = None
            for i, stack in enumerate(twin.replicas):
                if stack is None or i in twin.crashed or i == serving:
                    continue
                if stack.shard.coordinator.owned():
                    victim_idx = i
                    break
            if victim_idx is None:
                victim_idx = serving
            stack = twin.replicas[victim_idx]
            self.victim = stack.identity
            self.victim_owned = sorted(stack.shard.coordinator.owned())
            self.pre_epochs = {
                p: stack.shard.coordinator.epoch(p)
                for p in self.victim_owned
            }
            twin.crash(victim_idx)
        # a gentle moving curve keeps telemetry and digests changing
        loads = {
            node: 50 + 20 * ((t + i) % 5)
            for i, node in enumerate(twin.live_node_names())
        }
        twin.set_base_load(loads)

    def checks(self, twin: TwinCluster) -> List[Dict]:
        checks = self.slo_gates(twin, compliant=_CORE_SLOS)
        owners = twin.shard_owners()
        moved = {p: owners.get(p, "") for p in self.victim_owned}
        checks.append(
            self._check(
                "ownership_moved",
                bool(self.victim_owned)
                and all(o and o != self.victim for o in moved.values()),
                f"{self.victim} owned {self.victim_owned} -> {moved}",
            )
        )
        live0 = twin.live()[0]
        epochs = {
            p: live0.shard.coordinator.epoch(p) for p in self.victim_owned
        }
        checks.append(
            self._check(
                "epochs_fenced_forward",
                bool(epochs)
                and all(
                    epochs[p] > self.pre_epochs.get(p, 0)
                    for p in self.victim_owned
                ),
                f"epochs {self.pre_epochs} -> {epochs}",
            )
        )
        # fencing audit, store side: a digest the dead owner published
        # must not be SERVABLE anywhere after the handoff — fresh()
        # either answers with the new owner's epoch or fails open
        fenced_servable = []
        for stack in twin.live():
            for p in self.victim_owned:
                digest = stack.shard.store.fresh(p)
                if digest is not None and (
                    digest.owner == self.victim
                    or digest.epoch < epochs.get(p, 0)
                ):
                    fenced_servable.append(
                        (stack.identity, p, digest.owner, digest.epoch)
                    )
        checks.append(
            self._check(
                "no_verdict_from_fenced_owner",
                not fenced_servable,
                f"fenced digests servable: {fenced_servable}"
                if fenced_servable
                else "every servable digest carries the post-handoff epoch",
            )
        )
        duplicates = twin.duplicate_evictions()
        checks.append(
            self._check(
                "zero_duplicate_evictions",
                not duplicates,
                f"duplicates: {duplicates}",
            )
        )
        # every live replica really ingested partition-scoped: its
        # refresh filter dropped the non-owned world on every pass
        unscoped = [
            stack.identity
            for stack in twin.live()
            if stack.shard.counters.get(
                "pas_shard_refresh_nodes_total",
                kind="counter",
                labels={"scope": "skipped"},
            )
            <= 0
        ]
        checks.append(
            self._check(
                "refresh_partition_scoped",
                not unscoped,
                f"replicas that never skipped a non-owned node: {unscoped}",
            )
        )
        # fencing audit, spine side: ask /debug/explain about a pod the
        # verb traffic served and demand the handoff ride the chain as
        # tick-joined world-state context ("who owned this node when the
        # verdict fired" reads off these partition/epoch records)
        from platform_aware_scheduling_tpu.extender.server import Server

        extender = live0.extender
        server = Server(extender, metrics_provider=extender.metrics_text)
        response = server.route(
            HTTPRequest(
                method="GET",
                path="/debug/explain?pod=default/twin-pod-0",
                headers={},
                body=b"",
            )
        )
        handoffs = []
        if response.status == 200:
            payload = json.loads(response.body)
            handoffs = [
                r
                for r in (payload.get("context") or [])
                + (payload.get("events") or [])
                if r["kind"] == "shard"
                and r["event"] == "partition_handoff"
                and r.get("data", {}).get("partition") in self.victim_owned
            ]
        checks.append(
            self._check(
                "handoff_in_event_spine",
                response.status == 200 and len(handoffs) >= 1,
                f"{len(handoffs)} partition_handoff context events for "
                f"partitions {self.victim_owned} "
                f"(HTTP {response.status})",
            )
        )
        return checks


class GangWave(Scenario):
    """A gang deployment wave on a TPU mesh: two competing multi-host
    gangs arrive interleaved and must BOTH land as valid contiguous
    slices (the all-or-nothing invariant) while the twin's SLO engine
    watches the verbs that placed them."""

    name = "gang_wave"
    rows, cols = 4, 4
    gang_rows, gang_cols = 2, 4

    def build(self, scale: Dict) -> TwinCluster:
        scale = dict(scale)
        # the mesh IS the scale for this scenario; the matrix's node
        # count does not apply (a 100k-node mesh reserve is the gang
        # bench's subject, benchmarks/gang_load.py)
        scale.pop("num_nodes", None)
        scale.pop("pods", None)
        twin = TwinCluster(
            num_nodes=self.rows * self.cols,
            gang=True,
            mesh=(self.rows, self.cols),
            gas=False,
            **scale,
        )
        size = self.gang_rows * self.gang_cols
        topo = f"{self.gang_rows}x{self.gang_cols}"
        self.pending = []
        for i in range(size):  # strict interleave: a0 b0 a1 b1 ...
            for group in ("gang-a", "gang-b"):
                self.pending.append(self._pod_obj(
                    f"{group}-{i}", group, size, topo
                ))
        self.available = list(twin.mesh_nodes)
        self.bound: Dict[str, List[str]] = {"gang-a": [], "gang-b": []}
        return twin

    @staticmethod
    def _pod_obj(name: str, group: str, size: int, topo: str) -> Dict:
        return {
            "metadata": {
                "name": name,
                "namespace": "default",
                "labels": {
                    "telemetry-policy": POLICY_NAME,
                    shared_labels.GROUP_LABEL: group,
                    shared_labels.GANG_SIZE_LABEL: str(size),
                    shared_labels.GANG_TOPOLOGY_LABEL: topo,
                },
            }
        }

    def ticks(self, scale: Dict) -> int:
        return 12

    def apply(self, twin: TwinCluster, t: int) -> None:
        """One admission round per tick: every still-pending member
        tries Filter -> Prioritize -> Bind through the real verbs."""
        extender = twin.live()[0].extender
        progressed = []
        for pod_obj in self.pending:
            response = extender.filter(
                _request(
                    "/scheduler/filter",
                    json.dumps(
                        {"Pod": pod_obj, "NodeNames": self.available}
                    ).encode(),
                )
            )
            twin.traffic["requests"] += 1
            if response.status != 200:
                twin.traffic["errors"] += 1
                continue
            passing = list(
                json.loads(response.body).get("NodeNames") or []
            )
            if not passing:
                continue
            ranked = json.loads(
                extender.prioritize(
                    _request(
                        "/scheduler/prioritize",
                        json.dumps(
                            {"Pod": pod_obj, "NodeNames": passing}
                        ).encode(),
                    )
                ).body
                or b"[]"
            )
            node = (
                max(ranked, key=lambda e: e["Score"])["Host"]
                if ranked
                else passing[0]
            )
            extender.bind(
                _request(
                    "/scheduler/bind",
                    json.dumps(
                        {
                            "PodName": pod_obj["metadata"]["name"],
                            "PodNamespace": "default",
                            "PodUID": "uid",
                            "Node": node,
                        }
                    ).encode(),
                )
            )
            self.available.remove(node)
            group = pod_obj["metadata"]["labels"][shared_labels.GROUP_LABEL]
            self.bound[group].append(node)
            progressed.append(pod_obj)
        self.pending = [p for p in self.pending if p not in progressed]

    def _forms_slice(self, twin: TwinCluster, nodes: List[str]) -> bool:
        from platform_aware_scheduling_tpu.ops import topology

        mesh = topology.MeshView(twin.fake.list_nodes())
        mask = mesh.free_mask(nodes)
        if int(mask.sum()) != self.gang_rows * self.gang_cols:
            return False
        for h, w in {
            (self.gang_rows, self.gang_cols),
            (self.gang_cols, self.gang_rows),
        }:
            if topology.topology_feasibility_host(mask, h, w).anchor_ok.any():
                return True
        return False

    def checks(self, twin: TwinCluster) -> List[Dict]:
        checks = []
        size = self.gang_rows * self.gang_cols
        for group, nodes in sorted(self.bound.items()):
            checks.append(
                self._check(
                    f"{group}_admitted_as_slice",
                    len(nodes) == size and self._forms_slice(twin, nodes),
                    f"{len(nodes)}/{size} bound, contiguous="
                    f"{self._forms_slice(twin, nodes)}",
                )
            )
        checks.append(
            self._check(
                "zero_deadlock",
                not self.pending,
                f"{len(self.pending)} members unplaced",
            )
        )
        return checks


class ControlMetricStorm(Scenario):
    """The availability head-to-head program: a metric-API outage AND a
    demand surge land together on the queued-admission model, with a
    retry storm armed (each queue timeout retries once).  A static deep
    queue turns the surge into timeouts, and timeouts into MORE demand —
    the metastable amplification; a self-tuning run tightens the
    admission depth as the availability budget burns, converting the
    excess into cheap early sheds that never retry.  Both outcomes are
    bad availability events, so the final error-budget ledger is the
    honest comparison: fewer bad events, strictly more budget left.
    Run twice (control False/True) by :func:`control_headtohead`."""

    name = "control_metric_storm"
    healthy_ticks = 6
    surge_ticks = 12
    baseline_requests = 2
    surge_requests = 8

    def __init__(self, control: bool = False):
        self.control = bool(control)

    def build(self, scale: Dict) -> TwinCluster:
        scale = dict(scale)
        scale.update(
            gas=False,
            control=self.control,
            serving_capacity=4,
            requests_per_tick=self.baseline_requests,
            admission_depth=64,
            admission_timeout_ticks=2,
            retry_storm=True,
        )
        return TwinCluster(**scale)

    def ticks(self, scale: Dict) -> int:
        return self.healthy_ticks + self.surge_ticks + 8

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t == self.healthy_ticks:
            twin.mark_storm()
            twin.plan.outage("get_node_metric", status=503)
            twin.requests_per_tick = self.surge_requests
        if t == self.healthy_ticks + self.surge_ticks:
            twin.plan.clear("get_node_metric")
            twin.requests_per_tick = self.baseline_requests

    def checks(self, twin: TwinCluster) -> List[Dict]:
        q = twin.admission
        stressed = q is not None and (q.sheds + q.timeouts) > 0
        checks = [
            self._check(
                "admission_stressed",
                stressed,
                f"sheds {q.sheds}, timeouts {q.timeouts}"
                if q is not None
                else "no admission model",
            )
        ]
        actuations = (
            twin.controller.actuation_count()
            if twin.controller is not None
            else 0
        )
        if self.control:
            checks.append(
                self._check(
                    "controller_engaged",
                    actuations > 0,
                    f"{actuations} actuations under the storm",
                )
            )
        else:
            checks.append(
                self._check(
                    "static_config_untouched",
                    actuations == 0,
                    "no controller in the static run",
                )
            )
        return checks


class ControlDeploymentWave(Scenario):
    """The eviction-safety head-to-head program: the deployment wave
    lands exactly as in :class:`DeploymentWave`, but the eviction API is
    down for a window starting with the wave.  A static rebalancer slams
    its full churn budget into the broken dependency every cycle (every
    attempt a bad eviction-safety event, and five consecutive failures
    trip the kube circuit — collateral degradation); a self-tuning run
    throttles ``max_moves`` down and the drift hysteresis up as the
    safety budget burns, backing off the dependency, then drains the
    wave after the API heals.  Run twice by :func:`control_headtohead`;
    the ledger compared is eviction_safety's."""

    name = "control_deployment_wave"
    wave_start = DeploymentWave.wave_start
    ramp_ticks = DeploymentWave.ramp_ticks
    peak_base = DeploymentWave.peak_base
    outage_start = DeploymentWave.wave_start
    outage_ticks = 16

    def __init__(self, control: bool = False):
        self.control = bool(control)

    def build(self, scale: Dict) -> TwinCluster:
        scale = dict(scale)
        scale.update(gas=False, control=self.control)
        return TwinCluster(**scale)

    def ticks(self, scale: Dict) -> int:
        return 44

    _hot = DeploymentWave._hot
    _wave_apply = DeploymentWave.apply

    def apply(self, twin: TwinCluster, t: int) -> None:
        self._wave_apply(twin, t)
        if t == self.outage_start:
            twin.plan.outage("evict_pod", status=503)
        if t == self.outage_start + self.outage_ticks:
            twin.plan.clear("evict_pod")

    def checks(self, twin: TwinCluster) -> List[Dict]:
        residual = twin.violating_nodes()
        checks = [
            self._check(
                "wave_converged",
                not residual,
                f"violating nodes at end: {residual}",
            ),
            self._check(
                "rebalancer_engaged",
                len(twin.evictions()) > 0,
                f"{len(twin.evictions())} evictions after the API healed",
            ),
        ]
        actuations = (
            twin.controller.actuation_count()
            if twin.controller is not None
            else 0
        )
        if self.control:
            checks.append(
                self._check(
                    "controller_engaged",
                    actuations > 0,
                    f"{actuations} actuations under the outage",
                )
            )
        return checks


def control_headtohead(
    num_nodes: int = 16,
    pods: Optional[int] = None,
    period_s: float = 5.0,
) -> Dict:
    """The budget controller's acceptance A/B (docs/observability.md
    "Budget feedback control"): each head-to-head program runs twice on
    identical twins — static configuration vs self-tuning — and the
    verdict compares the trigger SLO's FINAL error-budget ledger.  The
    self-tuning run must finish strictly better on both programs, and a
    quiet diurnal day with the controller armed must end with zero
    actuations (a controller that fidgets on a healthy cluster is
    itself a defect)."""
    scale = {
        "num_nodes": num_nodes,
        "pods": pods if pods is not None else num_nodes,
        "period_s": period_s,
    }
    out: Dict = {"scenarios": {}}
    for key, cls, slo_name in (
        ("metric_storm", ControlMetricStorm, "verb_availability"),
        ("deployment_wave", ControlDeploymentWave, "eviction_safety"),
    ):
        static = cls(control=False).run(dict(scale))
        tuned = cls(control=True).run(dict(scale))
        static_entry = static["judgment"].get(slo_name) or {}
        tuned_entry = tuned["judgment"].get(slo_name) or {}
        static_budget = static_entry.get("error_budget_remaining")
        tuned_budget = tuned_entry.get("error_budget_remaining")
        out["scenarios"][key] = {
            "slo": slo_name,
            "static": {
                "budget": static_budget,
                "errors": static["traffic"]["errors"],
                "actuations": static["actuations"],
                "passed": static["passed"],
                "checks": static["checks"],
            },
            "self_tuning": {
                "budget": tuned_budget,
                "errors": tuned["traffic"]["errors"],
                "actuations": tuned["actuations"],
                "passed": tuned["passed"],
                "checks": tuned["checks"],
            },
            "strictly_better": bool(
                static_budget is not None
                and tuned_budget is not None
                and tuned_budget > static_budget
            ),
        }
    # the null hypothesis with the controller ARMED: a healthy diurnal
    # day must produce zero actuations — hysteresis means quiet
    quiet_scale = dict(scale)
    quiet_scale["control"] = True
    quiet = DiurnalLoad().run(quiet_scale)
    out["diurnal_quiet"] = {
        "actuations": quiet["actuations"],
        "passed": quiet["passed"],
        "ok": quiet["actuations"] == 0 and quiet["passed"],
    }
    out["all_strictly_better"] = all(
        entry["strictly_better"] for entry in out["scenarios"].values()
    )
    return out


class _AdmissionScenario(Scenario):
    """Shared machinery for the admission-plane scenarios: a 4x4 mesh
    twin with the priority plane armed, GangWave-style verb driving with
    per-pod candidate control, and fake-pod bookkeeping on Bind — a
    bound member lands as a REAL pod in the fake cluster (the
    kube-scheduler's side of Bind), so the preemption planner's pod
    census, the eviction verb, and the tracker's dead-gang sweep all see
    true cluster state instead of phantom members."""

    rows, cols = 4, 4
    high_rows, high_cols = 2, 4
    preemption = False
    starve_consults = 16

    def build(self, scale: Dict) -> TwinCluster:
        scale = dict(scale)
        scale.pop("num_nodes", None)
        scale.pop("pods", None)
        # each run tells ONE causal story: reset here (not in
        # TwinCluster.__init__ — /debug/whatif builds a twin inside a
        # live server's request and must not wipe the live journal), so
        # expect_chain() reads only this scenario's events
        events.JOURNAL.reset()
        twin = TwinCluster(
            num_nodes=self.rows * self.cols,
            gang=True,
            mesh=(self.rows, self.cols),
            gas=False,
            admission_plane=True,
            preemption=self.preemption,
            admission_starve_consults=self.starve_consults,
            **scale,
        )
        #: each entry: {"pod": obj, "group": str, "candidates": [...]|None}
        self.pending: List[Dict] = []
        self.bound: Dict[str, List[str]] = {}
        self.node_of: Dict[str, str] = {}
        self.single_nodes: Set[str] = set()
        self.admitted_at: Optional[int] = None
        return twin

    # -- pod bodies ------------------------------------------------------------

    @staticmethod
    def _gang_pod(
        name: str, group: str, size: int, topo: str, klass: str
    ) -> Dict:
        pod = GangWave._pod_obj(name, group, size, topo)
        pod["metadata"]["labels"][shared_labels.PRIORITY_LABEL] = klass
        return pod

    @staticmethod
    def _single_pod(name: str, klass: str) -> Dict:
        return {
            "metadata": {
                "name": name,
                "namespace": "default",
                "labels": {
                    "telemetry-policy": POLICY_NAME,
                    shared_labels.PRIORITY_LABEL: klass,
                },
            }
        }

    # -- verb driving ----------------------------------------------------------

    @staticmethod
    def _call(verb: Callable, path: str, payload: Dict) -> HTTPResponse:
        """One verb call carrying a REAL span, exactly as the live
        front-ends attach one: the handler stamps verb/pod attrs on it,
        and finishing it into trace.TRACES fires the span observer, so
        every twin verb lands a correlated ``wire`` event in the causal
        spine (utils/events.py) with a request_id chains can join on."""
        request = _request(path, json.dumps(payload).encode())
        request.span = trace.Span(f"POST {path}", trace.new_request_id())
        response = verb(request)
        trace.TRACES.add(request.span.finish(response.status))
        return response

    def _drive_round(
        self,
        twin: TwinCluster,
        only: Optional[str] = None,
        limit: Optional[int] = None,
    ) -> int:
        """One admission round: every still-pending pod (optionally only
        group ``only``) tries Filter -> Prioritize -> Bind through the
        real verbs, binding at most ``limit`` pods this round (the
        ration that keeps a gang's slice reserved-with-waiters across
        ticks).  Returns how many pods bound."""
        extender = twin.live()[0].extender
        bound_now = 0
        progressed = []
        # the kube-scheduler's one-pod-per-slot bookkeeping: a node
        # hosting a live pod is not offered again, sourced from the fake
        # cluster so completions and evictions free their nodes
        occupied = {
            p.spec_node_name
            for p in twin.fake.list_pods()
            if p.phase == "Running"
        }
        for item in self.pending:
            if only is not None and item["group"] != only:
                continue
            if limit is not None and bound_now >= limit:
                break
            pod_obj = item["pod"]
            candidates = item["candidates"]
            if candidates is None:
                candidates = [
                    n
                    for n in twin.mesh_nodes
                    if n not in self.single_nodes
                ]
            twin.traffic["requests"] += 1
            response = self._call(
                extender.filter,
                "/scheduler/filter",
                {"Pod": pod_obj, "NodeNames": candidates},
            )
            if response.status != 200:
                twin.traffic["errors"] += 1
                continue
            passing = list(
                json.loads(response.body).get("NodeNames") or []
            )
            if not passing:
                continue
            ranked = json.loads(
                self._call(
                    extender.prioritize,
                    "/scheduler/prioritize",
                    {"Pod": pod_obj, "NodeNames": passing},
                ).body
                or b"[]"
            )
            open_ranked = [
                e for e in ranked if e["Host"] not in occupied
            ]
            open_passing = [n for n in passing if n not in occupied]
            if open_ranked:
                node = max(open_ranked, key=lambda e: e["Score"])["Host"]
            elif open_passing:
                node = open_passing[0]
            else:
                continue  # every passing node already hosts a pod
            occupied.add(node)
            name = pod_obj["metadata"]["name"]
            self._call(
                extender.bind,
                "/scheduler/bind",
                {
                    "PodName": name,
                    "PodNamespace": "default",
                    "PodUID": "uid",
                    "Node": node,
                },
            )
            twin.fake.add_pod(
                make_pod(
                    name,
                    labels=dict(pod_obj["metadata"]["labels"]),
                    node_name=node,
                    phase="Running",
                )
            )
            self.bound.setdefault(item["group"], []).append(node)
            self.node_of[name] = node
            if shared_labels.GANG_SIZE_LABEL not in (
                pod_obj["metadata"]["labels"]
            ):
                self.single_nodes.add(node)
            bound_now += 1
            progressed.append(item)
        self.pending = [i for i in self.pending if i not in progressed]
        return bound_now

    def _complete_gang(self, twin: TwinCluster, names: List[str]) -> None:
        """A gang's job finishes: its pods leave the cluster and the
        tracker's dead-gang sweep releases the slice (gang/group.py) —
        forced inline so the release lands this tick, not whenever the
        next throttled background scan runs."""
        for name in names:
            twin.fake.delete_pod("default", name)
        for stack in twin.live():
            if stack.gangs is not None:
                stack.gangs.prune()

    def _forms(
        self, twin: TwinCluster, nodes: List[str], h: int, w: int
    ) -> bool:
        from platform_aware_scheduling_tpu.ops import topology

        mesh = topology.MeshView(twin.fake.list_nodes())
        mask = mesh.free_mask(nodes)
        if int(mask.sum()) != h * w:
            return False
        for hh, ww in {(h, w), (w, h)}:
            if topology.topology_feasibility_host(mask, hh, ww).anchor_ok.any():
                return True
        return False

    def _plane_counter(
        self, twin: TwinCluster, name: str, klass: Optional[str] = None
    ) -> float:
        plane = twin.priority_plane()
        if plane is None:
            return 0.0
        labels = {"class": klass} if klass is not None else None
        return plane.counters.get(name, kind="counter", labels=labels)


class PriorityInversionStorm(_AdmissionScenario):
    """The queue-and-hold half of the admission plane, no preemption: a
    fragmented mesh (free nodes exist, but no contiguous 2x4 window)
    queues a high-priority gang, and the batch singles that keep
    arriving are HELD behind it — without the gate they would nibble the
    very nodes the gang is waiting for (the classic priority inversion).
    When one fragment's job completes, the gang lands as a contiguous
    slice first; the singles flow in behind it."""

    name = "priority_inversion"
    high_arrival = 3
    singles_arrival = 4
    release_tick = 8

    def build(self, scale: Dict) -> TwinCluster:
        twin = super().build(scale)
        # two batch 2x2 gangs FORCED (via their candidate lists) onto
        # the middle columns: the 8 free nodes (columns 0 and 3) are two
        # disconnected 4x1 strips — no 2x4 or 4x2 window anywhere
        for group, rows_ in (("frag-a", (0, 1)), ("frag-b", (2, 3))):
            forced = [f"mesh-{r}-{c}" for r in rows_ for c in (1, 2)]
            for i in range(4):
                self.pending.append(
                    {
                        "pod": self._gang_pod(
                            f"{group}-{i}", group, 4, "2x2", "batch"
                        ),
                        "group": group,
                        "candidates": forced,
                    }
                )
        return twin

    def ticks(self, scale: Dict) -> int:
        return 20

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t == self.high_arrival:
            for i in range(8):
                self.pending.append(
                    {
                        "pod": self._gang_pod(
                            f"high-{i}", "gang-high", 8, "2x4", "high"
                        ),
                        "group": "gang-high",
                        "candidates": None,
                    }
                )
        if t == self.singles_arrival:
            for i in range(4):
                self.pending.append(
                    {
                        "pod": self._single_pod(f"batch-s-{i}", "batch"),
                        "group": "singles",
                        "candidates": None,
                    }
                )
        if t == self.release_tick:
            self._complete_gang(
                twin, [f"frag-a-{i}" for i in range(4)]
            )
        self._drive_round(twin)
        if (
            self.admitted_at is None
            and len(self.bound.get("gang-high", [])) == 8
        ):
            self.admitted_at = t

    def checks(self, twin: TwinCluster) -> List[Dict]:
        high = self.bound.get("gang-high", [])
        singles = self.bound.get("singles", [])
        blocked = self._plane_counter(
            twin, "pas_admission_blocked_total", "batch"
        )
        log = twin.priority_plane().decision_log
        enqueues = [
            r
            for r in log.snapshot(verb="admission", limit=256)["records"]
            if r.get("detail", {}).get("event") == "enqueue"
        ]
        checks = self.slo_gates(
            twin,
            compliant=("class_availability_high", "class_availability_batch"),
        )
        checks.extend(
            [
                self._check(
                    "high_admitted_as_slice",
                    len(high) == 8
                    and self._forms(
                        twin, high, self.high_rows, self.high_cols
                    ),
                    f"{len(high)}/8 bound after the fragment released",
                ),
                self._check(
                    "singles_held_then_admitted",
                    blocked > 0 and len(singles) == 4,
                    f"{blocked:g} holds, {len(singles)}/4 singles bound",
                ),
                self._check(
                    "holds_have_provenance",
                    len(enqueues) > 0,
                    f"{len(enqueues)} enqueue records in the decision log",
                ),
                self._check(
                    "no_sharp_edges",
                    len(twin.evictions()) == 0
                    and self._plane_counter(
                        twin, "pas_preemption_reservations_total"
                    )
                    == 0,
                    "queue-and-hold only: zero evictions, zero "
                    "preemptions",
                ),
            ]
        )
        return checks


class BackfillStarvation(_AdmissionScenario):
    """The backfill guarantee: while a high-priority gang drains into
    its RESERVED slice one member per tick (the window in which a naive
    priority queue would starve everyone behind the head), small batch
    singles keep arriving — each must be admitted through the backfill
    branch (the head's demand stays covered by its reservation), and
    none may starve."""

    name = "backfill_starvation"
    arrival = 2
    release_tick = 3
    singles_start = 4

    def build(self, scale: Dict) -> TwinCluster:
        twin = super().build(scale)
        # batch-a (2x4, rows 0-1) + batch-b (2x2, rows 2-3 x cols 0-1):
        # free is only the 2x2 block at rows 2-3 x cols 2-3, so the high
        # 2x4 gang is infeasible until batch-a completes
        for i in range(8):
            self.pending.append(
                {
                    "pod": self._gang_pod(
                        f"batch-a-{i}", "batch-a", 8, "2x4", "batch"
                    ),
                    "group": "batch-a",
                    "candidates": [
                        f"mesh-{r}-{c}" for r in (0, 1) for c in range(4)
                    ],
                }
            )
        for i in range(4):
            self.pending.append(
                {
                    "pod": self._gang_pod(
                        f"batch-b-{i}", "batch-b", 4, "2x2", "batch"
                    ),
                    "group": "batch-b",
                    "candidates": [
                        f"mesh-{r}-{c}" for r in (2, 3) for c in (0, 1)
                    ],
                }
            )
        return twin

    def ticks(self, scale: Dict) -> int:
        # 8 rationed one-per-tick member binds after the release (which
        # itself may wait a tick or two on the throttled dead-gang
        # sweep), plus slack: 20 ticks
        return 20

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t == self.arrival:
            for i in range(8):
                self.pending.append(
                    {
                        "pod": self._gang_pod(
                            f"high-{i}", "gang-high", 8, "2x4", "high"
                        ),
                        "group": "gang-high",
                        "candidates": None,
                    }
                )
        if t == self.release_tick:
            self._complete_gang(
                twin, [f"batch-a-{i}" for i in range(8)]
            )
        if self.singles_start <= t < self.singles_start + 4:
            self.pending.append(
                {
                    "pod": self._single_pod(
                        f"batch-s-{t - self.singles_start}", "batch"
                    ),
                    "group": "singles",
                    "candidates": None,
                }
            )
        if t < self.arrival:
            self._drive_round(twin)
            return
        # ration the high gang to ONE member bind per tick: the slice
        # stays reserved-with-waiters for several ticks — exactly the
        # window the backfill branch exists for
        self._drive_round(twin, only="gang-high", limit=1)
        self._drive_round(twin, only="batch-a")
        self._drive_round(twin, only="batch-b")
        self._drive_round(twin, only="singles")

    def checks(self, twin: TwinCluster) -> List[Dict]:
        high = self.bound.get("gang-high", [])
        singles = self.bound.get("singles", [])
        backfills = self._plane_counter(
            twin, "pas_admission_backfill_total", "batch"
        )
        starved = self._plane_counter(
            twin, "pas_admission_starved_total", "batch"
        )
        checks = self.slo_gates(
            twin,
            compliant=("class_availability_high", "class_availability_batch"),
        )
        checks.extend(
            [
                self._check(
                    "high_admitted_as_slice",
                    len(high) == 8
                    and self._forms(
                        twin, high, self.high_rows, self.high_cols
                    ),
                    f"{len(high)}/8 bound, one member per tick",
                ),
                self._check(
                    "singles_backfilled",
                    backfills > 0 and len(singles) == 4,
                    f"{backfills:g} backfill admissions, "
                    f"{len(singles)}/4 singles bound",
                ),
                self._check(
                    "nobody_starved",
                    starved == 0,
                    f"{starved:g} batch starvation events",
                ),
            ]
        )
        return checks


class PreemptionCascade(_AdmissionScenario):
    """The sharp edge, run with the planner ON or OFF over an identical
    program: two batch gangs fill the mesh, then a high-priority gang
    arrives.  ON, the planner evicts the cheapest whole batch gang
    all-or-nothing, reserves the freed slice while the victims drain,
    and the high gang binds within a bounded number of ticks — with a
    provenance record naming every victim.  OFF, the high gang starves
    (and its availability ledger shows it) while not a single pod is
    evicted.  :func:`admission_headtohead` compares the two runs."""

    name = "preemption_cascade"
    arrival = 4
    admit_budget_ticks = 3
    starve_consults = 4

    def __init__(self, preemption: bool = True):
        self.preemption = bool(preemption)
        if not preemption:
            self.name = "preemption_cascade_off"

    def build(self, scale: Dict) -> TwinCluster:
        twin = super().build(scale)
        for i in range(8):  # strict interleave, as in GangWave
            for group in ("batch-a", "batch-b"):
                self.pending.append(
                    {
                        "pod": self._gang_pod(
                            f"{group}-{i}", group, 8, "2x4", "batch"
                        ),
                        "group": group,
                        "candidates": None,
                    }
                )
        return twin

    def ticks(self, scale: Dict) -> int:
        return 16

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t == self.arrival:
            for i in range(8):
                self.pending.append(
                    {
                        "pod": self._gang_pod(
                            f"high-{i}", "gang-high", 8, "2x4", "high"
                        ),
                        "group": "gang-high",
                        "candidates": None,
                    }
                )
        self._drive_round(twin)
        if (
            self.admitted_at is None
            and len(self.bound.get("gang-high", [])) == 8
        ):
            self.admitted_at = t

    def checks(self, twin: TwinCluster) -> List[Dict]:
        high = self.bound.get("gang-high", [])
        evictions = twin.evictions()
        plane = twin.priority_plane()
        preemptions = self._plane_counter(
            twin, "pas_preemption_reservations_total"
        )
        records = plane.decision_log.snapshot(
            verb="preemption", limit=64
        )["records"]
        if not self.preemption:
            # the control arm: no planner, so the high gang must starve
            # visibly (the ledger is the head-to-head's comparison) and
            # nothing may be evicted
            starved = self._plane_counter(
                twin, "pas_admission_starved_total", "high"
            )
            return [
                self._check(
                    "high_never_admitted",
                    not high and self.admitted_at is None,
                    f"{len(high)} members bound without preemption",
                ),
                self._check(
                    "high_starvation_visible",
                    starved > 0,
                    f"{starved:g} starvation events for class high",
                ),
                self._check(
                    "zero_evictions",
                    len(evictions) == 0 and preemptions == 0,
                    f"{len(evictions)} evictions, {preemptions:g} "
                    f"preemption reservations",
                ),
            ]
        victim_classes = {
            v["class"]
            for r in records
            for v in r.get("detail", {}).get("victims", [])
        }
        survivor = [
            p
            for p in twin.fake.list_pods()
            if p.name.startswith("batch-") and p.phase == "Running"
        ]
        checks = self.slo_gates(
            twin, compliant=("class_availability_high",)
        )
        checks.extend(
            [
                self._check(
                    "high_admitted_in_bounded_ticks",
                    self.admitted_at is not None
                    and self.admitted_at
                    <= self.arrival + self.admit_budget_ticks
                    and len(high) == 8
                    and self._forms(
                        twin, high, self.high_rows, self.high_cols
                    ),
                    f"admitted at tick {self.admitted_at} "
                    f"(arrival {self.arrival}, budget "
                    f"{self.admit_budget_ticks})",
                ),
                self._check(
                    "one_whole_gang_evicted",
                    len(evictions) == 8
                    and len({e["pod"].rsplit("-", 1)[0] for e in evictions})
                    == 1,
                    f"{len(evictions)} evictions: "
                    f"{sorted(e['pod'] for e in evictions)}",
                ),
                self._check(
                    "every_preemption_has_provenance",
                    preemptions >= 1 and len(records) == int(preemptions),
                    f"{preemptions:g} reservations, {len(records)} "
                    f"provenance records",
                ),
                self._check(
                    "victims_strictly_lower_class",
                    victim_classes == {"batch"},
                    f"victim classes: {sorted(victim_classes)}",
                ),
                self._check(
                    "survivor_gang_intact",
                    len(survivor) == 8,
                    f"{len(survivor)} batch pods still running",
                ),
                # the causal spine must tell this scenario's WHOLE story
                # from one query: ask /debug/explain about the high
                # gang's leader and demand the ordered chain — enqueue,
                # preemption plan naming victims, slice reservation,
                # admission, score path, wire response (utils/events.py)
                self.expect_chain(
                    twin,
                    [
                        ("admission", "enqueue"),
                        ("preemption", "planned"),
                        ("preemption", "victim evicted"),
                        ("preemption", "slice reserved"),
                        ("admission", "admit"),
                        ("verdict", "filter"),
                        ("verdict", "prioritize"),
                        ("wire", "bind responded"),
                    ],
                    pod="default/high-0",
                ),
            ]
        )
        return checks


def admission_headtohead(period_s: float = 5.0) -> Dict:
    """The admission plane's acceptance A/B (docs/admission.md): the
    preemption cascade runs twice on identical twins — planner ON vs OFF
    — and the verdict compares the HIGH class's final error-budget
    ledger (ON must finish strictly better, having admitted the gang in
    bounded ticks; OFF must never admit it and never evict).  Plus the
    null hypothesis: a quiet diurnal day with the plane armed
    (queue-only, no contention) must end with zero queueing, zero
    preemptions, and every check green — a gate that fidgets on a
    healthy cluster is itself a defect."""
    scale = {"period_s": period_s}
    on = PreemptionCascade(preemption=True).run(dict(scale))
    off = PreemptionCascade(preemption=False).run(dict(scale))
    slo_name = "class_availability_high"
    on_budget = (on["judgment"].get(slo_name) or {}).get(
        "error_budget_remaining"
    )
    off_budget = (off["judgment"].get(slo_name) or {}).get(
        "error_budget_remaining"
    )
    # fresh timeline for the null hypothesis: the cascade arms above
    # legitimately filled (and may have overflowed) the event ring, and
    # DiurnalLoad builds a bare TwinCluster with no reset of its own
    events.JOURNAL.reset()
    quiet = DiurnalLoad().run(
        {
            "num_nodes": 16,
            "pods": 16,
            "period_s": period_s,
            "admission_plane": True,
        }
    )
    quiet_plane = quiet.get("admission_plane") or {}
    # the spine must never shed its own story on a healthy day: a quiet
    # diurnal run that overflows the event ring means the journal is
    # sized wrong for steady state (ISSUE: zero drops in quiet-diurnal)
    quiet_events_dropped = events.JOURNAL.dropped
    quiet_ok = (
        quiet["passed"]
        and quiet_plane.get("depth") == 0
        and (quiet_plane.get("counters") or {}).get("queued", 0) == 0
        and (quiet_plane.get("counters") or {}).get("preemptions", 0) == 0
        and quiet_events_dropped == 0
    )
    return {
        "slo": slo_name,
        "preemption_on": {
            "budget": on_budget,
            "admitted": any(
                c["check"] == "high_admitted_in_bounded_ticks" and c["ok"]
                for c in on["checks"]
            ),
            "passed": on["passed"],
            "checks": on["checks"],
        },
        "preemption_off": {
            "budget": off_budget,
            "passed": off["passed"],
            "checks": off["checks"],
        },
        "strictly_better": bool(
            on_budget is not None
            and off_budget is not None
            and on_budget > off_budget
        ),
        "diurnal_quiet": {
            "passed": quiet["passed"],
            "plane": quiet_plane,
            "events_dropped": quiet_events_dropped,
            "ok": quiet_ok,
        },
        "all_ok": bool(
            on["passed"]
            and off["passed"]
            and on_budget is not None
            and off_budget is not None
            and on_budget > off_budget
            and quiet_ok
        ),
    }


DEFAULT_SCENARIOS: Tuple[Scenario, ...] = (
    DiurnalLoad(),
    DeploymentWave(),
    NodeFailureWave(),
    MetricStorm(),
    LeaderKillComposite(),
    PartitionHandoff(),
    GangWave(),
)


def load_scenario(source) -> Scenario:
    """Load a committed fuzz find (``pas-fuzz-scenario/1`` JSON — a
    path, JSON text, or parsed dict) as a first-class Scenario, so a
    minimized reproducer under tests/scenarios/ replays anywhere a
    hand-written program does.  Lazy import: the fuzzer depends on this
    module, not the other way around."""
    from platform_aware_scheduling_tpu.testing import fuzz

    return fuzz.load_scenario(source)


def run_matrix(
    num_nodes: int = 64,
    pods: Optional[int] = None,
    period_s: float = 5.0,
    requests_per_tick: int = 2,
    latency_threshold_ms: float = 25.0,
    wire_slo_us: float = 500.0,
    scenarios: Tuple[Scenario, ...] = DEFAULT_SCENARIOS,
) -> Dict:
    """Run every scenario at the given scale; the bench's ``twin``
    section (benchmarks/twin_load.py) reports this matrix.  Fresh
    scenario INSTANCES per run — scenario objects carry per-run state.
    ``wire_slo_us`` tunes the diurnal wire-floor latency gate (0
    disables it)."""
    scale = {
        "num_nodes": num_nodes,
        "pods": pods if pods is not None else num_nodes,
        "period_s": period_s,
        "requests_per_tick": requests_per_tick,
        "latency_threshold_ms": latency_threshold_ms,
        "wire_slo_us": wire_slo_us,
    }
    results = {}
    for scenario in scenarios:
        fresh = type(scenario)()
        results[fresh.name] = fresh.run(scale)
    return {
        "num_nodes": num_nodes,
        "pods": scale["pods"],
        "period_s": period_s,
        "scenarios": results,
        "all_passed": all(r["passed"] for r in results.values()),
    }
