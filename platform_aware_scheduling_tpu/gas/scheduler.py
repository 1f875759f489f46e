"""GAS scheduling logic: Filter (per-card fit check) and Bind (card
assignment + annotation + bind).

Reference: gpu-aware-scheduling/pkg/gpuscheduler/scheduler.go.  Behaviors
reproduced:

  * Filter requires ``NodeNames`` (nodeCacheCapable mode, :455-461) and
    answers 404 + an Error result otherwise;
  * card selection is first-fit over sorted card names with per-GPU
    resource division via the ``i915`` count (:200-257, 180-198) — a card
    with room for several per-GPU shares can be picked more than once for
    the same container, exactly like the reference;
  * vanished GPUs (usage recorded for a card no longer in the node label)
    are tolerated and skipped (:230-234);
  * Bind re-runs scheduling on the chosen node, books resources, annotates
    the pod (``gas-ts`` + ``gas-container-cards``) with a 5-attempt
    conflict-retry, calls the Bind subresource, and rolls the booking back
    on any later failure (:385-445, 82-119);
  * Prioritize is 404 (:515-519).

The TPU path: Filter fans the per-node fit check out as ONE vmapped XLA
pass over all candidate nodes (ops/binpack.py) instead of the reference's
sequential per-node loop — the host loop remains as exact fallback/control.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

from platform_aware_scheduling_tpu.extender.server import (
    HTTPRequest,
    HTTPResponse,
)
from platform_aware_scheduling_tpu.extender.types import (
    Args,
    BindingArgs,
    BindingResult,
    FilterResult,
)
from platform_aware_scheduling_tpu.gas.cache import ADD, REMOVE, Cache
from platform_aware_scheduling_tpu.gas.resource_map import (
    NodeResources,
    ResourceMap,
)
from platform_aware_scheduling_tpu.gas.utils import (
    CARD_ANNOTATION,
    GPU_LIST_LABEL,
    GPU_PLUGIN_RESOURCE,
    RESOURCE_PREFIX,
    TS_ANNOTATION,
    container_requests,
)
from platform_aware_scheduling_tpu.kube.client import ConflictError
from platform_aware_scheduling_tpu.kube.retry import RetryPolicy
from platform_aware_scheduling_tpu.kube.objects import Node, Pod
from platform_aware_scheduling_tpu.utils import decisions, events, klog, trace
from platform_aware_scheduling_tpu.utils.quantity import Quantity
from platform_aware_scheduling_tpu.utils.tracing import LatencyRecorder

UPDATE_RETRY_COUNT = 5  # scheduler.go:28


class WontFitError(Exception):
    """will not fit (scheduler.go:49)"""


class NoGPUsError(WontFitError):
    """Node has no GPUs (vanished or never labeled) — a distinct
    provenance class from a genuine capacity miss, so the host loop and
    the device binpack produce the same reason code for it."""


def request_summary(pod: Pod) -> str:
    """Compact "res=total, ..." rendering of the pod's GPU resource
    request — the detail half of the gas capacity reason string,
    computed identically on the device and host paths (both read only
    the pod)."""
    totals: Dict[str, int] = {}
    for req in container_requests(pod):
        for name, value in req.items():
            totals[name] = totals.get(name, 0) + value
    return ", ".join(f"{k}={v}" for k, v in sorted(totals.items()))


class GASExtender:
    """extender.Scheduler implementation for GAS (scheduler.go:58-71)."""

    def __init__(
        self,
        kube_client,
        cache: Optional[Cache] = None,
        recorder: Optional[LatencyRecorder] = None,
        use_device: bool = True,
        use_mirror: bool = True,
        retry_policy: Optional[RetryPolicy] = None,
        sleep=time.sleep,
    ):
        self.kube_client = kube_client
        # backoff between annotate conflict-retries (the reference loop
        # at scheduler.go:82-119 retried with ZERO sleep, hammering the
        # API server exactly when it reported contention); deterministic
        # jitter, injectable sleep for hermetic tests
        self.retry_policy = (
            retry_policy
            if retry_policy is not None
            else RetryPolicy(
                max_attempts=UPDATE_RETRY_COUNT,
                base_delay_s=0.05,
                max_delay_s=1.0,
            )
        )
        self._sleep = sleep
        self.cache = cache if cache is not None else Cache(kube_client)
        self.recorder = recorder or LatencyRecorder()
        # workqueue work-latency histogram merges into this extender's
        # pas_request_duration_seconds family (verb="workqueue_work")
        self.cache.work_queue.recorder = self.recorder
        # what Bind and the host loop's Filter hold (scheduler.go's
        # rwmutex); a Filter the device answers takes the usage mirror's
        # lock instead and is only counted here, exactly under threads
        self._rwmutex = threading.RLock()
        self._in_flight_lock = threading.Lock()
        self._device_filters_in_flight = 0
        # opt-in utils.slo.SLOEngine (--slo=on): judged over this
        # extender's recorder; front-ends serve GET /debug/slo (404
        # while None) and /metrics gains the pas_slo_* gauges
        self.slo = None
        # opt-in utils.control.BudgetController (--sloControl=on): GAS
        # has no serving/rebalance/forecast actuators, so the controller
        # here only observes (ticks, /debug/control, pas_control_*) —
        # knobs attach where the subsystems exist
        self.control = None
        # opt-in utils.record.FlightRecorder (--flightRecorder=on):
        # gas_filter/gas_bind arrivals land in the ring as anonymized
        # (verb, candidate count) events — GAS has no interned-universe
        # layer, so the universe key is always null here; front-ends
        # serve GET /debug/record + POST /debug/whatif (404 while None)
        self.flight = None
        # opt-in admission.AdmissionPlane (--admission=on): GAS gets the
        # queue-only plane — capacity-class (WontFit) failures enqueue,
        # otherwise-admissible pods may be held behind higher-priority
        # waiters, and the front-ends serve GET /debug/admission (404
        # while None).  No gang tracker here, so backfill's covered-
        # demand check runs size-only and preemption never attaches
        # (docs/admission.md).  Off (None) keeps the wire byte-identical.
        self.admission = None
        self._device = None
        if use_device:
            # deferred import: keeps the host layer importable without jax
            from platform_aware_scheduling_tpu.gas.device import DeviceBinpacker

            self._device = DeviceBinpacker(self.cache, use_mirror=use_mirror)

    # -- verbs -----------------------------------------------------------------

    def metrics_text(self) -> str:
        """The /metrics provider for this extender (utils/trace.py);
        pas_slo_* gauges join only while an SLO engine is wired."""
        counter_sets = [self.slo.counters] if self.slo is not None else []
        if self.control is not None:
            counter_sets.append(self.control.counters)
        if self.flight is not None:
            counter_sets.append(self.flight.counters)
        if self.admission is not None:
            counter_sets.append(self.admission.counters)
        return trace.exposition(
            recorders=[self.recorder], counter_sets=counter_sets
        )

    def _record_flight_verb(self, verb: str, request: HTTPRequest) -> None:
        """Anonymized arrival event for the verb's finally (candidate
        count only — never node names); must never raise into the verb."""
        try:
            _uid, candidates = getattr(
                request, "flight_universe", (None, 0)
            )
            self.flight.record_verb(verb, None, candidates)
        except Exception as exc:
            klog.error("flight record failed: %r", exc)

    def readiness_conditions(self):
        """The /readyz conditions GAS contributes (utils/health.py):
        node + pod informer sync — GAS serves from its resource cache,
        so answering before the initial lists land would bind against
        a fictional cluster — plus the informational slo_burn condition
        while an SLO engine is wired."""
        conditions = [("informers_synced", self.cache.synced_condition)]
        if self.slo is not None:
            conditions.append(("slo_burn", self.slo.readiness_condition))
        return conditions

    def prioritize(self, request: HTTPRequest) -> HTTPResponse:
        # not implemented by GAS (scheduler.go:515-519)
        return HTTPResponse(status=404)

    def filter(self, request: HTTPRequest) -> HTTPResponse:
        start = time.perf_counter()
        span = trace.of(request)
        span.set("verb", "gas_filter")
        try:
            klog.v(4).info_s("filter request received", component="extender")
            try:
                with span.stage("decode"):
                    args = (
                        Args.from_json(request.body) if request.body else None
                    )
            except Exception as exc:
                args = None
                klog.error("cannot decode request %s", exc)
            if args is None:
                return HTTPResponse(status=404)
            if self.flight is not None:
                request.flight_universe = (
                    None, len(args.node_names or ())
                )
            admission_codes: Dict[str, int] = {}
            # kernel contains mirror_wait, state_upload, req_upload,
            # solve, rows and verdict (the host loop: lock_wait and the
            # loop): a container, so never annotated
            with span.stage("kernel", leaf=False):
                result = self._filter_nodes(
                    args, span=span, codes_out=admission_codes
                )
            span.set("pod", f"{args.pod.namespace}/{args.pod.name}")
            if self.admission is not None and not result.error:
                with span.stage("admission"):
                    result = self._admission_review(
                        args, result, admission_codes, span.trace_id
                    )
            status = 404 if result.error else 200
            with span.stage("encode"):
                body = result.to_json()
            events.JOURNAL.publish(
                "verdict",
                "gas_filter",
                request_id=span.trace_id,
                pod=f"{args.pod.namespace}/{args.pod.name}",
                data={
                    "failed": len(result.failed_nodes),
                    "path": str(span.attrs.get("path", "")),
                },
            )
            return HTTPResponse.json(body, status=status)
        finally:
            self.recorder.observe(
                "gas_filter", time.perf_counter() - start,
                trace_id=span.trace_id,
            )
            if self.flight is not None:
                self._record_flight_verb("gas_filter", request)

    def bind(self, request: HTTPRequest) -> HTTPResponse:
        start = time.perf_counter()
        span = trace.of(request)
        span.set("verb", "gas_bind")
        try:
            klog.v(4).info_s("bind request received", component="extender")
            try:
                with span.stage("decode"):
                    args = (
                        BindingArgs.from_json(request.body)
                        if request.body
                        else None
                    )
            except Exception as exc:
                args = None
                klog.error("cannot decode request %s", exc)
            if args is None:
                return HTTPResponse(status=404)
            # kernel contains pod_get, lock_wait, book, api_write, record
            with span.stage("kernel", leaf=False):
                result = self._bind_node(args, span=span)
            status = 404 if result.error else 200
            with span.stage("encode"):
                body = result.to_json()
            events.JOURNAL.publish(
                "verdict",
                "gas_bind",
                request_id=span.trace_id,
                pod=f"{args.pod_namespace}/{args.pod_name}",
                node=args.node,
                data={"status": status},
            )
            return HTTPResponse.json(body, status=status)
        finally:
            self.recorder.observe(
                "gas_bind", time.perf_counter() - start,
                trace_id=span.trace_id,
            )
            if self.flight is not None:
                self._record_flight_verb("gas_bind", request)

    # -- filter (scheduler.go:447-482) -----------------------------------------

    def _filter_nodes(
        self,
        args: Args,
        span=trace.NULL_SPAN,
        codes_out: Optional[Dict[str, int]] = None,
    ) -> FilterResult:
        if not args.node_names:
            error = (
                "No nodes to compare. This should not happen, perhaps the "
                "extender is misconfigured with NodeCacheCapable == false."
            )
            klog.error(error)
            return FilterResult(error=error)
        summary = request_summary(args.pod)
        if self._device is not None:
            # a Filter the device answers takes no verbs' mutex: the
            # mirror's own lock orders its solve against every booking
            # and release (gas/device.py _fit_mirror), and neither the
            # verdict nor the decision record reads the cluster cache
            with self._device_filter():
                try:
                    res = self._device.batch_fit(
                        args.pod, args.node_names, with_reasons=True,
                        span=span,
                    )
                except Exception as exc:
                    klog.error("device binpack failed, host fallback: %s", exc)
                    res = None
                if res is not None:
                    with span.stage("verdict"):
                        fits, codes = res
                        span.set("path", "device")
                        trace.COUNTERS.inc("pas_gas_filter_device_total")
                        node_names = list(
                            itertools.compress(args.node_names, fits)
                        )
                        missed = [not ok for ok in fits]
                        failed_names = list(
                            itertools.compress(args.node_names, missed)
                        )
                        failed_codes = list(itertools.compress(codes, missed))
                        # a reason is its code and the pod's request: one
                        # string a class, not one a node
                        reasons = {
                            code: decisions.gas_reason(code, summary)
                            for code in set(failed_codes)
                        }
                        failed = dict(zip(
                            failed_names,
                            map(reasons.__getitem__, failed_codes),
                        ))
                        if codes_out is not None:
                            codes_out.update(zip(failed_names, failed_codes))
                        self._record_filter_decision(
                            span, args.pod, args.node_names, failed, codes
                        )
                    return FilterResult(
                        node_names=node_names, failed_nodes=failed, error=""
                    )
        # the host loop reads the cache once a card a node, so it holds
        # the verbs' mutex as scheduler.go does.  The wait (the same
        # stage on Bind) apart from what is done under it: acquired by
        # hand so that the stage ends where the lock is held
        with span.stage("lock_wait"):
            self._rwmutex.acquire()
        try:
            span.set("path", "host")
            trace.COUNTERS.inc("pas_gas_filter_host_total")
            node_names: List[str] = []
            failed: Dict[str, str] = {}
            codes: List[int] = []
            for node_name in args.node_names:
                code = decisions.CODE_ELIGIBLE
                try:
                    self._run_scheduling_logic(args.pod, node_name)
                    node_names.append(node_name)
                except NoGPUsError:
                    code = decisions.CODE_GAS_NO_GPUS
                except WontFitError:
                    code = decisions.CODE_GAS_CAPACITY
                except KeyError:
                    # cache.fetch_node's miss signal — matches the device
                    # path's not-interned / not-known lanes
                    code = decisions.CODE_GAS_UNKNOWN_NODE
                except Exception:
                    # anything else (malformed capacity quantity, ...) is
                    # its own class: 'unknown to cache' would point an
                    # operator at a cache miss that never happened
                    code = decisions.CODE_GAS_ERROR
                if code != decisions.CODE_ELIGIBLE:
                    failed[node_name] = decisions.gas_reason(code, summary)
                    if codes_out is not None:
                        codes_out[node_name] = code
                codes.append(code)
            self._record_filter_decision(
                span, args.pod, args.node_names, failed, codes
            )
            return FilterResult(node_names=node_names, failed_nodes=failed, error="")
        finally:
            self._rwmutex.release()

    @contextlib.contextmanager
    def _device_filter(self):
        """Counts a Filter in while the device path has it: what a Bind
        reads to say whether it booked beside one
        (``pas_gas_bind_overlapped_total``)."""
        with self._in_flight_lock:
            self._device_filters_in_flight += 1
        try:
            yield
        finally:
            with self._in_flight_lock:
                self._device_filters_in_flight -= 1

    def _admission_review(
        self,
        args: Args,
        result: FilterResult,
        codes: Dict[str, int],
        request_id: str = "",
    ) -> FilterResult:
        """Consult the admission plane over one gas_filter verdict
        (admission/plane.py review contract): None keeps the verdict
        (admitted, or a WontFit-everywhere failure that enqueued); a
        replacement pair means HELD behind higher-priority queued work —
        every candidate fails CODE_ADMISSION_BLOCKED.  Fails open."""
        try:
            verdict = self.admission.review(
                args.pod,
                list(args.node_names or ()),
                dict(result.failed_nodes),
                codes,
                request_id=request_id,
            )
        except Exception as exc:
            klog.error("admission review failed open: %r", exc)
            return result
        if verdict is None:
            return result
        held, _codes = verdict
        merged = dict(result.failed_nodes)
        merged.update(held)
        node_names = [
            n for n in (result.node_names or []) if n not in held
        ]
        return FilterResult(
            node_names=node_names, failed_nodes=merged, error=result.error
        )

    def _record_filter_decision(
        self, span, pod: Pod, node_names, failed: Dict[str, str], codes
    ) -> None:
        """One gas_filter decision record + exact per-reason-class
        filtered-node counters (utils/decisions.py)."""
        log = decisions.DECISIONS
        if not log.enabled:
            return
        reason_counts = collections.Counter(codes)
        reason_counts.pop(decisions.CODE_ELIGIBLE, None)
        log.record_filter(
            verb="gas_filter",
            request_id=getattr(span, "trace_id", ""),
            pod_namespace=pod.namespace,
            pod_name=pod.name,
            policy="gas",
            path=str(span.attrs.get("path", "")),
            candidates=len(node_names),
            filtered=len(failed),
            violating=failed,
            violating_scope="request",
            reason_counts=reason_counts,
        )

    # -- scheduling core (scheduler.go:277-338) ---------------------------------

    def _run_scheduling_logic(self, pod: Pod, node_name: str) -> str:
        """Pick cards for every container of ``pod`` on ``node_name``;
        returns the annotation string, raises if the pod won't fit.  Does
        not mutate booked state."""
        node = self.cache.fetch_node(node_name)
        gpus = get_node_gpu_list(node)
        if not gpus:
            klog.warning("Node %s GPUs have vanished", node_name)
            raise NoGPUsError("will not fit")
        per_gpu_capacity = get_per_gpu_resource_capacity(node, len(gpus))
        used = self.cache.get_node_resource_status(node_name)
        gpu_set = set(gpus)
        for gpu in gpus:  # empty maps for unused cards (:269-275)
            used.setdefault(gpu, ResourceMap())
        annotation_parts: List[str] = []
        for i, request in enumerate(container_requests(pod)):
            cards = self._cards_for_container_request(
                request, per_gpu_capacity, node_name, pod.name, used, gpu_set
            )
            annotation_parts.append(",".join(cards))
        return "|".join(annotation_parts)

    def _cards_for_container_request(
        self,
        container_request: ResourceMap,
        per_gpu_capacity: ResourceMap,
        node_name: str,
        pod_name: str,
        used: NodeResources,
        gpu_set,
    ) -> List[str]:
        """First-fit card pick per requested GPU (scheduler.go:200-257);
        mutates ``used`` (the caller's scratch copy) as it books."""
        if not container_request:
            return []
        per_gpu_request, num_i915 = get_per_gpu_resource_request(container_request)
        cards: List[str] = []
        for _ in range(num_i915):
            fitted = False
            for gpu_name in sorted(used):
                if gpu_name not in gpu_set:
                    klog.warning(
                        "node %s gpu %s has vanished", node_name, gpu_name
                    )
                    continue
                if check_resource_capacity(
                    per_gpu_request, per_gpu_capacity, used[gpu_name]
                ):
                    try:
                        used[gpu_name].add_rm(per_gpu_request)
                    except Exception:
                        break
                    fitted = True
                    cards.append(gpu_name)
                    break
            if not fitted:
                klog.v(4).info_s(
                    f"pod {pod_name} will not fit node {node_name}",
                    component="extender",
                )
                raise WontFitError("will not fit")
        return cards

    # -- bind (scheduler.go:385-445) --------------------------------------------

    def _bind_node(
        self, args: BindingArgs, span=trace.NULL_SPAN
    ) -> BindingResult:
        try:
            with span.stage("pod_get"):
                pod = self.cache.fetch_pod(args.pod_namespace, args.pod_name)
        except Exception as exc:
            klog.warning("Pod %s couldn't be read or pod vanished", args.pod_name)
            return BindingResult(error=str(exc))
        with span.stage("lock_wait"):
            self._rwmutex.acquire()
        try:
            if self._device_filters_in_flight:
                trace.COUNTERS.inc("pas_gas_bind_overlapped_total")
            resources_adjusted = False
            annotation = ""
            try:
                with span.stage("book"):
                    annotation = self._run_scheduling_logic(pod, args.node)
                    self.cache.adjust_pod_resources_locked(
                        pod, ADD, annotation, args.node
                    )
                resources_adjusted = True
                with span.stage("api_write"):
                    self._annotate_pod_bind(annotation, pod)
                    self.kube_client.bind_pod(
                        args.pod_namespace, args.pod_name, args.pod_uid,
                        args.node,
                    )
                # outcome feedback: the successful bind closes this pod's
                # open gas_filter decision records (utils/decisions.py)
                with span.stage("record"):
                    decisions.DECISIONS.observe_bind(
                        args.pod_namespace, args.pod_name, args.node
                    )
                    if self.admission is not None:
                        self.admission.observe_bind(
                            args.pod_namespace, args.pod_name
                        )
                return BindingResult()
            except Exception as exc:
                klog.error("binding failed: %s", exc)
                if resources_adjusted:
                    # roll the booking back (scheduler.go:404-414)
                    try:
                        self.cache.adjust_pod_resources_locked(
                            pod, REMOVE, annotation, args.node
                        )
                    except Exception as rollback_exc:
                        klog.error("rollback failed: %s", rollback_exc)
                return BindingResult(error=str(exc))
        finally:
            self._rwmutex.release()

    def _annotate_pod_bind(self, annotation: str, pod: Pod) -> None:
        """Write gas-ts + gas-container-cards with a conflict-retry loop
        (scheduler.go:82-119)."""
        pod_copy = pod.deep_copy()
        ts = str(time.time_ns())  # pascheck: allow[clock] -- gas-ts is an externally-visible wall-clock annotation mirroring scheduler.go; nothing replays it
        last_exc: Optional[Exception] = None
        for attempt in range(UPDATE_RETRY_COUNT):
            pod_copy.annotations[TS_ANNOTATION] = ts
            pod_copy.annotations[CARD_ANNOTATION] = annotation
            try:
                self.kube_client.update_pod(pod_copy)
                klog.v(2).info_s(
                    f"Annotated pod {pod.name} with annotation {annotation}",
                    component="extender",
                )
                return
            except ConflictError as exc:
                last_exc = exc
                try:
                    pod_copy = self.kube_client.get_pod(
                        pod_copy.namespace, pod_copy.name
                    )
                except Exception:
                    klog.error("pod refresh failed")
                    break
                klog.error("pod update failed, retrying with refreshed pod")
                # back off before re-applying: a 409 means the API server
                # is under write contention on this object — re-hammering
                # it with zero sleep (the reference behavior) just
                # prolongs the conflict storm
                if attempt + 1 < UPDATE_RETRY_COUNT:
                    self._sleep(
                        self.retry_policy.backoff(
                            attempt + 1, verb="update_pod"
                        )
                    )
            except Exception as exc:
                last_exc = exc
                break
        klog.error(
            "Failed to annotate POD with container cards: %s", last_exc
        )
        raise last_exc if last_exc else RuntimeError("annotate failed")


# -- pure helpers (module-level like the reference) ----------------------------


def get_node_gpu_list(node: Node) -> List[str]:
    """Cards from the ``gpu.intel.com/cards`` label, "card0.card1..."
    (scheduler.go:132-148)."""
    labels = node.get_labels() if node is not None else None
    if not labels or GPU_LIST_LABEL not in labels:
        klog.error("gpulist label not found from node")
        return []
    return labels[GPU_LIST_LABEL].split(".")


def get_node_gpu_resource_capacity(node: Node) -> ResourceMap:
    """Allocatable entries under the gpu.intel.com/ prefix
    (scheduler.go:150-162)."""
    capacity = ResourceMap()
    for name, raw in node.allocatable.items():
        if name.startswith(RESOURCE_PREFIX):
            value, _ok = Quantity(str(raw)).as_int64()
            capacity[name] = value
    return capacity


def get_per_gpu_resource_capacity(node: Node, gpu_count: int) -> ResourceMap:
    """Node capacity divided evenly across cards — homogeneous-GPU
    assumption (scheduler.go:164-178)."""
    if gpu_count == 0:
        return ResourceMap()
    per_gpu = get_node_gpu_resource_capacity(node).new_copy()
    per_gpu.divide(gpu_count)
    return per_gpu


def get_num_i915(container_request: ResourceMap) -> int:
    """(scheduler.go:192-198)"""
    value = container_request.get(GPU_PLUGIN_RESOURCE, 0)
    return value if value > 0 else 0


def get_per_gpu_resource_request(
    container_request: ResourceMap,
) -> Tuple[ResourceMap, int]:
    """Divide the container request evenly across its i915 count
    (scheduler.go:180-190)."""
    per_gpu = container_request.new_copy()
    num_i915 = get_num_i915(container_request)
    if num_i915 > 1:
        per_gpu.divide(num_i915)
    return per_gpu, num_i915


def check_resource_capacity(
    needed: ResourceMap, capacity: ResourceMap, used: ResourceMap
) -> bool:
    """True when every needed resource fits under per-card capacity
    (scheduler.go:341-383): negative need/used fail, missing or non-positive
    capacity fails, int64 overflow of used+need fails."""
    int64_max = 2**63 - 1
    for name, need in needed.items():
        if need < 0:
            klog.error("negative resource request")
            return False
        cap = capacity.get(name)
        if cap is None or cap <= 0:
            klog.v(4).info_s(f" no capacity available for {name}")
            return False
        in_use = used.get(name, 0)
        if in_use < 0:
            klog.error("negative amount of resources in use")
            return False
        if in_use + need > int64_max:  # Go wraparound check (used+need < 0)
            klog.error("resource request overflow error")
            return False
        if cap < in_use + need:
            klog.v(4).info_s(" not enough resources")
            return False
    return True
