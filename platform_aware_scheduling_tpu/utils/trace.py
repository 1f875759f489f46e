"""End-to-end request tracing: spans, path attribution, JAX compile
visibility, and the metric-name inventory behind /metrics.

The reference PAS suite has no tracing or profiling at all (SURVEY §5.1 —
klog verbosity only).  This framework's north star is p99 Prioritize
latency under concurrent load, so "where did this request's 4 ms go" must
be answerable in production, not reconstructed from benchmarks:

  * :class:`Span` — one per HTTP request, opened at connection accept in
    BOTH front-ends (extender/server.py and serving/http.py), carrying a
    generated-or-propagated ``X-Request-ID`` (echoed on every response,
    including 503 backpressure rejections) and named child stage timings
    (read, queue_wait, coalesce, decode, kernel, encode, write) recorded
    by each layer as the request flows through;
  * :class:`TraceBuffer` — a bounded, lock-light ring of recent completed
    spans plus a bounded top-K of the slowest, served as JSON on
    ``GET /debug/traces``;
  * ``COUNTERS`` — process-wide path-attribution counters (fastpath
    hit/miss, native vs host fallback, filter cache tiers) and JAX
    compile/retrace counters, merged into ``/metrics``;
  * :func:`watch_jit` / :func:`install_jax_hooks` — lowering-count shim
    around the scoring kernels plus ``jax.monitoring`` listeners, so an
    unexpected recompile in the hot path is a visible metric
    (``pas_jax_retrace_total``), not a latency mystery;
  * :data:`METRICS` — the single declared inventory of every metric name
    this process may emit (``make trace-lint`` enforces the ``pas_``
    prefix / snake_case convention and no duplicates against it);
  * :func:`parse_prometheus_text` — an in-tree text-format parser used by
    tests to prove ``/metrics`` is real Prometheus exposition.

Tracing is always-on: a span costs two ``perf_counter`` reads per stage
and one short lock acquisition at completion.  A stage is ONE interval on
two clocks (:class:`_Stage`): ``perf_counter`` for the span (and, off a
request, a seconds counter), and — leaves only, while a profile is being
taken — a ``jax.profiler.TraceAnnotation("pas:<stage>")`` so that a
profiled window shows the program's stages on the profiler's own clock
beside the device's operations.  The sub-stages of the sub-millisecond
verbs are ``sampled``: recorded on one span in :data:`SAMPLE_EVERY`, a
no-op on the others.  This module must stay importable without jax (the
host layer's rule); everything jax touches is imported lazily.
"""

from __future__ import annotations

import gc
import itertools
import json
import sys
import threading
import time
import uuid
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from platform_aware_scheduling_tpu.utils.tracing import (
    CounterSet,
    LatencyRecorder,
    histograms_text,
)

# ---------------------------------------------------------------------------
# metric-name inventory
# ---------------------------------------------------------------------------

#: name -> (kind, help).  The ONE authority for every metric name this
#: process may emit; tests/test_trace_lint.py asserts live /metrics
#: output against it (pas_ prefix, snake_case, declared, no duplicates).
METRICS: Dict[str, Tuple[str, str]] = {}


def declare(name: str, kind: str, help_text: str) -> None:
    if name in METRICS:
        raise ValueError(f"metric {name!r} declared twice")
    METRICS[name] = (kind, help_text)


declare(
    "pas_request_duration_seconds",
    "histogram",
    "Verb/stage wall latency (labels: verb).",
)
# serving micro-batcher (serving/dispatcher.py, serving/batch.py)
declare("pas_serving_requests_total", "counter", "Requests submitted to the async dispatcher.")
declare("pas_serving_batches_total", "counter", "Coalesced batches dispatched.")
declare("pas_serving_batched_requests_total", "counter", "Requests served through coalesced batches.")
declare("pas_serving_rejected_total", "counter", "Requests shed with 503 at a saturated admission queue.")
declare("pas_serving_batch_fallback_total", "counter", "Batches that fell back to per-request routing.")
declare("pas_serving_fused_solves_total", "counter", "Device computations performed by fused batch warms.")
declare("pas_serving_queue_depth", "gauge", "Current admission-queue depth.")
# path attribution (tas/telemetryscheduler.py, tas/fastpath.py).  The
# three pas_prioritize_{native,native_host,exact}_total counters
# PARTITION prioritize requests by the path that produced the answer;
# host_fallback counts degradation EVENTS and overlaps them.
declare("pas_prioritize_native_total", "counter", "Prioritize requests answered by the native wire path's device fastpath (incl. its trivial empty answers).")
declare("pas_prioritize_native_host_total", "counter", "Prioritize requests on the native wire path answered with exact host semantics (host-only policy/metric, or after a device failure).")
declare("pas_prioritize_exact_total", "counter", "Prioritize requests served by the exact Python path.")
declare("pas_prioritize_host_fallback_total", "counter", "Device-path failures degraded to host semantics (events; overlaps the partition counters).")
declare("pas_device_path_errors_total", "counter", "Device-path failures caught off the Prioritize verb and served by the host path or the next pass (label: site in warm_fastpath/publish_round/warm_forecast/warm_batch/filter_probe/filter_violations/deschedule/gang_topology).")
declare("pas_fastpath_response_hit_total", "counter", "Prioritize response-reuse cache hits (span memcmp).")
declare("pas_fastpath_response_miss_total", "counter", "Prioritize response-reuse cache misses.")
declare("pas_filter_cache_hit_total", "counter", "Filter response cache hits.")
declare("pas_filter_cache_miss_total", "counter", "Filter cacheable requests that missed the response cache.")
declare("pas_filter_cache_bypass_total", "counter", "Filter requests not cacheable (host-only policy, odd shapes, no native scanner).")
declare("pas_filter_native_total", "counter", "Filter requests answered by the native encoder after a response-cache miss (label: wire in nodes/names — Nodes echoed as slices of the request, or NodeNames); over the Filters served it is the native path's share.")
# interned node-name universes (native/wirec.c UniverseCache via
# tas/fastpath.py).  hits+misses partition every probe against an
# available universe cache; evictions count universes dropped past the
# MRU bound (PAS_TPU_UNIVERSE_CACHE).
declare("pas_wire_intern_hits_total", "counter", "Candidate-span universe-cache hits (digest + memcmp-verified).")
declare("pas_wire_intern_misses_total", "counter", "Candidate-span universe-cache misses (cold span, or first sighting before interning).")
declare("pas_wire_intern_evictions_total", "counter", "Interned universes evicted past the MRU bound.")
declare("pas_gas_filter_device_total", "counter", "GAS Filter requests served by the vmapped device binpack.")
declare("pas_gas_filter_host_total", "counter", "GAS Filter requests served by the host loop.")
# how the resident usage mirror was brought current for a device solve
# (gas/device.py GASUsageMirror.stage); the two *_total partition the solves
declare("pas_gas_state_incremental_total", "counter", "GAS device solves whose usage state was brought current by an update block of changed rows (a zero-row block included).")
declare("pas_gas_state_full_restage_total", "counter", "GAS device solves that re-uploaded the whole usage tensor (more changed rows than the block holds, or a structure change).")
declare("pas_gas_state_rows_applied_total", "counter", "Usage rows sent to the device inside update blocks.")
declare("pas_gas_bind_overlapped_total", "counter", "GAS Binds that took the verbs' mutex while a device Filter was in flight (a Filter the device answers takes the usage mirror's lock, not the mutex).")
# batch planner (tas/planner.py; --batchPlanner): one replan after every
# refresh pass.  snapshot + solve + publish <= replan; promoted, stale and
# unplanned partition the Prioritize answers given with the planner on;
# reordered is the part of promoted in which the plan changed the answer.
declare("pas_planner_replans_total", "counter", "Batch-planner replans that solved a pending set.")
declare("pas_planner_replan_seconds_total", "counter", "Seconds spent in those replans, snapshot to published plan.")
declare("pas_planner_snapshot_seconds_total", "counter", "Replan seconds reading the pending set, the policies and the nodes' room into the solve's operands.")
declare("pas_planner_room_seconds_total", "counter", "Of the snapshot's seconds, those building the nodes' room rows and the pods' demand columns on the host.")
declare("pas_planner_solve_seconds_total", "counter", "Replan seconds from the solve's dispatch to the readback of its assignment.")
declare("pas_planner_publish_seconds_total", "counter", "Replan seconds building and publishing the plan's pod -> node table.")
# the solve over more than one device (--batchPlannerDevices > 1); never
# emitted by a one-device planner.  place lies between snapshot and solve:
# snapshot + place + solve + publish <= replan
declare("pas_planner_place_seconds_total", "counter", "Replan seconds placing the solve's operands on the mesh: the view's metric matrix and the room vector split over the nodes, the rest on every device.")
declare("pas_planner_mesh_devices", "gauge", "Devices the batch planner's solve spans (set only when more than one).")
declare("pas_planner_mesh_solves_total", "counter", "Replans solved node-sharded over the mesh.")
declare("pas_planner_mesh_demand_solves_total", "counter", "Of the mesh's replans, those in which every pod booked its own pods, cpu and memory: the pending pods asked for unlike amounts.")
declare("pas_planner_mesh_demand_seconds_total", "counter", "Replan seconds of those mesh replans, snapshot to published plan.")
declare("pas_planner_demand_solves_total", "counter", "Replans solved with per-pod demands: the pending pods asked for unlike amounts, and each booked its own pods, cpu and memory (a replan of alike pods books one unit a pod and does not count).")
declare("pas_planner_demand_classes", "gauge", "Distinct (cpu, memory) request vectors in the last replan's pending set.")
declare("pas_planner_conservative_room_total", "counter", "Replans of unlike pods solved with every pod counted as the largest request in the set (never an overcommit, not exact): the sinkhorn form alone, which takes no per-pod demand.")
declare("pas_planner_pending_pods", "gauge", "Pending pods the last replan solved.")
declare("pas_planner_promoted_total", "counter", "Prioritize answers that carried a current plan's node to rank 1.")
declare("pas_planner_reordered_total", "counter", "Of those, answers in which the plan's node was moved past a candidate the ordinal ranking put first: the answers the plan changed.")
declare("pas_planner_stale_total", "counter", "Prioritize answers for a planned pod whose plan's version was not the mirror's.")
declare("pas_planner_unplanned_total", "counter", "Prioritize answers with no plan entry for the pod, or whose planned node was not among the candidates.")
# JAX compile visibility (watch_jit shim + jax.monitoring listeners)
declare("pas_jax_kernel_compile_total", "counter", "Lowerings of watched scoring kernels (watch_jit shim).")
declare("pas_jax_retrace_total", "counter", "Watched-kernel lowerings past each kernel's first compile: unexpected hot-path retraces.")
declare("pas_jax_backend_compile_total", "counter", "Process-wide XLA backend compilations (jax.monitoring).")
declare("pas_jax_compile_seconds_total", "counter", "Process-wide seconds spent in XLA backend compilation.")
declare("pas_xla_compiles_total", "counter", "Jit cache growth per watched kernel (label: fn) — the recompile watch; steady state after warmup must be flat (ops/solveobs.py).")
# solve observatory (ops/solveobs.py; --solveObs=on): per-stage device-
# solve attribution + refresh churn.  Families emitted only while an
# observatory is enabled — the flight recorder's off-path convention.
declare("pas_solve_stage_us", "histogram", "Per-stage solve latency in microseconds (label: stage — snapshot/transfer/compile/execute/readback/encode).")
declare("pas_solve_samples_total", "counter", "Instrumented solves committed to the observatory ring (label: kind).")
declare("pas_state_churn_rows", "histogram", "Node columns changed per metric per refresh pass (label: metric); zero has its own bucket.")
declare("pas_state_churn_fraction", "histogram", "Changed columns as a fraction of world size per metric per refresh pass (label: metric).")
declare("pas_state_churn_passes_total", "counter", "Refresh passes whose churn the observatory flushed.")
declare("pas_state_churn_rows_changed_total", "counter", "Total node columns changed across all flushed refresh passes.")
# trace buffer health
declare("pas_traces_recorded_total", "counter", "Completed spans recorded into the trace ring buffer.")
# who held the interpreter (docs/observability.md "Who holds the
# interpreter").  Label-free families, folded for every POST /scheduler/*
# span in TraceBuffer.add under the ring's own lock and moved here when
# /metrics is rendered (_flush_verbs): a reader that sums a family over
# its label sets (perfbench's) can read each alone.
declare("pas_verb_total", "counter", "Served verb spans (POST /scheduler/*) finished.")
declare("pas_verb_seconds_total", "counter", "Their wall seconds, first byte there to answer written (the arrival wait included where it was stamped).")
declare("pas_verb_cpu_seconds_total", "counter", "Thread CPU seconds of the verb spans whose CPU clock was read (one span at most every trace.CPU_SAMPLE_GAP_S: a read of the thread's CPU clock is a system call), first byte held to answer written.")
declare("pas_verb_cpu_wall_seconds_total", "counter", "Wall seconds of those same spans over the same interval: pas_verb_cpu_seconds_total over this is the share of a verb its thread spent running, the rest blocked or waiting for the interpreter.")
declare("pas_verb_arrive_total", "counter", "Verb spans that carried an arrival stamp (plain socket, native recv_stamped).")
declare("pas_verb_arrive_wait_seconds_total", "counter", "Seconds verbs waited for the interpreter with their first bytes already received (stage arrive: recv returned -> GIL held).")
declare("pas_verb_read_seconds_total", "counter", "Seconds of verbs' read stage (first byte held -> last byte of the body).")
declare("pas_verb_read_gil_seconds_total", "counter", "Of those, seconds the reading thread waited for the interpreter after a recv had returned (span attribute read_gil_ms).")
declare("pas_verb_read_calls_total", "counter", "Reads the verbs' threads made from Python on their requests' way in, each one release of the interpreter (span attribute read_calls): the head's recvs, then one native read a body that did not come with its head — one recv_into a piece where the body is read through the socket object (TLS, no _wirec).")
declare("pas_verb_write_releases_total", "counter", "Answers whose native send gave the interpreter away (span attribute write_releases: 0 where the kernel took the whole answer with the GIL held, else 1); sendall's answers (TLS, no _wirec) are not counted.")
declare("pas_stage_handle_total", "counter", "Verb spans that recorded the sampled stage handle (one span in SAMPLE_EVERY).")
declare("pas_stage_handle_seconds_total", "counter", "Seconds of those handle stages: route(request), whole.")
declare("pas_stage_scan_total", "counter", "Sampled scan stages recorded on verb spans (Filter's native scan in the probe).")
declare("pas_stage_scan_seconds_total", "counter", "Seconds of those scan stages.")
declare("pas_stage_gang_reserve_total", "counter", "Verb spans that recorded the stage gang_reserve: a gang member's Filter (or Prioritize) that solved for a slice.")
declare("pas_stage_gang_reserve_seconds_total", "counter", "Seconds of those gang_reserve stages: held cells or map, free mask, the solve over every ICI domain, anchor and names.")
declare("pas_stage_gang_upload_seconds_total", "counter", "Seconds of the stages gang_upload inside gang_reserve: the solve's free mask put on the device (over pas_stage_gang_reserve_total, its share of a reservation).")
declare("pas_stage_gang_launch_seconds_total", "counter", "Seconds of the stages gang_launch inside gang_reserve: every orientation's program of the solve dispatched.")
declare("pas_stage_gang_fetch_seconds_total", "counter", "Seconds of the stages gang_fetch inside gang_reserve: the orientations' anchors read back, the one wait for the device and for the interpreter after it.")
declare("pas_stage_gang_overlay_total", "counter", "Verb spans that recorded the stage gang_overlay: the gang tracker's Filter or Prioritize overlay, on the exact path.")
declare("pas_stage_gang_overlay_seconds_total", "counter", "Seconds of those gang_overlay stages.")
declare("pas_stage_gang_merge_total", "counter", "Verb spans that recorded the stage gang_merge: a Filter that built the merged (telemetry + gang-held) verdict of a new reservation version.")
declare("pas_stage_gang_merge_seconds_total", "counter", "Seconds of those gang_merge stages: the merged violating rows, mask, reason table and reasons of one reservation version.")
declare("pas_refresh_pass_cpu_seconds_total", "counter", "The refresh thread's CPU seconds inside telemetry refresh passes (beside pas_refresh_pass_seconds_total: the rest of a pass it was blocked, asleep by design or waiting for the interpreter).")
# the thread ledger, read when /metrics is rendered (thread_cpu): CPU
# seconds by thread role — under one GIL, to first order who held the
# interpreter.  verbs + refresh + informers + other = the process's CPU.
declare("pas_cpu_verbs_seconds_total", "counter", "CPU seconds of the connection handlers (threads pas-conn-*), closed connections included.")
declare("pas_cpu_refresh_seconds_total", "counter", "CPU seconds of the refresh thread (pas-refresh: passes, publishes, warms and the batch planner's replan).")
declare("pas_cpu_informers_seconds_total", "counter", "CPU seconds of the informers and the GAS work-queue worker (pas-informer-*, pas-gas-worker).")
declare("pas_cpu_other_seconds_total", "counter", "The process's CPU seconds (time.process_time) less the three roles above: the accept loop, the other pas-* loops, JAX's and any unnamed thread.")
declare("pas_cpu_wall_seconds_total", "counter", "Monotonic seconds since utils/trace.py was imported (the process's start, nearly): the denominator of the pas_cpu_* shares.")
# collector pauses (watch_gc: one gc.callbacks entry; a span that a
# collection ended inside carries the attribute gc_ms)
declare("pas_gc_pause_seconds_total", "counter", "Seconds the interpreter spent inside garbage collections (every thread is stopped for them).")
declare("pas_gc_collections_total", "counter", "Garbage collections completed (label: generation).")
# health & readiness (utils/health.py: /healthz + /readyz on both front-ends)
declare("pas_ready", "gauge", "Composite readiness: 1 when every /readyz condition holds, else 0.")
declare("pas_ready_transitions_total", "counter", "Readiness flips (ready <-> not ready) observed across /readyz evaluations.")
# telemetry cache & controller health (tas/cache.py refresh loop,
# tas/strategies evaluation counters)
declare("pas_telemetry_metric_age_seconds", "gauge", "Seconds since each registered telemetry metric's last successful refresh (label: metric).")
declare("pas_telemetry_refresh_total", "counter", "Telemetry cache refresh passes completed.")
declare("pas_telemetry_refresh_errors_total", "counter", "Individual metric fetch failures across refresh passes.")
# refresh-pass split (tas/cache.py update_all_metrics + the warm it
# triggers): four families, not one with a stage label — fetch + publish +
# warm <= pass, the rest being pass accounting and end-of-pass hooks
declare("pas_refresh_pass_seconds_total", "counter", "Seconds spent inside telemetry refresh passes (whole update_all_metrics, end-of-pass hooks included).")
declare("pas_refresh_fetch_seconds_total", "counter", "Seconds of refresh passes spent fetching and parsing metrics from the custom-metrics API (per metric, refresh_filter included): the API's answer, plus pas_refresh_parse_seconds_total.")
declare("pas_refresh_parse_seconds_total", "counter", "The program's part of the fetch: seconds from the custom-metrics API's answer to the round handed on as columns (tas/metrics.py MetricColumns).")
declare("pas_refresh_ingest_total", "counter", "Data-bearing metric rounds published into the tensor mirror (label: path = columnar, a fetched round scattered as one vector | items, a plain dict or a partition-scoped mirror staged node by node).")
declare("pas_refresh_ingest_quantity_fallback_total", "counter", "Fetched value strings that were no plain decimal integer (or overflowed int64 in milli) and took the Quantity parser.")
declare("pas_refresh_publish_seconds_total", "counter", "Seconds of refresh passes spent in write_metric through the mirror's publish, less the fastpath warm it triggers.")
declare("pas_refresh_warm_seconds_total", "counter", "Seconds spent in warm_fastpath (ranking precompute, violation sets, response skeletons) — in steady state all on the refresh thread.")
declare("pas_refresh_handoff_seconds_total", "counter", "Seconds the publishing thread stood aside after one-row publishes, once each round could be answered (ops/state.py PUBLISH_HANDOFF_S a publish): idle by design, in the pass but in none of fetch, publish and warm.")
declare("pas_refresh_warm_total", "counter", "State changes of the tensor mirror warmed (label: path = incremental, a one-row publish applied to the resident device state and warmed by its one round trip | full, the whole matrix restaged: more than one row moved, a node or metric interned, a metric deleted, a partition scope, a plain-dict round, a device failure).")
declare("pas_refresh_warm_device_calls_total", "counter", "Uploads, dispatches and readbacks made by publishes, their warms and any pass made alone: 3 a round trip (the staged buffer, the program, the packed answer), 3 more for a view uploaded whole, 6 a ranking and 3 a rule pass made for one pair or policy (a request-path miss, a forecast view, the micro-batcher).")
declare("pas_strategy_evaluations_total", "counter", "Strategy violation evaluations (label: strategy).")
declare("pas_strategy_violations_total", "counter", "Violating nodes found by strategy evaluations (label: strategy).")
declare("pas_strategy_enforcements_total", "counter", "Enforcement passes completed without error (label: strategy); pairs with pas_strategy_violations_total for whether they changed anything.")
# controller plumbing (kube/workqueue.py + kube/informer.py; named
# instances only — an unnamed queue/informer stays silent)
declare("pas_workqueue_depth", "gauge", "Current work-queue depth (label: queue).")
declare("pas_workqueue_adds_total", "counter", "Items accepted into the work queue (label: queue).")
declare("pas_workqueue_retries_total", "counter", "Rate-limited re-adds after failures (label: queue).")
declare("pas_workqueue_done_total", "counter", "Items finished processing (label: queue).")
declare("pas_informer_relists_total", "counter", "Informer list/re-list passes started (label: informer).")
declare("pas_informer_watch_errors_total", "counter", "Informer watch streams that broke and forced a re-list (label: informer).")
declare("pas_informer_synced", "gauge", "1 once the informer's initial list has delivered (label: informer).")
# device & compile visibility (utils/backend.py, utils/devicewatch.py)
declare("pas_device_info", "gauge", "Devices JAX found at start-up (labels: platform, kind; value = device count) — a replica that fell back to the CPU shows platform=\"cpu\".")
declare("pas_device_memory_in_use_bytes", "gauge", "Device memory currently allocated (label: device; absent on backends without memory_stats).")
declare("pas_device_memory_peak_bytes", "gauge", "Peak device memory watermark (label: device).")
declare("pas_device_memory_limit_bytes", "gauge", "Device memory ceiling (label: device).")
declare("pas_device_kernel_flops", "gauge", "XLA cost-analysis FLOPs for each watched kernel's first compile (label: kernel).")
declare("pas_device_kernel_bytes", "gauge", "XLA cost-analysis bytes accessed for each watched kernel's first compile (label: kernel).")
declare("pas_profile_captures_total", "counter", "Bounded jax.profiler traces captured via GET /debug/profile.")
# closed-loop rebalancer (rebalance/: drift detector -> incremental
# replan -> safe eviction actuation; docs/rebalance.md)
declare("pas_rebalance_plans_total", "counter", "Rebalance cycles that produced a plan (including empty plans).")
declare("pas_rebalance_moves_planned_total", "counter", "Pod moves proposed by rebalance plans (within the churn budget).")
declare("pas_rebalance_moves_executed_total", "counter", "Pod evictions actually executed by the rebalance actuator.")
declare("pas_rebalance_moves_skipped_total", "counter", "Planned moves not executed (label: reason in dry_run/rate_limit/cooldown/min_available/pdb/gang_partial/fenced/error).")
declare("pas_rebalance_candidate_nodes", "gauge", "Nodes currently past the deschedule hysteresis threshold (eviction candidates).")
declare("pas_rebalance_convergence_cycles", "gauge", "Enforcement cycles the most recent violation episode took from first violation back to zero.")
declare("pas_rebalance_plan_latency_seconds", "gauge", "Wall latency of the most recent incremental replan solve.")
# fault-tolerant control plane (kube/retry.py + tas/degraded.py;
# docs/robustness.md): retried API calls, circuit-breaker state, and the
# per-subsystem degraded gauges
declare("pas_kube_retry_total", "counter", "API-call retries performed by the fault-tolerant client (labels: verb, reason in throttled/server_error/network/api_error).")
declare("pas_kube_giveup_total", "counter", "API calls abandoned after exhausting the retry budget or deadline (label: verb).")
declare("pas_circuit_state", "gauge", "Circuit-breaker state per endpoint group: 0 closed, 1 half-open, 2 open (label: group).")
declare("pas_circuit_transitions_total", "counter", "Circuit-breaker state transitions (labels: group, to).")
declare("pas_degraded", "gauge", "1 while the named subsystem runs degraded: telemetry (stale/unrefreshable), kube_api / metrics_api (circuit not closed), evictions (suspended) (label: subsystem).")
# decision provenance (utils/decisions.py: per-decision explain records,
# placement-quality feedback, /debug/decisions; docs/observability.md
# "Decision provenance")
declare("pas_decision_records_total", "counter", "Scheduling decisions recorded into the decision log (label: verb in filter/prioritize/gas_filter/rebalance/control/admission/preemption).")
declare("pas_decision_filtered_nodes_total", "counter", "Nodes filtered out of scheduling decisions, by reason class (label: reason in rule_violation/fail_closed/gas_unknown_node/gas_no_gpus/gas_capacity/gas_error/gang_reserved/gang_infeasible/admission_blocked).")
declare("pas_decision_open", "gauge", "Decision records currently awaiting outcome feedback (pod bind / rebalance).")
declare("pas_decision_closed_total", "counter", "Decision records closed by a pod-bind observation.")
declare("pas_decision_violated_at_bind_total", "counter", "Pods bound onto a node the Filter decision had marked violating — the placement-quality red flag.")
declare("pas_decision_chosen_rank_total", "counter", "Bind observations by the chosen node's rank in the Prioritize ordering (label: rank in 1/2/3/4_8/9_16/17_plus/unknown).")
declare("pas_decision_evicted_open_total", "counter", "Open decision records overwritten by the ring before any outcome feedback arrived (ring too small for the bind latency).")
# gang & topology-aware scheduling (gang/group.py + ops/topology.py:
# atomic multi-host slice placement with TTL reservations; docs/gang.md)
declare("pas_gang_reservations_total", "counter", "Gang slice reservations created (a feasible anchor found and its nodes held).")
declare("pas_gang_reserve_rows_total", "counter", "Of those, reservations whose free mask came from the Filter's candidate rows (a scatter of their mesh cells, less the held cells), not from the candidates' names.")
declare("pas_gang_reservation_expirations_total", "counter", "Gang reservations reclaimed after their TTL expired before the gang fully bound.")
declare("pas_gang_admitted_total", "counter", "Gangs fully bound (every member landed on its reserved slice).")
declare("pas_gang_filter_native_total", "counter", "Gang members' Filters answered by the native encoder from the tracker's compact verdict (each also counted in pas_filter_cache_miss_total and in pas_filter_native_total, wire names); over the Filters served it is the members' native share.")
declare("pas_gang_rejected_total", "counter", "Gang Filter passes that found no feasible slice (label: reason in infeasible/no_mesh).")
declare("pas_gang_active", "gauge", "Gangs currently tracked and not yet fully bound (forming or reserved).")
declare("pas_gang_reserved_nodes", "gauge", "Nodes currently held by gang reservations (bound gangs included until released).")
declare("pas_gang_member_binds_total", "counter", "Gang members' bindings the tracker learned (from the pod feed, or a Bind verb), each once.")
declare("pas_gang_domains", "gauge", "ICI domains (pas-tpu-domain values; unlabeled nodes form one) of the tracker's mesh when it was last built.")
declare("pas_gang_time_to_full_seconds", "histogram", "Time from a gang's first sighting to fully bound (label: topology).")
# predictive telemetry (forecast/engine.py + ops/forecast.py: batched
# EWMA/Holt fits over the refresh history; docs/forecast.md)
declare("pas_forecast_fit_passes_total", "counter", "Batched forecast fit passes completed (one per telemetry refresh pass with history movement).")
declare("pas_forecast_extrapolated_serves_total", "counter", "Degraded-mode requests served past the frozen-LKG window under forecast confidence: Prioritize ranks on the extrapolated predictions, Filter keeps the last-known-good verdicts alive.")
declare("pas_forecast_suppressed_evictions_total", "counter", "Eviction escalations held back because every violated metric was trending down (transient spike) when snapshot hysteresis would have escalated.")
declare("pas_forecast_metric_slope", "gauge", "Mean per-node forecast slope in metric units per second (label: metric).")
# HA control plane (kube/lease.py leader election + gang/journal.py
# crash-safe reservation journal; docs/robustness.md "HA & leader
# election")
declare("pas_leader", "gauge", "1 while this replica holds the leadership lease and runs the singleton actuation loops (label: replica).")
declare("pas_leader_transitions_total", "counter", "Local leadership role changes (gained or lost) observed by this replica's elector.")
declare("pas_gang_journal_writes_total", "counter", "Gang reservation journal snapshots committed to the ConfigMap backend.")
declare("pas_gang_journal_skipped_total", "counter", "Journal writes not attempted or failed, leaving the tracker in-memory-only (label: reason in circuit_open/error).")
declare("pas_gang_journal_recovered_total", "counter", "Gang reservations restored from the journal at startup after reconciling against live pods.")
declare("pas_gang_journal_discarded_total", "counter", "Journal entries discarded at recovery because live pods contradicted them (stale journal must not admit a straddling gang).")
# service-level objectives (utils/slo.py: declarative SLIs over the
# recorders/counters, multi-window multi-burn-rate alerting;
# docs/observability.md "SLOs & error budgets").  These families live in
# the SLO engine's own CounterSet and appear on /metrics only where an
# engine is wired (--slo=on) — the off path registers nothing.
declare("pas_slo_compliance", "gauge", "Good-event fraction over the budget window per SLO; 1.0 when the window saw no events (label: slo).")
declare("pas_slo_error_budget_remaining", "gauge", "Fraction of the error budget left over the budget window: 1 - burn_rate(budget window); negative means overspent (label: slo).")
declare("pas_slo_burn_rate", "gauge", "Error-budget burn rate per sliding window: bad fraction / (1 - objective); 1.0 spends the budget exactly by window end (labels: slo, window).")
declare("pas_slo_breaches_total", "counter", "Alert-tier entries per SLO, edge-triggered: page when both fast windows burn past page_burn, warn when both slow windows burn past warn_burn (labels: slo, tier).")
# budget feedback control (utils/control.py; docs/observability.md
# "Budget feedback control").  These families live in the controller's
# own CounterSet and appear on /metrics only where one is wired
# (--sloControl=on) — the off path registers nothing.
declare("pas_control_knob_setting", "gauge", "Current setting of each budget-controller knob (label: knob); equals the knob's baseline while no actuation has tightened it.")
declare("pas_control_actuations_total", "counter", "Budget-controller knob steps taken (labels: knob, direction in tighten/loosen, slo = trigger SLO or 'trend' for pre-arming).")
declare("pas_control_ticks_total", "counter", "Budget-controller evaluation passes completed (one per SLO engine tick while wired).")
declare("pas_control_prearmed", "gauge", "1 while the shed knob is tightened by the forecaster's trend signal ahead of any budget burn, else 0.")
# flight recorder + what-if serving (utils/record.py, testing/replay.py;
# docs/observability.md "Flight recorder & what-if").  The pas_record_*
# families live in the recorder's own CounterSet and appear on /metrics
# only while one is wired (--flightRecorder=on) — like pas_slo_*, the
# off path registers nothing and stays byte-identical on the wire.
declare("pas_record_events_total", "counter", "Anonymized events accepted into the flight-recorder ring (verb arrivals, telemetry deciles, eviction/leader flips).")
declare("pas_record_dropped_total", "counter", "Oldest flight-recorder events evicted by ring overflow.")
declare("pas_whatif_runs_total", "counter", "What-if twin replay runs served (POST /debug/whatif + the cmd.whatif CLI).")
declare("pas_whatif_failures_total", "counter", "What-if runs that failed to parse their capture or crashed mid-replay.")
# priority-aware admission plane (admission/plane.py + admission/preempt.py;
# docs/admission.md).  The pas_admission_*/pas_preemption_* families live
# in the plane's own CounterSet and appear on /metrics only where one is
# wired (--admission=on) — the off path registers nothing and stays
# byte-identical on the wire.
declare("pas_admission_queued_total", "counter", "Pods enqueued after a capacity-class Filter failure (label: class).")
declare("pas_admission_admitted_total", "counter", "Filter admissions the gate allowed through (label: class) — per decision, not per pod.")
declare("pas_admission_backfill_total", "counter", "Admissions that flowed around a higher-priority waiter whose demand stayed covered (label: class).")
declare("pas_admission_blocked_total", "counter", "Filter passes held back behind a higher-priority waiter (label: class) — the head-of-line gate.")
declare("pas_admission_rejected_total", "counter", "Queue departures without admission (labels: class, reason in overflow/terminal).")
declare("pas_admission_starved_total", "counter", "Queue consults past the starvation threshold (label: class) — the bad half of the per-class availability SLOs.")
declare("pas_admission_queue_depth", "gauge", "Current admission-queue depth (label: class).")
declare("pas_preemption_plans_total", "counter", "Preemption planning passes (label: outcome in planned/infeasible/over_budget/not_leader/actuation_refused/reserve_failed/no_pod_view).")
declare("pas_preemption_victim_gangs_total", "counter", "Whole gangs displaced by executed preemptions.")
declare("pas_preemption_evictions_total", "counter", "Pod evictions executed through the actuator's preemption verb.")
declare("pas_preemption_skipped_total", "counter", "Preemption evictions refused by the actuator's gates (label: reason in cooldown/rate_limit/dry_run/pdb/fenced/error).")
declare("pas_preemption_reservations_total", "counter", "Freed slices reserved for the preempting gang while its victims drain.")
# causal event spine + explain plane (utils/events.py; docs/observability.md
# "Explain plane").  Unlike pas_record_*, these land in the process-wide
# COUNTERS: the journal is on by default and both front-ends feed it.
declare("pas_events_published_total", "counter", "Typed events accepted into the causal event journal (label: kind in wire/verdict/admission/preemption/rebalance/control/slo/serving).")
declare("pas_events_dropped_total", "counter", "Oldest journal events evicted by ring overflow.")
declare("pas_explain_requests_total", "counter", "GET /debug/explain queries served (both front-ends).")
declare("pas_explain_chain_events", "gauge", "Events in the causal chain returned by the most recent /debug/explain query.")

# partition plane (shard/, docs/sharding.md) — populated only while a
# ShardPlane is wired (--shard=on); the off-path convention means every
# family below reads 0/absent in full-world mode
declare("pas_shard_ticks_total", "counter", "Shard refresh-pass drives completed (coordination tick + digest publish + gossip round, one per telemetry refresh pass).")
declare("pas_shard_refresh_nodes_total", "counter", "Nodes seen by the telemetry refresh ingest filter (label: scope in owned/skipped) — skipped/owned ratio is the measured ~1/P refresh-volume cut.")
declare("pas_shard_digests_published_total", "counter", "Per-partition digests built and shelved for owned partitions (one per owned partition per refresh pass with a usable view).")
declare("pas_shard_gossip_ingested_total", "counter", "Remote partition digests accepted from peer /debug/shard pulls (fenced and out-of-date digests are rejected before this counts).")
declare("pas_shard_digest_fenced_total", "counter", "Digests rejected at ingest because their ownership epoch predates the journaled epoch — a fenced-out owner's view stopped here (label: partition).")
declare("pas_shard_digest_stale_total", "counter", "Staleness-bound trips per partition, edge-triggered per episode: serving failed open to local-only answers until a fresh digest landed (label: partition).")
declare("pas_shard_gather_local_only_total", "counter", "Scatter/gather lookups answered WITHOUT a needed remote partition (digest missing/stale/fenced) — the fail-open visibility counter (label: verb).")
declare("pas_shard_gather_held_total", "counter", "Filter candidates held on REMOTE partition facts: a fresh digest listed them as policy violators.")
declare("pas_shard_gang_deferred_total", "counter", "Gang overlays skipped because another replica owns the slice's anchor partition (straddling-gang resolution, docs/sharding.md).")

#: process-wide counters: path attribution + JAX compile visibility.
#: Layer-local CounterSets (the dispatcher's serving counters) stay where
#: they are; everything request-path-shaped that crosses layers lands here.
COUNTERS = CounterSet()


# ---------------------------------------------------------------------------
# collector pauses
# ---------------------------------------------------------------------------

# Plain tallies that only the callback writes: it runs wherever an
# allocation set the collector off — inside CounterSet.inc with its lock
# held, for one — so it may take no lock this process also takes
# elsewhere.  Collections never nest, so there is one writer at a time;
# the exposition moves what has accrued into COUNTERS (_flush_gc).
_gc_seconds = 0.0
_gc_counts = [0, 0, 0]  # by generation
_gc_recent: Tuple[Tuple[float, float], ...] = ()  # last (ended, seconds)
_gc_last_end = -1.0
_gc_open = None  # the stage of the collection under way
_gc_flushed = (0.0, (0, 0, 0))  # what COUNTERS holds of the tallies
_gc_watch_lock = threading.Lock()


def _on_gc(phase: str, info: Dict) -> None:
    global _gc_seconds, _gc_recent, _gc_last_end, _gc_open
    if phase == "start":
        # two callbacks bracket a collection, so no ``with`` block can
        _gc_open = _Stage("gc").__enter__()
    elif _gc_open is not None:
        now = time.perf_counter()
        seconds = now - _gc_open._t0
        _gc_open.__exit__(None, None, None)
        _gc_open = None
        _gc_seconds += seconds
        _gc_counts[min(int(info.get("generation", 2)), 2)] += 1
        _gc_recent = (_gc_recent + ((now, seconds),))[-16:]
        _gc_last_end = now


def _flush_gc() -> None:
    """Move the collector's tallies into COUNTERS: from the exposition,
    never from the callback (which may take no lock)."""
    global _gc_flushed
    with _gc_watch_lock:
        seconds, counts = _gc_seconds, tuple(_gc_counts)
        was_seconds, was_counts = _gc_flushed
        _gc_flushed = (seconds, counts)
    if seconds > was_seconds:
        COUNTERS.inc("pas_gc_pause_seconds_total", seconds - was_seconds)
    for generation, (now, was) in enumerate(zip(counts, was_counts)):
        if now > was:
            COUNTERS.inc(
                "pas_gc_collections_total",
                now - was,
                labels={"generation": str(generation)},
            )


def watch_gc() -> None:
    """Time every garbage collection from now on (one ``gc.callbacks``
    entry, idempotent; both front-ends call it as they start): the two
    ``pas_gc_*`` families on /metrics, a ``pas:gc`` annotation in a
    profiled window, and ``gc_ms`` on every span a collection ended
    inside — so the ``slowest`` list of /debug/traces can tell a
    collector pause from a lock wait or an upload."""
    with _gc_watch_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


# ---------------------------------------------------------------------------
# the thread ledger: CPU seconds by thread role
# ---------------------------------------------------------------------------

# Python under one GIL runs one thread at a time, so CPU seconds by thread
# role over a window is, to first order, who held the interpreter.  Nothing
# here runs on a request: every thread the program starts carries a name
# with a role prefix (thread_name), and the exposition walks the live
# threads' CPU clocks when /metrics is rendered (_flush_cpu).

#: thread-name prefix -> role; a ``pas-`` thread of no listed prefix (the
#: accept loop, enforce, lease, slo, devicewatch) and every unnamed thread
#: (JAX's workers, a harness's) is ``other`` = the process less these
THREAD_ROLES = (
    ("pas-conn-", "verbs"),
    ("pas-refresh", "refresh"),
    ("pas-informer-", "informers"),
    ("pas-gas-worker", "informers"),
)
_ROLES = ("verbs", "refresh", "informers")
_CONN_SEQ = itertools.count()
_cpu_lock = threading.Lock()
_cpu_exited = {role: 0.0 for role in _ROLES}  # folded by threads that ended
_cpu_flushed = {}  # family -> what COUNTERS holds of it
_CPU_FAMILIES = {
    "verbs": "pas_cpu_verbs_seconds_total",
    "refresh": "pas_cpu_refresh_seconds_total",
    "informers": "pas_cpu_informers_seconds_total",
    "other": "pas_cpu_other_seconds_total",
    "wall": "pas_cpu_wall_seconds_total",
}
_IMPORTED_AT = time.monotonic()  # pascheck: allow[clock] -- the ledger's denominator is observability-only elapsed time, never control flow or replayed state


def _thread_clock(native_id: int) -> int:
    """Linux's clock id of one thread's CPU time, from its kernel thread
    id: what ``pthread_getcpuclockid`` computes, without its pointer —
    that call reads the thread's descriptor through a ``pthread_t`` that
    dangles once a detached thread has exited (every Python thread is
    detached), while the kernel refuses a dead id with EINVAL."""
    return (~native_id << 3) | 6  # CPUCLOCK_SCHED | CPUCLOCK_PERTHREAD_MASK


def _can_read_thread_clocks() -> bool:
    # held to pthread_getcpuclockid on the one thread it is safe for, this
    # one; any other platform reads no foreign clock and books all to other
    try:
        return time.pthread_getcpuclockid(
            threading.get_ident()
        ) == _thread_clock(threading.get_native_id())
    except (AttributeError, OSError, OverflowError):
        return False


_THREAD_CLOCKS = _can_read_thread_clocks()


def thread_role(name: str) -> Optional[str]:
    for prefix, role in THREAD_ROLES:
        if name.startswith(prefix):
            return role
    return None


def name_connection_thread() -> None:
    """Name the calling thread as a connection handler (``pas-conn-<n>``):
    socketserver starts them unnamed."""
    threading.current_thread().name = f"pas-conn-{next(_CONN_SEQ)}"


def fold_thread_cpu() -> None:
    """Book the calling thread's CPU seconds to its role for good and
    take the thread off the ledger's walk: called as a named thread ends
    (a connection handler's ``finally``), so that a closed connection's
    seconds are not lost.  A thread of no role books nothing."""
    thread = threading.current_thread()
    role = thread_role(thread.name)
    if role is None or getattr(thread, "_pas_cpu_folded", False):
        return
    with _cpu_lock:
        thread._pas_cpu_folded = True
        _cpu_exited[role] += time.thread_time()


def thread_cpu() -> Dict[str, float]:
    """{role: CPU seconds} as of now — verbs, refresh, informers, other —
    and ``wall``.  About a microsecond a live thread; never on a request."""
    with _cpu_lock:
        totals = dict(_cpu_exited)
        if _THREAD_CLOCKS:
            for thread in threading.enumerate():
                role = thread_role(thread.name)
                if role is None or getattr(thread, "_pas_cpu_folded", False):
                    continue
                native_id = thread.native_id
                if native_id is None:
                    continue
                try:
                    totals[role] += time.clock_gettime(_thread_clock(native_id))
                except OSError:
                    pass  # it ended between the walk and the read
    # read last: a thread's clock is then never ahead of the process's
    totals["other"] = max(time.process_time() - sum(totals.values()), 0.0)
    totals["wall"] = time.monotonic() - _IMPORTED_AT  # pascheck: allow[clock] -- as _IMPORTED_AT: observability-only
    return totals


def _flush_cpu() -> None:
    """Move the ledger's reading into the five ``pas_cpu_*`` families (from
    the exposition, as _flush_gc): counters, so a reading never goes back."""
    totals = thread_cpu()
    updates = []
    with _cpu_lock:
        for role, seconds in totals.items():
            family = _CPU_FAMILIES[role]
            grew = seconds - _cpu_flushed.get(family, 0.0)
            if grew > 0 or family not in _cpu_flushed:
                # a role nobody ran in yet still shows, at 0
                _cpu_flushed[family] = max(seconds, 0.0)
                updates.append((family, max(grew, 0.0)))
    COUNTERS.inc_many(updates)


# ---------------------------------------------------------------------------
# request ids and spans
# ---------------------------------------------------------------------------


def new_request_id() -> str:
    """A fresh X-Request-ID (uuid4 hex — 32 chars, no dashes)."""
    return uuid.uuid4().hex


#: jax.profiler.TraceAnnotation once resolved; False when jax has no
#: profiler; None while unresolved
_ANNOTATION = None


def _annotation():
    """The profiler's annotation class, or None: while jax is not
    imported nobody can be profiling (and a host-only process must not
    pay the import for a stage), and without a profiler there is
    nothing to annotate."""
    global _ANNOTATION
    if "jax" not in sys.modules:
        return None
    try:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    except Exception:
        _ANNOTATION = False
    return _ANNOTATION or None


class _Stage:
    """``with span.stage("decode"):`` / ``with trace.stage("rf.fetch"):``
    — one interval on two clocks.  By ``perf_counter`` it lands on the
    span as ``(name, start, dur)`` and, when a ``counter`` is named, in
    that seconds counter; a leaf additionally opens the profiler
    annotation ``pas:<name>`` for the same interval, on the thread that
    does the work, so a profiled window (perfbench --trace 1,
    GET /debug/profile, --profilePort) shows it beside the device's
    operations with nothing converted between clocks.  While no profile
    is being taken the annotation costs one call that says so.
    ``leaf=False`` is for a stage that contains others (handle,
    cache_probe, GAS's kernel): recorded on the span, never annotated — a gap is named by the two annotations that
    cover most of it, and a container would crowd its own children out.

    A stage on a span whose CPU clock is being read (``Span.stage_cpu``),
    and a stage off a request that names a ``cpu_counter``, is a
    :class:`_CpuStage`: the same interval read on a third clock, the
    thread's CPU seconds."""

    __slots__ = ("_name", "_span", "_counter", "_counters", "_leaf", "_mark", "_t0")

    def __init__(self, name, span=None, counter=None, counters=None, leaf=True):
        self._name = name
        self._span = span
        self._counter = counter
        self._counters = counters
        self._leaf = leaf
        self._mark = None

    def __enter__(self):
        if self._leaf:
            cls = _ANNOTATION
            if cls is None:
                cls = _annotation()
            if cls and cls.is_enabled():  # a profile is being taken
                self._mark = cls("pas:" + self._name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        now = time.perf_counter()
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
        t0 = self._t0
        span = self._span
        if span is not None:
            # a span's t0 is its first byte: no stage starts before it
            span.stages.append((self._name, t0 - span._t0, now - t0))
        if self._counter is not None:
            (self._counters or COUNTERS).inc(self._counter, now - t0)
        return False


class _CpuStage(_Stage):
    """A stage that also reads ``time.thread_time()`` at both ends: wall
    seconds say how long the interval lasted, CPU seconds how much of it
    this thread ran.  For a stage that blocks by nature (solve, write,
    lock_wait) the difference is the block plus the wait to get the
    interpreter back; for one that only computes (verdict, rows, decode)
    it is every slice another thread took.  On a span the CPU seconds
    land in ``span.stage_cpu`` under the stage's index; off a request
    they go to ``cpu_counter``.  ``thread_time()`` is a system call (0.3
    us on one host, 6 us on the benchmark's: PERF.md section 6, PR 37), so
    these are opened only on the spans :func:`cpu_sample_due` picks.  The
    bodies repeat :class:`_Stage`'s rather than call them: such a span
    opens a dozen, and three calls up the class are not free either."""

    __slots__ = ("_cpu_counter", "_c0")

    def __init__(self, name, span=None, counter=None, counters=None,
                 leaf=True, cpu_counter=None):
        self._name = name
        self._span = span
        self._counter = counter
        self._counters = counters
        self._leaf = leaf
        self._mark = None
        self._cpu_counter = cpu_counter

    def __enter__(self):
        if self._leaf:
            cls = _ANNOTATION
            if cls is None:
                cls = _annotation()
            if cls and cls.is_enabled():  # a profile is being taken
                self._mark = cls("pas:" + self._name)
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time()
        return self

    def __exit__(self, exc_type, exc, tb):
        cpu = time.thread_time() - self._c0
        now = time.perf_counter()
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
        t0 = self._t0
        span = self._span
        if span is not None:
            span.stage_cpu[len(span.stages)] = cpu
            span.stages.append((self._name, t0 - span._t0, now - t0))
        if self._counter is not None:
            (self._counters or COUNTERS).inc(self._counter, now - t0)
        if self._cpu_counter is not None:
            (self._counters or COUNTERS).inc(self._cpu_counter, cpu)
        return False


def stage(
    name: str,
    counter: Optional[str] = None,
    counters: Optional[CounterSet] = None,
    cpu_counter: Optional[str] = None,
    leaf: bool = True,
) -> _Stage:
    """A stage off any request (the refresh thread, an informer): no
    span to land on — the ``pas:<name>`` annotation, plus ``counter``
    (a ``*_seconds_total`` family, in ``counters`` or the process-wide
    set) when the interval is to be summed, and ``cpu_counter`` when the
    thread's CPU seconds inside it are (the refresh pass as a whole).
    ``leaf=False`` for an interval that contains other stages: summed,
    never annotated."""
    if cpu_counter is not None:
        return _CpuStage(name, None, counter, counters, leaf, cpu_counter)
    return _Stage(name, None, counter, counters, leaf)


#: at most one span every this many seconds reads its thread's CPU clock
#: (at both ends, and around every stage it records).  By time and not by
#: count, so that the cost is a share of the wall clock whatever the verbs'
#: rate: some 26 reads a span, 6 us each where a clock read is a slow
#: system call, is 0.15% of a second at ten spans a second — and a cell of
#: two verbs a second still reads every sampled span.
CPU_SAMPLE_GAP_S = 0.1
_cpu_sample_after = 0.0


def cpu_sample_due(now: float) -> bool:
    """Whether a span whose first byte is held at ``now`` (perf_counter)
    is to read CPU clocks: the front-end asks before it reads the first,
    and hands the reading to the span (``Span(cpu0=...)``).  Unlocked: two
    threads that ask in the same microsecond both read, which costs two
    spans' worth once."""
    global _cpu_sample_after
    if now < _cpu_sample_after:
        return False
    _cpu_sample_after = now + CPU_SAMPLE_GAP_S
    return True


#: one span in this many records the ``sampled`` stages (Span.stage).
#: Odd, so that a scheduler's alternating verbs (Filter, Prioritize,
#: Filter, ...) are both sampled.
SAMPLE_EVERY = 7
_SPAN_SEQ = itertools.count()


class Span:
    """One request's timeline: id, named child stages, attributes, links.

    Not thread-safe by design: a span is owned by whichever thread is
    currently serving its request (ownership hands off at well-defined
    points — event loop -> batch worker -> event loop), never written
    concurrently.  The ring buffer it lands in takes the lock."""

    __slots__ = (
        "trace_id",
        "name",
        "start_wall",
        "_t0",
        "duration_s",
        "status",
        "stages",
        "attrs",
        "links",
        "sampled",
        "_cpu0",
        "cpu_s",
        "stage_cpu",
    )

    def __init__(
        self,
        name: str,
        trace_id: Optional[str] = None,
        t0: Optional[float] = None,
        cpu0: Optional[float] = None,
    ):
        self.trace_id = trace_id or new_request_id()
        self.name = name
        now = time.perf_counter()
        self._t0 = t0 if t0 is not None else now
        # wall-clock start, back-dated when t0 predates construction
        self.start_wall = time.time() - (now - self._t0)  # pascheck: allow[clock] -- span start is observability-only wall time (log correlation), never control flow or replayed state
        self.duration_s: Optional[float] = None
        self.status: Optional[int] = None
        self.stages: List[Tuple[str, float, float]] = []  # (name, start, dur)
        self.attrs: Dict[str, object] = {}
        self.links: List[str] = []
        self.sampled = next(_SPAN_SEQ) % SAMPLE_EVERY == 0
        # the owning thread's CPU clock where the span's first byte was
        # held, on the spans picked to read it (cpu_sample_due: the
        # front-end passes its reading); finish() reads it again and every
        # stage the span records reads it at both ends.  Which stages it
        # records is the sequence number's business alone: the pick is by
        # time, so it favours the slow stretches of a window, and a stage
        # mean must not.  The other spans read no CPU clock
        self._cpu0 = cpu0
        self.cpu_s: Optional[float] = None
        # {index into stages: thread CPU seconds}, CPU-read spans only
        self.stage_cpu: Optional[Dict[int, float]] = (
            None if cpu0 is None else {}
        )

    def stage(self, name: str, leaf: bool = True, sampled: bool = False):
        """``with span.stage(name):`` — the one way to time an interval of
        this request (:class:`_Stage`).  ``sampled`` is for the new
        sub-stages of verbs that take a few hundred microseconds, where
        every recorded stage is a measurable share of the verb: such a
        stage is recorded on one span in :data:`SAMPLE_EVERY` and is a
        no-op on the others, so a stage mean over the ring is a mean over
        the spans that carry it.  On a span that reads its CPU clock
        (``Span(cpu0=...)``) every stage it records also records its
        thread CPU seconds (:class:`_CpuStage`); on the others a stage
        costs what it did."""
        if sampled and not self.sampled:
            return _NULL_STAGE
        if self.stage_cpu is not None:
            return _CpuStage(name, self, leaf=leaf)
        return _Stage(name, self, leaf=leaf)

    def add_stage(
        self,
        name: str,
        seconds: float,
        offset: Optional[float] = None,
        cpu: Optional[float] = None,
    ) -> None:
        """Record a stage that just ended (start inferred from now) —
        for an interval timed by hand: arrive (at ``offset`` 0: it ended
        before the thread held the interpreter), read, which began
        before the span existed, and write, as it always was.  It
        carries no profiler annotation (one cannot be back-dated);
        ``cpu`` is its thread CPU seconds where the caller read them (a
        span that reads its CPU clock)."""
        if offset is None:
            offset = max(0.0, time.perf_counter() - self._t0 - seconds)
        if cpu is not None and self.stage_cpu is not None:
            self.stage_cpu[len(self.stages)] = cpu
        self.stages.append((name, offset, seconds))

    def set(self, key: str, value) -> None:
        self.attrs[key] = value

    def link(self, trace_id: str) -> None:
        self.links.append(trace_id)

    def finish(self, status: Optional[int] = None) -> "Span":
        self.duration_s = time.perf_counter() - self._t0
        if self._cpu0 is not None:
            self.cpu_s = time.thread_time() - self._cpu0
        if status is not None:
            self.status = status
        if _gc_last_end >= self._t0:
            # a collection ended inside this span: every thread stood
            # still for it, whichever thread's allocation set it off
            self.attrs["gc_ms"] = round(
                sum(s for end, s in _gc_recent if end >= self._t0) * 1e3, 4
            )
        return self

    def cpu_wall_s(self) -> float:
        """The wall seconds ``cpu_s`` is a share of: first byte HELD to
        finish(), i.e. the span less its arrival wait."""
        waited = (
            self.stages[0][2]
            if self.stages and self.stages[0][0] == "arrive"
            else 0.0
        )
        return (self.duration_s or 0.0) - waited

    def stage_seconds(self) -> Dict[str, float]:
        """Total recorded seconds per stage name."""
        out: Dict[str, float] = {}
        for name, _start, dur in self.stages:
            out[name] = out.get(name, 0.0) + dur
        return out

    def _stage_dicts(self) -> List[Dict]:
        cpu = self.stage_cpu or {}
        out = []
        for index, (name, start, dur) in enumerate(self.stages):
            entry = {
                "name": name,
                "start_ms": round(start * 1e3, 4),
                "duration_ms": round(dur * 1e3, 4),
            }
            if index in cpu:  # a sampled span's stage
                entry["cpu_ms"] = round(cpu[index] * 1e3, 4)
            out.append(entry)
        return out

    def to_dict(self) -> Dict:
        out = {
            "id": self.trace_id,
            "name": self.name,
            "status": self.status,
            "start": round(self.start_wall, 6),
            "duration_ms": round((self.duration_s or 0.0) * 1e3, 4),
            "stages": self._stage_dicts(),
            "attrs": dict(self.attrs),
            "links": list(self.links),
        }
        if self.cpu_s is not None:
            # thread CPU, first byte held -> finish(), on the spans that
            # read it: under duration_ms less arrive by what the thread
            # spent blocked or waiting for the GIL
            out["cpu_ms"] = round(self.cpu_s * 1e3, 4)
        return out


class _NullSpan:
    """No-op span: instrumented code never branches on 'is tracing on'."""

    __slots__ = ()
    trace_id = ""
    name = ""
    duration_s = None
    cpu_s = None
    sampled = False
    status = None
    stages: List[Tuple[str, float, float]] = []
    attrs: Dict[str, object] = {}
    links: List[str] = []

    def stage(
        self, name: str, leaf: bool = True, sampled: bool = False
    ) -> "_NullStageTimer":
        return _NULL_STAGE

    def add_stage(self, name: str, seconds: float, offset=None, cpu=None) -> None:
        pass

    def set(self, key: str, value) -> None:
        pass

    def link(self, trace_id: str) -> None:
        pass

    def finish(self, status: Optional[int] = None) -> "_NullSpan":
        return self

    def stage_seconds(self) -> Dict[str, float]:
        return {}

    def to_dict(self) -> Dict:
        return {}


class _NullStageTimer:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()
_NULL_STAGE = _NullStageTimer()


def of(request) -> Span:
    """The span riding on an HTTPRequest, or the no-op span."""
    span = getattr(request, "span", None)
    return span if span is not None else NULL_SPAN


# ---------------------------------------------------------------------------
# trace ring buffer
# ---------------------------------------------------------------------------

#: callables ``(span)`` invoked after every completed span lands in the
#: buffer — the causal event spine (utils/events.py) registers here so
#: wire completions become journal events without trace.py importing it.
#: Observers run on the request thread and must never raise into the
#: caller; failures are swallowed (precedent: FIRST_COMPILE_HOOKS).
SPAN_OBSERVERS: List[Callable] = []


#: the label-free verb families, in the order of a buffer's tally
VERB_FAMILIES = (
    "pas_verb_total",
    "pas_verb_seconds_total",
    "pas_verb_cpu_seconds_total",
    "pas_verb_cpu_wall_seconds_total",
    "pas_verb_arrive_total",
    "pas_verb_arrive_wait_seconds_total",
    "pas_verb_read_seconds_total",
    "pas_verb_read_gil_seconds_total",
    "pas_verb_read_calls_total",
    "pas_verb_write_releases_total",
    "pas_stage_handle_total",
    "pas_stage_handle_seconds_total",
    "pas_stage_scan_total",
    "pas_stage_scan_seconds_total",
    "pas_stage_gang_reserve_total",
    "pas_stage_gang_reserve_seconds_total",
    "pas_stage_gang_overlay_total",
    "pas_stage_gang_overlay_seconds_total",
    "pas_stage_gang_merge_total",
    "pas_stage_gang_merge_seconds_total",
    "pas_stage_gang_upload_seconds_total",
    "pas_stage_gang_launch_seconds_total",
    "pas_stage_gang_fetch_seconds_total",
)
_VERB_SPANS = "POST /scheduler/"  # the name of a served verb's span begins so
_at = VERB_FAMILIES.index
_READ_GIL = _at("pas_verb_read_gil_seconds_total")
_READ_CALLS = _at("pas_verb_read_calls_total")
_WRITE_RELEASES = _at("pas_verb_write_releases_total")
_CPU = _at("pas_verb_cpu_seconds_total")
_CPU_WALL = _at("pas_verb_cpu_wall_seconds_total")
#: stage name -> (tally index of its count or -1, of its seconds): the
#: stages of a served verb whose seconds are summed window-wide as they
#: land in the ring, so that a metric reads the whole window and not the
#: ring's end
_FOLDED_STAGES = {
    "arrive": (
        _at("pas_verb_arrive_total"), _at("pas_verb_arrive_wait_seconds_total")
    ),
    "read": (-1, _at("pas_verb_read_seconds_total")),
    "handle": (
        _at("pas_stage_handle_total"), _at("pas_stage_handle_seconds_total")
    ),
    "scan": (_at("pas_stage_scan_total"), _at("pas_stage_scan_seconds_total")),
    "gang_reserve": (
        _at("pas_stage_gang_reserve_total"),
        _at("pas_stage_gang_reserve_seconds_total"),
    ),
    "gang_overlay": (
        _at("pas_stage_gang_overlay_total"),
        _at("pas_stage_gang_overlay_seconds_total"),
    ),
    "gang_merge": (
        _at("pas_stage_gang_merge_total"),
        _at("pas_stage_gang_merge_seconds_total"),
    ),
    # the solve's steps inside gang_reserve: counted by gang_reserve's count
    "gang_upload": (-1, _at("pas_stage_gang_upload_seconds_total")),
    "gang_launch": (-1, _at("pas_stage_gang_launch_seconds_total")),
    "gang_fetch": (-1, _at("pas_stage_gang_fetch_seconds_total")),
}


def summarize(spans: List[Span]) -> Dict:
    """What the ring says of who held the interpreter, over ``spans``:
    the mean arrival wait (over the spans that carry a stamp), thread CPU
    over wall time and each stage's mean wall and CPU milliseconds (over
    the spans that read their CPU clock), the mean ``read_gil_ms`` (over
    the spans that carry one) — /debug/traces' ``summary``, per verb with ``?verb=``."""
    verbs = [s for s in spans if s.name.startswith(_VERB_SPANS)]
    if not verbs:
        return {"spans": 0}
    wall = sum(s.duration_s or 0.0 for s in verbs)
    read = [s for s in verbs if s.cpu_s is not None]  # their CPU clock
    cpu_s = sum(s.cpu_s for s in read)
    cpu_wall = sum(s.cpu_wall_s() for s in read)
    arrive = [
        dur for s in verbs for name, _start, dur in s.stages if name == "arrive"
    ]
    gil = [s.attrs["read_gil_ms"] for s in verbs if "read_gil_ms" in s.attrs]
    stages: Dict[str, List[float]] = {}  # name -> [n, wall s, cpu s]
    for span in verbs:
        for index, cpu in (span.stage_cpu or {}).items():
            if index < len(span.stages):
                name, _start, dur = span.stages[index]
                row = stages.setdefault(name, [0, 0.0, 0.0])
                row[0] += 1
                row[1] += dur
                row[2] += cpu
    return {
        "spans": len(verbs),
        "duration_ms": round(wall / len(verbs) * 1e3, 4),
        # over the spans that read their CPU clock (cpu_sample_due)
        "cpu_spans": len(read),
        "cpu_ms": round(cpu_s / len(read) * 1e3, 4) if read else None,
        "oncpu_pct": round(100.0 * cpu_s / cpu_wall, 2) if cpu_wall > 0 else None,
        "arrive_spans": len(arrive),
        "arrive_ms": round(sum(arrive) / len(arrive) * 1e3, 4) if arrive else None,
        "read_gil_spans": len(gil),
        "read_gil_ms": round(sum(gil) / len(gil), 4) if gil else None,
        # CPU-read spans only: {stage: [spans, mean ms, mean cpu ms]}
        "stage_cpu": {
            name: [n, round(dur / n * 1e3, 4), round(cpu / n * 1e3, 4)]
            for name, (n, dur, cpu) in sorted(stages.items())
        },
    }


class TraceBuffer:
    """Bounded ring of recent completed spans + bounded top-K slowest.

    Lock-light: one short lock per completed request (append + an
    occasional sorted insert).  ``/debug/traces`` serves a snapshot; both
    lists are hard-bounded so the endpoint can never grow without limit."""

    def __init__(self, capacity: int = 256, slow_capacity: int = 32):
        self.capacity = max(1, capacity)
        self.slow_capacity = max(1, slow_capacity)
        self._lock = threading.Lock()
        self._recent: deque = deque(maxlen=self.capacity)
        self._slow: List[Span] = []  # sorted by duration, slowest first
        # VERB_FAMILIES as plain tallies, written under the ring's own
        # lock as a verb span lands: a request takes no lock for them that
        # it did not take already.  The exposition moves TRACES' into
        # COUNTERS (_flush_verbs), as it does the collector's.
        self._verbs = [0.0] * len(VERB_FAMILIES)
        self._verbs_taken = list(self._verbs)

    def add(self, span: Span) -> None:
        if span.duration_s is None:
            span.finish()
        with self._lock:
            self._recent.append(span)
            slow = self._slow
            if (
                len(slow) < self.slow_capacity
                or span.duration_s > slow[-1].duration_s
            ):
                # insertion point by duration desc (K is small: linear scan)
                i = 0
                while i < len(slow) and slow[i].duration_s >= span.duration_s:
                    i += 1
                slow.insert(i, span)
                del slow[self.slow_capacity :]
            if span.name.startswith(_VERB_SPANS):
                tally = self._verbs
                tally[0] += 1
                tally[1] += span.duration_s
                folded = _FOLDED_STAGES
                for name, _start, seconds in span.stages:
                    if name in folded:
                        count, total = folded[name]
                        if count >= 0:
                            tally[count] += 1
                        tally[total] += seconds
                if span.cpu_s is not None:
                    tally[_CPU] += span.cpu_s
                    tally[_CPU_WALL] += span.cpu_wall_s()
                gil_ms = span.attrs.get("read_gil_ms")
                if gil_ms is not None:
                    tally[_READ_GIL] += gil_ms * 1e-3
                tally[_READ_CALLS] += span.attrs.get("read_calls", 0)
                tally[_WRITE_RELEASES] += span.attrs.get("write_releases", 0)
        COUNTERS.inc("pas_traces_recorded_total")
        for observer in SPAN_OBSERVERS:
            try:
                observer(span)
            except Exception:
                pass

    def take_verb_tallies(self) -> List[float]:
        """What the verb spans that landed here have added to each of
        VERB_FAMILIES, in that order, since this was last asked."""
        with self._lock:
            grew = [now - was for now, was in zip(self._verbs, self._verbs_taken)]
            self._verbs_taken = list(self._verbs)
        return grew

    def find(self, trace_id: str) -> Optional[Span]:
        with self._lock:
            for span in reversed(self._recent):
                if span.trace_id == trace_id:
                    return span
        return None

    def clear(self) -> None:
        with self._lock:
            self._recent.clear()
            self._slow = []

    def __len__(self) -> int:
        with self._lock:
            return len(self._recent)

    def snapshot(
        self,
        verb: Optional[str] = None,
        min_ms: Optional[float] = None,
    ) -> Dict:
        """Both lists, optionally filtered: ``verb`` keeps spans whose
        ``verb`` attribute matches, ``min_ms`` keeps spans at least that
        slow — the /debug/traces ``?verb=`` / ``?min_ms=`` query params."""
        with self._lock:
            recent = list(self._recent)
            slow = list(self._slow)

        def keep(span: Span) -> bool:
            if verb is not None and span.attrs.get("verb") != verb:
                return False
            if min_ms is not None and (span.duration_s or 0.0) * 1e3 < min_ms:
                return False
            return True

        if verb is not None or min_ms is not None:
            recent = [s for s in recent if keep(s)]
            slow = [s for s in slow if keep(s)]
        out = {
            "capacity": self.capacity,
            "slow_capacity": self.slow_capacity,
            "recent": [s.to_dict() for s in recent],
            "slowest": [s.to_dict() for s in slow],
            # who held the interpreter, over ``recent``'s served verbs,
            # and the thread ledger as it stands (seconds by role)
            "summary": summarize(recent),
            "cpu_seconds": thread_cpu(),
        }
        if verb is not None:
            out["verb"] = verb
        if min_ms is not None:
            out["min_ms"] = min_ms
        return out

    def to_json(
        self,
        verb: Optional[str] = None,
        min_ms: Optional[float] = None,
    ) -> bytes:
        return (
            json.dumps(self.snapshot(verb=verb, min_ms=min_ms)).encode()
            + b"\n"
        )


#: the process-wide buffer both front-ends record into
TRACES = TraceBuffer()



def _flush_verbs() -> None:
    """Move TRACES' verb tallies into COUNTERS, from the exposition: one
    acquisition of the counters' lock a scrape, none a request."""
    COUNTERS.inc_many(zip(VERB_FAMILIES, TRACES.take_verb_tallies()))


# ---------------------------------------------------------------------------
# JAX compile visibility
# ---------------------------------------------------------------------------

_jax_hooks_lock = threading.Lock()
_jax_hooks_installed = False

#: callables ``(name, jitted_fn, args, kwargs)`` invoked once per watched
#: kernel, at its FIRST observed compile — the hook point the device
#: cost-analysis capture (utils/devicewatch.py) hangs off.  Hooks run in
#: whatever thread triggered the compile (the warm thread in production)
#: and must never raise into the caller; failures are swallowed.
FIRST_COMPILE_HOOKS: List[Callable] = []


def install_jax_hooks(counters: Optional[CounterSet] = None) -> bool:
    """Register ``jax.monitoring`` listeners feeding the compile counters.
    Idempotent; returns False (and stays silent) when jax is absent —
    the host layer must import without it."""
    global _jax_hooks_installed
    with _jax_hooks_lock:
        if _jax_hooks_installed:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False
        c = counters if counters is not None else COUNTERS

        def _on_duration(name: str, duration: float, **kw) -> None:
            if name.endswith("backend_compile_duration"):
                c.inc("pas_jax_backend_compile_total")
                c.inc("pas_jax_compile_seconds_total", duration)

        monitoring.register_event_duration_secs_listener(_on_duration)
        _jax_hooks_installed = True
        return True


class _JitWatch:
    """Lowering-count shim around one jitted kernel: growth of the jit
    cache past the kernel's first compile is a RETRACE — the silent
    latency cliff this exists to surface.  Attribute access delegates to
    the wrapped function (``.lower``, NamedTuple returns, everything)."""

    def __init__(self, name: str, fn, counters: CounterSet):
        self._name = name
        self._fn = fn
        self._counters = counters
        self._lock = threading.Lock()
        self._seen = 0

    @property
    def name(self) -> str:
        return self._name

    @property
    def compile_count(self) -> int:
        """Lowerings seen so far — the recompile watch's per-kernel
        reading, also served on /debug/solve."""
        with self._lock:
            return self._seen

    def cache_size(self) -> int:
        """The wrapped kernel's live jit-cache size (no lock: jax's own
        accounting) — instrumented solve sites diff this around a call
        to attribute compile time to the ``compile`` stage."""
        return self._fn._cache_size()

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        size = self._fn._cache_size()
        if size > self._seen:
            with self._lock:
                grew = size - self._seen
                if grew <= 0:
                    return out
                first = self._seen == 0
                self._seen = size
            self._counters.inc("pas_jax_kernel_compile_total", grew)
            self._counters.inc(
                "pas_xla_compiles_total", grew, labels={"fn": self._name}
            )
            retraces = grew - 1 if first else grew
            if retraces > 0:
                self._counters.inc("pas_jax_retrace_total", retraces)
            if first:
                for hook in list(FIRST_COMPILE_HOOKS):
                    try:
                        hook(self._name, self._fn, args, kwargs)
                    except Exception:
                        pass  # visibility hooks must never fail the kernel
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


#: every _JitWatch in creation order — the recompile watch's roster:
#: /debug/solve reports each watched kernel's lowering count from here
JIT_WATCHES: List[_JitWatch] = []


def watch_jit(name: str, fn, counters: Optional[CounterSet] = None):
    """Wrap a jitted callable with the retrace shim; a callable without a
    jit cache (a plain function) passes through untouched."""
    if not hasattr(fn, "_cache_size"):
        return fn
    watch = _JitWatch(name, fn, counters if counters is not None else COUNTERS)
    JIT_WATCHES.append(watch)
    return watch


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


def help_texts() -> Dict[str, str]:
    return {name: help_text for name, (_kind, help_text) in METRICS.items()}


#: process-wide extra exposition providers (zero-arg -> valid exposition
#: text or ""), appended to every /metrics page: subsystems whose metric
#: family is not a plain counter/gauge (the gang tracker's
#: pas_gang_time_to_full_seconds histogram lives in its own
#: LatencyRecorder) register ONE provider here at import time.
EXTRA_PROVIDERS: List[Callable[[], str]] = []


def exposition(
    recorders: Iterable[LatencyRecorder] = (),
    counter_sets: Iterable[CounterSet] = (),
    include_global: bool = True,
) -> str:
    """One valid Prometheus text page: every recorder merged under the
    single ``pas_request_duration_seconds`` family (one # TYPE line no
    matter how many recorders feed it), then each counter set, then the
    process-wide COUNTERS and EXTRA_PROVIDERS.  HELP text comes from the
    declared METRICS inventory."""
    helps = help_texts()
    parts = [histograms_text(list(recorders), help_texts=helps)]
    for cs in counter_sets:
        parts.append(cs.prometheus_text(help_texts=helps))
    if include_global:
        _flush_gc()
        _flush_verbs()
        _flush_cpu()
        parts.append(COUNTERS.prometheus_text(help_texts=helps))
        for provider in list(EXTRA_PROVIDERS):
            parts.append(provider())
    return "".join(parts)


def metrics_provider(
    recorders: Iterable[LatencyRecorder] = (),
    counter_sets: Iterable[CounterSet] = (),
) -> Callable[[], str]:
    """A zero-arg /metrics provider closing over the given sources."""
    recorders = list(recorders)
    counter_sets = list(counter_sets)
    return lambda: exposition(recorders, counter_sets)


_SAMPLE_VALUE_OK = {"+Inf", "-Inf", "NaN"}


def _parse_labels(raw: str, line: str) -> Dict[str, str]:
    labels: Dict[str, str] = {}
    rest = raw.strip()
    while rest:
        eq = rest.find("=")
        if eq < 0 or len(rest) < eq + 2 or rest[eq + 1] != '"':
            raise ValueError(f"bad label syntax: {line!r}")
        name = rest[:eq].strip()
        if not name.replace("_", "a").isalnum():
            raise ValueError(f"bad label name {name!r}: {line!r}")
        i = eq + 2
        value = []
        while i < len(rest):
            ch = rest[i]
            if ch == "\\":
                if i + 1 >= len(rest):
                    raise ValueError(f"dangling escape: {line!r}")
                value.append({"n": "\n", "\\": "\\", '"': '"'}.get(
                    rest[i + 1], rest[i + 1]
                ))
                i += 2
                continue
            if ch == '"':
                break
            value.append(ch)
            i += 1
        else:
            raise ValueError(f"unterminated label value: {line!r}")
        labels[name] = "".join(value)
        rest = rest[i + 1 :].lstrip()
        if rest.startswith(","):
            rest = rest[1:].lstrip()
        elif rest:
            raise ValueError(f"junk after label value: {line!r}")
    return labels


def parse_prometheus_text(text: str) -> Dict[str, Dict]:
    """Parse (and validate) Prometheus text exposition v0.0.4.

    Returns ``{family: {"type", "help", "samples": [(name, labels, value)]}}``
    where histogram series (``_bucket``/``_sum``/``_count``) fold into
    their base family.  Raises ValueError on: malformed sample lines,
    duplicate ``# TYPE`` for a family, a TYPE appearing after the
    family's samples, duplicate (name, labels) series, or a histogram
    whose buckets are non-cumulative / missing the ``+Inf`` bucket."""
    families: Dict[str, Dict] = {}
    seen_series = set()

    def family_of(name: str) -> str:
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix):
                base = name[: -len(suffix)]
                if base in families and families[base]["type"] == "histogram":
                    return base
        return name

    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 3 and parts[1] in ("TYPE", "HELP"):
                name = parts[2]
                fam = families.setdefault(
                    name, {"type": None, "help": None, "samples": []}
                )
                if parts[1] == "TYPE":
                    kind = parts[3].strip() if len(parts) > 3 else ""
                    if kind not in (
                        "counter", "gauge", "histogram", "summary", "untyped"
                    ):
                        raise ValueError(f"line {lineno}: bad TYPE {kind!r}")
                    if fam["type"] is not None:
                        raise ValueError(
                            f"line {lineno}: duplicate TYPE for {name}"
                        )
                    if fam["samples"]:
                        raise ValueError(
                            f"line {lineno}: TYPE after samples of {name}"
                        )
                    fam["type"] = kind
                else:
                    fam["help"] = parts[3] if len(parts) > 3 else ""
            continue
        # sample line: name[{labels}] value [timestamp] [# exemplar]
        # OpenMetrics exemplar annotations (`... # {trace_id="x"} 0.01`)
        # are emitted on our histogram buckets (utils/tracing.py); strip
        # them before brace-finding so rfind("}") can't grab the
        # exemplar's labelset instead of the sample's.
        exemplar = line.find(" # {")
        if exemplar >= 0:
            line = line[:exemplar].rstrip()
        brace = line.find("{")
        labels: Dict[str, str] = {}
        if brace >= 0:
            close = line.rfind("}")
            if close < brace:
                raise ValueError(f"line {lineno}: unbalanced braces")
            name = line[:brace]
            labels = _parse_labels(line[brace + 1 : close], line)
            rest = line[close + 1 :].strip()
        else:
            fields = line.split()
            if len(fields) < 2:
                raise ValueError(f"line {lineno}: no value: {line!r}")
            name = fields[0]
            rest = " ".join(fields[1:])
        if not name or not all(
            c.isalnum() or c in "_:" for c in name
        ) or name[0].isdigit():
            raise ValueError(f"line {lineno}: bad metric name {name!r}")
        value_str = rest.split()[0] if rest else ""
        try:
            value = float(value_str)
        except ValueError:
            if value_str not in _SAMPLE_VALUE_OK:
                raise ValueError(
                    f"line {lineno}: bad value {value_str!r}"
                ) from None
            value = float(value_str.replace("Inf", "inf"))
        series_key = (name, tuple(sorted(labels.items())))
        if series_key in seen_series:
            raise ValueError(f"line {lineno}: duplicate series {series_key}")
        seen_series.add(series_key)
        fam = families.setdefault(
            family_of(name), {"type": None, "help": None, "samples": []}
        )
        fam["samples"].append((name, labels, value))

    # histogram shape checks: cumulative buckets ending at +Inf == count
    for family, data in families.items():
        if data["type"] != "histogram":
            continue
        by_labelset: Dict[tuple, Dict] = {}
        for name, labels, value in data["samples"]:
            key = tuple(
                sorted((k, v) for k, v in labels.items() if k != "le")
            )
            entry = by_labelset.setdefault(
                key, {"buckets": [], "count": None}
            )
            if name.endswith("_bucket"):
                entry["buckets"].append((labels.get("le", ""), value))
            elif name.endswith("_count"):
                entry["count"] = value
        for key, entry in by_labelset.items():
            buckets = entry["buckets"]
            if not buckets:
                raise ValueError(f"{family}{key}: histogram without buckets")
            if "+Inf" not in [le for le, _ in buckets]:
                raise ValueError(f"{family}{key}: missing +Inf bucket")
            values = [v for _, v in buckets]
            if any(b > a for a, b in zip(values[1:], values)):
                raise ValueError(f"{family}{key}: non-cumulative buckets")
            inf_value = dict(buckets)["+Inf"]
            if entry["count"] is not None and inf_value != entry["count"]:
                raise ValueError(f"{family}{key}: +Inf bucket != count")
    return families
