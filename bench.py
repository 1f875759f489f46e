"""Headline benchmark: the batched scheduling solve on real TPU hardware,
plus the north-star HTTP serving A/B (BASELINE.json primary metric).

Scenario (BASELINE.md config #4 scaled to one chip): 10k nodes x 1k
pending pods, 4 metrics, a dontschedule rule set and per-pod
scheduleonmetric rules.  Measured: full solves/sec on device ->
pods-scheduled/sec, and per-solve latency.

Baseline/control: a faithful host reimplementation of the reference's
per-pod algorithm (read metric -> intersect candidates -> sort ->
pick best free node), i.e. exactly what the Go extender does per
kube-scheduler round-trip (reference telemetryscheduler.go:128-149 +
strategies/dontschedule).  The control is measured at FULL size — all
1k pods over all 10k nodes, no extrapolation (the round-3 verdict
retired the 30-pod scaled control).

The printed JSON also carries the north-star latency numbers captured by
benchmarks/http_load.py (p99 Prioritize/Filter through the live HTTP
path, device fastpath vs measured full-size host control, hit + miss
tiers, c=1 and c=8) and the BASELINE config benches (GAS bin-packing,
deschedule churn, solver comparison) from benchmarks/configs.py.

Prints ONE JSON line; the primary fields remain
{"metric", "value", "unit", "vs_baseline"}.

Process model (benchmarks/children.py): a chip belongs to one process at a
time, so this launcher never initializes a JAX backend (asserted at exit).
Every section runs as a child — ``python bench.py --section NAME`` — one at
a time; a child either computes on the chip itself (and refuses any
platform but a TPU) or only launches the services that do.  Each child's
JSON carries the platform it ran on.  A section that fails, or reports
``not_run``, makes this process exit non-zero; the headline line still
prints, last.

Line layout (round-4 verdict: the driver captures the TAIL of stdout and
r03/r04 both truncated the headline off the front): the bulky per-config
http_load device/control dicts go to BENCH_DETAIL_r{N}.json on disk, and
the line itself ends with the headline — speedup_p99* aliases first, then
{"metric", "value", "unit", "vs_baseline"} as the very last keys — so any
tail window that catches the end of the line catches everything that must
parse.  The headline JSON is also the LAST stdout line of the process
(the detail-file write and its stderr pointer happen before it, ADVICE
r5 #3), and the detail round can be pinned explicitly with
``--round N`` / ``PAS_TPU_BENCH_ROUND`` instead of glob inference.
"""

import json
import sys
import time
import os

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

NUM_NODES = 10_000
NUM_PODS = 1_000
NUM_METRICS = 4
DEVICE_REPS = 200  # solves per on-device loop; amortizes the host round trip


def build_problem(rng):
    from platform_aware_scheduling_tpu.models.batch_scheduler import example_inputs

    return example_inputs(
        num_metrics=NUM_METRICS, num_nodes=NUM_NODES, num_pods=NUM_PODS, seed=3
    )


def batched_solve():
    """Device pods/s on the full 10k x 1k problem vs the fully-measured
    host control; returns (result fields, stderr context string)."""
    import jax
    import jax.numpy as jnp

    from platform_aware_scheduling_tpu.models.batch_scheduler import (
        choose_assigner,
        scheduling_step,
    )

    rng = np.random.default_rng(0)
    state, pods = build_problem(rng)
    # chosen from the concrete operands: inside the loop's trace they are
    # tracers and carry no placement
    assigner = choose_assigner(state, pods)

    # --- device path: full batched solve ---
    # Device throughput apart from the per-dispatch host round trip: K
    # solves inside ONE compiled program (each iteration permutes the
    # candidate matrix so no work can be reused/DCE'd), one readback,
    # the round trip amortized over K.  The single-solve wall below
    # includes it.
    def loop_body(i, carry):
        checksum, cap = carry
        rolled = pods._replace(
            candidates=jnp.roll(pods.candidates, i, axis=1)
        )
        out = scheduling_step(
            state._replace(capacity=cap), rolled, assigner=assigner
        )
        return (
            checksum + jnp.sum(out.assignment.node_for_pod),
            out.assignment.capacity_left + jnp.int32(1),
        )

    @jax.jit
    def run_k_solves():
        return jax.lax.fori_loop(
            0, DEVICE_REPS, loop_body, (jnp.int32(0), state.capacity)
        )

    checksum, _ = run_k_solves()  # compile
    _ = int(checksum)
    t0 = time.perf_counter()
    checksum, _ = run_k_solves()
    _ = int(checksum)  # host materialization: forces completion
    wall = time.perf_counter() - t0
    device_solve_s = wall / DEVICE_REPS
    device_pods_per_s = NUM_PODS / device_solve_s

    out = scheduling_step(state, pods)
    t0 = time.perf_counter()
    out = scheduling_step(state, pods)
    _ = np.asarray(out.assignment.node_for_pod)
    single_solve_s = time.perf_counter() - t0

    # --- host control, fully measured (all pods, all nodes); the single
    # shared implementation lives in benchmarks/configs.py ---
    from benchmarks.configs import _host_prioritize_control

    host_full_s = _host_prioritize_control(state, pods, NUM_NODES, NUM_PODS)
    host_pods_per_s = NUM_PODS / host_full_s

    fields = {
        "metric": "batch_schedule_pods_per_sec_10k_nodes_1k_pods",
        "value": round(device_pods_per_s, 1),
        "unit": "pods/s",
        "vs_baseline": round(device_pods_per_s / host_pods_per_s, 1),
    }
    context = (
        f"device: {device_solve_s*1e3:.2f} ms/solve ({DEVICE_REPS} "
        f"capacity-chained solves in one program, {assigner} assigner), "
        f"{single_solve_s*1e3:.2f} ms single-solve wall incl. dispatch "
        f"round trip ({NUM_PODS} pods x {NUM_NODES} nodes) on "
        f"{jax.devices()[0].platform}/{jax.devices()[0].device_kind}; "
        f"host control: {host_full_s:.2f} s MEASURED at full size"
    )
    return fields, context


def _detail_path(round_override=None) -> str:
    """BENCH_DETAIL_r{N}.json beside this file.  N comes from (highest
    precedence first) the ``round_override`` argument, the
    ``PAS_TPU_BENCH_ROUND`` env var, or glob inference: one past the
    highest driver-written BENCH_r*.json (the driver writes its artifact
    AFTER this process exits, so max+1 is the current round).  The
    explicit override exists because the inference mislabels a manual
    re-run made after the driver has written the current round's
    artifact — that run lands on the NEXT round's name (last writer
    wins); pass the intended round to pin it."""
    import glob
    import re

    root = os.path.dirname(os.path.abspath(__file__))
    if round_override is None:
        round_override = os.environ.get("PAS_TPU_BENCH_ROUND") or None
    if round_override is not None:
        return os.path.join(
            root, f"BENCH_DETAIL_r{int(round_override):02d}.json"
        )
    rounds = [
        int(m.group(1))
        for f in glob.glob(os.path.join(root, "BENCH_r*.json"))
        for m in [re.search(r"BENCH_r(\d+)\.json$", f)]
        if m
    ]
    n = max(rounds) + 1 if rounds else 0
    return os.path.join(root, f"BENCH_DETAIL_r{n:02d}.json")


def assemble_line(
    headline, load, configs_out, gas=None, serving=None, rebalance=None,
    chaos=None, decisions=None, gang=None, forecast=None, ha=None,
    twin=None, record=None, control=None, admission=None, ledger=None,
    shard=None, fuzz=None,
):
    """(result, detail): the printed JSON line dict — insertion-ordered so
    the headline aliases and {metric, value, unit, vs_baseline} are the
    LAST keys (driver tail-capture keeps the end of the line) — and the
    bulky per-config http_load latency dicts destined for the on-disk
    detail file (tests/test_bench_line.py pins the layout)."""
    result = {}
    detail = {}
    if load is not None:
        detail["http_load"] = {
            "num_nodes": load["num_nodes"],
            "device": load["device"],
            "control": load["control"],
        }
        result["http_load"] = {"speedup": load["speedup"]}
    if configs_out is not None:
        result["configs"] = configs_out
    if gas is not None:
        detail["gas_filter"] = {
            "device": gas.get("device"),
            "control": gas.get("control"),
        }
        result["gas_filter"] = {
            "num_nodes": gas.get("num_nodes"),
            "speedup": gas.get("speedup"),
            "speedup_p99_gas_filter": gas.get("speedup_p99_gas_filter"),
        }
        if "baseline_shape_256" in gas:
            result["gas_filter"]["baseline_shape_256"] = gas[
                "baseline_shape_256"
            ]
    if serving is not None:
        # per-concurrency latency dicts to disk; the line keeps only the
        # scaling ratios (threaded vs async c=1 -> c=8 curve)
        detail["serving_scaling"] = serving
        compact = {"num_nodes": serving.get("num_nodes")}
        for mode in ("threaded", "async"):
            side = serving.get(mode)
            if side:
                compact[mode] = {
                    k: v
                    for k, v in side.items()
                    if k.startswith(("p99_scaling", "rps_scaling"))
                }
        result["serving_scaling"] = compact
    if rebalance is not None:
        # full per-mode cycle records to disk; the line keeps only the
        # convergence headline (active closes the loop, label-only cannot)
        detail["rebalance"] = rebalance
        active = rebalance.get("active") or {}
        label_only = rebalance.get("label_only") or {}
        result["rebalance"] = {
            "num_nodes": rebalance.get("num_nodes"),
            "cycles_to_zero_active": active.get("cycles_to_zero"),
            "evictions_active": active.get("evictions"),
            "plan_ms_p99": active.get("plan_ms_p99"),
            "label_only_converged": label_only.get("converged"),
            "label_only_residual_violations": label_only.get(
                "residual_violations"
            ),
        }
    if decisions is not None:
        # full per-verb latency dicts + placement-quality scrape to disk;
        # the line keeps only the overhead headline (the ISSUE 6
        # acceptance bar: decision logging on vs off <= 5% serving p99)
        detail["decisions"] = decisions
        result["decisions"] = {
            "num_nodes": decisions.get("num_nodes"),
            "overhead_pct_prioritize_p99": decisions.get(
                "overhead_pct_prioritize_p99"
            ),
            "overhead_pct_filter_p99": decisions.get(
                "overhead_pct_filter_p99"
            ),
        }
    if gang is not None:
        # full per-mode admission records to disk; the line keeps the
        # all-or-nothing headline (gang-on admits both competing gangs,
        # gang-off deadlocks half-placed — docs/gang.md) + the 10k-node
        # reservation-solve latency
        detail["gang"] = gang
        on = gang.get("gang_on") or {}
        off = gang.get("gang_off") or {}
        throughput = gang.get("throughput") or {}
        result["gang"] = {
            "gangs_admitted_on": on.get("gangs_admitted_as_valid_slice"),
            "deadlock_on": on.get("deadlock"),
            "gangs_admitted_off": off.get("gangs_admitted_as_valid_slice"),
            "deadlock_off": off.get("deadlock"),
            "reserve_ms_10k_nodes": throughput.get("reserve_ms"),
            "admissions_per_s_10k_nodes": throughput.get(
                "admissions_per_s"
            ),
        }
    if forecast is not None:
        # full scenario records to disk; the line keeps the placement-
        # quality headline (forecast-on avoids the violated-at-bind
        # placements and the transient-spike evictions snapshot mode
        # pays — docs/forecast.md) + the on-vs-off p99 overhead
        detail["forecast"] = forecast
        trending = forecast.get("trending") or {}
        spike = forecast.get("spike") or {}
        over = forecast.get("overhead") or {}
        result["forecast"] = {
            "violated_at_bind_snapshot": (trending.get("snapshot") or {}).get(
                "violated_at_bind"
            ),
            "violated_at_bind_forecast": (trending.get("forecast") or {}).get(
                "violated_at_bind"
            ),
            "spike_evictions_snapshot": (spike.get("snapshot") or {}).get(
                "evictions"
            ),
            "spike_evictions_forecast": (spike.get("forecast") or {}).get(
                "evictions"
            ),
            "spike_suppressed": (spike.get("forecast") or {}).get(
                "suppressed"
            ),
            "overhead_pct_prioritize_p99": over.get(
                "overhead_pct_prioritize_p99"
            ),
            "overhead_pct_filter_p99": over.get("overhead_pct_filter_p99"),
        }
    if chaos is not None:
        # full per-side latency dicts to disk; the line keeps only the
        # availability + p99-ratio headline (service stays flat through
        # a scripted 10% metrics-API error rate — docs/robustness.md)
        # plus the leader-kill failover headline
        detail["chaos"] = chaos
        clean = chaos.get("clean") or {}
        faulty = chaos.get("faulty") or {}
        lk = chaos.get("leader_kill") or {}
        result["chaos"] = {
            "num_nodes": chaos.get("num_nodes"),
            "availability_clean": clean.get("availability"),
            "availability_faulty": faulty.get("availability"),
            "p99_ratio_faulty_vs_clean": chaos.get(
                "p99_ratio_faulty_vs_clean"
            ),
            "failover_ticks": lk.get("failover_ticks"),
            "failover_availability": lk.get("availability"),
            "failover_duplicate_evictions": lk.get("duplicate_evictions"),
        }
    if ha is not None:
        # full per-replica latency dicts to disk; the line keeps the
        # scale-out ratios + failover accounting (docs/robustness.md
        # "HA & leader election")
        detail["ha"] = ha
        fo = ha.get("failover") or {}
        result["ha"] = {
            "num_nodes": ha.get("num_nodes"),
            "replicas": ha.get("replicas"),
            "rps_ratio_multi_vs_single": ha.get(
                "rps_ratio_multi_vs_single"
            ),
            "p99_ratio_multi_vs_single": ha.get(
                "p99_ratio_multi_vs_single"
            ),
            "failover_ticks": fo.get("failover_ticks"),
            "evictions_vs_baseline": (
                f"{fo.get('evictions')}/{fo.get('evictions_baseline')}"
            ),
            "duplicate_evictions": fo.get("duplicate_evictions"),
        }
    if shard is not None:
        # full per-owner drive dicts + refresh accounting to disk; the
        # line keeps the scale-out bet: aggregate Filter rps across the
        # partition owners vs one full-world replica, and the measured
        # per-replica refresh fraction vs the 1/P ideal — the ISSUE 19
        # acceptance surface (benchmarks/shard_load.py; docs/sharding.md)
        detail["shard"] = shard
        result["shard"] = {
            "num_nodes": shard.get("num_nodes"),
            "partitions": shard.get("partitions"),
            "rps_ratio_sharded_vs_full": shard.get(
                "rps_ratio_sharded_vs_full"
            ),
            "aggregate_requests_per_s": shard.get(
                "aggregate_requests_per_s"
            ),
            "refresh_fraction_mean": shard.get("refresh_fraction_mean"),
            "refresh_fraction_ideal": shard.get("refresh_fraction_ideal"),
            "passed": shard.get("passed"),
        }
    if twin is not None:
        # full per-scenario verdicts (checks + SLO judgments) to disk;
        # the line keeps the compact scenario matrix — the per-scenario
        # regression surface every future PR's BENCH_DETAIL must show
        # (testing/twin.py; docs/observability.md "SLOs & error budgets")
        detail["twin"] = twin
        result["twin"] = {
            "num_nodes": twin.get("num_nodes"),
            "all_passed": twin.get("all_passed"),
            "matrix": twin.get("matrix"),
        }
        replay = twin.get("replay")
        if replay:
            # the ISSUE 13 headline: round-trip fidelity rides the
            # matrix (replayed_diurnal); the line adds replay throughput
            # before/after vectorization and the 2x what-if verdict
            result["twin"]["replay"] = {
                "num_nodes": replay.get("num_nodes"),
                "ticks_per_s_legacy": replay.get("ticks_per_s_legacy"),
                "ticks_per_s_vectorized": replay.get(
                    "ticks_per_s_vectorized"
                ),
                "vectorized_speedup": replay.get("vectorized_speedup"),
                "whatif_degraded_at_2x": (
                    replay.get("whatif") or {}
                ).get("degraded_at_2x"),
            }
    if control is not None:
        # full head-to-head verdicts (checks + judgments) to disk; the
        # line keeps the final error-budget ledgers static vs
        # self-tuning per program — the ISSUE 15 acceptance surface
        # (benchmarks/control_load.py; docs/observability.md "Budget
        # feedback control")
        detail["control"] = control
        from benchmarks import control_load as _control_load

        result["control"] = _control_load.compact(control)
    if admission is not None:
        # full head-to-head (checks, judgments, plane snapshots) to
        # disk; the line keeps the HIGH class's final ledgers ON vs OFF,
        # the quiet-day null, and the per-review gate tax — the ISSUE 16
        # acceptance surface (benchmarks/admission_load.py;
        # docs/admission.md)
        detail["admission"] = admission
        from benchmarks import admission_load as _admission_load

        result["admission"] = _admission_load.compact(admission)
    if record is not None:
        # full pair-ratio lists + capture scrape to disk; the line keeps
        # the hermetic per-request delta (the stable number) next to the
        # wire A/B p99 percentages (the ISSUE 13 acceptance bar: <= 5%)
        detail["record"] = record
        inproc = record.get("inprocess") or {}
        result["record"] = {
            "prioritize_delta_us": inproc.get("prioritize_delta_us"),
            "filter_delta_us": inproc.get("filter_delta_us"),
            "overhead_pct_prioritize_p99": record.get(
                "overhead_pct_prioritize_p99"
            ),
            "overhead_pct_filter_p99": record.get(
                "overhead_pct_filter_p99"
            ),
        }
    if fuzz is not None:
        # full search summary + any finds to disk; the line keeps the
        # reproducibility verdict, the search volume, and the find count
        # — on the healthy tree ANY find is a real bug, so a nonzero
        # count here is the loudest number on the line
        # (benchmarks/fuzz_load.py; docs/robustness.md "Adversarial
        # scenario search")
        detail["fuzz"] = fuzz
        result["fuzz"] = {
            "reproducible": fuzz.get("reproducible"),
            "candidates": fuzz.get("candidates"),
            "candidates_per_s": fuzz.get("candidates_per_s"),
            "coverage_signals": fuzz.get("coverage_signals"),
            "finds": fuzz.get("finds"),
            "find_failures": fuzz.get("find_failures"),
        }
    if ledger is not None:
        # full measurement + overhead pin to disk; the line keeps the
        # drift verdict against the COMMITTED anchor — flagged stage
        # names plus the warm-verb instrumented-vs-off percentage (the
        # ISSUE 18 acceptance surface: off-path <= 5%)
        # (benchmarks/perf_ledger.py; docs/observability.md "Solve
        # observatory")
        detail["perf_ledger"] = ledger
        over = ledger.get("overhead") or {}
        result["perf_ledger"] = {
            "flagged": ledger.get("flagged", []),
            "anchor_written": ledger.get("anchor_written"),
            "warm_filter_overhead_pct": over.get(
                "warm_filter_overhead_pct"
            ),
        }
    if load is not None:
        # structural note: the filter MISS tier is ratio-capped independent
        # of implementation quality — the filter control skips the sort
        # (~25 ms at 10k nodes) while a span-cache miss still pays the
        # ~1 ms native floor (per-stage breakdown in
        # configs.filter_floor_breakdown)
        result["notes"] = (
            "filter_miss is ratio-capped: filter control has no sort "
            "(~25ms) vs ~1ms device floor on a true cache miss"
        )
        # the headline aliases, in http_load.run's own insertion order —
        # derived from the load dict so a new alias added there can never
        # be silently dropped here
        for key, value in load.items():
            if key.startswith("p99_prioritize_ms_") or key.startswith(
                "speedup_p99"
            ):
                result[key] = value
    # the wire-path floor next to the filter-miss speedup it caps: the
    # cold span-cache-miss verb total vs the intern-hit (warm-universe)
    # splice floor (configs.filter_floor_breakdown; ISSUE 11 acceptance:
    # warm < 250 us at 10k nodes)
    floor = (configs_out or {}).get("filter_floor_breakdown") or {}
    if floor.get("warm_verb_total_us"):
        result["filter_floor_cold_us"] = floor.get("verb_total_us")
        result["filter_floor_warm_us"] = floor.get("warm_verb_total_us")
        result["filter_floor_warm_parse_us"] = floor.get("warm_parse_us")
        result["filter_floor_warm_splice_us"] = floor.get(
            "warm_partition_encode_us"
        )
    result.update(headline)
    return result, detail


# -- sections: what each child runs, and the one-line summary it logs --------

HOLDS_CHIP = "holds_chip"  # the child computes with JAX itself
LAUNCHES = "launches"  # the child only launches the processes that do


def _headline():
    fields, context = batched_solve()
    print(context, file=sys.stderr)
    return fields


def _http_load():
    from benchmarks import http_load

    load = http_load.run(num_nodes=NUM_NODES)
    print(
        f"http_load: p99 device {load['p99_prioritize_ms_device']} ms vs "
        f"control {load['p99_prioritize_ms_control']} ms -> "
        f"{load['speedup_p99']}x",
        file=sys.stderr,
    )
    # per-stage attribution (scraped from /debug/traces): the detail
    # artifact carries the full breakdown; the stderr line answers
    # "where does a device-path request spend its time" at a glance
    obs = (load.get("device") or {}).get("observability") or {}
    if "ready" in obs:
        device_families = sorted((obs.get("device") or {}).keys())
        print(
            f"http_load observability: ready={obs['ready']} "
            f"flaps={obs.get('ready_transitions', 0)} "
            f"device_families={device_families}",
            file=sys.stderr,
        )
    stages = (load.get("device") or {}).get("stages") or {}
    if stages.get("stages"):
        top = ", ".join(
            f"{name} {agg['mean_ms']}ms"
            for name, agg in sorted(
                stages["stages"].items(),
                key=lambda kv: -kv[1]["mean_ms"],
            )[:6]
        )
        print(f"http_load stages (mean): {top}", file=sys.stderr)
    return load


def _gas():
    """Primary at 2k nodes + the BASELINE config-#3 shape (256 x 8) so the
    wire-path number exists at the scale BASELINE names (r4 weak #3)."""
    from benchmarks import gas_load

    gas = gas_load.run(num_nodes=2000)
    print(
        f"gas_filter: p99 speedup {gas['speedup_p99_gas_filter']}x "
        f"at {gas['num_nodes']} nodes",
        file=sys.stderr,
    )
    try:  # secondary shape: its failure must not discard the primary
        small = gas_load.run(
            num_nodes=256, concurrency_sweep=(1,), repeats=1
        )
        gas["baseline_shape_256"] = {
            "speedup": small["speedup"],
            "device_p99_ms": small["device"]["gas_filter_c1"]["p99_ms"],
            "control_p99_ms": small["control"]["gas_filter_c1"]["p99_ms"],
        }
        print(
            f"gas_filter 256-node shape: {small['speedup_p99_gas_filter']}x",
            file=sys.stderr,
        )
    except Exception as exc:  # kept in the result, and fails the section
        gas["baseline_shape_256"] = {"error": str(exc)[:300]}
    return gas


def _serving():
    from benchmarks import http_load

    serving = http_load.serving_scaling(num_nodes=2000)
    a = serving.get("async", {})
    t = serving.get("threaded", {})
    print(
        f"serving_scaling: c8/c1 p99 threaded "
        f"{t.get('p99_scaling_c8')}x vs async "
        f"{a.get('p99_scaling_c8')}x (rps x{a.get('rps_scaling_c8')})",
        file=sys.stderr,
    )
    return serving


def _rebalance():
    from benchmarks import rebalance_load

    rebalance = rebalance_load.run()
    active = rebalance["active"]
    print(
        f"rebalance: active converged in {active['cycles_to_zero']} "
        f"cycles ({active['evictions']} evictions, plan p99 "
        f"{active['plan_ms_p99']} ms); label-only residual "
        f"{rebalance['label_only']['residual_violations']} violating "
        f"nodes after {rebalance['label_only']['cycles']} cycles",
        file=sys.stderr,
    )
    return rebalance


def _chaos():
    from benchmarks import chaos_load

    chaos = chaos_load.run()
    print(
        f"chaos: availability clean={chaos['clean']['availability']} "
        f"faulty={chaos['faulty']['availability']} at 10% API errors; "
        f"p99 ratio x{chaos['p99_ratio_faulty_vs_clean']}",
        file=sys.stderr,
    )
    return chaos


def _decisions():
    from benchmarks import http_load

    out = http_load.decision_overhead(num_nodes=NUM_NODES)
    print(
        f"decisions: p99 overhead prioritize "
        f"{out['overhead_pct_prioritize_p99']}% / filter "
        f"{out['overhead_pct_filter_p99']}% (log on vs off)",
        file=sys.stderr,
    )
    return out


def _gang():
    from benchmarks import gang_load

    gang = gang_load.run()
    on, off = gang["gang_on"], gang["gang_off"]
    print(
        f"gang: on admitted {on['gangs_admitted_as_valid_slice']}/2 "
        f"gangs (deadlock={on['deadlock']}) vs off "
        f"{off['gangs_admitted_as_valid_slice']}/2 "
        f"(deadlock={off['deadlock']}); reserve "
        f"{gang['throughput']['reserve_ms']} ms at 10k nodes",
        file=sys.stderr,
    )
    return gang


def _forecast():
    from benchmarks import forecast_load

    out = forecast_load.run(num_nodes=NUM_NODES)
    trending = out["trending"]
    spike = out["spike"]
    print(
        f"forecast: violated-at-bind snapshot="
        f"{trending['snapshot']['violated_at_bind']} vs forecast="
        f"{trending['forecast']['violated_at_bind']}; spike evictions "
        f"{spike['snapshot']['evictions']} vs "
        f"{spike['forecast']['evictions']} (suppressed "
        f"{spike['forecast']['suppressed']}); overhead p99 "
        f"{out['overhead']['overhead_pct_prioritize_p99']}% "
        f"prioritize",
        file=sys.stderr,
    )
    return out


def _ha():
    from benchmarks import ha_load

    out = ha_load.run()
    fo = out["failover"]
    print(
        f"ha: rps x{out['rps_ratio_multi_vs_single']} over "
        f"{out['replicas']} replicas (p99 "
        f"x{out['p99_ratio_multi_vs_single']}); failover "
        f"{fo['failover_ticks']} ticks, evictions "
        f"{fo['evictions']}=={fo['evictions_baseline']} baseline, "
        f"{fo['duplicate_evictions']} duplicates",
        file=sys.stderr,
    )
    return out


def _shard():
    from benchmarks import shard_load

    out = shard_load.run()
    if "not_run" in out:
        print(f"shard: NOT RUN — {out['not_run']}", file=sys.stderr)
        return out
    print(
        f"shard: {out['num_nodes']} nodes / "
        f"{out['partitions']} partitions — aggregate "
        f"{out['aggregate_requests_per_s']} rps = "
        f"x{out['rps_ratio_sharded_vs_full']} vs full-world "
        f"{out['baseline']['requests_per_s']} rps; refresh "
        f"fraction {out['refresh_fraction_mean']} "
        f"(ideal {out['refresh_fraction_ideal']}); "
        f"passed={out['passed']}",
        file=sys.stderr,
    )
    return out


def _twin():
    from benchmarks import twin_load

    out = twin_load.run(num_nodes=NUM_NODES)
    compact = ", ".join(
        f"{name}={'pass' if entry['passed'] else 'FAIL'}"
        for name, entry in sorted(out["matrix"].items())
    )
    rep = out.get("replay") or {}
    print(
        f"twin: {out['num_nodes']} nodes, "
        f"{out['wall_s']}s wall — {compact}; replay "
        f"{rep.get('num_nodes')} nodes "
        f"{rep.get('ticks_per_s_legacy')} -> "
        f"{rep.get('ticks_per_s_vectorized')} ticks/s "
        f"({rep.get('vectorized_speedup')}x), 2x what-if "
        f"degraded={(rep.get('whatif') or {}).get('degraded_at_2x')}",
        file=sys.stderr,
    )
    return out


def _control():
    from benchmarks import control_load

    out = control_load.run()
    summary = ", ".join(
        f"{name}: static {entry['static']['budget']} vs tuned "
        f"{entry['self_tuning']['budget']} "
        f"({'better' if entry['strictly_better'] else 'NOT BETTER'})"
        for name, entry in sorted(out["scenarios"].items())
    )
    print(
        f"control: {summary}; quiet diurnal "
        f"{out['diurnal_quiet']['actuations']} actuations "
        f"({out['wall_s']}s wall)",
        file=sys.stderr,
    )
    return out


def _admission():
    from benchmarks import admission_load

    out = admission_load.run()
    on = out["preemption_on"]
    off = out["preemption_off"]
    print(
        f"admission: high-class budget ON {on['budget']} vs OFF "
        f"{off['budget']} "
        f"({'better' if out['strictly_better'] else 'NOT BETTER'}); "
        f"quiet diurnal ok={out['diurnal_quiet']['ok']}; "
        f"gate {out['gate_overhead']['mean_us']} us/review "
        f"({out['wall_s']}s wall)",
        file=sys.stderr,
    )
    return out


def _record():
    from benchmarks import http_load

    out = http_load.record_overhead(num_nodes=NUM_NODES)
    inproc = out.get("inprocess") or {}
    print(
        f"record: in-process delta prioritize "
        f"{inproc.get('prioritize_delta_us')} us / filter "
        f"{inproc.get('filter_delta_us')} us per request "
        f"(recorder on vs off); wire p99 A/B prioritize "
        f"{out['overhead_pct_prioritize_p99']}% / filter "
        f"{out['overhead_pct_filter_p99']}%",
        file=sys.stderr,
    )
    return out


def _fuzz():
    from benchmarks import fuzz_load

    out = fuzz_load.run()
    print(
        f"fuzz: reproducible={out['reproducible']}, "
        f"{out['candidates']} candidates "
        f"({out['candidates_per_s']}/s, "
        f"{out['coverage_signals']} coverage signals, corpus "
        f"{out['corpus_size']}); finds={out['finds']}"
        + (f" REAL BUGS {out['find_failures']}" if out["finds"] else ""),
        file=sys.stderr,
    )
    return out


def _ledger():
    from benchmarks import perf_ledger

    out = perf_ledger.report()
    over = out.get("overhead") or {}
    flagged = out.get("flagged") or []
    print(
        f"perf ledger: drift {'FLAGGED ' + ','.join(flagged) if flagged else 'clean'}"
        f" vs committed anchor; warm filter obs-on overhead "
        f"{over.get('warm_filter_overhead_pct')}% "
        f"(solve instrumented {over.get('solve_overhead_pct')}%)",
        file=sys.stderr,
    )
    return out


def _configs():
    from benchmarks import configs as config_benches

    out = config_benches.run_all()
    floor = out.get("filter_floor_breakdown") or {}
    if floor.get("warm_verb_total_us"):
        # the wire-path floor behind the filter_nodenames_miss
        # speedup tier: cold miss vs intern-hit splice
        print(
            f"filter floor: cold {floor.get('verb_total_us')} us -> "
            f"warm-universe {floor.get('warm_verb_total_us')} us "
            f"(parse {floor.get('warm_parse_us')} + splice "
            f"{floor.get('warm_partition_encode_us')}; prioritize "
            f"warm {floor.get('warm_prioritize_verb_us')} us)",
            file=sys.stderr,
        )
    return out


#: name -> (process kind, callable), in run order.  The names are
#: assemble_line's keyword arguments (plus the headline).
SECTIONS = {
    "headline": (HOLDS_CHIP, _headline),
    "load": (LAUNCHES, _http_load),
    "gas": (LAUNCHES, _gas),
    "serving": (LAUNCHES, _serving),
    "rebalance": (HOLDS_CHIP, _rebalance),
    "chaos": (HOLDS_CHIP, _chaos),
    "decisions": (LAUNCHES, _decisions),
    "gang": (HOLDS_CHIP, _gang),
    "forecast": (LAUNCHES, _forecast),
    "ha": (HOLDS_CHIP, _ha),
    "shard": (LAUNCHES, _shard),
    "twin": (HOLDS_CHIP, _twin),
    "control": (HOLDS_CHIP, _control),
    "admission": (HOLDS_CHIP, _admission),
    "record": (LAUNCHES, _record),
    "fuzz": (HOLDS_CHIP, _fuzz),
    "ledger": (HOLDS_CHIP, _ledger),
    "configs_out": (LAUNCHES, _configs),
}

SECTION_TIMEOUT_S = 3600


def run_section(name: str) -> int:
    """Child side: run ONE section in this process and print
    ``{"section", "platform", "result"}`` as the last stdout line."""
    from benchmarks import children

    kind, fn = SECTIONS[name]
    platform = None
    if kind == HOLDS_CHIP:
        platform = children.hold_chip(f"bench section {name}")["platform"]
    result = fn()
    if kind == LAUNCHES:
        children.assert_launcher(f"bench section {name}")
        platform = result.get("platform")
    print(json.dumps({"section": name, "platform": platform, "result": result}))
    return 0


def section_problems(result) -> list:
    """Why a finished section still counts as failed or not run: an
    ``error``/``not_run`` at its top level, or an entry that kept an error
    beside its siblings' partial results (configs, the gas secondary
    shape)."""
    from benchmarks import children

    problems = [
        f"{key}: {result[key]}" for key in ("error", "not_run") if key in result
    ]
    problems += [
        f"{name}: {error}"
        for name, error in children.nested_errors(result).items()
    ]
    return problems


def main():
    argv = sys.argv[1:]
    if "--section" in argv:
        return run_section(argv[argv.index("--section") + 1])

    from benchmarks import children

    # explicit round pin for the detail artifact (ADVICE r5 #3):
    # `python bench.py --round 6` or PAS_TPU_BENCH_ROUND=6.  Validated up
    # front — a malformed pin must fail fast here, not after the whole
    # bench has run
    round_override = None
    raw_round = None
    if "--round" in argv and argv.index("--round") + 1 < len(argv):
        raw_round = argv[argv.index("--round") + 1]
    else:
        raw_round = os.environ.get("PAS_TPU_BENCH_ROUND") or None
    if raw_round is not None:
        try:
            round_override = int(raw_round)
        except ValueError:
            raise SystemExit(
                f"bench.py: --round/PAS_TPU_BENCH_ROUND must be an "
                f"integer, got {raw_round!r}"
            )

    outputs = {}
    platforms = {}
    failed = {}
    script = os.path.abspath(__file__)
    for name in SECTIONS:  # one child at a time: one process per chip
        try:
            child = children.run_child(
                [script, "--section", name], timeout=SECTION_TIMEOUT_S
            )
        except Exception as exc:  # a dead child is a failed section; the
            # others still run and the headline line still prints
            failed[name] = str(exc)
            print(f"section {name} FAILED: {exc}", file=sys.stderr)
            continue
        outputs[name] = child["result"]
        platforms[name] = child["platform"]
        problems = section_problems(child["result"])
        if child["platform"] != "tpu":
            problems.append(f"ran on platform {child['platform']!r}, not tpu")
        if problems:
            failed[name] = "; ".join(problems)
            print(f"section {name} FAILED: {failed[name]}", file=sys.stderr)

    headline = {
        "platforms": sorted({p for p in platforms.values() if p}),
        "failed_sections": sorted(failed),
        **(
            outputs.pop("headline", None)
            or {
                "metric": "batch_schedule_pods_per_sec_10k_nodes_1k_pods",
                "value": None,
                "unit": "pods/s",
                "vs_baseline": None,
            }
        ),
    }
    result, detail = assemble_line(
        headline, **{name: outputs.get(name) for name in SECTIONS if name != "headline"}
    )
    detail["sections"] = {"platform": platforms, "failed": failed}
    # detail (and its stderr pointer) go FIRST; the headline JSON must be
    # the LAST stdout line so a tail-capturing driver always parses it
    # (ADVICE r5 #3 — r03/r04 lost the headline to output after it)
    path = _detail_path(round_override)
    try:
        with open(path, "w") as f:
            json.dump(detail, f, indent=2)
        print(f"detail -> {path}", file=sys.stderr)
    except OSError as exc:  # counted, but must not cost the headline line
        failed["detail"] = f"detail write failed: {exc}"
        print(failed["detail"], file=sys.stderr)
    children.assert_launcher("bench.py")
    print(json.dumps(result))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
