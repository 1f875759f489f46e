"""Coverage-guided adversarial scenario fuzzing for the digital twin
(docs/robustness.md "Adversarial scenario search"; ROADMAP item 5a).

The thirteen committed scenarios are hand-scripted; this module is the
search engine that writes the fourteenth.  It mutates twin timelines —
load shapes, FaultPlan schedules (outages, flaps, error rates, injected
latency, truncated gossip), failure timing (node kills, replica
crashes, leader kills, partition-owner kills mid-handoff), controller
knob schedules, and admission class mixes — and runs each candidate
against the oracle pack (testing/oracles.py), hunting hard invariant
violations, crashes, and SLO-verdict flips.

Three layers:

  * **genome** — a typed, JSON-serializable description of one
    candidate: a mode (``core`` non-gang fleet / ``admission`` 4x4 mesh
    with the priority plane armed), a config gene set, a tick count,
    and a timeline of typed events.  :class:`FuzzScenario` interprets a
    genome as a first-class ``Scenario`` — same ``build/apply/checks``
    surface as every hand-written program, so a find replays anywhere a
    scenario does.
  * **search** — :class:`FuzzEngine`: a seeded LCG drives generation
    and mutation (never the ``random`` module — pascheck's
    ``randomness`` check enforces the reproducibility contract
    statically); coverage signals come from counter families, journal
    event kinds, non-latency SLO tier transitions, and bucketed
    eviction/fault counts; a candidate contributing a novel signal
    joins the corpus AFL-style.  Candidate #i's genome is a pure
    function of (seed, corpus state), and corpus state is a pure
    function of the deterministic verdicts before it — so two runs with
    the same seed produce byte-identical candidate sequences, and a
    wall-clock budget only truncates the sequence.
  * **minimization** — :func:`minimize` delta-debugs a failing genome:
    drop events, shrink the tick count, simplify config genes — keeping
    each reduction only if the SAME oracle still fires.  The result
    serializes as a versioned JSON scenario (``pas-fuzz-scenario/1``)
    that ``tests/scenarios/`` commits and ``tests/test_twin.py``
    auto-replays.

Planted bugs (:func:`planted_bug`) deliberately reintroduce known bug
classes — the PR-19 stale-digest splice, a rebind path that loses
pods — so the smoke gate (``make fuzz-smoke``) can prove the fuzzer
still finds them within budget, and committed minimized scenarios can
prove they still DETECT the bug class while passing green on the
healthy tree.
"""

from __future__ import annotations

import copy
import hashlib
import json
import math
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Callable, Dict, List, Optional, Set, Tuple

from platform_aware_scheduling_tpu.testing.oracles import (
    DEFAULT_PROGRESS_K,
    OraclePack,
)
from platform_aware_scheduling_tpu.testing.twin import (
    THRESHOLD,
    TwinCluster,
    _AdmissionScenario,
)
from platform_aware_scheduling_tpu.utils import events

#: versioned on-disk scenario format (tests/scenarios/*.json)
SCENARIO_FORMAT = "pas-fuzz-scenario/1"
GENOME_VERSION = 1

#: the design scale every candidate runs at: 16 nodes keeps jax shapes
#: constant across candidates (one compile, thousands of reuses) and
#: matches the tier-1 scenario scale
CORE_NODES = 16
CORE_PODS = 16
PERIOD_S = 5.0

#: FaultPlan verbs the fuzzer may schedule faults on
FAULT_VERBS = ("get_node_metric", "shard_gossip")

#: knob schedule targets (controller territory — the fuzzer turns the
#: same dials the BudgetController does, mid-flight)
KNOB_NAMES = ("admission_depth", "preemption_max_victims")


# ---------------------------------------------------------------------------
# seeded randomness
# ---------------------------------------------------------------------------


class LCG:
    """64-bit linear congruential generator (Knuth's MMIX constants):
    the fuzzer's ONLY randomness source, fully determined by its seed.
    pascheck's ``randomness`` check keeps ``random.*`` out of testing/
    so this contract can't erode silently."""

    _MULT = 6364136223846793005
    _INC = 1442695040888963407
    _MASK = (1 << 64) - 1

    def __init__(self, seed: int):
        self.state = (int(seed) ^ 0x9E3779B97F4A7C15) & self._MASK
        self.u32()  # churn: nearby seeds decorrelate
        self.u32()

    def u32(self) -> int:
        self.state = (self._MULT * self.state + self._INC) & self._MASK
        return (self.state >> 32) & 0xFFFFFFFF

    def random(self) -> float:
        return self.u32() / float(1 << 32)

    def randint(self, a: int, b: int) -> int:
        """Uniform integer in [a, b] inclusive."""
        if b <= a:
            return a
        return a + self.u32() % (b - a + 1)

    def choice(self, seq):
        return seq[self.u32() % len(seq)]

    def chance(self, p: float) -> bool:
        return self.random() < p


def genome_digest(genome: Dict) -> str:
    """Stable content digest: the byte-identity pin compares these."""
    canonical = json.dumps(genome, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canonical.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# the genome
# ---------------------------------------------------------------------------

#: sub-threshold load ceiling a quiet timeline may reach: one resident
#: pod (POD_LOAD) plus this base stays under THRESHOLD with margin
QUIET_LOAD_MAX = THRESHOLD - 200

_QUIET_EVENT_TYPES = ("load_flat", "load_sine")


def is_quiet_genome(genome: Dict) -> bool:
    """A genome is quiet when its timeline could not possibly justify
    an actuation: only sub-threshold load events, no faults, no kills.
    Quiet genomes run with the zero-actuation oracle armed."""
    if genome.get("mode") != "core":
        return False
    for ev in genome.get("events", ()):
        if ev["type"] not in _QUIET_EVENT_TYPES:
            return False
        level = ev.get("value", ev.get("amplitude", 0))
        if level > QUIET_LOAD_MAX:
            return False
    return True


#: every event verb the interpreter understands — the loader's gate
#: (a committed scenario with a typo'd event must fail to load, not
#: silently replay a different timeline)
EVENT_TYPES = frozenset({
    "load_flat",
    "load_sine",
    "load_spike",
    "fail_nodes",
    "crash_replica",
    "restart_replica",
    "kill_leader",
    "kill_owner",
    "fault",
    "knob",
    "submit_gang",
    "submit_singles",
    "complete_gang",
})


def validate_genome(genome: Dict) -> Dict:
    """Shape-check a genome (the loader's gate); returns it."""
    if not isinstance(genome, dict):
        raise ValueError("genome must be a dict")
    if genome.get("version") != GENOME_VERSION:
        raise ValueError(
            f"unsupported genome version {genome.get('version')!r} "
            f"(expected {GENOME_VERSION})"
        )
    if genome.get("mode") not in ("core", "admission"):
        raise ValueError(f"unknown genome mode {genome.get('mode')!r}")
    ticks = genome.get("ticks")
    if not isinstance(ticks, int) or not 1 <= ticks <= 200:
        raise ValueError(f"genome ticks {ticks!r} out of [1, 200]")
    if not isinstance(genome.get("config", {}), dict):
        raise ValueError("genome config must be a dict")
    for ev in genome.get("events", ()):
        if not isinstance(ev, dict) or "type" not in ev or "t" not in ev:
            raise ValueError(f"malformed genome event {ev!r}")
        if ev["type"] not in EVENT_TYPES:
            raise ValueError(f"unknown genome event type {ev['type']!r}")
        if not 0 <= int(ev["t"]) < ticks:
            raise ValueError(
                f"event {ev['type']} at t={ev['t']} outside run of "
                f"{ticks} ticks"
            )
    return genome


def describe_genome(genome: Dict) -> str:
    """One-line human summary for triage output."""
    cfg = genome.get("config", {})
    bits = [genome["mode"], f"{genome['ticks']}t"]
    if cfg.get("replicas", 1) > 1:
        bits.append(f"r{cfg['replicas']}")
    if cfg.get("shard_partitions"):
        bits.append(f"shard{cfg['shard_partitions']}")
    if cfg.get("control"):
        bits.append("ctl")
    if cfg.get("admission_depth") is not None:
        bits.append(f"q{cfg['admission_depth']}")
    bits.extend(
        f"{ev['type']}@{ev['t']}" for ev in genome.get("events", ())
    )
    return " ".join(bits)


# -- generation --------------------------------------------------------------


def _gen_load_event(rng: LCG, t: int, quiet: bool) -> Dict:
    kind = rng.choice(("load_flat", "load_sine", "load_spike"))
    if quiet and kind == "load_spike":
        kind = "load_sine"
    if kind == "load_flat":
        ceiling = QUIET_LOAD_MAX if quiet else THRESHOLD + 300
        return {"type": "load_flat", "t": t, "value": rng.randint(0, ceiling)}
    if kind == "load_sine":
        ceiling = QUIET_LOAD_MAX if quiet else THRESHOLD + 200
        return {
            "type": "load_sine",
            "t": t,
            "amplitude": rng.randint(50, ceiling),
            "period": rng.choice((8, 12, 24)),
        }
    return {
        "type": "load_spike",
        "t": t,
        "frac": rng.choice((0.125, 0.25, 0.5)),
        "value": rng.randint(THRESHOLD, THRESHOLD + 500),
        "duration": rng.randint(2, 8),
    }


def _gen_fault_event(rng: LCG, t: int, ticks: int, shard: bool) -> Dict:
    verbs = FAULT_VERBS if shard else FAULT_VERBS[:1]
    verb = rng.choice(verbs)
    op = rng.choice(
        ("outage", "error_rate", "latency", "fail", "flap", "truncate")
    )
    if op == "truncate" and verb != "shard_gossip":
        op = "fail"
    ev: Dict = {"type": "fault", "t": t, "verb": verb, "op": op}
    if op == "outage":
        ev["duration"] = rng.randint(1, max(1, min(6, ticks - t - 1)))
    elif op == "error_rate":
        ev["rate"] = rng.choice((0.1, 0.25, 0.5))
        ev["duration"] = rng.randint(2, max(2, min(8, ticks - t - 1)))
    elif op == "latency":
        ev["count"] = rng.randint(1, 6)
        ev["seconds"] = rng.choice((0.5, 2.0, 10.0))
    elif op == "fail":
        ev["count"] = rng.randint(1, 6)
    elif op == "flap":
        ev["ok"] = rng.randint(1, 3)
        ev["fail"] = rng.randint(1, 3)
        ev["cycles"] = rng.randint(1, 3)
    elif op == "truncate":
        ev["count"] = rng.randint(1, 6)
        ev["keep"] = rng.randint(0, 2)
    return ev


def generate_genome(rng: LCG) -> Dict:
    """One fresh random genome; every draw comes off ``rng``."""
    mode = "admission" if rng.chance(0.25) else "core"
    if mode == "admission":
        ticks = rng.randint(8, 18)
        config = {"preemption": rng.chance(0.7)}
        events_list: List[Dict] = []
        # batch fill, then contention
        gangs = rng.randint(1, 2)
        for g in range(gangs):
            events_list.append(
                {
                    "type": "submit_gang",
                    "t": 0,
                    "group": f"batch-{g}",
                    "klass": "batch",
                    "size": 8,
                    "topo": "2x4",
                }
            )
        if rng.chance(0.8):
            events_list.append(
                {
                    "type": "submit_gang",
                    "t": rng.randint(2, 5),
                    "group": "gang-high",
                    "klass": "high",
                    "size": 8,
                    "topo": "2x4",
                }
            )
        if rng.chance(0.5):
            events_list.append(
                {
                    "type": "submit_singles",
                    "t": rng.randint(1, 6),
                    "klass": rng.choice(("batch", "high")),
                    "count": rng.randint(1, 4),
                }
            )
        if rng.chance(0.4):
            events_list.append(
                {"type": "complete_gang", "t": rng.randint(5, ticks - 1)}
            )
        if rng.chance(0.3):
            events_list.append(
                _gen_fault_event(rng, rng.randint(1, ticks - 2), ticks, False)
            )
        if rng.chance(0.3):
            events_list.append(
                {
                    "type": "knob",
                    "t": rng.randint(1, ticks - 2),
                    "name": "preemption_max_victims",
                    "value": rng.randint(1, 16),
                }
            )
    else:
        ticks = rng.randint(8, 26)
        shard = rng.chance(0.35)
        replicas = 3 if (shard or rng.chance(0.2)) else 1
        config = {"replicas": replicas}
        if shard:
            config["shard_partitions"] = 4
        if rng.chance(0.25):
            config["control"] = True
        if rng.chance(0.25):
            config["admission_depth"] = rng.randint(2, 12)
            config["serving_capacity"] = rng.randint(1, 4)
        quiet_leaning = rng.chance(0.25)
        events_list = [_gen_load_event(rng, 0, quiet_leaning)]
        extra = rng.randint(0, 5)
        for _ in range(extra):
            t = rng.randint(1, max(1, ticks - 3))
            roll = rng.random()
            if roll < 0.35:
                events_list.append(_gen_load_event(rng, t, quiet_leaning))
            elif roll < 0.55:
                events_list.append(_gen_fault_event(rng, t, ticks, shard))
            elif roll < 0.65:
                events_list.append(
                    {
                        "type": "fail_nodes",
                        "t": t,
                        "count": rng.randint(1, CORE_NODES // 4),
                    }
                )
            elif roll < 0.75 and replicas > 1:
                events_list.append({"type": "kill_leader", "t": t})
            elif roll < 0.85 and shard:
                events_list.append(
                    {"type": "kill_owner", "t": t, "partition": rng.randint(0, 3)}
                )
            elif roll < 0.92 and replicas > 1:
                idx = rng.randint(0, replicas - 1)
                events_list.append(
                    {"type": "crash_replica", "t": t, "index": idx}
                )
                if rng.chance(0.6) and t + 2 < ticks:
                    events_list.append(
                        {
                            "type": "restart_replica",
                            "t": rng.randint(t + 1, ticks - 1),
                            "index": idx,
                        }
                    )
            elif config.get("admission_depth") is not None:
                events_list.append(
                    {
                        "type": "knob",
                        "t": t,
                        "name": "admission_depth",
                        "value": rng.randint(1, 16),
                    }
                )
            else:
                events_list.append(_gen_load_event(rng, t, quiet_leaning))
    events_list.sort(key=lambda ev: ev["t"])
    return {
        "version": GENOME_VERSION,
        "mode": mode,
        "ticks": ticks,
        "config": config,
        "events": events_list,
    }


def mutate_genome(rng: LCG, genome: Dict) -> Dict:
    """1–3 structured mutations on a copy: add/drop/tweak events, bend
    the tick count, toggle a config gene."""
    out = copy.deepcopy(genome)
    for _ in range(rng.randint(1, 3)):
        roll = rng.random()
        evs = out["events"]
        if roll < 0.35:  # add an event
            t = rng.randint(0, max(0, out["ticks"] - 2))
            if out["mode"] == "admission":
                evs.append(
                    {
                        "type": "submit_singles",
                        "t": t,
                        "klass": rng.choice(("batch", "high")),
                        "count": rng.randint(1, 4),
                    }
                    if rng.chance(0.5)
                    else {"type": "complete_gang", "t": t}
                )
            else:
                shard = bool(out["config"].get("shard_partitions"))
                evs.append(
                    _gen_fault_event(rng, t, out["ticks"], shard)
                    if rng.chance(0.5)
                    else _gen_load_event(rng, t, False)
                )
        elif roll < 0.55 and len(evs) > 1:  # drop an event
            evs.pop(rng.u32() % len(evs))
        elif roll < 0.75 and evs:  # tweak an event's tick
            ev = rng.choice(evs)
            ev["t"] = rng.randint(0, max(0, out["ticks"] - 2))
        elif roll < 0.9:  # bend the tick count
            out["ticks"] = max(
                4,
                min(
                    40,
                    out["ticks"] + rng.choice((-4, -2, 2, 4, 8)),
                ),
            )
            out["events"] = [
                ev for ev in evs if ev["t"] < out["ticks"] - 1
            ] or evs[:1]
            for ev in out["events"]:
                ev["t"] = min(ev["t"], out["ticks"] - 1)
        elif out["mode"] == "core":  # toggle a config gene
            gene = rng.choice(("control", "admission", "replicas"))
            cfg = out["config"]
            if gene == "control":
                cfg["control"] = not cfg.get("control", False)
            elif gene == "admission":
                if cfg.get("admission_depth") is None:
                    cfg["admission_depth"] = rng.randint(2, 12)
                    cfg["serving_capacity"] = rng.randint(1, 4)
                else:
                    cfg.pop("admission_depth", None)
                    cfg.pop("serving_capacity", None)
            else:
                cfg["replicas"] = 3 if cfg.get("replicas", 1) == 1 else 1
                if cfg["replicas"] == 1:
                    cfg.pop("shard_partitions", None)
                    out["events"] = [
                        ev
                        for ev in out["events"]
                        if ev["type"]
                        not in (
                            "kill_leader",
                            "kill_owner",
                            "crash_replica",
                            "restart_replica",
                        )
                    ] or out["events"][:1]
    out["events"].sort(key=lambda ev: ev["t"])
    return out


# ---------------------------------------------------------------------------
# the interpreter: a genome as a first-class Scenario
# ---------------------------------------------------------------------------


class FuzzScenario(_AdmissionScenario):
    """Interpret one genome as a replayable scenario program.  The
    genome is authoritative — the ``scale`` argument every Scenario
    carries is ignored so a committed find replays identically
    everywhere.  Checks are the oracle pack's: the fuzzer hunts
    invariant violations, not scripted expectations."""

    def __init__(self, genome: Dict, progress_k: int = DEFAULT_PROGRESS_K):
        self.genome = validate_genome(genome)
        self.progress_k = progress_k
        self.name = f"fuzz-{genome_digest(self.genome)}"
        self.coverage: Set[str] = set()
        self.pack: Optional[OraclePack] = None

    # -- construction ----------------------------------------------------------

    def build(self, scale: Dict) -> TwinCluster:
        # each candidate tells one causal story: reset the process-wide
        # journal here (the _AdmissionScenario convention), never in
        # TwinCluster.__init__
        events.JOURNAL.reset()
        genome = self.genome
        cfg = genome.get("config", {})
        if genome["mode"] == "admission":
            self.pending = []
            self.bound = {}
            self.node_of = {}
            self.single_nodes = set()
            self.admitted_at = None
            twin = TwinCluster(
                num_nodes=self.rows * self.cols,
                gang=True,
                mesh=(self.rows, self.cols),
                gas=False,
                admission_plane=True,
                preemption=bool(cfg.get("preemption", True)),
                admission_starve_consults=4,
                period_s=PERIOD_S,
                requests_per_tick=1,
            )
        else:
            twin = TwinCluster(
                num_nodes=CORE_NODES,
                pods=CORE_PODS,
                period_s=PERIOD_S,
                requests_per_tick=1,
                gas=False,
                replicas=int(cfg.get("replicas", 1)),
                shard_partitions=int(cfg.get("shard_partitions", 0)),
                control=bool(cfg.get("control", False)),
                admission_depth=cfg.get("admission_depth"),
                serving_capacity=cfg.get("serving_capacity"),
            )
        self._by_tick: Dict[int, List[Dict]] = {}
        for ev in genome.get("events", ()):
            self._by_tick.setdefault(int(ev["t"]), []).append(ev)
        self._load_program: Optional[Dict] = None
        self._spikes: List[Dict] = []
        self._clears: Dict[int, List[str]] = {}
        self._last_alerts: Dict[str, str] = {}
        self.coverage = set()
        self.pack = OraclePack(
            quiet=is_quiet_genome(genome), progress_k=self.progress_k
        )
        self.pack.start(twin)
        return twin

    def ticks(self, scale: Dict) -> int:
        return self.genome["ticks"]

    # -- the timeline ----------------------------------------------------------

    def apply(self, twin: TwinCluster, t: int) -> None:
        if t > 0:
            self._observe(twin, t - 1)
        for verb in self._clears.pop(t, ()):
            twin.plan.clear(verb)
        for ev in self._by_tick.get(t, ()):
            self._apply_event(twin, t, ev)
        if self.genome["mode"] == "admission":
            self._drive_round(twin)
            if (
                self.admitted_at is None
                and len(self.bound.get("gang-high", [])) == 8
            ):
                self.admitted_at = t
        else:
            self._apply_load(twin, t)

    def _apply_event(self, twin: TwinCluster, t: int, ev: Dict) -> None:
        kind = ev["type"]
        if kind in ("load_flat", "load_sine"):
            self._load_program = ev
        elif kind == "load_spike":
            self._spikes.append(dict(ev, until=t + int(ev["duration"])))
        elif kind == "fail_nodes":
            live = twin.live_node_names()
            count = min(int(ev["count"]), max(0, len(live) - 4))
            if count > 0:
                twin.fail_nodes(live[-count:])
        elif kind == "crash_replica":
            idx = int(ev["index"])
            if idx < len(twin.replicas):
                twin.crash(idx)
        elif kind == "restart_replica":
            idx = int(ev["index"])
            if idx < len(twin.replicas) and idx in twin.crashed:
                twin.restart(idx)
        elif kind == "kill_leader":
            for i, stack in enumerate(twin.replicas):
                if (
                    stack is not None
                    and i not in twin.crashed
                    and stack.is_leader()
                ):
                    twin.crash(i)
                    break
        elif kind == "kill_owner":
            owners = twin.shard_owners()
            owner = owners.get(int(ev["partition"]))
            if owner and owner.startswith("replica-"):
                idx = int(owner.split("-", 1)[1])
                if idx not in twin.crashed:
                    twin.crash(idx)
        elif kind == "fault":
            self._apply_fault(twin, t, ev)
        elif kind == "knob":
            self._apply_knob(twin, ev)
        elif kind == "submit_gang":
            for i in range(int(ev["size"])):
                self.pending.append(
                    {
                        "pod": self._gang_pod(
                            f"{ev['group']}-{i}",
                            ev["group"],
                            int(ev["size"]),
                            ev["topo"],
                            ev["klass"],
                        ),
                        "group": ev["group"],
                        "candidates": None,
                    }
                )
        elif kind == "submit_singles":
            for i in range(int(ev["count"])):
                name = f"single-{ev['klass']}-{t}-{i}"
                self.pending.append(
                    {
                        "pod": self._single_pod(name, ev["klass"]),
                        "group": name,
                        "candidates": None,
                    }
                )
        elif kind == "complete_gang":
            done = [
                g
                for g, nodes in sorted(self.bound.items())
                if len(nodes) >= 8 and g.startswith(("batch", "gang"))
            ]
            if done:
                group = done[0]
                names = [
                    n
                    for n in self.node_of
                    if n.startswith(f"{group}-")
                ]
                self._complete_gang(twin, names)
                self.bound.pop(group, None)
                for n in names:
                    self.node_of.pop(n, None)

    def _apply_fault(self, twin: TwinCluster, t: int, ev: Dict) -> None:
        plan, verb, op = twin.plan, ev["verb"], ev["op"]
        if op == "outage":
            plan.outage(verb)
            self._clears.setdefault(
                t + int(ev.get("duration", 2)), []
            ).append(verb)
        elif op == "error_rate":
            plan.error_rate(verb, float(ev["rate"]))
            self._clears.setdefault(
                t + int(ev.get("duration", 4)), []
            ).append(verb)
        elif op == "latency":
            plan.latency(verb, int(ev["count"]), float(ev["seconds"]))
        elif op == "fail":
            plan.fail(verb, int(ev["count"]))
        elif op == "flap":
            plan.flap(
                verb, int(ev["ok"]), int(ev["fail"]), int(ev["cycles"])
            )
        elif op == "truncate":
            plan.truncate(verb, int(ev["count"]), int(ev["keep"]))

    def _apply_knob(self, twin: TwinCluster, ev: Dict) -> None:
        name, value = ev["name"], int(ev["value"])
        if name == "admission_depth" and twin.admission is not None:
            twin.admission.max_queue_depth = max(1, value)
        elif name == "preemption_max_victims":
            plane = twin.priority_plane()
            if plane is not None and plane.preemption is not None:
                plane.preemption.max_victims = max(1, value)

    def _apply_load(self, twin: TwinCluster, t: int) -> None:
        program = self._load_program
        base: Dict[str, int] = {}
        live = twin.live_node_names()
        if program is not None:
            if program["type"] == "load_flat":
                base = {n: int(program["value"]) for n in live}
            else:  # load_sine
                amplitude = int(program["amplitude"])
                period = int(program["period"])
                for i, node in enumerate(live):
                    phase = 2.0 * math.pi * (
                        t / period + i / max(1, twin.num_nodes)
                    )
                    base[node] = int(
                        amplitude * 0.5 * (1.0 + math.sin(phase))
                    )
        had_spikes = bool(self._spikes)
        self._spikes = [s for s in self._spikes if s["until"] > t]
        for spike in self._spikes:
            hot = max(1, int(len(live) * float(spike["frac"])))
            for node in live[:hot]:
                base[node] = base.get(node, 0) + int(spike["value"])
        # an expired spike must actually END: republish even when the
        # surviving program is empty, or the last spike values stick
        if base or self._load_program is not None or had_spikes:
            twin.set_base_load(base)

    # -- observation: coverage signals -----------------------------------------

    def _observe(self, twin: TwinCluster, t: int) -> None:
        if self.pack is not None:
            self.pack.on_tick(twin, t)
        engine = twin.engine
        if engine is None:
            return
        for name, entry in engine.judge().items():
            if engine.slos[name].sli == "latency":
                continue  # wall-clock jitter must not steer the search
            alert = entry.get("alert") or "ok"
            if alert != "ok":
                self.coverage.add(f"alert:{name}:{alert}")
            last = self._last_alerts.get(name)
            if last is not None and last != alert:
                self.coverage.add(f"flip:{name}:{last}->{alert}")
            self._last_alerts[name] = alert

    @staticmethod
    def _bucket(n: int) -> int:
        return n.bit_length()  # 0, 1, 2, 2, 3, 3, 3, 3, 4 ...

    def _final_coverage(self, twin: TwinCluster) -> None:
        engine = twin.engine
        for record in events.JOURNAL.snapshot():
            if record["kind"] == "slo" and engine is not None:
                slo = engine.slos.get(record["data"].get("slo"))
                if slo is not None and slo.sli == "latency":
                    continue  # as in _observe: a wall-clock flip
            self.coverage.add(f"kind:{record['kind']}")
        counter_sets = [("serving", twin.serving_counters)]
        plane = twin.priority_plane()
        if plane is not None:
            counter_sets.append(("admission", plane.counters))
        for i, stack in enumerate(twin.replicas):
            if stack is not None and getattr(stack, "shard", None):
                counter_sets.append((f"shard{i}", stack.shard.counters))
        for tag, cs in counter_sets:
            with cs._lock:
                families = [
                    name
                    for table in (cs._counters, cs._gauges)
                    for name, series in table.items()
                    if any(series.values())
                ]
            for family in families:
                self.coverage.add(f"counter:{tag}:{family}")
        self.coverage.add(
            f"evictions:b{self._bucket(len(twin.evictions()))}"
        )
        self.coverage.add(
            f"traffic_errors:b{self._bucket(twin.traffic.get('errors', 0))}"
        )
        for i, stack in enumerate(twin.replicas):
            if stack is not None and getattr(stack, "shard", None):
                gossip = stack.shard.gossip
                if gossip.pulls_failed:
                    self.coverage.add(
                        f"gossip_failed:b{self._bucket(gossip.pulls_failed)}"
                    )
                if stack.shard.store.fenced_rejects:
                    self.coverage.add("digest_fenced")

    # -- judgment --------------------------------------------------------------

    def checks(self, twin: TwinCluster) -> List[Dict]:
        self._observe(twin, self.genome["ticks"] - 1)
        self._final_coverage(twin)
        return self.pack.checks(twin) if self.pack is not None else []


# ---------------------------------------------------------------------------
# the search engine
# ---------------------------------------------------------------------------

#: hand-authored starting points (standard fuzzing practice: the corpus
#: seeds aim the mutator at each subsystem's interesting region).  The
#: engine runs them as candidates 0..k-1 before generating fresh ones.
SEED_GENOMES: Tuple[Dict, ...] = (
    {  # quiet diurnal: the null hypothesis (zero-actuation pin armed)
        "version": 1,
        "mode": "core",
        "ticks": 10,
        "config": {"replicas": 1},
        "events": [
            {"type": "load_sine", "t": 0, "amplitude": 150, "period": 8}
        ],
    },
    {  # deployment spike: evictions + rebinds (population territory)
        "version": 1,
        "mode": "core",
        "ticks": 14,
        "config": {"replicas": 1},
        "events": [
            {
                "type": "load_spike",
                "t": 2,
                "frac": 0.25,
                "value": 600,
                "duration": 8,
            }
        ],
    },
    {  # partition-owner kill mid-handoff, gossip dark through the
        # handoff window: survivors shelve pre-kill digests while the
        # journal epoch moves past them (splice/fencing territory)
        "version": 1,
        "mode": "core",
        "ticks": 14,
        "config": {"replicas": 3, "shard_partitions": 4},
        "events": [
            {"type": "load_flat", "t": 0, "value": 120},
            {"type": "kill_owner", "t": 5, "partition": 0},
            {
                "type": "fault",
                "t": 5,
                "verb": "shard_gossip",
                "op": "outage",
                "duration": 8,
            },
        ],
    },
    {  # metric storm: outage then recovery
        "version": 1,
        "mode": "core",
        "ticks": 12,
        "config": {"replicas": 1},
        "events": [
            {
                "type": "fault",
                "t": 3,
                "verb": "get_node_metric",
                "op": "outage",
                "duration": 4,
            }
        ],
    },
    {  # gossip chaos: truncated + slow + flaky digest exchange
        "version": 1,
        "mode": "core",
        "ticks": 14,
        "config": {"replicas": 3, "shard_partitions": 4},
        "events": [
            {
                "type": "fault",
                "t": 2,
                "verb": "shard_gossip",
                "op": "truncate",
                "count": 6,
                "keep": 1,
            },
            {
                "type": "fault",
                "t": 6,
                "verb": "shard_gossip",
                "op": "error_rate",
                "rate": 0.5,
                "duration": 6,
            },
        ],
    },
    {  # admission class mix: preemption cascade shape
        "version": 1,
        "mode": "admission",
        "ticks": 12,
        "config": {"preemption": True},
        "events": [
            {
                "type": "submit_gang",
                "t": 0,
                "group": "batch-0",
                "klass": "batch",
                "size": 8,
                "topo": "2x4",
            },
            {
                "type": "submit_gang",
                "t": 0,
                "group": "batch-1",
                "klass": "batch",
                "size": 8,
                "topo": "2x4",
            },
            {
                "type": "submit_gang",
                "t": 4,
                "group": "gang-high",
                "klass": "high",
                "size": 8,
                "topo": "2x4",
            },
        ],
    },
)

_GOLDEN = 0x9E3779B9


def run_candidate(
    genome: Dict, progress_k: int = DEFAULT_PROGRESS_K
) -> Dict:
    """Run one genome to a deterministic verdict record.  The record
    carries ONLY fake-clock-deterministic facts (oracle outcomes,
    coverage signals, crash reprs) — never wall-clock latencies — so
    two runs of the same genome compare byte-equal."""
    scenario = FuzzScenario(genome, progress_k=progress_k)
    failures: List[str] = []
    error = None
    try:
        result = scenario.run()
        failures = [
            c["check"] for c in result["checks"] if not c["ok"]
        ]
        verdict = "fail" if failures else "ok"
    except Exception as exc:  # a crash IS a find
        verdict = "crash"
        error = f"{type(exc).__name__}: {exc}"
    record = {
        "digest": genome_digest(genome),
        "verdict": verdict,
        "failures": sorted(failures),
        "coverage": sorted(scenario.coverage),
    }
    if error is not None:
        record["error"] = error
    return record


class FuzzEngine:
    """The coverage-guided search loop.  Candidate #i's genome is a
    pure function of (seed, the deterministic verdicts of candidates
    0..i-1); a wall-clock budget only truncates the sequence, so two
    invocations with one seed produce byte-identical prefixes."""

    def __init__(
        self,
        seed: int = 7,
        max_corpus: int = 64,
        progress_k: int = DEFAULT_PROGRESS_K,
    ):
        self.seed = int(seed)
        self.max_corpus = int(max_corpus)
        self.progress_k = progress_k
        self.corpus: List[Dict] = []  # {"genome", "coverage"}
        self.seen: Set[str] = set()
        self.records: List[Dict] = []
        self.finds: List[Dict] = []

    def next_genome(self, i: int) -> Dict:
        if i < len(SEED_GENOMES):
            return copy.deepcopy(SEED_GENOMES[i])
        rng = LCG(self.seed * _GOLDEN + i * 2654435761)
        if self.corpus and rng.chance(0.7):
            entry = rng.choice(self.corpus)
            return mutate_genome(rng, entry["genome"])
        return generate_genome(rng)

    def run_one(self, i: int) -> Dict:
        genome = self.next_genome(i)
        record = dict(run_candidate(genome, self.progress_k), index=i)
        fresh = set(record["coverage"]) - self.seen
        record["new_signals"] = len(fresh)
        if fresh:
            self.seen.update(fresh)
            self.corpus.append(
                {"genome": genome, "coverage": record["coverage"]}
            )
            if len(self.corpus) > self.max_corpus:
                self.corpus.pop(0)
        if record["verdict"] != "ok":
            self.finds.append(
                {
                    "index": i,
                    "genome": genome,
                    "verdict": record["verdict"],
                    "failures": record["failures"],
                    "error": record.get("error"),
                }
            )
        self.records.append(record)
        return record

    def fuzz(
        self,
        time_budget_s: Optional[float] = None,
        max_candidates: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
        stop_on_find: bool = False,
    ) -> Dict:
        """Run candidates until the budget (wall clock and/or count) is
        spent.  Returns the summary fuzz_load reports."""
        if time_budget_s is None and max_candidates is None:
            raise ValueError("need a time budget or a candidate cap")
        started = clock()
        i = len(self.records)
        first = i
        while True:
            if max_candidates is not None and i - first >= max_candidates:
                break
            if (
                time_budget_s is not None
                and clock() - started >= time_budget_s
            ):
                break
            record = self.run_one(i)
            i += 1
            if stop_on_find and record["verdict"] != "ok":
                break
        elapsed = clock() - started
        return {
            "candidates": i - first,
            "elapsed_s": round(elapsed, 3),
            "candidates_per_s": round(
                (i - first) / elapsed, 2
            ) if elapsed > 0 else None,
            "corpus_size": len(self.corpus),
            "coverage_signals": len(self.seen),
            "finds": len(self.finds),
            "find_failures": sorted(
                {f for find in self.finds for f in find["failures"]}
            ),
        }


# ---------------------------------------------------------------------------
# minimization
# ---------------------------------------------------------------------------


def _still_fails(
    genome: Dict,
    expect: Set[str],
    runner: Callable[[Dict], Dict],
) -> bool:
    try:
        record = runner(genome)
    except Exception:
        return False
    if expect == {"crash"}:
        return record["verdict"] == "crash"
    return bool(expect & set(record["failures"]))


def minimize(
    genome: Dict,
    failures: List[str],
    runner: Optional[Callable[[Dict], Dict]] = None,
    max_attempts: int = 120,
) -> Dict:
    """Delta-debug a failing genome to a minimal reproducer: drop
    events, shrink the tick count, zero out config genes — each
    reduction survives only if one of the ORIGINAL failing oracles
    still fires.  Returns ``{"genome", "attempts", "failures"}``."""
    runner = runner or run_candidate
    expect = set(failures) or {"crash"}
    current = copy.deepcopy(validate_genome(genome))
    attempts = 0

    def try_reduce(candidate: Dict) -> bool:
        nonlocal attempts, current
        if attempts >= max_attempts:
            return False
        attempts += 1
        try:
            validate_genome(candidate)
        except ValueError:
            return False
        if _still_fails(candidate, expect, runner):
            current = candidate
            return True
        return False

    # 1. drop events, largest-first sweeps until a fixed point
    changed = True
    while changed and attempts < max_attempts:
        changed = False
        for idx in range(len(current["events"]) - 1, -1, -1):
            candidate = copy.deepcopy(current)
            del candidate["events"][idx]
            if candidate["events"] and try_reduce(candidate):
                changed = True
    # 2. shrink the tick count: binary search down to the latest event
    floor = max(
        (int(ev["t"]) for ev in current["events"]), default=0
    ) + 2
    lo, hi = floor, current["ticks"]
    while lo < hi and attempts < max_attempts:
        mid = (lo + hi) // 2
        candidate = copy.deepcopy(current)
        candidate["ticks"] = mid
        if try_reduce(candidate):
            hi = mid
        else:
            lo = mid + 1
    # 3. zero out config genes one at a time
    for gene in ("control", "admission_depth", "serving_capacity"):
        if current["config"].get(gene):
            candidate = copy.deepcopy(current)
            candidate["config"].pop(gene, None)
            if gene == "admission_depth":
                candidate["config"].pop("serving_capacity", None)
            try_reduce(candidate)
    # 4. shrink noisy numeric event params
    for idx, ev in enumerate(list(current["events"])):
        for key in ("count", "duration", "cycles"):
            if int(ev.get(key, 0)) > 1:
                candidate = copy.deepcopy(current)
                candidate["events"][idx][key] = 1
                try_reduce(candidate)
    final = runner(current)
    return {
        "genome": current,
        "attempts": attempts,
        "failures": final["failures"] or (
            ["crash"] if final["verdict"] == "crash" else []
        ),
    }


# ---------------------------------------------------------------------------
# planted bugs
# ---------------------------------------------------------------------------

PLANTED_BUGS = ("stale_digest_splice", "lost_rebind")


@contextmanager
def planted_bug(name: str):
    """Deliberately reintroduce a known bug class for the duration of
    the context — the smoke gate's ground truth.  Patches are
    class-level and restored unconditionally.

    * ``stale_digest_splice`` (the PR-19 class): the DigestStore stops
      enforcing epoch fencing at ingest AND serves held digests without
      the epoch/staleness re-check — a fenced-out owner's view reaches
      verdicts after a handoff (oracle ``shard_splice`` fires).
    * ``lost_rebind``: the twin's kube-controller stand-in acknowledges
      evictions without re-creating the pods — evicted pods vanish
      (oracle ``population`` fires on any timeline that evicts).
    """
    if name == "stale_digest_splice":
        from platform_aware_scheduling_tpu.shard.digest import DigestStore

        orig_put, orig_fresh = DigestStore.put, DigestStore.fresh

        def put(self, digest):
            with self._lock:
                held = self._digests.get(digest.partition)
                if held is not None and held.stamp > digest.stamp:
                    return False
                self._digests[digest.partition] = digest
                self._stale_flagged[digest.partition] = False
            return True

        def fresh(self, partition):
            with self._lock:
                return self._digests.get(int(partition))

        DigestStore.put, DigestStore.fresh = put, fresh
        try:
            yield
        finally:
            DigestStore.put, DigestStore.fresh = orig_put, orig_fresh
    elif name == "lost_rebind":
        orig = TwinCluster._rebind_evicted

        def lost(self):
            self._seen_evictions = len(self.fake.evictions)

        TwinCluster._rebind_evicted = lost
        try:
            yield
        finally:
            TwinCluster._rebind_evicted = orig
    else:
        raise ValueError(
            f"unknown planted bug {name!r} (known: {PLANTED_BUGS})"
        )


# ---------------------------------------------------------------------------
# versioned scenario serialization
# ---------------------------------------------------------------------------


def scenario_to_obj(
    genome: Dict,
    *,
    expect: List[str],
    planted: Optional[str] = None,
    seed: Optional[int] = None,
    notes: str = "",
) -> Dict:
    """The committed-scenario JSON shape.  ``expect`` names the oracle
    checks that fired when this was found; ``planted`` names the
    planted bug (if any) the find came from — replay asserts the
    scenario passes GREEN on the healthy tree and still detects the
    bug class when the plant is re-applied."""
    return {
        "format": SCENARIO_FORMAT,
        "genome": validate_genome(genome),
        "expect": sorted(expect),
        "planted_bug": planted,
        "seed": seed,
        "notes": notes,
    }


def save_scenario(path, obj: Dict) -> None:
    Path(path).write_text(json.dumps(obj, indent=2, sort_keys=True) + "\n")


def load_scenario(source) -> FuzzScenario:
    """Load a committed fuzz scenario (path, JSON text, or dict) into a
    first-class replayable Scenario.  The attached ``expect`` /
    ``planted`` attributes drive the regression replay contract."""
    if isinstance(source, (str, Path)) and not str(source).lstrip().startswith(
        "{"
    ):
        obj = json.loads(Path(source).read_text())
    elif isinstance(source, (str, bytes)):
        obj = json.loads(source)
    else:
        obj = source
    if obj.get("format") != SCENARIO_FORMAT:
        raise ValueError(
            f"not a fuzz scenario (format {obj.get('format')!r}, "
            f"expected {SCENARIO_FORMAT})"
        )
    scenario = FuzzScenario(obj["genome"])
    scenario.expect = list(obj.get("expect") or [])
    scenario.planted = obj.get("planted_bug")
    scenario.notes = obj.get("notes", "")
    return scenario


__all__ = [
    "EVENT_TYPES",
    "FAULT_VERBS",
    "FuzzEngine",
    "FuzzScenario",
    "GENOME_VERSION",
    "KNOB_NAMES",
    "LCG",
    "PLANTED_BUGS",
    "SCENARIO_FORMAT",
    "SEED_GENOMES",
    "describe_genome",
    "generate_genome",
    "genome_digest",
    "is_quiet_genome",
    "load_scenario",
    "minimize",
    "mutate_genome",
    "planted_bug",
    "run_candidate",
    "save_scenario",
    "scenario_to_obj",
    "validate_genome",
]
