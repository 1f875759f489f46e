"""The plain reference of the ``batch-10k`` deployment, and the comparison
that decides ``correct`` in its cells.

It imports nothing of the program and takes nothing the program has made:
only what crossed the wire (the driver's records), what the played APIs saw
(telemetry fetches, ``pods/binding`` writes) and stamps taken on the
harness's own clock through the program's hooks (each replan's begin and
end; when the planner's informer had been fed each binding).  NumPy and the
standard library, run after the window has closed.

**The plan.**  For pod i in creation order, among the nodes that (a) report
its policy's ``scheduleonmetric`` metric, (b) do not violate its own
policy's ``dontschedule`` at the round in force and (c) have room left by
kube-scheduler's NodeResourcesFit after the bound and the already-planned
pods: the best by the rule's operator, ties to the lowest node index.  All
pods of a policy share one ranking, so a plan is one pointer per policy
walking its ranked list (``Plan``), extended only as far as a pod is asked
for.

**The states a plan may have been solved on.**  A replan runs in the refresh
thread after a pass's publishes, so its rounds are those fetched before its
begin.  Bindings go out in pod order on one connection and reach the
planner in that order, so the bound set it read is a prefix of them: at
least those its informer had been fed before the replan began, at most those
sent before it ended.  Every prefix between the two is admissible, and an
answer is held to either.

**A plan that is withheld.**  Where the plan's node is the ordinal
ranking's first host already — every answer of ``batch-10k.backlog-drain``,
whose pods are alike and are offered every node kube's Fit still passes — an
answer given without the plan has the same bytes as one given with it, and
no comparison of answers can miss it.  So ``promotions_missing`` also holds
the program's own count of the answers it led with a current plan's node
(``led``: the window's increase of ``pas_planner_promoted_total``, counted
where the promotion is made) to this reference's count of the answers that
had to be (``plan_current``, less those already missed by their bytes): a
planner that answers nothing, or withholds a part of its plan larger than
the share of answers given while no plan was certainly current, cannot pass.

Every number compared is a count of disagreements, so every limit is 0.
"""

from __future__ import annotations

import bisect
import itertools

import numpy as np

import batch_world
from generator import bench_pod_name, node_names, tas_policies
from reference import Rounds, tas_filter, tas_prioritize, tas_violating


class Plan:
    """Greedy in creation order over one state, as far as it is asked."""

    HORIZON = 4096  # pending pods a plan is ever asked past: none

    def __init__(self, pending: np.ndarray, which: np.ndarray, ranked: list,
                 room: np.ndarray):
        # positions, in creation order
        self.pending = pending[: self.HORIZON].tolist()
        self.which, self.ranked, self.room = which, ranked, room
        self.pointer = [0] * len(ranked)
        self.done = 0  # pending pods planned so far
        self.node = {}  # position -> node index, or None

    def node_of(self, position: int):
        """The plan's node for the pod at ``position`` (None: no node has
        room; also None for a pod that is bound in this state)."""
        pending, room = self.pending, self.room
        while (position not in self.node and self.done < len(pending)
               and pending[self.done] <= position):
            at = pending[self.done]
            self.done += 1
            policy = self.which[at]
            order, pointer = self.ranked[policy], self.pointer[policy]
            while pointer < len(order) and room[order[pointer]] <= 0:
                pointer += 1
            self.pointer[policy] = pointer
            if pointer < len(order):
                room[order[pointer]] -= 1
                self.node[at] = int(order[pointer])
            else:
                self.node[at] = None
        return self.node.get(position)


def ranked_nodes(policy: dict, columns) -> np.ndarray:
    """Node indices best first by the policy's scheduleonmetric rule, less
    those its own dontschedule forbids; ``columns(metric)`` is the metric's
    column at the round in force."""
    metric, operator, _ = policy["strategies"]["scheduleonmetric"][0]
    column = columns(metric)
    order = np.argsort(-column if operator == "GreaterThan" else column,
                       kind="stable")
    rules = policy["strategies"]["dontschedule"]
    forbidden = tas_violating(rules, {m: columns(m) for m, _, _ in rules})
    return order[~forbidden[order]]


class World:
    """What the seed and the played APIs fix: pods by position in creation
    order (the warm-up's first, then the backlog), their policies, the
    bindings in the order the API took them."""

    def __init__(self, config: dict, seed: int, warm_pods: int,
                 bindings: list, observed: dict, records: list):
        n = config["nodes"]
        self.names = node_names(config["node_prefix"], n)
        index = {name: i for i, name in enumerate(self.names)}
        self.policies = tas_policies(config)
        self.fit = batch_world.fit_per_node(config)
        self.warm_pods = warm_pods
        self.which = np.concatenate((
            np.arange(warm_pods) % len(self.policies),
            batch_world.pod_policies(config, seed))).astype(np.int64)
        position = {f"warm-{i:05d}": i for i in range(warm_pods)}
        base = np.bincount(batch_world.init_pod_nodes(config, seed), minlength=n)
        sent_by = {bench_pod_name(r["index"]): r["bind_t"][0] for r in records}
        # the bindings in API order: position, node, sent, fed to the planner
        self.bound, self.twice, self.unknown = [], 0, 0
        seen = set()
        for taken, pod, node in sorted(bindings):
            at = position.get(pod)
            if at is None and pod.startswith("bench-"):
                at = warm_pods + int(pod[6:])
            if at is None or at >= len(self.which) or node not in index:
                self.unknown += 1
                continue
            if at in seen:
                self.twice += 1
                continue
            seen.add(at)
            sent = sent_by.get(pod, np.nan)
            self.bound.append((at, index[node], taken if np.isnan(sent) else sent,
                               observed.get(pod, np.inf)))
        self.bound_rank = np.full(len(self.which), len(self.bound) + 1)
        for rank, (at, _node, _sent, _fed) in enumerate(self.bound):
            self.bound_rank[at] = rank
        self.base = base
        self.bound_nodes = np.array([b[1] for b in self.bound], dtype=np.int64)
        self.sent = [b[2] for b in self.bound]
        # fed[k]: when the planner had been fed the first k+1 bindings
        self.fed = list(itertools.accumulate((b[3] for b in self.bound), max))

    def position(self, record: dict) -> int:
        return self.warm_pods + record["index"]

    def states(self, begin: float, end: float) -> range:
        """The prefixes of the bindings a replan over [begin, end] may have
        read: from those fed to the planner before it began to those sent
        before it ended."""
        return range(bisect.bisect_left(self.fed, begin),
                     bisect.bisect_left(self.sent, end) + 1)

    def held(self, bound: int = None) -> np.ndarray:
        """Pods on each node once the first ``bound`` bindings (None: all)
        have landed."""
        return self.base + np.bincount(
            self.bound_nodes[:bound], minlength=len(self.base))

    def plan(self, ranked: list, bound: int) -> Plan:
        pending = np.flatnonzero(self.bound_rank >= bound)
        return Plan(pending, self.which, ranked, self.fit - self.held(bound))


def compare(config: dict, seed: int, window: dict, fetches: list, replans: list,
            bindings: list, observed: dict, warm_pods: int, led: int) -> dict:
    """Hold the window to the reference.  ``replans``: [begin, end] of every
    replan since the start, in order; ``bindings``: (taken at, pod, node)
    as the played API took them; ``observed``: pod -> when the planner's
    informer had fed its binding in; ``led``: the window's Prioritize
    answers the program counted as led by a current plan's node."""
    records = window["records"]
    world = World(config, seed, warm_pods, bindings, observed, records)
    rounds = Rounds(config, seed, fetches)
    policies, names, n = world.policies, world.names, config["nodes"]
    fetch_times = sorted(at for at, _metric, _round in fetches)
    numbers = {
        "filter_mismatched": 0, "prioritize_mismatched": 0,
        "rounds_backwards": 0, "promotions_wrong": 0, "promotions_missing": 0,
        "room_exceeded": int(np.maximum(world.held() - world.fit, 0).sum()),
        "pods_unplaced": 0, "pods_placed_twice": world.twice + world.unknown,
        "dontschedule_violated": 0,
    }
    counted = {"filters": 0, "prioritizes": 0, "kept": 0, "bindings": 0,
               "promoted": 0, "plan_current": 0, "plan_followed": 0,
               "plan_node_not_offered": 0, "states_a_replan": 0, "led": led,
               "nodes_filled": len(window.get("left", ())),
               # answers the driver's short readers could not take, and what
               # the driver itself costs a pod: the window less the verbs'
               # round trips, over the pods (kube-scheduler's side, in Python)
               "answers_read_whole": sum(window.get("read_whole", ())),
               "client_ms_a_pod": 1e3 * (
                   window["ended"] - window["began"] - sum(
                       np.nansum(np.diff(r["t"])[::2]) for r in records)
               ) / max(len(records), 1)}
    notes = []
    current = {m: 0 for m in rounds.metrics}  # oldest round still admissible
    violating = {}  # (policy, its metrics' rounds) -> mask over all nodes

    def forbidden(which: int, chosen: dict) -> np.ndarray:
        key = (which, tuple(sorted(chosen.items())))
        if key not in violating:
            violating[key] = tas_violating(
                policies[which]["strategies"]["dontschedule"],
                {m: rounds.column(m, k) for m, k in chosen.items()})
        return violating[key]

    def admissible(metrics: list, at: float) -> list:
        """Combinations of rounds, oldest first: none older than the last
        seen, none newer than the API had served."""
        spans = [range(current[m], rounds.served_before(m, at) + 1)
                 for m in metrics]
        return [dict(zip(metrics, combo))
                for combo in sorted(itertools.product(*spans), key=sum)]

    def search(metrics: list, options: list, matches):
        """The oldest of ``options`` (admissible combinations of rounds)
        under which ``matches`` holds, or None."""
        for chosen in options:
            if matches(chosen):
                for metric, k in chosen.items():
                    current[metric] = max(current[metric], k)
                return chosen
        for metric in metrics:  # one step back, only to name the fault
            if current[metric] > 0:
                stale = {m: current[m] for m in metrics}
                stale[metric] -= 1
                if matches(stale):
                    numbers["rounds_backwards"] += 1
                    break
        return None

    # -- the replans, and the plans each may have published ---------------------
    replans = [tuple(r) for r in replans]
    plans = {}  # replan -> [Plan per admissible state]

    def plans_of(at: int) -> list:
        if at not in plans:
            begin, end = replans[at]
            in_force = {m: rounds.served_before(m, begin) for m in rounds.metrics}
            ranked = [ranked_nodes(p, lambda m: rounds.column(m, in_force[m]))
                      if min(in_force.values()) >= 0 else np.zeros(0, np.int64)
                      for p in policies]
            plans[at] = [world.plan(ranked, k) for k in world.states(begin, end)]
            counted["states_a_replan"] = max(
                counted["states_a_replan"], len(plans[at]))
        return plans[at]

    ends = [end for _begin, end in replans]

    def replans_for(sent: float, answered: float) -> tuple:
        """(replans whose plan the answer may carry, the replan whose plan it
        must carry or None).  A plan is published by the end of its replan
        and is dropped with the first publish of the next pass, which lies
        after that pass's first fetch and before its own replan ends."""
        may, must = [], None
        for at, (begin, end) in enumerate(replans):
            if begin >= answered:
                break
            dropped_by = ends[at + 1] if at + 1 < len(ends) else np.inf
            if sent < dropped_by:
                may.append(at)
            after = bisect.bisect_right(fetch_times, begin)
            next_fetch = (fetch_times[after] if after < len(fetch_times)
                          else np.inf)
            if end < sent and answered < next_fetch:
                must = at
        return may, must

    # -- the window, cycle by cycle -----------------------------------------------
    feasible = np.ones(n, dtype=bool)
    left, gone = window.get("left", []), 0
    in_passed = np.zeros(n, dtype=bool)
    for record in records:
        if record["error"]:  # counted by run.py, as requests_failed
            notes.append(f"cycle {record['index']}: {record['error']}")
            continue
        which = record["which"]
        policy = policies[which]
        while gone < record["gone"]:
            feasible[left[gone]] = False
            gone += 1
        candidates = np.flatnonzero(feasible)
        rules = policy["strategies"]["dontschedule"]
        metrics = sorted({metric for metric, _, _ in rules})
        got_passed, got_failed = record["passed"], np.sort(record["failed"])

        def filter_matches(chosen):
            passed, failed = tas_filter(candidates, forbidden(which, chosen))
            return (np.array_equal(passed, got_passed)
                    and np.array_equal(failed, got_failed))

        counted["filters"] += 1
        options = admissible(metrics, record["t"][1])
        if search(metrics, options, filter_matches) is None:
            numbers["filter_mismatched"] += 1
            notes.append(
                f"cycle {record['index']} filter ({policy['name']}): no "
                f"admissible round of {metrics} gives {len(got_passed)} passed "
                f"/ {len(got_failed)} failed of {len(candidates)} (rounds "
                f"from {current})")
        if record["second"] != "prioritize":
            continue

        # Prioritize: the ordinal ranking, or that ranking with one node first
        counted["prioritizes"] += 1
        metric, operator, _ = policy["strategies"]["scheduleonmetric"][0]
        top = record["node"]
        in_passed[:] = False
        in_passed[got_passed] = True
        sent, answered = record["t"][2], record["t"][3]

        def ordinal_top(k: int) -> int:
            column = rounds.column(metric, k)[got_passed]
            best = column.argmax() if operator == "GreaterThan" else column.argmin()
            return int(got_passed[best])

        if "order" in record:
            counted["kept"] += 1
            order, scores = record["order"], record["scores"]
            promoted = [None]

            def prioritize_matches(chosen):
                want, want_scores = tas_prioritize(
                    got_passed, rounds.column(metric, chosen[metric]), operator)
                promoted[0] = len(order) > 0 and order[0] != want[0]
                if promoted[0] and in_passed[order[0]]:
                    want = np.concatenate(
                        ([order[0]], want[want != order[0]]))
                return (len(order) == len(want)
                        and np.array_equal(scores, want_scores)
                        and np.array_equal(order, want))

            if search([metric], admissible([metric], answered),
                      prioritize_matches) is None:
                numbers["prioritize_mismatched"] += 1
                notes.append(
                    f"cycle {record['index']} prioritize ({policy['name']}): no "
                    f"admissible round of {metric} gives this order, plain or "
                    f"with its first host promoted (rounds from {current[metric]})")
                continue
            is_promoted = bool(promoted[0])
        else:
            tops = {ordinal_top(c[metric]) for c in admissible([metric], answered)}
            is_promoted = top not in tops
        position = world.position(record)
        may, must = replans_for(sent, answered)
        if is_promoted:
            counted["promoted"] += 1
            if any(plan.node_of(position) == top
                   for at in may for plan in plans_of(at)):
                counted["plan_current"] += must is not None
                counted["plan_followed"] += must is not None
            else:
                numbers["promotions_wrong"] += 1
                notes.append(
                    f"cycle {record['index']}: {names[top]} promoted, the "
                    f"plan's node for the pod in no admissible state "
                    f"({len(may)} replans)")
        elif must is not None:
            planned = {plan.node_of(position) for plan in plans_of(must)}
            if None in planned or not all(in_passed[node] for node in planned):
                counted["plan_node_not_offered"] += 1
            else:
                counted["plan_current"] += 1
                if top in planned:
                    counted["plan_followed"] += 1
                else:
                    numbers["promotions_missing"] += 1
                    notes.append(
                        f"cycle {record['index']}: no promotion, though a plan "
                        f"on the version served gives the pod "
                        f"{sorted(names[p] for p in planned)}, among the "
                        f"candidates sent")

        # the binding: acknowledged, once, on a node the pod's own policy
        # allowed at a round admissible for this cycle
        if top >= 0:
            counted["bindings"] += 1
            if record["bind_status"] != 201 or world.bound_rank[position] > len(
                    world.bound):
                numbers["pods_unplaced"] += 1
                notes.append(f"cycle {record['index']}: picked {names[top]}, "
                             f"binding status {record['bind_status']}")
            if options and all(forbidden(which, c)[top] for c in options):
                numbers["dontschedule_violated"] += 1
                notes.append(f"cycle {record['index']}: bound on {names[top]}, "
                             f"which {policy['name']} forbids at every "
                             f"admissible round")
    # the answers that had to lead with the plan's node and, by their bytes,
    # did, against the program's own count of those it led with one
    withheld = (counted["plan_current"] - numbers["promotions_missing"]) - led
    if withheld > 0:
        numbers["promotions_missing"] += withheld
        notes.append(
            f"{counted['plan_current']} answers were given while a plan on the "
            f"version served was current and gave the pod a node among the "
            f"candidates sent; the program counts {led} answers led by a "
            f"plan's node: {withheld} withheld")
    if numbers["room_exceeded"]:
        over = np.flatnonzero(world.held() > world.fit)
        notes.append(f"{len(over)} nodes hold more than {world.fit} pods, e.g. "
                     f"{[names[i] for i in over[:3]]}")
    return {"numbers": numbers, "notes": notes, "counted": counted,
            "replans": replans}
