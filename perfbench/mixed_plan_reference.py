"""The plain reference of the ``alibaba-colo-4k`` deployment, and the
comparison that decides ``correct`` in its cell: ``plan_reference.py``'s,
over pods that ask for unlike amounts.

It imports nothing of the program and takes nothing the program has made:
only what crossed the wire (the driver's records), what the played APIs saw
(telemetry fetches, ``pods/binding`` writes) and stamps taken on the
harness's own clock.  NumPy and the standard library; the room is kept and
compared in Python integers.

**The plan.**  For pod i in creation order, among the nodes that (a) report
its policy's ``scheduleonmetric`` metric, (b) do not violate its own
policy's ``dontschedule`` at the round in force and (c) have
``free[r] >= request[r]`` for pods, cpu and memory — the pod's OWN requests,
kube-scheduler's NodeResourcesFit, after the bound and the already-planned
pods: the best by the rule's operator, ties to the lowest node index.  The
chosen node's free amounts lose the pod's requests.  A node without room for
one class may have room for another, but never again for the same: so a plan
is one pointer per (policy, class) walking the policy's ranked list
(``Plan``), extended only as far as a pod is asked for.

**What is ``plan_reference``'s and is imported**: the ranked lists, the
bindings' order and the states a replan may have read (``World``), the
rules for an answer that may or must carry a plan's node, and what a
withheld plan is held to (``led`` against ``plan_current``).  What is this
module's: the per-pod room, the candidates per class (a record's ``gone``
counts into its class's ``left``), and ``room_exceeded`` resource by
resource.

Every number compared is a count of disagreements, so every limit is 0.
"""

from __future__ import annotations

import bisect
import itertools

import numpy as np

import mixed_world
import plan_reference
from reference import Rounds, tas_filter, tas_prioritize, tas_violating

RESOURCES = mixed_world.RESOURCES


class Plan:
    """Greedy in creation order over one state, each pod at its own
    requests, as far as it is asked."""

    HORIZON = plan_reference.Plan.HORIZON

    def __init__(self, pending: np.ndarray, which: np.ndarray, klass: np.ndarray,
                 demand: list, ranked: list, free: list):
        self.pending = pending[: self.HORIZON].tolist()
        self.which, self.klass, self.demand = which, klass, demand
        self.ranked = [order.tolist() for order in ranked]
        self.free = free  # one list of Python integers a resource
        self.pointer = {}  # (policy, class) -> how far down the ranked list
        self.done = 0
        self.node = {}  # position -> node index, or None

    def node_of(self, position: int):
        """The plan's node for the pod at ``position`` (None: no node has
        room; also None for a pod that is bound in this state)."""
        pending, free = self.pending, self.free
        while (position not in self.node and self.done < len(pending)
               and pending[self.done] <= position):
            at = pending[self.done]
            self.done += 1
            policy, klass = int(self.which[at]), int(self.klass[at])
            want, order = self.demand[klass], self.ranked[policy]
            pointer = self.pointer.get((policy, klass), 0)
            while pointer < len(order) and any(
                    free[r][order[pointer]] < want[r] for r in range(len(want))):
                pointer += 1
            self.pointer[policy, klass] = pointer
            if pointer < len(order):
                node = order[pointer]
                for r, asked in enumerate(want):
                    free[r][node] -= asked
                self.node[at] = node
            else:
                self.node[at] = None
        return self.node.get(position)


class World(plan_reference.World):
    """``plan_reference.World`` — positions, policies, the bindings in the
    order the API took them — with what each pod asks and each node holds,
    resource by resource."""

    def __init__(self, config: dict, seed: int, warm_pods: int,
                 bindings: list, observed: dict, records: list):
        # the base sizes one fit from one request; nothing here reads it
        super().__init__(
            {**config, "pod_requests": config["pod_classes"][0]["requests"]},
            seed, warm_pods, bindings, observed, records)
        self.fit = None
        self.demand = mixed_world.demands(config)  # [classes, 3]
        self.klass = np.concatenate((
            [mixed_world.warm_class(config, i) for i in range(warm_pods)],
            mixed_world.pod_classes(config, seed))).astype(np.int64)
        self.alloc = mixed_world.allocatable(config)
        self.base = mixed_world.initial_held(config, seed)  # [nodes, 3]
        self.bound_asks = self.demand[self.klass[[b[0] for b in self.bound]]] \
            if self.bound else np.zeros((0, len(RESOURCES)), dtype=np.int64)

    def held(self, bound: int = None) -> np.ndarray:
        """int64 [nodes, 3]: what each node holds once the first ``bound``
        bindings (None: all) have landed."""
        held = self.base.copy()
        np.add.at(held, self.bound_nodes[:bound], self.bound_asks[:bound])
        return held

    def plan(self, ranked: list, bound: int) -> Plan:
        pending = np.flatnonzero(self.bound_rank >= bound)
        free = (self.alloc[None, :] - self.held(bound)).T.tolist()
        return Plan(pending, self.which, self.klass, self.demand.tolist(),
                    ranked, free)


def compare(config: dict, seed: int, window: dict, fetches: list, replans: list,
            bindings: list, observed: dict, warm_pods: int, led: int) -> dict:
    """Hold the window to the reference; the arguments are
    ``plan_reference.compare``'s."""
    records = window["records"]
    world = World(config, seed, warm_pods, bindings, observed, records)
    rounds = Rounds(config, seed, fetches)
    policies, names, n = world.policies, world.names, config["nodes"]
    classes = mixed_world.class_names(config)
    fetch_times = sorted(at for at, _metric, _round in fetches)
    over = np.maximum(world.held() - world.alloc[None, :], 0)
    numbers = {
        "filter_mismatched": 0, "prioritize_mismatched": 0,
        "rounds_backwards": 0, "promotions_wrong": 0, "promotions_missing": 0,
        **{f"room_exceeded_{r}": int((over[:, k] > 0).sum())
           for k, r in enumerate(RESOURCES)},
        "pods_unplaced": 0, "pods_placed_twice": world.twice + world.unknown,
        "dontschedule_violated": 0, "candidates_not_fit": 0,
    }
    left = window.get("left") or [[] for _ in classes]
    short = window.get("short") or [[] for _ in classes]
    leavings = sum(len(s) for s in short)
    counted = {"filters": 0, "prioritizes": 0, "kept": 0, "bindings": 0,
               "promoted": 0, "plan_current": 0, "plan_followed": 0,
               "plan_node_not_offered": 0, "plan_node_not_ordinal_top": 0,
               "states_a_replan": 0, "led": led,
               "nodes_left_a_class": leavings,
               **{f"left_short_of_{r}": sum(
                   1 for s in short for bits in s if bits >> k & 1)
                  for k, r in enumerate(RESOURCES)},
               "answers_read_whole": sum(window.get("read_whole", ())),
               "client_ms_a_pod": 1e3 * (
                   window["ended"] - window["began"] - sum(
                       np.nansum(np.diff(r["t"])[::2]) for r in records)
               ) / max(len(records), 1)}
    notes = [
        f"{leavings} times a node left a class's candidates "
        f"({', '.join(f'{c} {len(s)}' for c, s in zip(classes, short))}): short "
        + ", ".join(f"of {r} {counted[f'left_short_of_{r}']}" for r in RESOURCES)]
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
        spans = [range(current[m], rounds.served_before(m, at) + 1)
                 for m in metrics]
        return [dict(zip(metrics, combo))
                for combo in sorted(itertools.product(*spans), key=sum)]

    def search(metrics: list, options: list, matches):
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
            ranked = [plan_reference.ranked_nodes(
                          p, lambda m: rounds.column(m, in_force[m]))
                      if min(in_force.values()) >= 0 else np.zeros(0, np.int64)
                      for p in policies]
            plans[at] = [world.plan(ranked, k) for k in world.states(begin, end)]
            counted["states_a_replan"] = max(
                counted["states_a_replan"], len(plans[at]))
        return plans[at]

    ends = [end for _begin, end in replans]

    def replans_for(sent: float, answered: float) -> tuple:
        """``plan_reference.compare``'s rule: (replans whose plan the answer
        may carry, the replan whose plan it must carry or None)."""
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
    # who the driver still offered each class, and what kube's own Fit says
    # of it: free[r] >= the class's request, from the nodes picked so far
    feasible = [np.ones(n, dtype=bool) for _ in classes]
    gone = [0] * len(classes)
    in_passed = np.zeros(n, dtype=bool)
    for record in records:
        if record["error"]:  # counted by run.py, as requests_failed
            notes.append(f"cycle {record['index']}: {record['error']}")
            continue
        which = record["which"]
        policy = policies[which]
        position = world.position(record)
        klass = int(world.klass[position])
        if record.get("klass") != klass:
            numbers["candidates_not_fit"] += 1
            notes.append(f"cycle {record['index']}: sent as class "
                         f"{record.get('klass')}, the seed gives {klass}")
            continue
        while gone[klass] < record["gone"]:
            feasible[klass][left[klass][gone[klass]]] = False
            gone[klass] += 1
        candidates = np.flatnonzero(feasible[klass])
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

        tops = {ordinal_top(c[metric]) for c in admissible([metric], answered)}
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
            is_promoted = top not in tops
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
                    f"cycle {record['index']} ({classes[klass]}): {names[top]} "
                    f"promoted, the plan's node for the pod in no admissible "
                    f"state ({len(may)} replans)")
        elif must is not None:
            planned = {plan.node_of(position) for plan in plans_of(must)}
            if None in planned or not all(in_passed[node] for node in planned):
                counted["plan_node_not_offered"] += 1
            else:
                counted["plan_current"] += 1
                if top in planned:
                    counted["plan_followed"] += 1
                    counted["plan_node_not_ordinal_top"] += not planned <= tops
                else:
                    numbers["promotions_missing"] += 1
                    notes.append(
                        f"cycle {record['index']} ({classes[klass]}): no "
                        f"promotion, though a plan on the version served gives "
                        f"the pod {sorted(names[p] for p in planned)}, among "
                        f"the candidates sent")

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
    # what the driver offered is kube's own Fit: at the end of the window a
    # node is among a class's candidates iff its free amounts, by the
    # bindings the API took, cover the class's request
    free = world.alloc[None, :] - world.held()
    for klass, name in enumerate(classes):
        for node in left[klass][gone[klass]:]:
            feasible[klass][node] = False
        fits = (free >= world.demand[klass][None, :]).all(axis=1)
        wrong = int((fits != feasible[klass]).sum())
        if wrong and not numbers["pods_unplaced"]:
            numbers["candidates_not_fit"] += wrong
            notes.append(f"class {name}: {wrong} nodes are candidates against "
                         f"kube's Fit over the bindings taken, or the reverse")
    for k, resource in enumerate(RESOURCES):
        if numbers[f"room_exceeded_{resource}"]:
            nodes = np.flatnonzero(over[:, k] > 0)
            notes.append(
                f"{len(nodes)} nodes hold more {resource} than their "
                f"allocatable, e.g. {[names[i] for i in nodes[:3]]}")
    return {"numbers": numbers, "notes": notes, "counted": counted,
            "replans": replans}
