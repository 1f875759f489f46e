"""The plain references, and the comparison that decides ``correct``.

Written straight from the semantics (SURVEY.md §3.2, §3.3, §3.6) over the
seeded data: TAS's rule evaluation and ordinal ranking, GAS's per-card
first fit.  It imports nothing of the program and takes nothing the program
has made — only what crossed the wire (the generator's records), what the
played APIs saw (fetches, pod updates, deletes) and the stamps of the
program's hooks.  Everything here is NumPy on the host, run after the
window has closed.

Every number compared is a count of disagreements, so every limit is 0.
"""

from __future__ import annotations

import collections
import itertools
import json

import numpy as np

from generator import (
    GAS_RESOURCES,
    bench_pod_name,
    gas_cards,
    gas_template_sequence,
    gas_templates,
    metric_round,
    node_names,
    node_object,
    rng,
    tas_policies,
)

# -- TAS ------------------------------------------------------------------------


def rule_holds(values: np.ndarray, operator: str, target: int) -> np.ndarray:
    """core.EvaluateRule over a metric column (operator.go:13-26)."""
    if operator == "GreaterThan":
        return values > target
    if operator == "LessThan":
        return values < target
    if operator == "Equals":
        return values == target
    return np.zeros(values.shape, dtype=bool)


def tas_violating(rules: list, columns: dict) -> np.ndarray:
    """dontschedule: a node violates when ANY rule holds for it."""
    out = None
    for metric, operator, target in rules:
        held = rule_holds(columns[metric], operator, target)
        out = held if out is None else out | held
    return out


def tas_filter(candidates: np.ndarray, violating: np.ndarray) -> tuple:
    """(passed, failed) node indices, each in candidate order."""
    bad = violating[candidates]
    return candidates[~bad], candidates[bad]


def tas_prioritize(candidates: np.ndarray, column: np.ndarray,
                   operator: str) -> tuple:
    """(hosts in rank order, scores): OrderedList then ``10 - rank``
    (telemetryscheduler.go:128-149); GreaterThan ranks the largest first,
    LessThan the smallest."""
    values = column[candidates]
    order = np.argsort(-values if operator == "GreaterThan" else values,
                       kind="stable")
    return candidates[order], 10 - np.arange(len(candidates))


class Rounds:
    """Telemetry rounds as the played custom-metrics API served them."""

    def __init__(self, config: dict, seed: int, fetches: list):
        self.config, self.seed = config, seed
        self.metrics = list(config["metrics"])
        self.fetched = {m: {} for m in self.metrics}  # metric -> round -> time
        for at, metric, round_index in fetches:
            self.fetched[metric].setdefault(round_index, at)
        self._columns = {}

    def column(self, metric: str, round_index: int) -> np.ndarray:
        key = (metric, round_index)
        if key not in self._columns:
            self._columns[key] = metric_round(
                self.seed, round_index, self.metrics.index(metric),
                self.config["nodes"], self.config["value_step"],
            )
        return self._columns[key]

    def served_before(self, metric: str, at: float) -> int:
        """The newest round of ``metric`` fetched before ``at`` (-1: none)."""
        rounds = [k for k, t in self.fetched[metric].items() if t < at]
        return max(rounds, default=-1)


def tas_compare(config: dict, seed: int, window: dict, fetches: list,
                wire: str) -> dict:
    """Hold every answer of the window to the reference: it must equal the
    reference's answer for one round of each metric it reads, no older than
    the last round seen for that metric and no newer than the API has served.

    Returns the numbers compared (limit 0 each), the per-round first
    reflection times for ``telemetry_lag_ms``, and notes on what failed."""
    rounds = Rounds(config, seed, fetches)
    policies = tas_policies(config)
    n = config["nodes"]
    names = node_names(config["node_prefix"], n)
    current = {m: 0 for m in rounds.metrics}  # oldest round still admissible
    reflected = {m: {} for m in rounds.metrics}  # metric -> round -> time
    numbers = {"filter_mismatched": 0, "prioritize_mismatched": 0,
               "rounds_backwards": 0, "echo_mismatched": 0,
               "requests_failed": 0}
    notes = []
    counted = {"filters": 0, "prioritizes": 0, "straddled": 0, "deep": 0}
    violating = {}  # (policy, its metrics' rounds) -> mask over all nodes

    def settle(metric_rounds: dict, at: float) -> None:
        for metric, k in metric_rounds.items():
            for older in range(current[metric], k + 1):
                reflected[metric].setdefault(older, at)
            current[metric] = max(current[metric], k)

    def search(metrics: list, at: float, matches) -> dict:
        """The oldest admissible combination of rounds under which
        ``matches`` holds, or None; one step back is tried last, only to
        name a mismatch as a round gone backwards."""
        spans = []
        for metric in metrics:
            newest = rounds.served_before(metric, at)
            spans.append(range(current[metric], newest + 1))
        for combo in sorted(itertools.product(*spans), key=sum):
            chosen = dict(zip(metrics, combo))
            if matches(chosen):
                return chosen
        for metric in metrics:
            if current[metric] > 0:
                stale = {m: current[m] for m in metrics}
                stale[metric] -= 1
                if matches(stale):
                    numbers["rounds_backwards"] += 1
                    return None
        return None

    for record in window["records"]:
        if record["error"]:
            numbers["requests_failed"] += 1
            notes.append(f"cycle {record['index']}: {record['error']}")
            continue
        policy = policies[record["which"]]
        candidates = (record["start"] + np.arange(record["count"])) % n
        rules = policy["strategies"]["dontschedule"]
        metrics = sorted({metric for metric, _, _ in rules})
        got_passed, got_failed = record["passed"], np.sort(record["failed"])

        def filter_matches(chosen):
            key = (record["which"], tuple(sorted(chosen.items())))
            if key not in violating:
                violating[key] = tas_violating(
                    rules, {m: rounds.column(m, k) for m, k in chosen.items()})
            passed, failed = tas_filter(candidates, violating[key])
            return (np.array_equal(passed, got_passed)
                    and np.array_equal(np.sort(failed), got_failed))

        counted["filters"] += 1
        chosen = search(metrics, record["t"][1], filter_matches)
        if chosen is None:
            numbers["filter_mismatched"] += 1
            notes.append(
                f"cycle {record['index']} filter ({policy['name']}): no "
                f"admissible round of {metrics} gives {len(got_passed)} "
                f"passed / {len(got_failed)} failed (rounds from {current})")
        else:
            settle(chosen, record["t"][1])
        filter_round = max(chosen.values()) if chosen else None

        if "filter_body" in record:
            counted["deep"] += 1
            numbers["echo_mismatched"] += echo_faults(
                record, config, seed, names, wire, notes)

        if "second_body" not in record:
            continue
        counted["prioritizes"] += 1
        metric, operator, _ = policy["strategies"]["scheduleonmetric"][0]
        try:
            answer = json.loads(record["second_body"])
            hosts = [entry["Host"] for entry in answer]
            scores = np.array([entry["Score"] for entry in answer])
        except (ValueError, KeyError, TypeError) as exc:
            numbers["prioritize_mismatched"] += 1
            notes.append(f"cycle {record['index']} prioritize unreadable: {exc!r}")
            continue

        def prioritize_matches(chosen):
            order, want_scores = tas_prioritize(
                got_passed, rounds.column(metric, chosen[metric]), operator)
            return (len(hosts) == len(order)
                    and np.array_equal(scores, want_scores)
                    and hosts == [names[i] for i in order])

        chosen = search([metric], record["t"][3], prioritize_matches)
        if chosen is None:
            numbers["prioritize_mismatched"] += 1
            notes.append(
                f"cycle {record['index']} prioritize ({policy['name']}): no "
                f"admissible round of {metric} gives this order "
                f"(rounds from {current[metric]})")
        else:
            settle(chosen, record["t"][3])
            if filter_round is not None and chosen[metric] != filter_round:
                counted["straddled"] += 1

    return {"numbers": numbers, "notes": notes, "counted": counted,
            "reflected": reflected, "fetched": rounds.fetched}


def echo_faults(record: dict, config: dict, seed: int, names: list,
                wire: str, notes: list) -> int:
    """A kept Filter answer read in full: NodeNames and, on the Nodes wire,
    the echoed objects must be the passing candidates, unchanged, in order."""
    try:
        answer = json.loads(record["filter_body"])
    except ValueError as exc:
        notes.append(f"cycle {record['index']} filter body unreadable: {exc!r}")
        return 1
    want = [names[i] for i in record["passed"]]
    faults = 0
    if [x for x in answer.get("NodeNames") or () if x] != want:
        faults += 1
    if wire == "nodes":
        items = (answer.get("Nodes") or {}).get("items") or []
        shape = config["node_object"]
        sent = [node_object(seed, int(i), names[i], shape)
                for i in record["passed"]]
        if items != sent:
            faults += 1
    if faults:
        notes.append(f"cycle {record['index']}: Filter echo differs from what was sent")
    return faults


def telemetry_lags(compared: dict, began: float, ended: float) -> tuple:
    """(lags in seconds, censored): for every (metric, round) the played API
    served inside the window, the time to the first answer on the wire that
    reflects that round or a newer one.  A round served so late that no
    answer showed it before the window closed is censored, not dropped
    silently."""
    lags, censored = [], 0
    for metric, served in compared["fetched"].items():
        for round_index, at in served.items():
            if not began <= at <= ended:
                continue
            seen = compared["reflected"][metric].get(round_index)
            if seen is None:
                censored += 1
            else:
                lags.append(seen - at)
    return lags, censored


# -- GAS ------------------------------------------------------------------------


class GasCluster:
    """Per-card usage of every node, booked and released by first fit."""

    def __init__(self, config: dict, seed: int):
        self.config = config
        self.ncards = gas_cards(config, seed)
        n, width = len(self.ncards), int(self.ncards.max())
        self.capacity = np.array(
            [config["per_card"][r] for r in GAS_RESOURCES], dtype=np.int64)
        self.used = np.zeros((n, width, len(GAS_RESOURCES)), dtype=np.int64)
        self.valid = np.arange(width)[None, :] < self.ncards[:, None]
        self.templates = [
            [(np.array([c[r] // c[GAS_RESOURCES[0]] for r in GAS_RESOURCES],
                       dtype=np.int64), int(c[GAS_RESOURCES[0]]))
             for c in template]
            for template in gas_templates(config)
        ]

    def fit(self, rows: np.ndarray, template: int, used: np.ndarray = None):
        """(fits, cards) for one pod template on each of ``rows``: per
        container and per requested GPU share, the first card in sorted
        order with room for the per-GPU request (scheduler.go:200-257); a
        card may be picked again while it has room.  ``cards`` has one column
        per share, in booking order."""
        used = (self.used if used is None else used)[rows].copy()
        valid = self.valid[rows]
        fits = np.ones(len(rows), dtype=bool)
        at = np.arange(len(rows))
        cards = []
        for need, shares in self.templates[template]:
            for _ in range(shares):
                room = valid & np.all(
                    used + need[None, None, :] <= self.capacity[None, None, :],
                    axis=2)
                first = room.argmax(axis=1)
                found = room.any(axis=1)
                fits &= found
                used[at, first] += need[None, :] * found[:, None]
                cards.append(first)
        return fits, np.stack(cards, axis=1)

    def annotation(self, template: int, cards: np.ndarray) -> str:
        """gas-container-cards for one node's picks: containers joined by
        ``|``, each container's cards by ``,``."""
        parts, at = [], 0
        for _need, shares in self.templates[template]:
            parts.append(",".join(f"card{c}" for c in cards[at: at + shares]))
            at += shares
        return "|".join(parts)

    def book(self, node: int, template: int, annotation: str, sign: int = 1):
        for (need, _shares), picked in zip(
                self.templates[template], annotation.split("|")):
            for card in picked.split(","):
                self.used[node, int(card[4:])] += sign * need

    def over_capacity(self, node: int) -> bool:
        return bool((self.used[node] > self.capacity).any()
                    or (self.used[node] < 0).any())


def gas_prebook(config: dict, seed: int) -> tuple:
    """Pods booked from the seed until the occupancy target is reached:
    [(name, template, node index, annotation)], and the cluster holding
    them.  Each pod goes to the first node of a seeded window that fits it,
    as a scheduler would have placed it."""
    cluster = GasCluster(config, seed)
    n = len(cluster.ncards)
    target = (config["occupancy_target"] * cluster.ncards.sum()
              * config["per_card"][GAS_RESOURCES[1]])
    sequence = gas_template_sequence(config, seed, stream=2)
    gen = rng(seed, 21)
    pods, booked, k = [], 0, 0
    while booked < target:
        template = int(sequence[k])
        rows = (int(gen.integers(0, n)) + np.arange(min(n, 64))) % n
        fits, cards = cluster.fit(rows, template)
        if fits.any():
            at = int(fits.argmax())
            annotation = cluster.annotation(template, cards[at])
            cluster.book(int(rows[at]), template, annotation)
            pods.append((f"booked-{len(pods):06d}", template, int(rows[at]),
                         annotation))
            booked += sum(need[1] * shares
                          for need, shares in cluster.templates[template])
        k += 1
    return pods, cluster


def gas_compare(config: dict, seed: int, window: dict, probe: list,
                annotations: dict, deletes: list) -> dict:
    """Replay the window against the first-fit reference.

    ``annotations``: pod name -> the gas-container-cards the played kube API
    was sent; ``deletes``: [(issued at, released at or inf, pod name)] in
    issue order — issued by the harness's churn, released when the program's
    booking hook fired with that pod's booking gone.  Bind books before it
    answers, so bookings are exact; a release lands somewhere between its
    delete being issued and that stamp, so a node with a release in flight
    is held to either of the two states — fit only grows with a release."""
    pods, cluster = gas_prebook(config, seed)
    n = len(cluster.ncards)
    names = node_names(config["node_prefix"], n)
    live = {name: (template, node, note) for name, template, node, note in pods}
    numbers = {"filter_mismatched": 0, "cards_mismatched": 0,
               "capacity_exceeded": 0, "requests_failed": 0,
               "probe_mismatched": 0,
               "releases_unhandled": sum(1 for d in deletes if d[1] == np.inf)}
    notes = []
    counted = {"filters": 0, "binds": 0, "unschedulable": 0,
               "held_to_either": 0}
    pending = collections.deque(deletes)  # in issue order
    opened = []  # issued before the replay's clock, release not applied yet

    def settle(until: float) -> None:
        """Apply every release the program made before ``until``."""
        while pending and pending[0][0] < until:
            opened.append(pending.popleft())
        for entry in [e for e in opened if e[1] < until]:
            opened.remove(entry)
            template, node, note = live.pop(entry[2])
            cluster.book(node, template, note, sign=-1)

    def in_flight(before: float) -> dict:
        """node -> [(template, annotation)] of deletes issued before
        ``before`` whose release is not applied yet."""
        flight = {}
        for issued, _released, pod in itertools.chain(opened, pending):
            if issued >= before:
                break
            template, node, note = live[pod]
            flight.setdefault(node, []).append((template, note))
        return flight

    def released(flight: dict) -> np.ndarray:
        """Usage with every release in ``flight`` applied."""
        used = cluster.used.copy()
        for node, entries in flight.items():
            for template, note in entries:
                for (need, _shares), picked in zip(
                        cluster.templates[template], note.split("|")):
                    for card in picked.split(","):
                        used[node, int(card[4:])] -= need
        return used

    def check_filter(record: dict, label: str) -> None:
        candidates = (record["start"] + np.arange(record["count"])) % n
        settle(record["t"][0])
        flight = in_flight(record["t"][1])
        fits, _ = cluster.fit(candidates, record["which"])
        got = np.zeros(n, dtype=bool)
        got[record["passed"]] = True
        got = got[candidates]
        wrong = fits != got
        if flight and wrong.any():
            either, _ = cluster.fit(candidates, record["which"], released(flight))
            free = np.isin(candidates, list(flight))
            wrong &= ~(free & (either == got))
            counted["held_to_either"] += int(free.sum())
        said = np.zeros(n, dtype=bool)
        said[record["failed"]] = True
        if (wrong.any() or not np.array_equal(record["passed"], candidates[got])
                or not np.array_equal(said[candidates], ~got)):
            numbers[label] += 1
            notes.append(
                f"cycle {record['index']} filter (template {record['which']}): "
                f"{int(wrong.sum())} of {len(candidates)} verdicts differ from "
                f"first fit; e.g. {[names[c] for c in candidates[wrong][:3]]}")

    for record in window["records"]:
        if record["error"]:
            numbers["requests_failed"] += 1
            notes.append(f"cycle {record['index']}: {record['error']}")
            continue
        counted["filters"] += 1
        check_filter(record, "filter_mismatched")
        if record["second"] != "bind":
            counted["unschedulable"] += 1
            continue
        counted["binds"] += 1
        node, template = record["node"], record["which"]
        pod = bench_pod_name(record["index"])
        settle(record["t"][2])
        flight = in_flight(record["t"][3])
        note = annotations.get(pod)
        _, cards = cluster.fit(np.array([node]), template)
        want = [cluster.annotation(template, cards[0])]
        if node in flight:
            _, cards = cluster.fit(
                np.array([node]), template, released({node: flight[node]}))
            want.append(cluster.annotation(template, cards[0]))
        if note not in want:
            numbers["cards_mismatched"] += 1
            notes.append(f"cycle {record['index']} bind on {names[node]}: "
                         f"cards {note!r}, first fit gives {want}")
            note = want[0]
        cluster.book(node, template, note)
        live[pod] = (template, node, note)
        if cluster.over_capacity(node):
            numbers["capacity_exceeded"] += 1
            notes.append(f"cycle {record['index']}: {names[node]} over capacity")

    for record in probe:
        if record["error"]:
            numbers["requests_failed"] += 1
            notes.append(f"probe {record['which']}: {record['error']}")
            continue
        check_filter(record, "probe_mismatched")
    return {"numbers": numbers, "notes": notes, "counted": counted}
