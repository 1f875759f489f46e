"""The plain reference of the ``tpu-v5e-fleet-12k`` deployment, and the
comparison that decides ``correct`` in its cell.

It imports nothing of the program and takes nothing the program has made:
only what crossed the wire (the driver's records and its log of the hosts
taken and given back), what the played APIs saw (telemetry fetches,
``pods/binding`` writes, pod deletions) and stamps taken on the harness's own
clock through a hook of the program (when it released a gang's slice).
NumPy and the standard library, run after the window has closed.

It replays the window serially, attempt by attempt, with a gang ledger of its
own: a gang's first Filter reserves the slice :func:`gang_world.place` gives
— a rectangle inside one ICI domain, counted window by window — over the
candidates that are clean at the round in force and held by no other gang;
while it holds, its members pass that slice and nothing else, every other
pod fails the hosts it holds, and a member lands on it.  A gang's hosts are
held from its reservation until the job is deleted; from the deletion until
the program released it, or at the latest until ``RELEASE_SLACK_S`` after
the played API took the last of its pods' deletions, a host is held to
either state, as GAS's ``held_to_either``.  Past that bound the hosts are
free: a Filter that still treats them as held is ``slice_not_released``.

Every number compared is a count of disagreements, so every limit is 0:
``slice_wrong`` (a reserving Filter whose answer is the rule's slice — or its
verdict that none fits — in no admissible state), ``slice_straddles_domain``
and ``slice_not_rectangle`` (what the program reserved, as its answer says),
``members_off_slice``, ``reserved_host_taken``, ``gangs_half_placed`` (at the
window's end: some but not all members bound and no live reservation),
``gangs_not_admitted`` (gangs whose last member was bound in the window,
against the program's own ``pas_gang_admitted_total``),
``gangs_admitted_unbound`` (that counter above the gangs whose every member
the API bound in the window), ``slice_not_released``, TAS's
``filter_mismatched``, ``prioritize_mismatched`` and ``rounds_backwards``,
and ``pods_unplaced``, ``pods_placed_twice``, ``dontschedule_violated``,
``candidates_mismatched`` (the driver's candidates against the replay).
"""

from __future__ import annotations

import itertools

import numpy as np

import gang_world
from generator import tas_policies
from reference import Rounds, tas_prioritize, tas_violating

#: a reservation without bind progress lapses after this (docs/gang.md)
TTL_S = 30.0
#: a gang whose last member was bound this close to the window's end may be
#: counted after the closing scrape; it is not held against the program
SCRAPE_SLACK_S = 0.5
#: a deleted gang's hosts are free this long after the played API took the
#: last of its pods' deletions, whether or not the program said it released
#: them: one sync period (a pod feed releases within milliseconds; a pod
#: LIST every 30 s does not)
RELEASE_SLACK_S = 2.0
#: past this many gangs deleted and not yet released at once, a reserving
#: Filter is held to all of them held or none (a sound program releases
#: within milliseconds: one or two are ever in flight)
MAX_IN_FLIGHT = 4


class Gang:
    """The reference's ledger entry for one backlog gang."""

    __slots__ = ("job", "slice", "bound", "touched", "deleted", "released",
                 "due")

    def __init__(self, job):
        self.job = job
        self.slice = None  # host indices, row-major, while reserved
        self.bound = set()
        self.touched = -np.inf  # when a member's verb last answered
        self.deleted = None  # when its first pod's deletion was taken
        self.released = np.inf  # when the program released it after that
        self.due = np.inf  # when its hosts must be free, released or not

    def holds(self, at: float) -> bool:
        """A live reservation or a bound gang not yet deleted."""
        if self.slice is None or self.deleted is not None:
            return False
        return len(self.bound) == self.job.size or at - self.touched <= TTL_S


def compare(config: dict, seed: int, window: dict, fetches: list,
            bindings: list, deletes: list, releases: list, admitted: int) -> dict:
    records = window["records"]
    names = gang_world.hosts(config)
    n = len(names)
    _domains, rows, cols = gang_world.grid(config)
    per_domain = rows * cols
    policies = tas_policies(config)
    rounds = Rounds(config, seed, fetches)
    jobs = gang_world.backlog(config, seed)
    pod_of = gang_world.pod_jobs(jobs)
    free = gang_world.free_at_start(config, gang_world.history(config, seed))
    gangs = {job.name: Gang(job) for job in jobs if gang_world.is_gang(job.shape)}
    deleted_at = {}
    for at, pod in sorted(deletes):
        deleted_at.setdefault(pod, at)
    released_at = {}
    for at, gang_id in sorted(releases):
        released_at.setdefault(gang_id, []).append(at)
    numbers = {
        "slice_wrong": 0, "slice_straddles_domain": 0, "slice_not_rectangle": 0,
        "members_off_slice": 0, "reserved_host_taken": 0, "gangs_half_placed": 0,
        "gangs_not_admitted": 0, "gangs_admitted_unbound": 0,
        "slice_not_released": 0, "filter_mismatched": 0,
        "prioritize_mismatched": 0, "rounds_backwards": 0, "pods_unplaced": 0,
        "pods_placed_twice": 0, "dontschedule_violated": 0,
        "candidates_mismatched": 0,
    }
    counted = {"filters": 0, "prioritizes": 0, "kept": 0, "bindings": 0,
               "reservations": 0, "no_slice": 0, "retried": 0,
               "held_either": 0, "gangs_deleted": 0, "admitted": admitted,
               "release_lag_ms_max": 0.0,
               "answers_read_whole": sum(window.get("read_whole", ())),
               "client_ms_a_pod": 1e3 * (
                   window["ended"] - window["began"] - sum(
                       np.nansum(np.diff(r["t"])[::2]) for r in records)
               ) / max(len(records), 1)}
    notes = []
    current = {m: 0 for m in rounds.metrics}  # oldest round still admissible
    violating = {}  # (policy, its metrics' rounds) -> mask over all hosts

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

    def search(metrics: list, options: list, states: list, matches,
               name_fault: bool = True):
        """The oldest admissible rounds (and a release state) under which
        ``matches`` holds, or None."""
        for chosen in options:
            for state in states:
                if matches(chosen, state):
                    for metric, k in chosen.items():
                        current[metric] = max(current[metric], k)
                    return chosen, state
        if not name_fault:
            return None
        for metric in metrics:  # one step back, only to name the fault
            if current[metric] > 0:
                stale = {m: current[m] for m in metrics}
                stale[metric] -= 1
                if any(matches(stale, state) for state in states):
                    numbers["rounds_backwards"] += 1
                    break
        return None

    # the gangs whose hosts a candidate can be held by: reserved and not
    # yet whole (a whole gang's hosts are taken), or deleted and not yet
    # released
    reserved, in_flight = {}, {}

    def held_by_others(sent: float, excluding: str = "") -> tuple:
        """(bool [hosts] of the hosts other gangs hold, [the hosts of each
        gang deleted and not yet released before ``sent``]: those may be
        held or not, bool [hosts] of the hosts of the gangs deleted whose
        release is overdue: free, and held only by a fault)."""
        held = np.zeros(n, dtype=bool)
        for name, gang in reserved.items():
            if name != excluding and gang.holds(sent):
                held[gang.slice] = True
        maybe, overdue = [], np.zeros(n, dtype=bool)
        for name, gang in list(in_flight.items()):
            if gang.released < sent:
                del in_flight[name]
            elif gang.due < sent:
                overdue[gang.slice] = True
            else:
                maybe.append(gang.slice)
        if maybe:
            counted["held_either"] += 1
        return held, maybe, overdue

    def release_states(held: np.ndarray, maybe: list) -> list:
        """The held masks of the states the program may have been in: each
        gang deleted and not yet released holds its hosts or does not (all
        together or none, where more than MAX_IN_FLIGHT are in flight)."""
        choices = (itertools.product((True, False), repeat=len(maybe))
                   if len(maybe) <= MAX_IN_FLIGHT
                   else [(True,) * len(maybe), (False,) * len(maybe)])
        states = []
        for choice in choices:
            state = held.copy()
            for keep, hosts in zip(choice, maybe):
                if keep:
                    state[hosts] = True
            states.append(state)
        return states

    def apply(change) -> None:
        if change[0] == 0:
            free[change[1]] = False
            return
        _kind, job, hosts = change
        free[hosts] = True
        gang = gangs.get(job)
        if gang is not None:
            counted["gangs_deleted"] += 1
            reserved.pop(job, None)
            in_flight[job] = gang
            gang.deleted = min(deleted_at.get(pod, np.inf) for pod in gang.job.pods)
            gang.due = RELEASE_SLACK_S + max(
                deleted_at.get(pod, np.inf) for pod in gang.job.pods)
            after = [at for at in released_at.get(gang.job.gang_id, ())
                     if at >= gang.deleted]
            gang.released = min(after, default=np.inf)
            if np.isfinite(gang.released):
                counted["release_lag_ms_max"] = max(
                    counted["release_lag_ms_max"],
                    1e3 * (gang.released - gang.due + RELEASE_SLACK_S))

    def slice_faults(passed: np.ndarray, shape: tuple) -> None:
        """What the program reserved, as its answer says."""
        spans = set((passed // per_domain).tolist())
        if len(spans) > 1:
            numbers["slice_straddles_domain"] += 1
            notes.append(f"a slice of {len(passed)} hosts spans domains "
                         f"{sorted(spans)[:4]}")
            return
        r, c = passed % per_domain // cols, passed % cols
        box = (int(r.max() - r.min() + 1), int(c.max() - c.min() + 1))
        if len(passed) != shape[0] * shape[1] or box[0] * box[1] != len(passed) \
                or box not in (shape, shape[::-1]):
            numbers["slice_not_rectangle"] += 1
            notes.append(f"a {shape[0]}x{shape[1]} gang got {len(passed)} hosts "
                         f"in a {box[0]}x{box[1]} box")

    changes, applied = window.get("changes", []), 0
    for record in records:
        while applied < record["changes"]:
            apply(changes[applied])
            applied += 1
        if record["error"]:  # counted by run.py, as requests_failed
            notes.append(f"attempt of pod {record['index']}: {record['error']}")
            continue
        candidates = np.flatnonzero(free)
        if len(candidates) != record["count"]:
            numbers["candidates_mismatched"] += 1
        counted["filters"] += 1
        counted["retried"] += record["attempt"] > 0
        sent, answered = record["t"][0], record["t"][1]
        job = jobs[pod_of[record["index"]][0]]
        which = record["which"]
        policy = policies[which]
        rules = policy["strategies"]["dontschedule"]
        metrics = sorted({metric for metric, _, _ in rules})
        got_passed, got_failed = record["passed"], np.sort(record["failed"])
        gang = gangs.get(job.name)
        options = admissible(metrics, answered)
        reserving = gang is not None and not gang.holds(sent)
        if reserving:
            gang.slice = None
            gang.bound = set()
            reserved.pop(job.name, None)
        held, maybe, overdue = held_by_others(sent, excluding=job.name)
        either = np.zeros(n, dtype=bool)
        for hosts in maybe:
            either[hosts] = True
        states = release_states(held, maybe) if reserving else [held]
        chosen_slice = []

        def expected(chosen, state) -> np.ndarray:
            clean = candidates[~forbidden(which, chosen)[candidates]]
            if gang is None:
                return clean[~state[clean]]
            if not reserving:
                return clean[np.isin(clean, gang.slice)]
            mask = np.zeros(n, dtype=bool)
            mask[clean[~state[clean]]] = True
            found = gang_world.place(gang_world.free_mask(config, mask), job.shape)
            chosen_slice[:] = [found]
            if found is None:
                return clean[:0]
            return gang_world.slice_hosts(config, found)

        def filter_matches(chosen, state) -> bool:
            passed = expected(chosen, state)
            if gang is None and maybe:
                # a host of a gang still in flight may pass or fail
                got = np.zeros(n, dtype=bool)
                got[got_passed] = True
                want = np.zeros(n, dtype=bool)
                want[passed] = True
                if (got & ~want).any() or (want & ~either & ~got).any():
                    return False
                passed = got_passed
            failed = candidates[~np.isin(candidates, passed)]
            return (np.array_equal(passed, got_passed)
                    and np.array_equal(failed, got_failed))

        found = search(metrics, options, states, filter_matches,
                       name_fault=not overdue.any())
        if found is None and overdue.any():
            # the hosts of a gang deleted long enough ago still held
            found = search(metrics, options, [s | overdue for s in states],
                           filter_matches)
            if found is not None:
                numbers["slice_not_released"] += 1
                notes.append(
                    f"attempt of pod {record['index']} ({job.name}): the hosts "
                    f"of a gang deleted over {RELEASE_SLACK_S} s before are "
                    "still held")
        if found is None:
            numbers["slice_wrong" if reserving else "filter_mismatched"] += 1
            notes.append(
                f"attempt of pod {record['index']} ({job.name}, "
                f"{'reserving' if reserving else 'filter'}): no admissible "
                f"round of {metrics} and no release state gives "
                f"{len(got_passed)} passed / {len(got_failed)} failed of "
                f"{len(candidates)}")
        if reserving:
            if len(got_passed):
                slice_faults(got_passed, job.shape)
            if found is not None and chosen_slice and chosen_slice[0] is not None:
                counted["reservations"] += 1
                gang.slice = gang_world.slice_hosts(config, chosen_slice[0])
            elif found is None and len(got_passed) == job.size:
                gang.slice = np.array(got_passed, dtype=np.int64)
            else:
                counted["no_slice"] += found is not None
            if gang.slice is not None:
                reserved[job.name] = gang
        if gang is not None and gang.slice is not None:
            gang.touched = answered

        if record["second"] != "prioritize":
            continue
        counted["prioritizes"] += 1
        top = record["node"]
        p_answered = record["t"][3]
        if gang is not None:
            # the slice's hosts among those sent, row-major, less those the
            # pod's policy forbids at the round in force
            def ranked(chosen) -> np.ndarray:
                kept = got_passed[np.isin(got_passed, gang.slice)] if (
                    gang.slice is not None) else got_passed[:0]
                return kept[~forbidden(which, chosen)[kept]]

            options_p = admissible(metrics, p_answered)
        else:
            metric, operator, _ = policy["strategies"]["scheduleonmetric"][0]

            def ranked(chosen) -> np.ndarray:
                return tas_prioritize(
                    got_passed, rounds.column(metric, chosen[metric]), operator)[0]

            options_p = admissible([metric], p_answered)
        if "order" in record:
            counted["kept"] += 1

            def prioritize_matches(chosen, _state) -> bool:
                want = ranked(chosen)
                return (np.array_equal(record["order"], want) and np.array_equal(
                    record["scores"], 10 - np.arange(len(want))))
        else:
            def prioritize_matches(chosen, _state) -> bool:
                want = ranked(chosen)
                return top == (int(want[0]) if len(want) else int(got_passed[0]))
        metrics_p = metrics if gang is not None else [metric]
        if search(metrics_p, options_p, [None], prioritize_matches) is None:
            numbers["prioritize_mismatched"] += 1
            notes.append(f"attempt of pod {record['index']} ({job.name}) "
                         f"prioritize: no admissible round gives this answer")
        if gang is not None:
            gang.touched = p_answered

        # the binding: acknowledged, on a host no other gang holds, a
        # member on its gang's slice, on a host the policy allowed
        if top < 0:
            continue
        counted["bindings"] += 1
        if record["bind_status"] != 201:
            numbers["pods_unplaced"] += 1
            notes.append(f"pod {record['index']}: binding status "
                         f"{record['bind_status']}")
        if any(other.holds(sent) and name != job.name and top in other.slice
               for name, other in reserved.items()):
            numbers["reserved_host_taken"] += 1
            notes.append(f"pod {record['index']} bound on {names[top]}, which "
                         "another gang holds")
        if gang is not None:
            if gang.slice is None or top not in gang.slice:
                numbers["members_off_slice"] += 1
                notes.append(f"{job.name}: a member bound on {names[top]}, off "
                             "its slice")
            gang.bound.add(top)
            if len(gang.bound) == job.size:
                reserved.pop(job.name, None)
        if options and all(forbidden(which, c)[top] for c in options):
            numbers["dontschedule_violated"] += 1

    # the bindings the API took: once a pod, and every gang whose last
    # member was bound in the window counted by the program
    taken, counts, last = set(), {}, {}
    for at, pod, _node in sorted(bindings):
        if pod in taken:
            numbers["pods_placed_twice"] += 1
        taken.add(pod)
        if not pod.startswith("bench-"):
            continue
        job = jobs[pod_of[int(pod[6:])][0]]
        counts[job.name] = counts.get(job.name, 0) + 1
        if job.name in gangs and counts[job.name] == job.size:
            last[job.name] = at
    # the program's count is held to a band: at least the gangs completed
    # before the closing scrape's slack, at most every gang completed
    must = sum(1 for at in last.values()
               if window["began"] <= at <= window["ended"] - SCRAPE_SLACK_S)
    most = sum(1 for at in last.values()
               if window["began"] <= at <= window["ended"])
    counted["gangs_completed"] = len(last)
    if must > admitted:
        numbers["gangs_not_admitted"] = must - admitted
        notes.append(f"{must} gangs had every member bound in the window; the "
                     f"program counts {admitted} admitted")
    if admitted > most:
        numbers["gangs_admitted_unbound"] = admitted - most
        notes.append(f"the program counts {admitted} gangs admitted; the API "
                     f"bound every member of {most} in the window")
    for name, gang in gangs.items():
        if 0 < len(gang.bound) < gang.job.size and not gang.holds(window["ended"]):
            numbers["gangs_half_placed"] += 1
            notes.append(f"{name}: {len(gang.bound)} of {gang.job.size} bound, "
                         "no live reservation")
    return {"numbers": numbers, "notes": notes, "counted": counted}
