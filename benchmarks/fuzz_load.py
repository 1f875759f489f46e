"""``make fuzz-smoke``: the budgeted adversarial-search gate
(testing/fuzz.py; docs/robustness.md "Adversarial scenario search").

One wall-clock-budgeted run (default 60s, fixed seed) that must prove
four properties every time CI runs:

  1. **reproducibility** — two engine invocations with the same seed
     and candidate cap produce byte-identical candidate sequences
     (genome digests, verdicts, failure lists).  This is the contract
     that makes any future find a one-command replay.
  2. **detection power** — with a known bug class deliberately planted
     (the PR-19 stale-digest splice; a rebind path that loses pods),
     the search must FIND it within the smoke budget and
     :func:`testing.fuzz.minimize` must shrink the find to a reproducer
     of <= 20 ticks and <= 8 genome events.
  3. **no false positives** — every hand-authored seed genome passes
     every oracle on the healthy tree.
  4. **throughput** — the remaining budget must clear the candidate
     floor (>= 200 candidates at the default 60s budget, 16-node
     scale), so the search stays a real search and not three
     ceremonial runs.

``run()`` (``--bench``) is the compact report: candidates/s, corpus
size, coverage signal count, and finds from a short budgeted run.
Exits nonzero from the CLI when any gate fails.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, List, Optional

from platform_aware_scheduling_tpu.testing import fuzz

#: the planted bug the smoke hunts, and the seed-corpus genome class
#: that must catch it (detection must not depend on mutation luck)
SMOKE_PLANT = "stale_digest_splice"
SMOKE_EXPECT = "oracle:shard_splice"

#: acceptance bounds for the minimized reproducer
MAX_MIN_TICKS = 20
MAX_MIN_EVENTS = 8

#: candidate floor at the default 60s budget
CANDIDATE_FLOOR = 200
DEFAULT_BUDGET_S = 60.0

#: candidates compared byte-for-byte in the reproducibility gate:
#: covers every seed genome plus a tail of generated/mutated ones
REPRO_CANDIDATES = 14


def _gate(name: str, ok: bool, detail: str) -> Dict:
    return {"gate": name, "ok": bool(ok), "detail": detail}


def _signature(records: List[Dict]) -> List:
    return [
        (r["digest"], r["verdict"], tuple(r["failures"])) for r in records
    ]


def reproducibility_gate(seed: int = 7) -> Dict:
    """Gate 1: same seed, same cap => identical candidate sequences."""
    runs = []
    for _ in range(2):
        engine = fuzz.FuzzEngine(seed=seed)
        engine.fuzz(max_candidates=REPRO_CANDIDATES)
        runs.append(_signature(engine.records))
    identical = runs[0] == runs[1]
    return _gate(
        "reproducibility",
        identical and len(runs[0]) == REPRO_CANDIDATES,
        f"{len(runs[0])} candidates byte-identical across two runs"
        if identical
        else f"sequences diverged: {runs[0]} vs {runs[1]}",
    )


def planted_bug_gate(
    seed: int = 7, budget_s: float = 20.0
) -> Dict:
    """Gate 2: plant a known bug, demand the search find it within
    budget and the minimizer shrink it inside the acceptance bounds."""
    with fuzz.planted_bug(SMOKE_PLANT):
        engine = fuzz.FuzzEngine(seed=seed)
        engine.fuzz(time_budget_s=budget_s, stop_on_find=True)
        hit = next(
            (
                f
                for f in engine.finds
                if SMOKE_EXPECT in f["failures"]
            ),
            None,
        )
        if hit is None:
            return _gate(
                "planted_bug",
                False,
                f"{SMOKE_PLANT} not found in {len(engine.records)} "
                f"candidates / {budget_s}s",
            )
        minimized = fuzz.minimize(hit["genome"], [SMOKE_EXPECT])
    genome = minimized["genome"]
    ticks, n_events = genome["ticks"], len(genome["events"])
    ok = (
        SMOKE_EXPECT in minimized["failures"]
        and ticks <= MAX_MIN_TICKS
        and n_events <= MAX_MIN_EVENTS
    )
    return _gate(
        "planted_bug",
        ok,
        f"{SMOKE_PLANT} found at candidate #{hit['index']}, minimized "
        f"to {ticks} ticks / {n_events} events "
        f"({minimized['attempts']} attempts): "
        f"{fuzz.describe_genome(genome)}",
    )


def false_positive_gate() -> Dict:
    """Gate 3: the healthy tree is green under every oracle for every
    hand-authored seed genome."""
    noisy = []
    for i, genome in enumerate(fuzz.SEED_GENOMES):
        record = fuzz.run_candidate(genome)
        if record["verdict"] != "ok":
            noisy.append(
                f"seed#{i} {record['verdict']} {record['failures']}"
            )
    return _gate(
        "no_false_positives",
        not noisy,
        "; ".join(noisy)
        if noisy
        else f"all {len(fuzz.SEED_GENOMES)} seed genomes green",
    )


def throughput_run(
    seed: int = 7,
    budget_s: float = 30.0,
    floor: Optional[int] = None,
) -> Dict:
    """Gate 4 + the bench numbers: one budgeted search; real finds (on
    the healthy tree any find is a real bug) are reported, minimized
    upstream by the operator, never swallowed."""
    engine = fuzz.FuzzEngine(seed=seed)
    summary = engine.fuzz(time_budget_s=budget_s)
    out = dict(summary)
    out["finds_detail"] = [
        {
            "index": f["index"],
            "verdict": f["verdict"],
            "failures": f["failures"],
            "genome": f["genome"],
            "error": f.get("error"),
        }
        for f in engine.finds
    ]
    if floor is not None:
        out["gate"] = _gate(
            "throughput",
            summary["candidates"] >= floor,
            f"{summary['candidates']} candidates in "
            f"{summary['elapsed_s']}s "
            f"({summary['candidates_per_s']}/s) vs floor {floor}",
        )
    return out


def smoke(seed: int = 7, budget_s: float = DEFAULT_BUDGET_S) -> Dict:
    """The full CI smoke: all four gates inside one wall-clock budget.
    The throughput leg gets whatever the correctness gates leave, and
    its floor scales with the budget actually granted."""
    started = time.monotonic()
    gates = [reproducibility_gate(seed=seed)]
    gates.append(
        planted_bug_gate(
            seed=seed,
            budget_s=max(5.0, budget_s / 3.0),
        )
    )
    gates.append(false_positive_gate())
    remaining = max(10.0, budget_s - (time.monotonic() - started))
    floor = max(
        25, int(CANDIDATE_FLOOR * min(1.0, remaining / DEFAULT_BUDGET_S))
    )
    search = throughput_run(seed=seed, budget_s=remaining, floor=floor)
    gates.append(search.pop("gate"))
    return {
        "seed": seed,
        "budget_s": budget_s,
        "wall_s": round(time.monotonic() - started, 2),
        "gates": gates,
        "search": search,
        "passed": all(g["ok"] for g in gates),
    }


def run(seed: int = 7, budget_s: float = 8.0) -> Dict:
    """The ``--bench`` report: a short budgeted search plus the
    reproducibility pin."""
    started = time.monotonic()
    repro = reproducibility_gate(seed=seed)
    search = throughput_run(seed=seed, budget_s=budget_s)
    return {
        "seed": seed,
        "wall_s": round(time.monotonic() - started, 2),
        "reproducible": repro["ok"],
        "candidates": search["candidates"],
        "candidates_per_s": search["candidates_per_s"],
        "corpus_size": search["corpus_size"],
        "coverage_signals": search["coverage_signals"],
        "finds": search["finds"],
        "find_failures": search["find_failures"],
    }


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument(
        "--budget-s", type=float, default=DEFAULT_BUDGET_S
    )
    parser.add_argument(
        "--bench",
        action="store_true",
        help="emit the compact report instead of the smoke gates",
    )
    args = parser.parse_args(argv)
    if args.bench:
        out = run(seed=args.seed, budget_s=args.budget_s)
        print(json.dumps(out, indent=2))
        return 0
    out = smoke(seed=args.seed, budget_s=args.budget_s)
    print(json.dumps(out, indent=2))
    for gate in out["gates"]:
        status = "ok" if gate["ok"] else "FAIL"
        print(f"{status}: {gate['gate']} — {gate['detail']}", file=sys.stderr)
    if out["search"]["finds"]:
        print(
            f"NOTE: {out['search']['finds']} find(s) on the healthy "
            f"tree — real bugs; see finds_detail above",
            file=sys.stderr,
        )
    return 0 if out["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
