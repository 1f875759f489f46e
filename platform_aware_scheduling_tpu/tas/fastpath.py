"""Per-request fast path for the Prioritize/Filter verbs.

The reference re-sorts per HTTP request (telemetryscheduler.go:128-149).
But the ordering is *request-independent*: for one (metric, operator) the
rank order over all nodes is fixed until the cluster state changes, and a
request's answer is exactly the global order restricted to its candidate
set (the sort key — metric value with node-index tiebreak, ops/scoring.py
— does not depend on which candidates are present).  Same for Filter's
violation set (noted request-independent at SURVEY §3.3).

So the device work moves OFF the request path entirely:

  * on a state-version change, ``prioritize_kernel`` ranks ALL nodes in
    one XLA pass per (metric row, op) in use — amortized over every
    request in the sync window (the reference recomputes per request);
  * a request then costs: candidate-row lookup (dict), a vectorized
    subsequence selection (numpy), and JSON assembly from per-node byte
    fragments pre-rendered at view-build time.

No host↔device round trip, no sort, no per-node Python objects at
request time — this is what makes p99 at 10k nodes flat.

Byte-for-byte output parity with ``encode_host_priority_list`` over the
equivalent HostPriority list is covered by tests/test_fastpath.py.
"""

from __future__ import annotations

import json
import os
import threading
from collections.abc import Mapping
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from platform_aware_scheduling_tpu.ops.rules import OP_IDS
from platform_aware_scheduling_tpu.ops.scoring import (
    batch_prioritize_kernel,
    filter_explain_kernel,
    prioritize_kernel,
)
from platform_aware_scheduling_tpu.ops import solveobs
from platform_aware_scheduling_tpu.ops.state import (
    CompiledPolicy,
    DeviceView,
    Round,
    RoundAsks,
    run_round,
)
from platform_aware_scheduling_tpu.utils import decisions, trace
from platform_aware_scheduling_tpu.utils import labels as shared_labels

# op id -> operator name, for decoding device rule indexes into the
# shared reason strings (decisions.rule_reason keeps host parity)
_OP_NAMES = {op_id: name for name, op_id in OP_IDS.items()}

# rank -> b'<score>}' suffix bytes; grown on demand (scores are ordinal
# 10 - rank and go negative past rank 10, telemetryscheduler.go:145)
_SCORE_SUFFIX: List[bytes] = []
_SCORE_LOCK = threading.Lock()


def _score_suffixes(n: int) -> List[bytes]:
    if len(_SCORE_SUFFIX) < n:
        with _SCORE_LOCK:
            for i in range(len(_SCORE_SUFFIX), n):
                _SCORE_SUFFIX.append(f"{10 - i}}}".encode())
    return _SCORE_SUFFIX


def count_plan(promotion: int) -> None:
    """The planner's counters for one Prioritize answer given with a
    current plan's node, counted where the promotion is made: 0 the node
    is not among the ranked candidates (unplanned), 1 it leads the answer
    and led the ordinal ranking already (promoted), 2 it leads because it
    was moved past a better-ranked candidate (promoted and reordered)."""
    if not promotion:
        trace.COUNTERS.inc("pas_planner_unplanned_total")
        return
    trace.COUNTERS.inc("pas_planner_promoted_total")
    if promotion == 2:
        trace.COUNTERS.inc("pas_planner_reordered_total")


def _response_cache_size(default: int = 32) -> int:
    """PAS_TPU_RESPONSE_CACHE, validated: malformed or non-positive
    values fall back to the default rather than crashing the import or
    silently disabling the caches via negative slice bounds."""
    raw = os.environ.get("PAS_TPU_RESPONSE_CACHE", "")
    try:
        value = int(raw)
    except ValueError:
        return default
    return value if value >= 1 else default


def _universe_cache_size(default: int = 8) -> int:
    """PAS_TPU_UNIVERSE_CACHE: universes kept per fastpath (each holds
    the raw candidate span + slices + encode metadata — ~0.5 MB at 10k
    nodes).  ``0`` disables interning entirely (the wire then serves
    exactly the pre-universe span-cache paths, byte-identical — pinned
    by tests/test_wire_universe.py); malformed values fall back."""
    raw = os.environ.get("PAS_TPU_UNIVERSE_CACHE", "")
    try:
        value = int(raw)
    except ValueError:
        return default
    return value if value >= 0 else default


class _ViewTable:
    """Per-interning-version request-time tables: name->row index,
    pre-rendered JSON fragments (Python path), and the native NameTable
    (_wirec path).  Keyed by the view's ``intern_version`` — pure metric
    value churn does not invalidate name tables/fragments, so the encode
    table survives every sync period until a new node actually appears.
    Both table kinds build lazily — only the serving variant in use pays."""

    __slots__ = (
        "version",
        "node_index",
        "node_names",
        "node_capacity",
        "_fragments",
        "_native",
    )

    def __init__(self, view: DeviceView):
        self.version = view.intern_version
        self.node_index = view.node_index  # immutable snapshot dict
        self.node_names = view.node_names
        self.node_capacity = view.node_capacity
        self._fragments: Optional[List[bytes]] = None
        self._native = None

    @property
    def fragments(self) -> List[bytes]:
        fragments = self._fragments
        if fragments is None:
            # json.dumps handles any escaping exactly like the slow path
            fragments = [
                f'{{"Host": {json.dumps(name)}, "Score": '.encode()
                for name in self.node_names
            ]
            self._fragments = fragments
        return fragments

    def native(self, wirec):
        table = self._native
        if table is None:
            table = wirec.build_table(self.node_names)
            self._native = table
        return table


class _MergedReasons(Mapping):
    """``{node: reason}`` of a merged verdict: the telemetry's reasons,
    then every clean held node with its holder's reason, in the held
    map's order — made on the first read of an entry.  A decision record
    keeps it by reference and asks its length alone; only a reader of the
    entries (``/debug/decisions``, a bind's outcome) pays the walk."""

    __slots__ = ("base", "_held", "_index", "_violating", "_len", "_map")

    def __init__(self, reasons, held, index, violating, length: int):
        self.base = reasons  # the telemetry's own {node: reason}
        self._held = held
        self._index = index
        self._violating = violating
        self._len = length
        self._map = None

    def _built(self) -> Dict[str, str]:
        merged = self._map
        if merged is None:
            merged = dict(self.base)
            index, violating = self._index, self._violating
            for node, gang_id in self._held.map.items():
                row = index.get(node)
                if row is not None and not violating[row]:
                    merged[node] = shared_labels.gang_reserved_reason(gang_id)
            self._map = merged
        return merged

    def __getitem__(self, node: str) -> str:
        return self._built()[node]

    def __iter__(self):
        return iter(self._built())

    def __len__(self) -> int:
        return self._len


class GangMerged(NamedTuple):
    """A merged (telemetry + gang-held) Filter verdict at one reservation
    version (:meth:`PrioritizeFastPath.gang_merged`), over the name
    table's rows."""

    violations: frozenset  # violating or held-clean rows
    reasons: "_MergedReasons"  # {node: reason} of those rows
    table: list  # JSON reason bytes a row, for the native encoder
    holder_at: np.ndarray  # int32 a row: its holder in held_reasons, or -1
    held_reasons: list  # a holding gang's reason, in the held set's order


class PrioritizeFastPath:
    """Caches global rankings + violation sets per state version and
    answers verbs with numpy selections over them."""

    # response-reuse entries kept per fastpath (each ~ request span +
    # response bytes — ~0.5 MB at 10k nodes, so the default 32 costs at
    # most ~17 MB per verb).  The round-3 verdict flagged 8 as thrashable
    # by more than 8 interleaved candidate sets; override via
    # PAS_TPU_RESPONSE_CACHE for constrained deployments.
    RESPONSE_CACHE_SIZE = _response_cache_size()
    # interned node-name universes kept (bounded MRU, wirec.c; 0 = off)
    UNIVERSE_CACHE_SIZE = _universe_cache_size()

    def __init__(self):
        self._lock = threading.Lock()
        self._table: Optional[_ViewTable] = None
        # _wirec.UniverseCache, created lazily on the first probe (the
        # native module may not be loadable at construction time); False
        # marks "tried and unavailable" so the probe stays O(1)
        self._universes = None
        # (row_content_version, metric_row, op) -> int64 np global order
        self._rank: Dict[Tuple[int, int, int], np.ndarray] = {}
        # (row-version tuple, rows, ruleset tensors) -> (frozenset of
        # violating row indices, {row: first matching rule index}) — the
        # rule map is the device's compact reason code per violating node
        # (ops/scoring.filter_explain_kernel), decoded into reason
        # strings once per state by violation_reasons()
        self._violations: Dict[Tuple, Tuple[frozenset, Dict[int, int]]] = {}
        # decoded provenance per (violation-set identity, policy name):
        # [violations, policy_name, {name: reason str}, {name: rule idx},
        #  encoded-reason-bytes-per-row list or None (built lazily for
        #  the native filter_encode)] — MRU, shared by every request at
        # one state so record creation stays O(1)
        self._viol_reasons: List[list] = []
        # response-reuse cache: the kube-scheduler prioritizes every
        # pending pod against the same filter result, so consecutive
        # requests carry byte-identical candidate lists; entries are keyed
        # by (ranking identity, table identity, planned row) and VERIFIED
        # by comparing the raw candidate-span bytes — identical span +
        # identical ranking implies a byte-identical response, with zero
        # false positives (no hashing trust).  List of
        # [ranked, table, planned_row, span_bytes, response, promotion]
        # (what the planned row did in it: count_plan), MRU first.
        self._responses: List[list] = []
        # same idea for Filter: [violation_set, use_nn, span_bytes, body,
        # n_failed, gang_version] — the failed-entry count rides along so
        # decision records on cache hits stay O(1); gang_version keys the
        # reservation state a gang-mode response encoded (None = no gang
        # tracker), so a reservation change can never serve stale bytes
        self._filter_responses: List[list] = []
        # pre-rendered response skeletons, the universe-keyed layer UNDER
        # the span caches: once a request's candidate span is interned
        # (wirec.c UniverseCache), the full response body is keyed by
        # OBJECT IDENTITY — (violation set, universe, gang reservation
        # version) for Filter, (ranking, table, planned row, universe)
        # for Prioritize — so a warm hit costs identity compares instead
        # of a span memcmp, and any state change (new frozenset / new
        # ranking / new reservation version) misses by construction.
        # Entries: [violations, universe, gang_version, body, n_failed]
        # and [ranked, table, planned_row, universe, body, promotion].
        self._filter_skeletons: List[list] = []
        self._prioritize_skeletons: List[list] = []
        # merged (telemetry + gang reservation) Filter verdicts, one per
        # (violation-set identity, reservation version, policy):
        # [violations, version, policy, GangMerged] — MRU, shared by every
        # non-gang request at one (state, reservation) generation
        self._gang_merged: List[list] = []
        # {gang id: its hosts' rows and reason} while it holds (_gang_part)
        self._gang_parts: Dict[str, tuple] = {}
        # (name table, mesh view, row -> flat cell) (_mesh_rows)
        self._row_cells = None
        # [ranked, table, top-K (name, score) head] — the shared
        # prioritize score breakdown decision records reference
        self._explain_heads: List[list] = []
        # violation frozenset -> uint8-per-row bitmask bytes for the
        # native filter_encode; keyed by OBJECT identity (sets are
        # identity-stable per state) with the set itself held in the
        # entry so an id can never alias a collected set
        self._viol_masks: List[list] = []

    # -- table/cache maintenance ----------------------------------------------

    def _table_for(self, view: DeviceView) -> _ViewTable:
        """The encode table for this view's interning.  Forward-only: a
        stale in-flight request (view older than the installed table) gets
        a throwaway table and must never displace the warmed current one
        — otherwise one slow request would make the next request pay the
        rebuild the warmer already did."""
        table = self._table
        if table is not None and table.version == view.intern_version:
            return table
        if table is not None and view.intern_version < table.version:
            return _ViewTable(view)
        with self._lock:
            current = self._table
            if current is None or current.version < view.intern_version:
                current = _ViewTable(view)
                self._table = current
            elif current.version > view.intern_version:  # raced past us
                return _ViewTable(view)
            return current

    def _ranking(self, view: DeviceView, row: int, op: int) -> np.ndarray:
        # keyed by the ROW's content version: metric churn on other rows
        # (or node interning alone) leaves this ranking valid
        key = (view.row_version(row), row, op)
        ranked = self._rank.get(key)
        if ranked is None:
            obs = solveobs.ACTIVE
            timer = obs.begin("prioritize_rank") if obs is not None else None
            compiled_before = (
                prioritize_kernel.cache_size() if timer is not None else 0
            )
            # ONE device pass ranks all nodes; every request until this
            # row's next content change reuses it
            res = prioritize_kernel(
                view.values,
                view.present,
                np.int32(row),
                np.int32(op),
                np.ones(view.node_capacity, dtype=bool),
            )
            if timer is not None:
                # attribute the dispatch to compile when the jit cache
                # grew during the call, then block so execute carries the
                # device time instead of hiding inside the readback
                grew = prioritize_kernel.cache_size() > compiled_before
                timer.mark("compile" if grew else "execute")
                res.perm.block_until_ready()
                timer.mark("execute")
            count = int(res.valid_count)
            ranked = np.asarray(res.perm)[:count]
            # three host arrays up, the dispatch, two readbacks
            trace.COUNTERS.inc("pas_refresh_warm_device_calls_total", 6)
            if timer is not None:
                timer.mark("readback")
            ranked = ranked.astype(np.int64)
            with self._lock:
                self._rank[key] = ranked
            if timer is not None:
                timer.mark("encode")
                timer.done(nodes=view.node_capacity)
        return ranked

    def warm_rankings_batched(self, view: DeviceView, pairs) -> int:
        """Seed the ranking cache for every not-yet-warm (metric row, op)
        pair in ONE device dispatch (``batch_prioritize_kernel`` vmapped
        over the pair axis) — the serving micro-batcher's fused solve:
        a coalesced batch of requests needing K distinct rankings costs
        one XLA program, not K (and zero when all are warm).  Cache
        entries are identical to what per-pair :meth:`_ranking` would
        store, so responses stay byte-identical to the per-request path.
        Returns the number of pairs actually computed."""
        missing = [
            (int(row), int(op))
            for row, op in pairs
            if (view.row_version(int(row)), int(row), int(op))
            not in self._rank
        ]
        if not missing:
            return 0
        obs = solveobs.ACTIVE
        timer = obs.begin("warm_batch") if obs is not None else None
        compiled_before = (
            batch_prioritize_kernel.cache_size() if timer is not None else 0
        )
        # plain host arrays: the dispatch uploads them, no program of their own
        rows_dev = np.asarray([row for row, _ in missing], dtype=np.int32)
        ops_dev = np.asarray([op for _, op in missing], dtype=np.int32)
        mask_dev = np.ones((len(missing), view.node_capacity), dtype=bool)
        if timer is not None:
            timer.mark("transfer")
        res = batch_prioritize_kernel(
            view.values, view.present, rows_dev, ops_dev, mask_dev
        )
        if timer is not None:
            grew = batch_prioritize_kernel.cache_size() > compiled_before
            timer.mark("compile" if grew else "execute")
            res.perm.block_until_ready()
            timer.mark("execute")
        perms = np.asarray(res.perm)
        counts = np.asarray(res.valid_count)
        trace.COUNTERS.inc("pas_refresh_warm_device_calls_total", 6)
        if timer is not None:
            timer.mark("readback")
        with self._lock:
            for i, (row, op) in enumerate(missing):
                key = (view.row_version(row), row, op)
                self._rank[key] = perms[i][: int(counts[i])].astype(np.int64)
        if timer is not None:
            timer.mark("encode")
            timer.done(pairs=len(missing), nodes=view.node_capacity)
        return len(missing)

    def warm_pairs(self, view: DeviceView, pairs) -> None:
        """Warm rankings for (metric row, op) pairs against ``view``
        WITHOUT the precompute pruning — the forecast warmer's entry
        (forecast views carry negative version markers the prune would
        drop; they expire naturally when the next fit publishes)."""
        for row, op in pairs:
            self._ranking(view, int(row), int(op))

    def precompute(
        self,
        view: DeviceView,
        pairs,
        wirec=None,
        asks: Optional[RoundAsks] = None,
    ) -> None:
        """Warm the request-time state for (metric_row, op) pairs: the
        ranking cache, plus the response table for whichever encoder will
        serve (native NameTable when ``wirec`` is given, fragments
        otherwise).  With the round's ``asks`` every ranking and violation
        set the view lacks comes from ONE round trip (:meth:`warm_round`)
        — the view's own, when a one-row publish made it; without, one
        device pass a pair.

        Called from state-refresh threads via the mirror's post-publish
        hook (TensorStateMirror.on_state_change) so steady-state requests
        never pay a device pass or a table build.  Also prunes cache
        entries whose row content has moved on by more than one change:
        a one-row publish warms its view before requests can get it, so
        the round they are still served from must stay whole meanwhile."""
        table = self._table_for(view)
        if wirec is not None:
            table.native(wirec)
        else:
            table.fragments
        if asks is not None:
            self.warm_round(view, asks)
        for row, op in pairs:
            self._ranking(view, int(row), int(op))
        with self._lock:
            self._rank = {
                k: v
                for k, v in self._rank.items()
                if 0 <= view.row_version(k[1]) - k[0] <= 1
            }
            self._violations = {
                k: v
                for k, v in self._violations.items()
                if all(
                    0 <= view.row_version(r) - seen <= 1
                    for r, seen in zip(k[1], k[0])
                )
            }

    def warm_round(self, view: DeviceView, asks: RoundAsks) -> int:
        """Install every ranking and violation set ``asks`` names that
        the caches lack at ``view``'s state, from one packed answer: the
        one that came with the view (a one-row publish, ops/state.py) or,
        for a view uploaded whole, one round trip made here.  The entries
        are what per-pair :meth:`_ranking` and per-policy
        :meth:`_violation_set_counted` would store — same keys, same
        identity rules.  Returns the round trips made (0 or 1)."""
        answered = view.round
        if answered is not None and answered.asks is asks:
            self._install_round(view, answered)
            return 0
        if all(
            (view.row_version(row), row, op) in self._rank
            for row, op in asks.pairs
        ) and all(
            self._violation_sig(rules, view) in self._violations
            for rules in asks.rule_sets
        ):
            return 0
        obs = solveobs.ACTIVE
        timer = obs.begin("publish_round") if obs is not None else None
        _, _, answered = run_round(view.values, view.present, asks, timer=timer)
        self._install_round(view, answered)
        if timer is not None:
            timer.mark("encode")
            timer.done(nodes=view.node_capacity)
        return 1

    def _install_round(self, view: DeviceView, answered: Round) -> None:
        asks, packed = answered
        first_codes = asks.shape[0]
        counts = packed[-1]
        fresh = []
        for i, (row, op) in enumerate(asks.pairs):
            key = (view.row_version(row), row, op)
            if key not in self._rank:
                fresh.append((key, packed[i, : counts[i]].astype(np.int64)))
        sets = []
        for j, rules in enumerate(asks.rule_sets):
            sig = self._violation_sig(rules, view)
            if sig not in self._violations:
                sets.append((sig, self._decode_codes(packed[first_codes + j])))
        with self._lock:
            for key, ranked in fresh:
                self._rank.setdefault(key, ranked)
            for sig, cached in sets:
                # a concurrent computer may have won: one set a state
                self._violations.setdefault(sig, cached)

    # -- universe interning ----------------------------------------------------

    def universe_probe(self, wirec, parsed, use_node_names: bool):
        """The interned universe for this request's candidate span, or
        None (cold span, interning disabled, or an old native artifact
        without universe support).  A span is interned on its SECOND
        sighting (the cache's once-seen digest ring), so one-shot
        candidate lists never pay intern/evict churn.  Counters:
        ``pas_wire_intern_{hits,misses,evictions}_total`` partition every
        probe against an available cache into hit/miss (evictions ride
        along).  Never raises into the verb."""
        cache = self._universe_cache(wirec)
        if cache is None:
            return None
        try:
            # ONE digest pass covers hit lookup, the once-seen check, and
            # a second-sighting intern (wirec.c UniverseCache.probe)
            universe, interned, evicted = cache.probe(parsed, use_node_names)
            if universe is not None and not interned:
                trace.COUNTERS.inc("pas_wire_intern_hits_total")
                return universe
            trace.COUNTERS.inc("pas_wire_intern_misses_total")
            if evicted:
                trace.COUNTERS.inc("pas_wire_intern_evictions_total", evicted)
            # freshly interned (or first sighting, None): the request
            # itself still renders, but may promote a span-cache body
            return universe
        except Exception:
            return None  # interning is an optimization, never a failure

    def _universe_cache(self, wirec):
        cache = self._universes
        if cache is not None:
            return cache or None  # False = tried, unavailable
        if (
            self.UNIVERSE_CACHE_SIZE <= 0
            or wirec is None
            or not hasattr(wirec, "UniverseCache")
        ):
            self._universes = False
            return None
        with self._lock:
            if self._universes is None:
                self._universes = wirec.UniverseCache(
                    capacity=self.UNIVERSE_CACHE_SIZE
                )
            return self._universes or None

    def warm_skeletons(
        self,
        wirec,
        compiled: CompiledPolicy,
        view: DeviceView,
        policy_name: str,
        filter_ok: bool = True,
        prioritize_ok: bool = True,
    ) -> int:
        """Pre-render response skeletons for every interned NodeNames
        universe at the CURRENT state — called from the state-refresh
        warm pass (MetricsExtender.warm_fastpath), so a metric refresh
        that mints a new violation set / ranking re-renders each live
        universe's body ONCE off the request path and the first request
        of the sync window still splices.  Only the no-gang keys are
        warmed (gang reservation versions move between warm passes; a
        gang-mode miss renders on demand as before).  Returns the number
        of bodies rendered; never raises past the warm pass's guard."""
        cache = self._universes
        if (
            not cache
            or wirec is None
            or not hasattr(wirec, "filter_respond")
        ):
            return 0
        rendered = 0
        table = self._table_for(view)
        n_rows = len(table.node_names)
        native = table.native(wirec)
        violations = None
        reasons = None
        if filter_ok:
            counted = self._violation_set_counted(compiled, view)
            if counted is not None:
                violations, rule_map = counted[0]
                reasons = self.reason_table(
                    compiled, view, policy_name, violations, rule_map,
                    n_rows,
                )
        ranked = None
        if prioritize_ok and compiled.scheduleonmetric_row >= 0:
            ranked = self._ranking(
                view,
                compiled.scheduleonmetric_row,
                compiled.scheduleonmetric_op,
            )
        for universe in cache.snapshot():
            if not universe.use_node_names:
                continue
            if violations is not None:
                with self._lock:
                    have = any(
                        entry[0] is violations
                        and entry[1] is universe
                        and entry[2] is None
                        for entry in self._filter_skeletons
                    )
                if not have:
                    mask = self._violation_mask(violations, n_rows)
                    body, n_failed = wirec.filter_respond(
                        universe, native, mask, reasons
                    )
                    self.filter_store(
                        violations, True, None, body, n_failed, None,
                        universe=universe,
                    )
                    rendered += 1
            if ranked is not None:
                with self._lock:
                    have = any(
                        entry[0] is ranked
                        and entry[1] is table
                        and entry[2] == -1
                        and entry[3] is universe
                        for entry in self._prioritize_skeletons
                    )
                if not have:
                    body = wirec.select_encode_universe(
                        universe, native, ranked, -1
                    )
                    with self._lock:
                        self._prioritize_skeletons.insert(
                            0, [ranked, table, -1, universe, body, 0]
                        )
                        del self._prioritize_skeletons[
                            self.RESPONSE_CACHE_SIZE :
                        ]
                    rendered += 1
        return rendered

    def wire_debug(self) -> Dict:
        """The /debug/wire payload: universe-cache occupancy + interning
        counters + the skeleton-cache keys (universe uid, violation-set
        size, gang version / planned row) — the operator's view of why a
        request was cold, interned, or spliced."""
        out: Dict = {
            "enabled": bool(self._universes),
            "capacity": self.UNIVERSE_CACHE_SIZE,
            "counters": {
                "hits": trace.COUNTERS.get("pas_wire_intern_hits_total"),
                "misses": trace.COUNTERS.get("pas_wire_intern_misses_total"),
                "evictions": trace.COUNTERS.get(
                    "pas_wire_intern_evictions_total"
                ),
            },
        }
        cache = self._universes
        if not cache:
            out["occupancy"] = 0
            out["universes"] = []
        else:
            out["occupancy"] = cache.occupancy
            out["universes"] = cache.universes()
        with self._lock:
            out["skeletons"] = {
                "filter": [
                    {
                        "universe": entry[1].uid,
                        "violating": len(entry[0]),
                        "gang_version": entry[2],
                        "bytes": len(entry[3]),
                    }
                    for entry in self._filter_skeletons
                ],
                "prioritize": [
                    {
                        "universe": entry[3].uid,
                        "planned_row": entry[2],
                        "bytes": len(entry[4]),
                    }
                    for entry in self._prioritize_skeletons
                ],
            }
        return out

    # -- prioritize ------------------------------------------------------------

    def prioritize_parsed(
        self,
        wirec,
        compiled: CompiledPolicy,
        view: DeviceView,
        parsed,
        planned: Optional[str] = None,
        use_node_names: bool = False,
        span=trace.NULL_SPAN,
        universe=None,
    ) -> bytes:
        """Native variant: candidate lookup + selection + byte assembly all
        happen in ``_wirec.select_encode`` over the parsed body's zero-copy
        name slices — no per-node Python objects at any point.  When the
        request's raw candidate span matches a cached one under the same
        ranking/table/plan, the stored response is returned without any
        selection or encoding at all (see _responses).  With an interned
        ``universe`` the skeleton layer serves first — identity compares
        only, no span memcmp — and a miss renders through the universe's
        cached row map (``select_encode_universe``, zero hashing); either
        way the bytes are identical to the span path's.  With a current
        plan's node (``planned``) the planner's counters say what it did
        to this answer (:func:`count_plan`)."""
        response, promotion = self._prioritize_parsed(
            wirec, compiled, view, parsed, planned, use_node_names, span,
            universe,
        )
        if planned is not None:
            count_plan(promotion)
        return response

    def _prioritize_parsed(
        self, wirec, compiled, view, parsed, planned, use_node_names, span,
        universe,
    ) -> Tuple[bytes, int]:
        """(response, what the planned row did in it: see
        :func:`count_plan`), from the caches or rendered."""
        table = self._table_for(view)
        with span.stage("kernel"):
            ranked = self._ranking(
                view,
                compiled.scheduleonmetric_row,
                compiled.scheduleonmetric_op,
            )
        planned_row = -1
        if planned is not None:
            planned_row = table.node_index.get(planned, -1)
        with span.stage("lookup", sampled=True), self._lock:
            if universe is not None:
                skeletons = self._prioritize_skeletons
                for idx, entry in enumerate(skeletons):
                    if (
                        entry[0] is ranked
                        and entry[1] is table
                        and entry[2] == planned_row
                        and entry[3] is universe
                    ):
                        if idx:
                            skeletons.insert(0, skeletons.pop(idx))
                        span.set("fastpath", "hit")
                        trace.COUNTERS.inc("pas_fastpath_response_hit_total")
                        return entry[4], entry[5]
            responses = self._responses
            for idx, entry in enumerate(responses):
                if (
                    entry[0] is ranked
                    and entry[1] is table
                    and entry[2] == planned_row
                    and parsed.span_matches(use_node_names, entry[3])
                ):
                    if idx:  # move to front (MRU)
                        responses.insert(0, responses.pop(idx))
                    if universe is not None:
                        # promote the span-cached body into the skeleton
                        # layer so the next warm request skips the memcmp
                        self._prioritize_skeletons.insert(
                            0,
                            [ranked, table, planned_row, universe, *entry[4:]],
                        )
                        del self._prioritize_skeletons[
                            self.RESPONSE_CACHE_SIZE :
                        ]
                    span.set("fastpath", "hit")
                    trace.COUNTERS.inc("pas_fastpath_response_hit_total")
                    return entry[4], entry[5]
        span.set("fastpath", "miss")
        trace.COUNTERS.inc("pas_fastpath_response_miss_total")
        # asked, the encoder also says what it did with the planned row
        # (wirec.c emit_ranked): 0 without one
        with span.stage("encode"):
            if universe is not None and hasattr(
                wirec, "select_encode_universe"
            ):
                response, promotion = wirec.select_encode_universe(
                    universe, table.native(wirec), ranked, planned_row, True
                )
            else:
                response, promotion = wirec.select_encode(
                    parsed, table.native(wirec), ranked, planned_row,
                    use_node_names, True,
                )
        if universe is not None:
            with self._lock:
                self._prioritize_skeletons.insert(
                    0,
                    [ranked, table, planned_row, universe, response, promotion],
                )
                del self._prioritize_skeletons[self.RESPONSE_CACHE_SIZE :]
            return response, promotion
        # cand_span: the request's raw candidate byte-span (the cache key)
        # — distinct from the trace `span` parameter above
        cand_span = (
            parsed.node_names_span() if use_node_names else parsed.nodes_span()
        )
        if cand_span is not None:
            entry = [ranked, table, planned_row, cand_span, response, promotion]
            with self._lock:
                self._responses.insert(0, entry)
                del self._responses[self.RESPONSE_CACHE_SIZE :]
        return response, promotion

    def prioritize_bytes(
        self,
        compiled: CompiledPolicy,
        view: DeviceView,
        names: List[str],
        planned: Optional[str] = None,
        span=trace.NULL_SPAN,
    ) -> bytes:
        """The full Prioritize response body for one request: global order
        restricted to ``names`` (candidate ∩ metric-present), ordinal
        scores, optional batch-plan promotion to rank 1."""
        table = self._table_for(view)
        with span.stage("kernel"):
            ranked = self._ranking(
                view,
                compiled.scheduleonmetric_row,
                compiled.scheduleonmetric_op,
            )
        with span.stage("encode"):
            index = table.node_index
            sentinel = table.node_capacity
            mask = np.zeros(sentinel + 1, dtype=bool)
            rows = np.fromiter(
                (index.get(n, sentinel) for n in names),
                dtype=np.int64,
                count=len(names),
            )
            mask[rows] = True
            mask[sentinel] = False
            sel = ranked[mask[ranked]]
            if planned is not None:
                at = np.nonzero(sel == index.get(planned, -1))[0]
                if at.size:
                    sel = np.concatenate(([sel[at[0]]], np.delete(sel, at[0])))
                count_plan((2 if at[0] else 1) if at.size else 0)
            return self._encode(table, sel)

    @staticmethod
    def _encode(table: _ViewTable, sel: np.ndarray) -> bytes:
        if sel.size == 0:
            return b"[]\n"
        fragments = table.fragments
        suffix = _score_suffixes(sel.size)
        parts = [fragments[r] + suffix[i] for i, r in enumerate(sel.tolist())]
        return b"[" + b", ".join(parts) + b"]\n"

    # -- filter ----------------------------------------------------------------

    def violation_set(
        self, compiled: CompiledPolicy, view: DeviceView
    ) -> Optional[frozenset]:
        """Identity-stable violating-row frozenset for this policy at this
        state — the Filter response cache keys on the OBJECT identity, so
        a state change (new frozenset) can never serve stale bytes."""
        result = self._violation_set_counted(compiled, view)
        return result if result is None else result[0][0]

    def violation_rule_map(
        self, compiled: CompiledPolicy, view: DeviceView
    ) -> Optional[Dict[int, int]]:
        """{violating row: first matching rule index} at this state — the
        device's raw reason codes (decoded by violation_reasons)."""
        result = self._violation_set_counted(compiled, view)
        return result if result is None else result[0][1]

    def violation_reasons(
        self, compiled: CompiledPolicy, view: DeviceView, policy_name: str
    ):
        """Decision provenance for one policy at the current state:
        ``(violations frozenset, {node name: reason string},
        {node name: rule index})`` — or None when the policy has no
        device-evaluable dontschedule rules.

        The maps are built ONCE per (violation set, policy) and shared by
        reference across every request and decision record at that state;
        the strings are byte-identical to the host path's
        (dontschedule.violated_details) because both format the same
        milli integers through decisions.rule_reason."""
        counted = self._violation_set_counted(compiled, view)
        if counted is None:
            return None
        violations, rule_map = counted[0]
        entry = self._reason_entry(compiled, view, policy_name, violations, rule_map)
        return violations, entry[2], entry[3]

    def _reason_entry(
        self,
        compiled: CompiledPolicy,
        view: DeviceView,
        policy_name: str,
        violations: frozenset,
        rule_map: Dict[int, int],
    ) -> list:
        with self._lock:
            for idx, entry in enumerate(self._viol_reasons):
                if entry[0] is violations and entry[1] == policy_name:
                    if idx:
                        self._viol_reasons.insert(
                            0, self._viol_reasons.pop(idx)
                        )
                    return entry
        rules = compiled.dontschedule
        node_names = view.node_names
        n_names = len(node_names)
        # padding lanes never violate real nodes
        rows = [row for row in sorted(rule_map) if row < n_names]
        ridxs = [rule_map[row] for row in rows]
        names = [node_names[row] for row in rows]
        milli = view.milli_rows()
        # constant per rule: everything of the reason but the observed
        # value, and the metric row the value is read from
        parts = {}
        values = np.zeros(len(rows), dtype=np.int64)
        rows_at = np.asarray(rows, dtype=np.intp)
        ridx_at = np.asarray(ridxs, dtype=np.intp)
        for ridx in set(ridxs):
            metric = (
                rules.metric_names[ridx]
                if ridx < len(rules.metric_names)
                else ""
            )
            parts[ridx] = decisions.rule_reason_parts(
                policy_name,
                metric,
                _OP_NAMES.get(int(rules.op_ids[ridx]), "?"),
                decisions.fmt_milli(int(rules.targets[ridx])),
            )
            if milli is not None:
                mine = ridx_at == ridx
                values[mine] = milli[int(rules.metric_rows[ridx])][rows_at[mine]]
        shown: Dict[int, str] = {}  # each distinct value formatted once
        reasons: Dict[str, str] = {}
        for name, ridx, value in zip(names, ridxs, values.tolist()):
            head, tail = parts[ridx]
            if milli is None:
                value_str = "?"
            else:
                value_str = shown.get(value)
                if value_str is None:
                    value_str = shown[value] = decisions.fmt_milli(value)
            reasons[name] = head + value_str + tail
        indexes: Dict[str, int] = dict(zip(names, ridxs))
        entry = [violations, policy_name, reasons, indexes, None]
        with self._lock:
            for existing in self._viol_reasons:
                if existing[0] is violations and existing[1] == policy_name:
                    return existing  # a concurrent builder won
            self._viol_reasons.insert(0, entry)
            del self._viol_reasons[self.RESPONSE_CACHE_SIZE :]
        return entry

    def reason_table(
        self,
        compiled: CompiledPolicy,
        view: DeviceView,
        policy_name: str,
        violations: frozenset,
        rule_map: Dict[int, int],
        n_rows: int,
    ) -> list:
        """Per-row pre-JSON-encoded reason bytes (aligned with the
        violation bitmask) for the native ``_wirec.filter_encode`` — the
        C encoder splices entry bytes verbatim, so parity with the exact
        path's json.dumps holds by construction.  Built lazily once per
        (violation set, policy) and cached on the reason entry."""
        entry = self._reason_entry(
            compiled, view, policy_name, violations, rule_map
        )
        table = entry[4]
        if table is None or len(table) < n_rows:
            table = [None] * n_rows
            index = view.node_index
            for name, reason in entry[2].items():
                row = index.get(name)
                if row is not None and row < n_rows:
                    table[row] = json.dumps(reason).encode()
            entry[4] = table
        return table

    def warm_violations(
        self, compiled: CompiledPolicy, view: DeviceView
    ) -> int:
        """Warm the violation set for one policy, reporting whether a
        device computation actually ran (1) or the set was already cached
        (0) — the serving micro-batcher's fused-solve accounting
        (MetricsExtender.warm_batch)."""
        result = self._violation_set_counted(compiled, view)
        return 0 if result is None else int(result[1])

    def _violation_set_counted(
        self, compiled: CompiledPolicy, view: DeviceView
    ):
        """((violation frozenset, {row: rule index}), computed-now?) or
        None (no device rules).  One fused device pass produces both the
        verdict and the per-node first-matching-rule index — the compact
        provenance vector decoded host-side by violation_reasons()."""
        rules = compiled.dontschedule
        if rules is None:
            return None
        sig = self._violation_sig(rules, view)
        cached = self._violations.get(sig)
        if cached is not None:
            return cached, False
        device_rules = compiled.device_rules("dontschedule")
        if device_rules is None:
            return None
        obs = solveobs.ACTIVE
        timer = obs.begin("filter_explain") if obs is not None else None
        compiled_before = (
            filter_explain_kernel.cache_size() if timer is not None else 0
        )
        res = filter_explain_kernel(
            view.values,
            view.present,
            device_rules,
            np.ones(view.node_capacity, dtype=bool),
        )
        if timer is not None:
            grew = filter_explain_kernel.cache_size() > compiled_before
            timer.mark("compile" if grew else "execute")
            res.first_rule.block_until_ready()
            timer.mark("execute")
        first_rule = np.asarray(res.first_rule)
        trace.COUNTERS.inc("pas_refresh_warm_device_calls_total", 3)
        if timer is not None:
            timer.mark("readback")
        cached = self._decode_codes(first_rule)
        if timer is not None:
            timer.mark("encode")
            timer.done(nodes=view.node_capacity)
        with self._lock:
            # a concurrent computer may have won: keep ITS set so the
            # identity-keyed response caches see one object per state
            existing = self._violations.get(sig)
            if existing is not None:
                return existing, False
            self._violations[sig] = cached
        return cached, True

    @staticmethod
    def _decode_codes(first_rule: np.ndarray) -> Tuple[frozenset, Dict[int, int]]:
        """(violating rows, {row: first matching rule}) of a device
        first-rule vector, as plain ints — in vectors, not a node at a time."""
        rows = np.nonzero(first_rule >= 0)[0]
        at = rows.tolist()
        return frozenset(at), dict(zip(at, first_rule[rows].tolist()))

    @staticmethod
    def _violation_sig(rules, view: DeviceView) -> Tuple:
        """The violation cache's key: the rule rows' content versions (not
        the global state version — churn on unrelated metrics keeps a set
        warm) and the rules themselves."""
        rule_rows = tuple(rules.metric_rows[rules.active].tolist())
        return (
            tuple(view.row_version(r) for r in rule_rows),
            rule_rows,
            rules.op_ids.tobytes(),
            rules.targets.tobytes(),
            rules.active.tobytes(),
        )

    def _violation_mask(self, violations: frozenset, n_rows: int) -> bytes:
        """uint8-per-row bitmask form of a violation frozenset (the shape
        ``_wirec.filter_encode`` consumes); cached per set identity."""
        with self._lock:
            for idx, entry in enumerate(self._viol_masks):
                if entry[0] is violations and entry[1] == n_rows:
                    if idx:
                        self._viol_masks.insert(0, self._viol_masks.pop(idx))
                    return entry[2]
        mask = np.zeros(n_rows, dtype=np.uint8)
        if violations:
            rows = np.fromiter(
                (i for i in violations if i < n_rows), dtype=np.int64
            )
            if rows.size:
                mask[rows] = 1
        mask_bytes = mask.tobytes()
        with self._lock:
            self._viol_masks.insert(0, [violations, n_rows, mask_bytes])
            del self._viol_masks[self.RESPONSE_CACHE_SIZE :]
        return mask_bytes

    def filter_parsed(
        self,
        wirec,
        view: DeviceView,
        parsed,
        violations: frozenset,
        compiled: Optional[CompiledPolicy] = None,
        policy_name: str = "",
        reason_table: Optional[list] = None,
        universe=None,
    ) -> Optional[Tuple[bytes, int]]:
        """Native Filter response: candidate row lookup, violation
        partition, and byte assembly all happen in ``_wirec.filter_encode``
        over the parsed body's zero-copy name slices — the Filter analog of
        :meth:`prioritize_parsed` (byte parity with the exact path pinned
        by tests/test_wirec.py).  With an interned ``universe``,
        ``_wirec.filter_respond`` partitions over the universe's cached
        row map instead (one int32 read per candidate, zero hashing) —
        identical bytes by construction.

        The request says which form the answer takes: one that carried
        ``Nodes`` (the exact path's own test, a non-empty items list) is
        answered by ``_wirec.filter_encode_nodes``, which echoes every
        passing ``v1.Node`` as the slice of the request it arrived in —
        byte-identical to the exact path outside the items, JSON-equal
        inside them (the request's separators and escapes are kept).  It
        returns None where it will not vouch for the reference's
        ``split(" ")`` quirk (an empty name, a name with a space), and
        the exact path answers.

        Returns ``(body, failed count)``.  With ``compiled`` given, the
        FailedNodes values carry the concrete per-rule reason strings
        (pre-encoded once per state via :meth:`reason_table`); without it
        the reference literal "Node violates" is emitted.  An explicit
        ``reason_table`` (the gang-merged overlay, :meth:`gang_merged`)
        overrides the per-rule one."""
        table = self._table_for(view)
        n_rows = len(table.node_names)
        mask = self._violation_mask(violations, n_rows)
        reasons = reason_table
        if reasons is None and compiled is not None:
            rule_map = self.violation_rule_map(compiled, view)
            if rule_map is not None:
                reasons = self.reason_table(
                    compiled, view, policy_name, violations, rule_map, n_rows
                )
        if parsed.nodes_present and parsed.num_nodes > 0:
            return wirec.filter_encode_nodes(
                parsed, table.native(wirec), mask, reasons
            )
        if universe is not None and hasattr(wirec, "filter_respond"):
            return wirec.filter_respond(
                universe, table.native(wirec), mask, reasons
            )
        return wirec.filter_encode(parsed, table.native(wirec), mask, reasons)

    def gang_merged(
        self,
        compiled: CompiledPolicy,
        view: DeviceView,
        policy_name: str,
        violations: frozenset,
        reasons: Dict[str, str],
        held,
        version: int,
        span=trace.NULL_SPAN,
    ) -> "GangMerged":
        """The non-gang-pod Filter verdict under active reservations
        (:class:`GangMerged`) — telemetry violations plus gang-held
        nodes, with the telemetry reason winning a collision exactly like
        the exact path's overlay merge (the overlay only ever fails
        telemetry-CLEAN candidates).  ``held`` is the tracker's
        ``HeldSet`` at ``version`` (gang/group.py).  Memoized per
        (violation-set identity, reservation version, policy) so every
        cached request at one generation shares the same objects.

        A new version's build is the stage ``gang_merge`` on ``span``:
        whole-array steps over every holding gang's table rows, with no
        step a held host by name (:meth:`_gang_part`)."""
        with self._lock:
            for idx, entry in enumerate(self._gang_merged):
                if (
                    entry[0] is violations
                    and entry[1] == version
                    and entry[2] == policy_name
                ):
                    if idx:
                        self._gang_merged.insert(
                            0, self._gang_merged.pop(idx)
                        )
                    return entry[3]
        with span.stage("gang_merge"):
            merged = self._gang_merge(
                compiled, view, policy_name, violations, reasons, held
            )
        with self._lock:
            for existing in self._gang_merged:
                if (
                    existing[0] is violations
                    and existing[1] == version
                    and existing[2] == policy_name
                ):
                    return existing[3]  # another thread built it first
            self._gang_merged.insert(
                0, [violations, version, policy_name, merged]
            )
            del self._gang_merged[self.RESPONSE_CACHE_SIZE :]
        return merged

    def _gang_merge(
        self, compiled, view, policy_name, violations, reasons, held
    ) -> "GangMerged":
        index = view.node_index
        n_rows = len(view.node_names)
        violating = np.frombuffer(
            self._violation_mask(violations, n_rows), dtype=np.uint8
        )
        known, kept = self._gang_parts, {}
        for gang_id, hosts in held.parts:
            part = known.get(gang_id)
            if part is None or part[0] is not hosts or part[1] is not index:
                part = self._gang_part(index, gang_id, hosts)
            kept[gang_id] = part
        self._gang_parts = kept  # gangs that no longer hold fall out
        parts = list(kept.values())
        rows = (
            np.concatenate([part[2] for part in parts])
            if parts
            else np.zeros(0, dtype=np.int32)
        )
        holder = np.repeat(
            np.arange(len(parts), dtype=np.int32),
            [len(part[2]) for part in parts],
        )
        held_rows = np.zeros(n_rows, dtype=bool)
        held_rows[rows] = True
        if np.count_nonzero(held_rows) < len(rows):
            # a host two gangs hold takes the later one's reason, as the
            # held map's last writer: each row's last occurrence is kept
            last = len(rows) - 1 - np.unique(rows[::-1], return_index=True)[1]
            rows, holder = rows[last], holder[last]
        # a violating row keeps its telemetry reason
        clean = violating[rows] == 0
        rows, holder = rows[clean], holder[clean]
        rule_map = self.violation_rule_map(compiled, view)
        if rule_map is not None:
            base = self.reason_table(
                compiled, view, policy_name, violations, rule_map, n_rows
            )
            table = np.fromiter(base[:n_rows], dtype=object, count=n_rows)
        else:
            table = np.full(n_rows, None, dtype=object)
        table[rows] = np.array([part[4] for part in parts], dtype=object)[
            holder
        ]
        holder_at = np.full(n_rows, -1, dtype=np.int32)
        holder_at[rows] = holder
        merged = violations.union(rows.tolist())
        mask = violating | held_rows
        self._seed_mask(merged, n_rows, mask.view(np.uint8).tobytes())
        return GangMerged(
            merged,
            _MergedReasons(
                reasons, held, index, violating,
                len(reasons) + len(merged) - len(violations),
            ),
            table.tolist(),
            holder_at,
            [part[3] for part in parts],
        )

    def _gang_part(self, index, gang_id: str, hosts: List[str]) -> tuple:
        """``(hosts, index, int32 table rows of its hosts, its reason, the
        reason's JSON bytes)`` of one holding gang — made once its hold
        (or the name table) is new and kept while it lasts (``_gang_merge``):
        the only step a host by name is here, a gang's hosts once."""
        at = [index.get(host) for host in hosts]
        reason = shared_labels.gang_reserved_reason(gang_id)
        return (
            hosts,
            index,
            np.array([r for r in at if r is not None], dtype=np.int32),
            reason,
            json.dumps(reason).encode(),
        )

    def _seed_mask(
        self, violations: frozenset, n_rows: int, mask_bytes: bytes
    ) -> None:
        """Give :meth:`_violation_mask` a set's mask made another way."""
        with self._lock:
            self._viol_masks.insert(0, [violations, n_rows, mask_bytes])
            del self._viol_masks[self.RESPONSE_CACHE_SIZE :]

    def _mesh_rows(self, table: _ViewTable, mesh) -> np.ndarray:
        """int32 flat mesh cell of each of ``table``'s rows, -1 for a node
        off the mesh: built once a (name table, mesh view) pair."""
        memo = self._row_cells
        if memo is None or memo[0] is not table or memo[1] is not mesh:
            memo = (table, mesh, mesh.cells_of(table.node_names))
            self._row_cells = memo
        return memo[2]

    def gang_member_filter(
        self,
        wirec,
        compiled: CompiledPolicy,
        view: DeviceView,
        policy_name: str,
        explained,
        parsed,
        verdict_of,
        span=trace.NULL_SPAN,
    ):
        """A gang member's NodeNames-wire Filter, natively: ``(body,
        failed count, {reason code: count}, the first failed entries)``,
        or None — before ``verdict_of`` is called — where the exact path
        must answer (a name the rows will not vouch for, a candidate the
        name table lacks: the exact overlay fails such a name, the
        encoder would pass it), or where ``verdict_of`` found no member.

        ``explained`` is :meth:`violation_reasons`' triple; ``verdict_of``
        takes two functions — one that materialises the telemetry-clean
        candidate names, one that gives them as a bool mask of a mesh
        view's cells (a scatter of the candidate rows' cells:
        :meth:`_mesh_rows`) — and returns the tracker's compact verdict
        (gang/group.py ``MemberVerdict``).  The verdict is three row sets
        over one mask: the slice's clean rows pass; a violating row keeps
        its telemetry reason; every other row fails with its holder's
        reason where a gang holds it and the slice is held
        (:meth:`gang_merged`'s table), else with the verdict's own reason
        (the encoder's default).  Never stored in the response caches: a
        member's verdict is its gang's, and its Filter has side effects."""
        violations, reasons, _indexes = explained
        table = self._table_for(view)
        n_rows = len(table.node_names)
        native = table.native(wirec)
        rows_raw = wirec.candidate_rows(parsed, native)
        if rows_raw is None:
            return None
        rows = np.frombuffer(rows_raw, dtype=np.int32)
        if rows.size == 0 or int(rows.min()) < 0:
            return None
        violating = np.frombuffer(
            self._violation_mask(violations, n_rows), dtype=np.uint8
        )

        def clean_names() -> List[str]:
            names = parsed.node_names_list()
            keep = np.flatnonzero(violating[rows] == 0).tolist()
            return [names[k] for k in keep]

        def clean_cells(mesh) -> np.ndarray:
            return mesh.mask_of(
                self._mesh_rows(table, mesh)[rows[violating[rows] == 0]]
            )

        verdict = verdict_of(clean_names, clean_cells)
        if verdict is None:
            return None
        mask = np.ones(n_rows, dtype=np.uint8)
        merged = None
        if verdict.holds_slice:
            merged = self.gang_merged(
                compiled, view, policy_name, violations, reasons,
                verdict.held, verdict.version, span,
            )
            reason_rows = merged.table
            # rows failing with a reason of their own: violating or held
            own_reason = np.frombuffer(
                self._violation_mask(merged.violations, n_rows),
                dtype=np.uint8,
            )
            index = table.node_index
            at = [
                row
                for row in (index.get(name) for name in verdict.allowed)
                if row is not None
            ]
            mask[at] = violating[at]
        else:
            own_reason = violating
            rule_map = self.violation_rule_map(compiled, view)
            reason_rows = (
                self.reason_table(
                    compiled, view, policy_name, violations, rule_map, n_rows
                )
                if rule_map is not None
                else [None] * n_rows
            )
        # a violating row keeps its telemetry reason, never the default
        bare = [row for row in violations if row < n_rows
                and reason_rows[row] is None]
        if bare:
            reason_rows = list(reason_rows)
            for row in bare:
                reason_rows[row] = b'"Node violates"'
        with span.stage("fencode", sampled=True):
            body, n_failed = wirec.filter_encode(
                parsed, native, mask, reason_rows,
                json.dumps(verdict.reason).encode(),
            )
        # the decision record's exact counts, from the same partition
        failed = np.zeros(n_rows, dtype=bool)
        failed[rows] = True
        failed &= mask.astype(bool)
        n_violating = int(np.count_nonzero(failed & violating.astype(bool)))
        n_own = int(np.count_nonzero(failed & own_reason.astype(bool)))
        counts = {
            code: count
            for code, count in (
                (decisions.CODE_RULE_VIOLATION, n_violating),
                (decisions.CODE_GANG_RESERVED, n_own - n_violating),
                (decisions.CODE_GANG_INFEASIBLE, int(n_failed) - n_own),
            )
            if count
        }
        # the exact record keeps a request's first failed entries
        head: Dict[str, str] = {}
        node_names = table.node_names
        for row in rows[np.flatnonzero(mask[rows])].tolist():
            if len(head) >= decisions.RETAIN_NODE_CAP:
                break
            name = node_names[row]
            if name not in head:
                if violating[row]:
                    head[name] = reasons.get(name, "Node violates")
                elif own_reason[row]:
                    head[name] = merged.held_reasons[merged.holder_at[row]]
                else:
                    head[name] = verdict.reason
        return body, int(n_failed), counts, head

    # -- filter response reuse -------------------------------------------------

    def filter_lookup(
        self,
        violations: frozenset,
        use_node_names: bool,
        parsed,
        gang_version: Optional[int] = None,
        universe=None,
    ) -> Optional[Tuple[bytes, int]]:
        """Cached (response bytes, failed count) for this exact candidate
        span under this exact violation set (and, in gang mode, this
        exact reservation version), or None.  With an interned
        ``universe`` the skeleton layer is probed first (identity
        compares, no span memcmp); a span-layer hit is promoted into it
        so the next warm request splices without touching the span."""
        with self._lock:
            if universe is not None:
                skeletons = self._filter_skeletons
                for idx, entry in enumerate(skeletons):
                    if (
                        entry[0] is violations
                        and entry[1] is universe
                        and entry[2] == gang_version
                    ):
                        if idx:
                            skeletons.insert(0, skeletons.pop(idx))
                        return entry[3], entry[4]
            responses = self._filter_responses
            for idx, entry in enumerate(responses):
                if (
                    entry[0] is violations
                    and entry[1] == use_node_names
                    and entry[5] == gang_version
                    and parsed.span_matches(use_node_names, entry[2])
                ):
                    if idx:
                        responses.insert(0, responses.pop(idx))
                    if universe is not None:
                        self._filter_skeletons.insert(
                            0,
                            [violations, universe, gang_version, entry[3],
                             entry[4]],
                        )
                        del self._filter_skeletons[self.RESPONSE_CACHE_SIZE :]
                    return entry[3], entry[4]
        return None

    def filter_store(
        self,
        violations: frozenset,
        use_node_names: bool,
        parsed,
        body: bytes,
        n_failed: int = 0,
        gang_version: Optional[int] = None,
        universe=None,
    ) -> None:
        if universe is not None:
            with self._lock:
                self._filter_skeletons.insert(
                    0, [violations, universe, gang_version, body, n_failed]
                )
                del self._filter_skeletons[self.RESPONSE_CACHE_SIZE :]
            return
        span = (
            parsed.node_names_span() if use_node_names else parsed.nodes_span()
        )
        if span is None:
            return
        with self._lock:
            self._filter_responses.insert(
                0,
                [violations, use_node_names, span, body, n_failed,
                 gang_version],
            )
            del self._filter_responses[self.RESPONSE_CACHE_SIZE :]

    # -- decision provenance ---------------------------------------------------

    def explain_prioritize(
        self, compiled: CompiledPolicy, view: DeviceView, k: int = 10
    ):
        """(score head, ranked, node_index) for one policy at the current
        state: the top-``k`` ``(node, ordinal score)`` pairs of the
        GLOBAL ranking (shared by reference across every decision record
        at this state — O(1) per request after the first) plus the raw
        ranking + interning table for exact chosen-rank lookup at bind
        time (utils/decisions.DecisionRecord.chosen_rank)."""
        table = self._table_for(view)
        ranked = self._ranking(
            view,
            compiled.scheduleonmetric_row,
            compiled.scheduleonmetric_op,
        )
        with self._lock:
            for idx, entry in enumerate(self._explain_heads):
                if entry[0] is ranked and entry[1] is table:
                    if idx:
                        self._explain_heads.insert(
                            0, self._explain_heads.pop(idx)
                        )
                    return entry[2], ranked, table.node_index
        names = table.node_names
        head = [
            (names[r], 10 - i)
            for i, r in enumerate(ranked[:k].tolist())
            if r < len(names)
        ]
        with self._lock:
            self._explain_heads.insert(0, [ranked, table, head])
            del self._explain_heads[self.RESPONSE_CACHE_SIZE :]
        return head, ranked, table.node_index
