"""Host-side tensor mirror of the TAS cache: interning tables + dense
device tensors, updated incrementally by cache mutation hooks.

SURVEY §7 step 2: alongside the exact host cache (tas/cache.py) the mirror
maintains interned node-ID <-> row-index tables, a dense
``[metric_capacity, node_capacity]`` int64-milli metric matrix (split hi/lo
for TPU, see ops/i64.py), per-row presence masks, and compiled per-policy
rule tensors.  Capacities grow by doubling so XLA recompiles only
per-bucket, never per-node — the recompile-avoidance half of the
"dynamic shapes vs XLA" hard part (SURVEY §7).

Fidelity contract: metric values are stored as exact milli-units when the
``Quantity`` converts exactly (utils/quantity.py ``milli_value_exact``);
any inexact value or unknown rule operator marks the affected metric/policy
host-only and the scheduler falls back to the exact host path for requests
touching it.  Device compares/sorts are then bit-identical to
``Quantity.CmpInt64`` / ``OrderedList`` (reference operator.go:13-42).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from platform_aware_scheduling_tpu.ops import i64, solveobs
from platform_aware_scheduling_tpu.ops.rules import OP_IDS, RuleSet
from platform_aware_scheduling_tpu.tas.metrics import MetricColumns
from platform_aware_scheduling_tpu.tas.policy.v1alpha1 import TASPolicy
from platform_aware_scheduling_tpu.utils import trace

MIN_NODE_CAPACITY = 64
MIN_METRIC_CAPACITY = 8
RULE_PAD = 8

#: forecast history staging keeps per-metric values inside int32 after a
#: per-row arithmetic right shift.  The budget is WINDOW-AWARE (see
#: history_value_bits): the Holt recursion's per-step sums (level + trend
#: + error) need ~2 bits of headroom over the value range, and the
#: residual accumulator sums up to W-1 absolute errors on top — so the
#: value range must shrink by another ceil(log2 W) bits or a full-window
#: noisy series near the bit ceiling wraps ``acc`` negative in int32
#: (garbage resid/band, identically on both execution paths)
HISTORY_VALUE_BITS = 30


def history_value_bits(window: int) -> int:
    """Max bits of staged value magnitude for ``window`` samples such
    that level/trend/error, the W-1-term residual accumulator, AND the
    band tail ``resid * (1 + h)`` at the clamped max horizon (~2W,
    forecast/engine._steps_now) all stay inside int32 (floored at 8
    bits — milli precision loss past that would be worse than the
    microscopic overflow risk)."""
    return max(8, HISTORY_VALUE_BITS - 2 - max(int(window) - 1, 0).bit_length())


class HistoryTensor(NamedTuple):
    """Dense device staging of the telemetry refresh history
    (tas/cache.AutoUpdatingCache history rings), aligned to one
    DeviceView's ``[metric row, node column]`` universe plus a trailing
    time axis: the last ``W`` refresh samples, oldest first, right-aligned
    at ``W - 1`` (shorter series lead with invalid slots).

    Values are milli-units arithmetic-right-shifted per metric row by
    ``shift[m]`` so every sample fits int32 (ops/forecast.py consumes the
    scaled domain; predictions shift back up host-side).  ``valid`` marks
    real samples — a node absent from a sample, a metric with fewer than
    W samples, and rows/columns outside the view all stay False."""

    values: np.ndarray  # int32 [M, N, W] — milli >> shift[m]
    valid: np.ndarray  # bool [M, N, W]
    shift: np.ndarray  # int64 [M] — per-metric de-scale amount
    last_stamp: np.ndarray  # float64 [M] — newest sample stamp (nan: none)


def build_history_tensor(
    view: "DeviceView",
    history: Dict[str, List[Tuple[float, Dict[str, int]]]],
    window: int,
) -> HistoryTensor:
    """Stage the cache's history rings into the dense ``[M, N, W]`` form
    (see :class:`HistoryTensor`) against ``view``'s interning.  Metrics or
    nodes unknown to the view are dropped — the forecast universe is
    exactly the snapshot the rankings run against."""
    metric_index = view.metric_index or {}
    node_index = view.node_index
    m_cap = view.values.hi.shape[0]
    n_cap = view.node_capacity
    w = int(window)
    values64 = np.zeros((m_cap, n_cap, w), dtype=np.int64)
    valid = np.zeros((m_cap, n_cap, w), dtype=bool)
    last_stamp = np.full(m_cap, np.nan, dtype=np.float64)
    # per-sample scatter via fancy indexing: the column lookup is the only
    # per-node Python left (the refresh thread restages every pass, so the
    # N x W inner work must stay vectorized at 10k-node scale)
    for name, ring in history.items():
        row = metric_index.get(name)
        if row is None or row >= m_cap:
            continue
        samples = ring[-w:]
        base = w - len(samples)
        for j, (stamp, sample) in enumerate(samples):
            if not sample:
                continue
            slot = base + j
            cols = np.fromiter(
                (node_index.get(node, -1) for node in sample),
                dtype=np.int64,
                count=len(sample),
            )
            vals = np.fromiter(
                sample.values(), dtype=np.int64, count=len(sample)
            )
            keep = (cols >= 0) & (cols < n_cap)
            values64[row, cols[keep], slot] = vals[keep]
            valid[row, cols[keep], slot] = True
        if samples:
            last_stamp[row] = samples[-1][0]
    # per-metric de-scale so the largest magnitude fits the window-aware
    # bit budget (residual accumulator headroom, see history_value_bits)
    bits = history_value_bits(w)
    masked = np.where(valid, np.abs(values64), 0)
    max_abs = masked.max(axis=(1, 2))
    shift = np.zeros(m_cap, dtype=np.int64)
    over = max_abs >> np.int64(bits)
    for row in np.nonzero(over)[0]:
        extra = int(max_abs[row]).bit_length() - bits
        shift[row] = extra
    scaled = (values64 >> shift[:, None, None]).astype(np.int32)
    return HistoryTensor(
        values=scaled, valid=valid, shift=shift, last_stamp=last_stamp
    )


def _next_capacity(current: int, needed: int) -> int:
    while current < needed:
        current *= 2
    return current


@dataclass
class CompiledRuleSet:
    """Host (numpy) staging of one strategy's rule list, padded to RULE_PAD
    multiples for stable jit shapes."""

    metric_rows: np.ndarray  # int32 [R_pad]
    op_ids: np.ndarray  # int32 [R_pad]
    targets: np.ndarray  # int64 [R_pad] milli-units
    active: np.ndarray  # bool [R_pad]
    host_only: bool = False  # unknown operator somewhere -> host fallback
    metric_names: Tuple[str, ...] = ()  # for host-only metric checks

    def to_device(self) -> RuleSet:
        t_hi, t_lo = i64.split_int64_np(self.targets)
        return RuleSet(
            metric_row=jnp.asarray(self.metric_rows),
            op_id=jnp.asarray(self.op_ids),
            target=i64.I64(hi=jnp.asarray(t_hi), lo=jnp.asarray(t_lo)),
            active=jnp.asarray(self.active),
        )


@dataclass
class CompiledPolicy:
    """Device-ready view of one TASPolicy's strategies."""

    dontschedule: Optional[CompiledRuleSet] = None
    deschedule: Optional[CompiledRuleSet] = None
    # scheduleonmetric uses only Rules[0] (telemetryscheduler.go:115-124).
    # Unknown operators compile to op_id -1 == index-order ranking, which is
    # within the reference's envelope (Go map order is randomized there), so
    # scheduleonmetric never forces a host fallback.
    scheduleonmetric_row: int = -1
    scheduleonmetric_op: int = -1
    scheduleonmetric_metric: str = ""
    _device_cache: Dict[str, RuleSet] = field(default_factory=dict)

    def device_rules(self, strategy: str) -> Optional[RuleSet]:
        compiled = getattr(self, strategy, None)
        if compiled is None or compiled.host_only:
            return None
        if strategy not in self._device_cache:
            self._device_cache[strategy] = compiled.to_device()
        return self._device_cache[strategy]


class DeviceView:
    """An immutable snapshot handed to kernels: the split metric matrix, the
    presence mask, and the interning tables it was built against.

    Besides the global ``version``, the view carries fine-grained change
    counters so per-version caches invalidate only what actually changed
    under metric churn (every sync period rewrites every metric,
    autoupdating.go:37-59):

      * ``row_versions[r]`` bumps only when metric row ``r``'s content
        changes — a ranking for (row, op) stays valid across other rows'
        updates;
      * ``intern_version`` bumps only when the node interning (and thus
        the name list / response fragments) changes — the encode table
        survives pure value churn.
    """

    def __init__(
        self,
        values: i64.I64,
        present: jnp.ndarray,
        node_names: List[str],
        node_index: Dict[str, int],
        version: int,
        row_versions: Tuple[int, ...] = (),
        intern_version: int = 0,
        values_milli: Optional[np.ndarray] = None,
        metric_index: Optional[Dict[str, int]] = None,
        partition_versions: Optional[Dict[int, int]] = None,
    ):
        self.values = values
        self.present = present
        self.node_names = node_names
        self.node_index = node_index
        self.version = version
        self.row_versions = row_versions
        self.intern_version = intern_version
        # host-readable copy of the milli-unit matrix, for decision
        # provenance: decoding a device rule-index vector into "metric
        # cpu=93 > threshold 80" needs the observed values WITHOUT a
        # device readback (utils/decisions.py).  None in synthetic views
        # built without it — reasons then omit the observed value.
        self.values_milli = values_milli
        # metric name -> row, so row-aligned overlays (the forecast
        # history tensor, ops/forecast.py) can be built against this
        # exact snapshot.  None in synthetic views built without it.
        self.metric_index = metric_index
        # partition id -> change counter, populated only in partition-
        # scoped mode (shard/plane.py): a digest built for partition p is
        # stale iff partition_versions[p] moved, independent of churn in
        # the other partitions this replica happens to own.  None when
        # the mirror is unscoped (full-world mode — the global ``version``
        # is the only clock).
        self.partition_versions = partition_versions

    def partition_version(self, partition: int) -> int:
        if self.partition_versions is None:
            return self.version
        return self.partition_versions.get(int(partition), 0)

    def row_version(self, row: int) -> int:
        return self.row_versions[row] if row < len(self.row_versions) else 0

    @property
    def node_capacity(self) -> int:
        return self.present.shape[1]

    def candidate_mask(self, names: Sequence[str]) -> Tuple[jnp.ndarray, List[str]]:
        """Bool [N_cap] mask of interned candidates + the names the mirror
        has never seen (they carry no metrics, so the caller handles them
        with metric-absent semantics)."""
        mask = np.zeros(self.node_capacity, dtype=bool)
        unknown: List[str] = []
        for name in names:
            row = self.node_index.get(name)
            if row is None:
                unknown.append(name)
            else:
                mask[row] = True
        return jnp.asarray(mask), unknown


class TensorStateMirror:
    """Subscribes to AutoUpdatingCache mutation hooks and keeps the device
    tensors in sync.  Thread-safe; reads publish copy-on-write snapshots."""

    def __init__(
        self,
        node_capacity: int = MIN_NODE_CAPACITY,
        metric_capacity: int = MIN_METRIC_CAPACITY,
    ):
        self._lock = threading.Lock()
        self._node_index: Dict[str, int] = {}
        self._node_names: List[str] = []
        self._metric_index: Dict[str, int] = {}
        self._free_metric_rows: List[int] = []
        self._values = np.zeros((metric_capacity, node_capacity), dtype=np.int64)
        self._present = np.zeros((metric_capacity, node_capacity), dtype=bool)
        # fine-grained change counters (see DeviceView doc)
        self._row_versions: Dict[int, int] = {}
        self._intern_version = 0
        self._host_only_metrics: Dict[str, bool] = {}
        # metric -> (names list of its last columnar round, their columns)
        self._round_cols: Dict[str, Tuple[List[str], np.ndarray]] = {}
        self._policies: Dict[Tuple[str, str], CompiledPolicy] = {}
        # sources kept so policies can be recompiled when a freed metric row
        # is reused (their rule tensors hold row indices)
        self._policy_sources: Dict[Tuple[str, str], TASPolicy] = {}
        # tensor version: bumped only when the device snapshot's content
        # (values/present/interning) changes — policy churn must not force a
        # metric-matrix re-upload
        self._version = 0
        self._view: Optional[DeviceView] = None
        # post-publish callbacks, fired OUTSIDE the lock after a mutation
        # that changed the device snapshot or the compiled-policy set; the
        # extender's fastpath warmer subscribes here so the device ranking
        # pass runs in the state-refresh thread, never on a request
        # (reference refresh loop: cmd/main.go:76-78)
        self.on_state_change: List = []
        # per-metric churn since the last drain: metric name ->
        # [changed columns, saw-delete flag].  Written only while a solve
        # observatory is enabled (ops/solveobs.ACTIVE), under the mirror
        # lock the writer already holds — no extra locking on the write
        # path; drained per refresh pass by the observatory's
        # cache.on_refresh_pass hook
        self._churn_pending: Dict[str, List[int]] = {}
        # partition-scoped mode (shard/plane.py): (PartitionMap, callable
        # returning the owned-partition set).  When set, metric writes
        # skip non-owned nodes BEFORE interning — the ~1/P memory cut —
        # and per-partition change counters ride the version bumps.  None
        # (the default) is full-world mode: zero cost, zero behavior
        # change.
        self._partition_scope = None
        self._partition_versions: Dict[int, int] = {}

    # -- wiring ---------------------------------------------------------------

    def attach(self, cache) -> None:
        """Subscribe to a tas.cache.AutoUpdatingCache's mutation hooks."""
        cache.on_metric_write.append(self.on_metric_write)
        cache.on_metric_delete.append(self.on_metric_delete)
        cache.on_policy_write.append(self.on_policy_write)
        cache.on_policy_delete.append(self.on_policy_delete)

    def set_partition_scope(self, pmap, owned) -> None:
        """Enter partition-scoped mode: metric writes keep only nodes in
        partitions ``owned()`` currently returns (re-read per write, so
        ownership handoff takes effect on the next refresh pass without
        re-wiring).  Already-interned non-owned nodes keep their columns
        but stop receiving values — their presence decays to False on the
        next write of each metric, which is exactly the host semantics of
        a node leaving the metric map."""
        with self._lock:
            self._partition_scope = (pmap, owned)

    # -- interning ------------------------------------------------------------

    def _intern_node(self, name: str) -> int:
        row = self._node_index.get(name)
        if row is not None:
            return row
        row = len(self._node_names)
        if row >= self._values.shape[1]:
            new_cap = _next_capacity(self._values.shape[1], row + 1)
            self._values = np.pad(
                self._values, ((0, 0), (0, new_cap - self._values.shape[1]))
            )
            self._present = np.pad(
                self._present, ((0, 0), (0, new_cap - self._present.shape[1]))
            )
        self._node_index[name] = row
        self._node_names.append(name)
        self._intern_version += 1
        return row

    def _intern_metric(self, name: str) -> int:
        row = self._metric_index.get(name)
        if row is not None:
            return row
        if self._free_metric_rows:
            row = self._free_metric_rows.pop()
        else:
            row = len(self._metric_index)
            if row >= self._values.shape[0]:
                new_cap = _next_capacity(self._values.shape[0], row + 1)
                self._values = np.pad(
                    self._values, ((0, new_cap - self._values.shape[0]), (0, 0))
                )
                self._present = np.pad(
                    self._present, ((0, new_cap - self._present.shape[0]), (0, 0))
                )
        self._metric_index[name] = row
        self._values[row, :] = 0
        self._present[row, :] = False
        self._row_versions[row] = self._row_versions.get(row, 0) + 1
        return row

    def _round_columns(self, metric_name: str, names: List[str]) -> np.ndarray:
        """The node columns of a fetched round's names.  A cluster's nodes
        seldom change between two passes, so the array is kept with the
        names list it was made for and one list comparison finds it again
        (a node's column never moves); any other list is interned as the
        items are."""
        seen = self._round_cols.get(metric_name)
        if seen is not None and seen[0] == names:
            return seen[1]
        cols = np.fromiter(
            map(self._intern_node, names), dtype=np.intp, count=len(names)
        )
        self._round_cols[metric_name] = (names, cols)
        return cols

    # -- cache hooks ----------------------------------------------------------

    def _notify(self) -> None:
        """Run the post-publish callbacks; never let a subscriber break the
        writer (the cache refresh loop must keep ticking)."""
        for callback in list(self.on_state_change):
            try:
                callback()
            except Exception as exc:  # noqa: BLE001 — subscriber errors are theirs
                from platform_aware_scheduling_tpu.utils import klog

                klog.error("state-change subscriber failed: %r", exc)

    def on_metric_write(self, metric_name: str, info) -> None:
        """info: NodeMetricsInfo (node -> NodeMetric) or None (registration
        only, autoupdating.go:105-122)."""
        # the mirror's part of a refresh pass's publish, apart from the
        # warm that _notify sets off (tas/cache.py sums the seconds)
        with trace.stage("rf.publish"):
            changed = self._metric_write_locked(metric_name, info)
        if changed:
            self._notify()

    def _metric_write_locked(self, metric_name: str, info) -> bool:
        with self._lock:
            shape_before = self._values.shape
            row = self._intern_metric(metric_name)
            if info is None:
                if self._values.shape != shape_before:
                    self._version += 1
                    return True
                return False
            # stage the new row, then bump the version only on real change:
            # the periodic refresh re-writes every metric each sync period
            # (autoupdating.go:37-59) and steady-state values must not
            # invalidate snapshots/plans or force device re-uploads
            scope = self._partition_scope
            owned_parts = None
            if scope is not None:
                pmap, owned = scope
                try:
                    owned_parts = owned()
                except Exception:
                    owned_parts = frozenset()
            changed_partitions: Dict[int, bool] = {}
            # one algorithm, two input forms: a fetched round arrives as
            # columns and is scattered; a plain dict, and any round under
            # a partition scope (which keeps nodes one by one), is staged
            # item by item
            columnar = isinstance(info, MetricColumns) and owned_parts is None
            trace.COUNTERS.inc(
                "pas_refresh_ingest_total",
                labels={"path": "columnar" if columnar else "items"},
            )
            if columnar:
                cols = self._round_columns(metric_name, info.names)
                milli = info.milli
                host_only = not info.exact
            else:
                host_only = False
                staged: Dict[int, int] = {}
                for node_name, metric in info.items():
                    if owned_parts is not None:
                        partition = pmap.partition_of(node_name)
                        if partition not in owned_parts:
                            continue  # not ours: never interned, never stored
                    col = self._intern_node(node_name)
                    value, exact = metric.value.milli_value_exact()
                    if not exact:
                        host_only = True
                    staged[col] = value
                cols = np.fromiter(staged, dtype=np.intp, count=len(staged))
                milli = np.fromiter(
                    staged.values(), dtype=np.int64, count=len(staged)
                )
            grew = self._values.shape != shape_before
            new_values = np.zeros(self._values.shape[1], dtype=np.int64)
            new_present = np.zeros(self._values.shape[1], dtype=bool)
            new_values[cols] = milli
            new_present[cols] = True
            changed = (
                grew
                or not np.array_equal(self._present[row], new_present)
                or not np.array_equal(self._values[row], new_values)
            )
            if solveobs.ACTIVE is not None:
                # churn telemetry: how many node columns this write
                # actually moved.  A freshly interned row is all-zero /
                # all-absent, so a metric's FIRST pass naturally counts
                # every present column (full churn — to a cold solver the
                # whole row is news); a byte-identical refresh counts 0.
                moved = int(
                    np.count_nonzero(
                        (self._values[row] != new_values)
                        | (self._present[row] != new_present)
                    )
                )
                entry = self._churn_pending.setdefault(metric_name, [0, 0])
                entry[0] += moved
            self._host_only_metrics[metric_name] = host_only
            if changed:
                if owned_parts is not None:
                    # attribute the change to the partitions whose columns
                    # actually moved, so a digest for a quiet partition
                    # stays valid through churn in a noisy one
                    diff = np.nonzero(
                        (self._values[row] != new_values)
                        | (self._present[row] != new_present)
                    )[0]
                    for col in diff:
                        if col < len(self._node_names):
                            changed_partitions[
                                pmap.partition_of(self._node_names[col])
                            ] = True
                    for partition in changed_partitions:
                        self._partition_versions[partition] = (
                            self._partition_versions.get(partition, 0) + 1
                        )
                self._values[row] = new_values
                self._present[row] = new_present
                self._version += 1
                self._row_versions[row] = self._row_versions.get(row, 0) + 1
            return changed

    def on_metric_delete(self, metric_name: str) -> None:
        deleted = False
        with self._lock:
            row = self._metric_index.pop(metric_name, None)
            self._host_only_metrics.pop(metric_name, None)
            self._round_cols.pop(metric_name, None)
            if row is not None:
                deleted = True
                if solveobs.ACTIVE is not None:
                    # a delete churns every column it tears down
                    entry = self._churn_pending.setdefault(
                        metric_name, [0, 0]
                    )
                    entry[0] += int(np.count_nonzero(self._present[row]))
                    entry[1] = 1
                self._present[row, :] = False
                self._free_metric_rows.append(row)
                self._version += 1
                self._row_versions[row] = self._row_versions.get(row, 0) + 1
                # compiled rule tensors may reference the freed row; if it is
                # later reused for another metric they would silently read the
                # wrong values — recompile every policy against live rows
                for key, source in self._policy_sources.items():
                    self._policies[key] = self._compile_policy(source)
        if deleted:
            self._notify()

    def on_policy_write(self, namespace: str, name: str, policy: TASPolicy) -> None:
        with self._lock:
            shape_before = self._values.shape
            self._policy_sources[(namespace, name)] = policy
            self._policies[(namespace, name)] = self._compile_policy(policy)
            if self._values.shape != shape_before:  # rule interned a new metric
                self._version += 1
        # fire even without a version bump: a new policy can introduce new
        # (metric row, op) pairs that need warming at the current version
        self._notify()

    def on_policy_delete(self, namespace: str, name: str) -> None:
        with self._lock:
            self._policies.pop((namespace, name), None)
            self._policy_sources.pop((namespace, name), None)

    def drain_churn(self) -> Tuple[Dict[str, Tuple[int, bool]], int]:
        """Take (and reset) the per-metric churn accumulated since the
        last drain, plus the current world size.  Called once per refresh
        pass by the solve observatory's ``cache.on_refresh_pass`` hook."""
        with self._lock:
            pending = self._churn_pending
            self._churn_pending = {}
            world = len(self._node_names)
        return (
            {
                metric: (changed, bool(deleted))
                for metric, (changed, deleted) in pending.items()
            },
            world,
        )

    # -- policy compilation ---------------------------------------------------

    def _compile_rules(self, rules) -> CompiledRuleSet:
        count = len(rules)
        pad = max(RULE_PAD, -(-count // RULE_PAD) * RULE_PAD)
        metric_rows = np.zeros(pad, dtype=np.int32)
        op_ids = np.zeros(pad, dtype=np.int32)
        targets = np.zeros(pad, dtype=np.int64)
        active = np.zeros(pad, dtype=bool)
        host_only = False
        for idx, rule in enumerate(rules):
            metric_rows[idx] = self._intern_metric(rule.metricname)
            op = OP_IDS.get(rule.operator)
            if op is None:
                host_only = True
                op = -1
            op_ids[idx] = op
            if abs(int(rule.target)) > (2**63 - 1) // 1000:
                host_only = True  # milli-domain target would overflow int64
            else:
                targets[idx] = np.int64(rule.target) * np.int64(1000)
            active[idx] = True
        return CompiledRuleSet(
            metric_rows=metric_rows,
            op_ids=op_ids,
            targets=targets,
            active=active,
            host_only=host_only,
            metric_names=tuple(rule.metricname for rule in rules),
        )

    def _compile_policy(self, policy: TASPolicy) -> CompiledPolicy:
        compiled = CompiledPolicy()
        strategies = policy.strategies
        if "dontschedule" in strategies:
            compiled.dontschedule = self._compile_rules(
                strategies["dontschedule"].rules
            )
        if "deschedule" in strategies:
            compiled.deschedule = self._compile_rules(strategies["deschedule"].rules)
        som = strategies.get("scheduleonmetric")
        if som is not None and som.rules and som.rules[0].metricname:
            rule = som.rules[0]
            compiled.scheduleonmetric_row = self._intern_metric(rule.metricname)
            op = OP_IDS.get(rule.operator)
            compiled.scheduleonmetric_op = -1 if op is None else op
            compiled.scheduleonmetric_metric = rule.metricname
        return compiled

    # -- reads ----------------------------------------------------------------

    def policy(self, namespace: str, name: str) -> Optional[CompiledPolicy]:
        with self._lock:
            return self._policies.get((namespace, name))

    def metric_host_only(self, metric_name: str) -> bool:
        with self._lock:
            return self._host_only_metrics.get(metric_name, False)

    @property
    def version(self) -> int:
        with self._lock:
            return self._version

    def device_view(self) -> DeviceView:
        """Publish (and memoize per version) the device snapshot.  The numpy
        staging arrays are copied at snapshot time so in-flight kernels never
        see a torn update."""
        with self._lock:
            return self._view_locked()

    def policy_with_view_by_name(
        self, name: str
    ) -> Tuple[Optional[CompiledPolicy], Optional[DeviceView]]:
        """Lookup by bare policy name — strategies registered with the
        enforcer only carry the name, not the namespace (the reference's
        enforcement loop has the same ambiguity, deschedule/enforce.go)."""
        with self._lock:
            for (_ns, pname), compiled in self._policies.items():
                if pname == name:
                    return compiled, self._view_locked()
        return None, None

    def policies_with_view(
        self, keys: Sequence[Tuple[str, str]]
    ) -> Tuple[Dict[Tuple[str, str], Optional[CompiledPolicy]], DeviceView, frozenset]:
        """Atomic ({(ns, name): policy}, view, host-only metric names) for a
        whole batch under ONE lock acquisition — a per-policy loop could
        straddle a metric delete + row reuse, leaving earlier policies'
        compiled row indices pointing at a different metric in the view the
        solve actually uses."""
        with self._lock:
            policies = {key: self._policies.get(key) for key in keys}
            host_only = frozenset(
                name for name, flag in self._host_only_metrics.items() if flag
            )
            return policies, self._view_locked(), host_only

    def policies_snapshot(
        self,
    ) -> Tuple[Dict[Tuple[str, str], CompiledPolicy], DeviceView, Dict[str, bool]]:
        """Atomic ({(ns, name): policy}, view, host-only metric map) under
        one lock acquisition — for the fastpath warmer, which must see a
        policy set consistent with the view it precomputes against.  Keys
        ride along so the warmer can pre-render the per-policy violation
        REASONS (the strings carry the policy name)."""
        with self._lock:
            return (
                dict(self._policies),
                self._view_locked(),
                dict(self._host_only_metrics),
            )

    def policy_with_view(
        self, namespace: str, name: str
    ) -> Tuple[Optional[CompiledPolicy], DeviceView]:
        """Atomic (compiled policy, device snapshot) pair under ONE lock
        acquisition — the policy's rule tensors hold metric ROW indices, so
        reading them and the matrix in two steps could straddle a metric-row
        reuse and evaluate the wrong metric."""
        with self._lock:
            return self._policies.get((namespace, name)), self._view_locked()

    def _view_locked(self) -> DeviceView:
        if self._view is not None and self._view.version == self._version:
            return self._view
        obs = solveobs.ACTIVE
        timer = obs.begin("view_build") if obs is not None else None
        hi, lo = i64.split_int64_np(self._values)
        present_host = self._present.copy()
        values_milli = self._values.copy()
        if timer is not None:
            timer.mark("snapshot")
        values = i64.I64(hi=jnp.asarray(hi), lo=jnp.asarray(lo))
        present = jnp.asarray(present_host)
        if timer is not None:
            # jnp.asarray may return before the upload lands; block so
            # the transfer stage carries its real cost, not dispatch time
            try:
                present.block_until_ready()
            except Exception:
                pass
            timer.mark("transfer")
        rows = self._values.shape[0]
        self._view = DeviceView(
            values=values,
            present=present,
            node_names=list(self._node_names),
            node_index=dict(self._node_index),
            version=self._version,
            row_versions=tuple(
                self._row_versions.get(r, 0) for r in range(rows)
            ),
            intern_version=self._intern_version,
            values_milli=values_milli,
            metric_index=dict(self._metric_index),
            partition_versions=(
                dict(self._partition_versions)
                if self._partition_scope is not None
                else None
            ),
        )
        if timer is not None:
            timer.mark("encode")
            timer.done(rows=rows, nodes=len(self._node_names))
        return self._view
