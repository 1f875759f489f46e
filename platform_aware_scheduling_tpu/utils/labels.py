"""Shared cluster-object label vocabulary.

One home for every ``pas-*`` label the subsystems read off pods and
nodes, so ``gang/``, ``rebalance/``, and the decision records all import
one definition (hoisted out of ``rebalance/actuator.py``, which keeps a
back-compat alias).  This module must stay importable without jax.

  * ``GROUP_LABEL`` — the workload-group key: the rebalance actuator's
    min-available accounting unit AND (together with ``GANG_SIZE_LABEL``)
    the gang identity for all-or-nothing co-scheduling (docs/gang.md);
  * ``GANG_SIZE_LABEL`` — the gang's total member count ``k``; a pod
    carrying both group and size labels is a gang member;
  * ``GANG_TOPOLOGY_LABEL`` — the required ICI sub-mesh shape, e.g.
    ``4x4`` (a contiguous 4-row by 4-column slice); absent means any
    ``k`` mesh nodes (no adjacency constraint);
  * ``TPU_COORD_LABEL`` — a node's mesh coordinate ``"row,col"``
    (synthesized by testing/fake_kube for hermetic meshes);
  * ``TPU_DOMAIN_LABEL`` — the ICI domain (a TPU pod) the coordinate
    lies in: no ICI link joins two domains, so a slice never spans one
    and coordinates repeat from domain to domain.  Nodes without it
    form one domain together;
  * ``PRIORITY_LABEL`` — the pod's admission priority class name
    (admission/plane.py; unlabeled or unknown-class pods take the
    plane's default class).
"""

from __future__ import annotations

import math
from typing import Dict, Optional

GROUP_LABEL = "pas-workload-group"
GANG_SIZE_LABEL = "pas-gang-size"
GANG_TOPOLOGY_LABEL = "pas-gang-topology"
TPU_COORD_LABEL = "pas-tpu-coord"
TPU_DOMAIN_LABEL = "pas-tpu-domain"
PRIORITY_LABEL = "pas-priority"


def gang_reserved_reason(gang_id: str) -> str:
    """The Filter FailedNodes reason for a node held by another gang's
    reservation.  ONE format shared by the tracker's overlay
    (gang/group.py) and the Filter response cache's merged verdict
    (tas/fastpath.gang_merged) — the cached and exact paths must stay
    byte-identical, so the string may only ever change here."""
    return f"gang: node reserved by gang {gang_id}"


def gang_id_for(namespace: str, pod_labels: Dict[str, str]) -> Optional[str]:
    """The gang identity of a pod, or None when the pod is not a gang
    member.  A gang needs BOTH the group label (identity) and a
    WELL-FORMED size label (+ consistent topology when given) — a bare
    ``pas-workload-group`` stays what it always was: the rebalance
    min-available unit.  The validation here is the single classifier
    (GangSpec.from_pod gates on it), so a pod with a malformed gang
    label is non-gang EVERYWHERE — scheduler and rebalance actuator can
    never disagree about membership."""
    group = pod_labels.get(GROUP_LABEL)
    if not group:
        return None
    raw_size = pod_labels.get(GANG_SIZE_LABEL)
    if raw_size is None:
        return None
    try:
        size = int(raw_size)
    except ValueError:
        return None
    if size < 1:
        return None
    raw_topo = pod_labels.get(GANG_TOPOLOGY_LABEL)
    if raw_topo:
        topo = parse_topology(raw_topo)
        if topo is None or topo[0] * topo[1] != size:
            return None
    return f"{namespace}/{group}"


def priority_class_for(pod_labels: Dict[str, str], classes) -> Optional[str]:
    """The pod's declared admission priority class, or None when the pod
    is unlabeled or names a class outside ``classes`` (the configured
    ladder).  This is the single classifier — the admission plane, the
    preemption planner's victim census, and the decision records all go
    through it, so a mislabeled pod degrades to the default class
    EVERYWHERE instead of crashing Filter or forking semantics."""
    raw = pod_labels.get(PRIORITY_LABEL)
    if not raw:
        return None
    if raw not in classes:
        return None
    return raw


#: sanity ceiling per mesh dimension: the dense [rows, cols] grids the
#: topology kernel allocates are sized by the LARGEST labeled
#: coordinate, so one mislabeled node (``"1000000,1000000"``) must not
#: turn every gang Filter into a terabyte allocation.  1024x1024 = 1M
#: cells comfortably covers real TPU pod meshes.
MAX_MESH_DIM = 1024


def mesh_dim_limit(domains: int) -> int:
    """The bound on a coordinate when ``domains`` grids (the padded
    domain count) share one shape: the cells of all of them together stay
    within one ``MAX_MESH_DIM`` x ``MAX_MESH_DIM`` mesh, so one
    mislabeled node cannot size every domain's grid — 1024 for one
    domain, 64 for 256."""
    return math.isqrt(MAX_MESH_DIM * MAX_MESH_DIM // max(domains, 1))


def format_coord(row: int, col: int) -> str:
    """The ``pas-tpu-coord`` label value for one mesh cell — the single
    writer-side formatter (parse_coord is the reader); every mesh
    synthesizer goes through it so the wire format cannot fork."""
    return f"{row},{col}"


def parse_coord(node_labels: Dict[str, str]) -> Optional[tuple]:
    """``pas-tpu-coord: "2,3"`` -> (2, 3); None when absent/malformed or
    outside the ``MAX_MESH_DIM`` sanity bound (a coordinate-less node
    simply sits outside the mesh)."""
    raw = node_labels.get(TPU_COORD_LABEL)
    if not raw:
        return None
    row, sep, col = raw.partition(",")
    if not sep:
        return None
    try:
        i, j = int(row), int(col)
    except ValueError:
        return None
    if i < 0 or j < 0 or i >= MAX_MESH_DIM or j >= MAX_MESH_DIM:
        return None
    return i, j


def parse_topology(raw: str) -> Optional[tuple]:
    """``"4x4"`` -> (4, 4); None when malformed."""
    a, sep, b = raw.partition("x")
    if not sep:
        return None
    try:
        rows, cols = int(a), int(b)
    except ValueError:
        return None
    if rows <= 0 or cols <= 0:
        return None
    return rows, cols
