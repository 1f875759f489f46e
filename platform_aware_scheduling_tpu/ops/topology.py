"""Topology-feasibility kernel: contiguous sub-mesh placement on device.

A multi-host TPU training job needs ``k`` nodes forming a valid ICI
topology — a contiguous ``h x w`` sub-mesh of the cluster's ``M x N``
node mesh — placed atomically or not at all (docs/gang.md).  The
question a gang reservation must answer is: *given the free mask over
the mesh, where can an ``h x w`` slice go, and which anchor strands the
fewest free neighbors?*

One fused pass evaluates EVERY candidate anchor position at once, the
same all-candidates-in-one-program shape as ``ops/binpack.py`` (which
scans all cards of all nodes per request) and the masked-selection
idiom of its first-fit (invalid lanes pushed past a big-order sentinel
rather than branched around):

  * 2-D integral images (two ``cumsum``s) turn "is the whole ``h x w``
    window free" into four gathers per anchor — ``anchor_ok`` for all
    anchors in O(M*N);
  * the same trick over a one-cell halo counts the free cells a placed
    window would leave stranded on its perimeter — ``anchor_score``
    (lower = tighter packing, fewer fragments), ``INFEASIBLE``
    (a binpack-style big-order mask value) where the window does not
    fit;
  * a windowed min (``lax.reduce_window``) folds anchor scores onto the
    nodes they would cover — ``node_score`` ranks every node by the
    quality of the best slice it could complete, which is exactly what
    Prioritize needs, and ``node_score < INFEASIBLE`` is the per-node
    feasibility verdict Filter needs.

Counts are bounded by ``M * N`` mesh cells, so exact int32 suffices —
unlike binpack's i64 capacities there is nothing to overflow, and the
host mirror (:func:`topology_feasibility_host`, numpy, used for
device<->host parity exactly like the dontschedule/GAS dual paths) is
byte-comparable by construction.

A cluster of many ICI domains (TPU pods: no ICI link joins two, so a
slice lies inside one) is a ``[D, M, N]`` free mask, one grid a domain
(``MeshView``).  The gang reservation asks only for the best anchor, so
:func:`best_domain_anchor` evaluates every anchor of every domain in ONE
program per orientation (``_domains_best_anchor``: the same arithmetic
over the leading domain axis, an argmin over all of it) and reads back
four integers: (score, domain, row, col).  ``D`` is padded to a power of
two with empty domains, so a domain that appears or disappears does not
compile a new program.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from platform_aware_scheduling_tpu.utils import labels as shared_labels
from platform_aware_scheduling_tpu.utils import trace

#: big-order sentinel for "no feasible window here" (the masking idiom of
#: ops/binpack.py's first-fit: invalid lanes sort past every real score)
INFEASIBLE = 2**30


def _count_fallback() -> None:
    """A device-path failure served by the host mirror: counted, so a
    broken device path never runs unseen."""
    trace.COUNTERS.inc(
        "pas_device_path_errors_total", labels={"site": "gang_topology"}
    )


class TopologyFeasibility(NamedTuple):
    """Host-side (numpy) result — identical from either execution path."""

    anchor_ok: np.ndarray  # bool [M, N]: h x w window at (i, j) is free
    anchor_score: np.ndarray  # int32 [M, N]: stranded-perimeter count; INFEASIBLE when not ok
    node_ok: np.ndarray  # bool [M, N]: node is coverable by >= 1 feasible window
    node_score: np.ndarray  # int32 [M, N]: best (lowest) covering-window score


def _window_sums(integral, h: int, w: int):
    """All ``h x w`` window sums from a padded integral image
    (``integral[..., a, b] = sum grid[..., :a, :b]``), over any leading
    axes."""
    return (
        integral[..., h:, w:]
        - integral[..., :-h, w:]
        - integral[..., h:, :-w]
        + integral[..., :-h, :-w]
    )


def _anchor_grid(xp, fi, h: int, w: int):
    """(ok, score) of every ``h x w`` anchor of an int32 ``[..., M, N]``
    free grid (0/1), each ``[..., M-h+1, N-w+1]``: ``ok`` where the whole
    window is free, ``score`` the free cells in the one-cell halo ring
    around it that placing it would leave behind (fewest = best anchor),
    ``INFEASIBLE`` where not ok.  ``xp`` is ``jnp`` (the kernels) or
    ``np`` (the host mirrors): one arithmetic, so the two are
    byte-comparable by construction."""
    lead = [(0, 0)] * (fi.ndim - 2)

    def integral(grid):
        summed = xp.cumsum(
            xp.cumsum(grid, axis=-2, dtype=xp.int32), axis=-1, dtype=xp.int32
        )
        return xp.pad(summed, lead + [(1, 0), (1, 0)])

    window = _window_sums(integral(fi), h, w)
    ok = window == h * w
    halo = _window_sums(
        integral(xp.pad(fi, lead + [(1, 1), (1, 1)])), h + 2, w + 2
    )  # the same anchor grid
    return ok, xp.where(ok, halo - window, xp.int32(INFEASIBLE))


@partial(jax.jit, static_argnames=("h", "w"))
def _topology_kernel(free: jnp.ndarray, h: int, w: int):
    """(anchor_ok, anchor_score, node_score) over a bool [M, N] free mask
    for an ``h x w`` window — one fused pass for every anchor."""
    m, n = free.shape
    ok_valid, score_valid = _anchor_grid(jnp, free.astype(jnp.int32), h, w)
    anchor_ok = jnp.zeros((m, n), bool)
    anchor_score = jnp.full((m, n), INFEASIBLE, jnp.int32)
    anchor_ok = anchor_ok.at[: m - h + 1, : n - w + 1].set(ok_valid)
    anchor_score = anchor_score.at[: m - h + 1, : n - w + 1].set(score_valid)
    # fold anchor scores onto covered nodes: node (x, y) is covered by
    # anchors (x-h+1..x, y-w+1..y), a windowed min with top/left padding
    node_score = jax.lax.reduce_window(
        anchor_score,
        jnp.int32(INFEASIBLE),
        jax.lax.min,
        window_dimensions=(h, w),
        window_strides=(1, 1),
        padding=((h - 1, 0), (w - 1, 0)),
    )
    return anchor_ok, anchor_score, node_score


def topology_feasibility_device(
    free: np.ndarray, h: int, w: int
) -> TopologyFeasibility:
    """Device path: the jitted kernel over the free mask."""
    m, n = free.shape
    if h > m or w > n:  # static shape guard: the window cannot fit at all
        return _all_infeasible(m, n)
    anchor_ok, anchor_score, node_score = _topology_kernel(
        jnp.asarray(free, dtype=bool), int(h), int(w)
    )
    node_score_np = np.asarray(node_score)
    return TopologyFeasibility(
        anchor_ok=np.asarray(anchor_ok),
        anchor_score=np.asarray(anchor_score),
        node_ok=node_score_np < INFEASIBLE,
        node_score=node_score_np,
    )


def _all_infeasible(m: int, n: int) -> TopologyFeasibility:
    return TopologyFeasibility(
        anchor_ok=np.zeros((m, n), bool),
        anchor_score=np.full((m, n), INFEASIBLE, np.int32),
        node_ok=np.zeros((m, n), bool),
        node_score=np.full((m, n), INFEASIBLE, np.int32),
    )


def topology_feasibility_host(
    free: np.ndarray, h: int, w: int
) -> TopologyFeasibility:
    """Exact host mirror of the device kernel (numpy, same integral-image
    arithmetic) — the parity control and the no-device fallback, mirroring
    the dontschedule/GAS dual-path structure."""
    free = np.asarray(free, dtype=bool)
    m, n = free.shape
    if h > m or w > n:
        return _all_infeasible(m, n)
    ok_valid, score_valid = _anchor_grid(np, free.astype(np.int32), h, w)
    anchor_ok = np.zeros((m, n), bool)
    anchor_score = np.full((m, n), INFEASIBLE, np.int32)
    anchor_ok[: m - h + 1, : n - w + 1] = ok_valid
    anchor_score[: m - h + 1, : n - w + 1] = score_valid
    # windowed min via the h*w shift union (h, w are small static ints)
    node_score = np.full((m, n), INFEASIBLE, np.int32)
    for a in range(h):
        for b in range(w):
            # anchor (x-a, y-b) covers node (x, y)
            shifted = np.full((m, n), INFEASIBLE, np.int32)
            shifted[a:, b:] = anchor_score[: m - a, : n - b]
            node_score = np.minimum(node_score, shifted)
    return TopologyFeasibility(
        anchor_ok=anchor_ok,
        anchor_score=anchor_score,
        node_ok=node_score < INFEASIBLE,
        node_score=node_score,
    )


# ---------------------------------------------------------------------------
# the best anchor over many ICI domains, one program per orientation
# ---------------------------------------------------------------------------


def padded_domains(count: int) -> int:
    """Domains padded to a power of two (at least one)."""
    return 1 << max(count - 1, 0).bit_length()


@partial(jax.jit, static_argnames=("h", "w"))
def _domains_best_anchor(free: jnp.ndarray, h: int, w: int) -> jnp.ndarray:
    """int32 [4]: (score, domain, row, col) of the best ``h x w`` anchor
    over every domain of a bool [D, M, N] free mask — fewest stranded
    free ring cells inside its own domain, ties to the lowest (domain,
    row, col) (argmin's first occurrence); score ``INFEASIBLE`` where no
    window fits anywhere."""
    _, score = _anchor_grid(jnp, free.astype(jnp.int32), h, w)
    _, rows, cols = score.shape
    flat = score.reshape(-1)
    best = jnp.argmin(flat).astype(jnp.int32)
    return jnp.stack(
        [flat[best], best // (rows * cols), best // cols % rows, best % cols]
    )


def _anchor_of(best) -> Optional[Tuple[int, int, int, int]]:
    score, d, i, j = (int(x) for x in best)
    return None if score >= INFEASIBLE else (score, d, i, j)


def _dispatch_anchor(free, h: int, w: int):
    """The device program of one orientation over a bool [D, M, N] mask,
    dispatched and not read back; None where the window cannot fit."""
    _, m, n = free.shape
    if h > m or w > n:
        return None
    return _domains_best_anchor(jnp.asarray(free, dtype=bool), int(h), int(w))


def domains_anchor_device(free, h: int, w: int):
    """Device path: ``(score, domain, row, col)`` of the best anchor over
    a bool [D, M, N] mask (a NumPy array, or one already on the device),
    None when no window fits; one dispatch and a 16-byte readback."""
    best = _dispatch_anchor(free, h, w)
    return None if best is None else _anchor_of(np.asarray(best))


def domains_anchor_host(free: np.ndarray, h: int, w: int):
    """Exact host mirror of :func:`domains_anchor_device` (NumPy, the same
    arithmetic and the same tie order)."""
    free = np.asarray(free, dtype=bool)
    _, m, n = free.shape
    if h > m or w > n:
        return None
    _, score = _anchor_grid(np, free.astype(np.int32), h, w)
    rows, cols = score.shape[1:]
    best = int(np.argmin(score))
    return _anchor_of(
        (score.reshape(-1)[best], best // (rows * cols), best // cols % rows,
         best % cols)
    )


def best_domain_anchor(
    free: np.ndarray,
    shapes: Sequence[Tuple[int, int]],
    use_device: bool = True,
    span=trace.NULL_SPAN,
) -> Optional[Tuple[int, int, int, int, int]]:
    """``(h, w, domain, row, col)`` of the best anchor of a bool [D, M, N]
    free mask over the orientations ``shapes`` (in preference order): the
    lowest (score, orientation, domain, row, col).  The mask goes up once
    and each orientation is one program over every domain, every one
    dispatched before any is read back (one wait for the chip, not one an
    orientation); device trouble falls back to the host mirror,
    counted.  The three steps are the stages ``gang_upload``,
    ``gang_launch`` and ``gang_fetch`` on ``span``."""
    found = None
    if use_device:
        try:
            with span.stage("gang_upload"):
                on_device = jnp.asarray(free, dtype=bool)
            with span.stage("gang_launch"):
                runs = [_dispatch_anchor(on_device, h, w) for h, w in shapes]
            with span.stage("gang_fetch"):
                read = iter(
                    jax.device_get([r for r in runs if r is not None])
                )
            found = [None if r is None else _anchor_of(next(read)) for r in runs]
        except Exception:
            _count_fallback()
    if found is None:
        found = [domains_anchor_host(free, h, w) for h, w in shapes]
    best = None
    for index, ((h, w), anchor) in enumerate(zip(shapes, found)):
        if anchor is None:
            continue
        score, d, i, j = anchor
        if best is None or (score, index) < best[0]:
            best = ((score, index), (h, w, d, i, j))
    return None if best is None else best[1]


# ---------------------------------------------------------------------------
# wraparound (twisted-torus) windows
# ---------------------------------------------------------------------------
#
# Real TPU pods close their ICI links into a torus: a 4x4 slice whose
# rows wrap from column N-1 back to column 0 is just as valid as a
# rectangle in the interior.  The SAME integral-image kernel answers the
# wrapped question when run over a torus-padded copy of the free mask:
#
#   * one wrapped row/column on the TOP/LEFT so every anchor's one-cell
#     halo ring sees true torus neighbors (not synthetic zeros);
#   * ``h`` rows / ``w`` columns wrapped onto the BOTTOM/RIGHT so every
#     anchor in [0, M) x [0, N) has its full window and halo in-bounds.
#
# Cropping the anchor grids back to [0, M) x [0, N) de-duplicates the
# wrapped copies (each torus anchor appears exactly once), and the
# node fold becomes a modular shift union.  The device path runs the
# jitted kernel on the padded mask and shares the numpy crop/fold with
# the host mirror, so torus parity reduces to the (already pinned)
# rectangular kernel parity.


def _torus_pad(free: np.ndarray, h: int, w: int) -> np.ndarray:
    """The torus-padded free mask: [1 + M + h, 1 + N + w]."""
    rows = np.concatenate([free[-1:, :], free, free[:h, :]], axis=0)
    return np.concatenate([rows[:, -1:], rows, rows[:, :w]], axis=1)


def _torus_fold(
    anchor_ok: np.ndarray, anchor_score: np.ndarray, h: int, w: int
) -> TopologyFeasibility:
    """Fold cropped torus anchor scores onto the nodes they cover:
    anchor (i, j) covers nodes ((i+a) mod M, (j+b) mod N) — a modular
    shift union (np.roll), the torus analogue of the rectangular
    mirror's shift loop."""
    m, n = anchor_score.shape
    node_score = np.full((m, n), INFEASIBLE, np.int32)
    for a in range(h):
        rolled_rows = np.roll(anchor_score, a, axis=0)
        for b in range(w):
            node_score = np.minimum(
                node_score, np.roll(rolled_rows, b, axis=1)
            )
    return TopologyFeasibility(
        anchor_ok=anchor_ok,
        anchor_score=anchor_score,
        node_ok=node_score < INFEASIBLE,
        node_score=node_score,
    )


def torus_feasibility_device(
    free: np.ndarray, h: int, w: int
) -> TopologyFeasibility:
    """Device path: the rectangular kernel over the torus-padded mask;
    crop and fold happen host-side, shared verbatim with the mirror."""
    free = np.asarray(free, dtype=bool)
    m, n = free.shape
    if h > m or w > n:  # a wrapped window larger than the torus self-overlaps
        return _all_infeasible(m, n)
    padded = _torus_pad(free, h, w)
    _, anchor_score_p, _ = _topology_kernel(
        jnp.asarray(padded, dtype=bool), int(h), int(w)
    )
    anchor_score = np.asarray(anchor_score_p)[1 : m + 1, 1 : n + 1]
    return _torus_fold(anchor_score < INFEASIBLE, anchor_score, h, w)


def torus_feasibility_host(
    free: np.ndarray, h: int, w: int
) -> TopologyFeasibility:
    """Exact host mirror: the rectangular host kernel over the same
    torus-padded mask, then the shared crop/fold."""
    free = np.asarray(free, dtype=bool)
    m, n = free.shape
    if h > m or w > n:
        return _all_infeasible(m, n)
    padded = _torus_pad(free, h, w)
    feas = topology_feasibility_host(padded, h, w)
    anchor_score = feas.anchor_score[1 : m + 1, 1 : n + 1]
    return _torus_fold(anchor_score < INFEASIBLE, anchor_score, h, w)


def torus_feasibility(
    free: np.ndarray, h: int, w: int, use_device: bool = True
) -> TopologyFeasibility:
    """Dual-path entry for wraparound windows: the device kernel, the
    exact host mirror as the fallback (device trouble must never fail a
    verb)."""
    if use_device:
        try:
            return torus_feasibility_device(free, h, w)
        except Exception:
            pass
    return torus_feasibility_host(free, h, w)


def torus_slice_cells(
    i: int, j: int, h: int, w: int, m: int, n: int
) -> List[Tuple[int, int]]:
    """The wrapped window's cells in deterministic row-major order,
    coordinates taken modulo the [m, n] torus."""
    return [
        ((i + a) % m, (j + b) % n) for a in range(h) for b in range(w)
    ]


def best_anchor(feas: TopologyFeasibility) -> Optional[Tuple[int, int, int]]:
    """The deterministic best anchor ``(row, col, score)``: lowest
    stranded-fragment score, row-major smallest position on ties; None
    when no window fits."""
    flat = int(np.argmin(feas.anchor_score))
    n = feas.anchor_score.shape[1]
    i, j = divmod(flat, n)
    score = int(feas.anchor_score[i, j])
    if score >= INFEASIBLE:
        return None
    return i, j, score


def slice_cells(i: int, j: int, h: int, w: int) -> List[Tuple[int, int]]:
    """The window's cells in deterministic row-major order."""
    return [(i + a, j + b) for a in range(h) for b in range(w)]


class MeshView:
    """Node-name <-> mesh-coordinate mapping built from ``pas-tpu-coord``
    node labels (testing/fake_kube synthesizes them for hermetic
    meshes), one grid per ICI domain (``pas-tpu-domain``; the nodes
    without one form one domain together).  Domains are indexed in the
    sorted order of their labels and share one ``rows x cols`` shape, the
    largest any of them needs.  Nodes without a parseable coordinate —
    or with one past ``labels.mesh_dim_limit`` of the padded domain count,
    so that one mislabeled node cannot size every domain's grid — sit
    outside the mesh and can never join a topology-constrained gang
    slice."""

    def __init__(self, nodes):
        parsed = []
        for node in nodes:
            node_labels = node.get_labels()
            coord = shared_labels.parse_coord(node_labels)
            if coord is not None:
                domain = node_labels.get(shared_labels.TPU_DOMAIN_LABEL, "")
                parsed.append((node.name, domain, coord))
        self.domains: List[str] = sorted({domain for _, domain, _ in parsed})
        self.padded_domains = padded_domains(len(self.domains))
        limit = shared_labels.mesh_dim_limit(self.padded_domains)
        index = {domain: d for d, domain in enumerate(self.domains)}
        coord_of: Dict[str, Tuple[int, int]] = {}
        domain_of: Dict[str, int] = {}
        name_at: Dict[Tuple[int, int, int], str] = {}
        max_row = -1
        max_col = -1
        for name, domain, coord in parsed:
            cell = (index[domain], *coord)
            # first writer wins on a duplicate coordinate (deterministic
            # given the provider's stable node order)
            if coord[0] >= limit or coord[1] >= limit or cell in name_at:
                continue
            coord_of[name] = coord
            domain_of[name] = cell[0]
            name_at[cell] = name
            max_row = max(max_row, coord[0])
            max_col = max(max_col, coord[1])
        self.coord_of = coord_of
        self.domain_of = domain_of
        self.name_at = name_at
        self.rows = max_row + 1
        self.cols = max_col + 1
        #: {name: flat cell} — ``(domain * rows + row) * cols + col``, the
        #: index of the node's cell in a flattened :meth:`free_masks`
        self.cell_of: Dict[str, int] = {
            name: (d * self.rows + i) * self.cols + j
            for (d, i, j), name in name_at.items()
        }

    def __len__(self) -> int:
        return len(self.coord_of)

    def same_as(self, other: Optional["MeshView"]) -> bool:
        """Whether ``other`` maps every name to the same cell of the same
        grids (a rebuilt view of an unchanged cluster)."""
        return (
            other is not None
            and other.domains == self.domains
            and other.name_at == self.name_at
        )

    @property
    def n_cells(self) -> int:
        """Cells of the flattened ``[padded domains, rows, cols]`` grids."""
        return self.padded_domains * self.rows * self.cols

    def cells_of(self, names) -> np.ndarray:
        """int32 flat cell of each name, -1 for a name off the mesh."""
        get = self.cell_of.get
        return np.fromiter((get(name, -1) for name in names), dtype=np.int32)

    def mask_of(self, cells: np.ndarray) -> np.ndarray:
        """bool [padded domains, rows, cols]: True at ``cells`` (flat; a
        negative cell is no cell) — :meth:`free_masks` of the names the
        cells belong to."""
        mask = np.zeros(self.n_cells, dtype=bool)
        mask[cells[cells >= 0]] = True
        return mask.reshape(self.padded_domains, self.rows, self.cols)

    def window_cells(self, domain: int, i: int, j: int, h: int, w: int):
        """int32 flat cells of the ``h x w`` window at (i, j) of one domain,
        in row-major slice order."""
        rows = (domain * self.rows + i + np.arange(h, dtype=np.int32))
        cols = j + np.arange(w, dtype=np.int32)
        return (rows[:, None] * self.cols + cols[None, :]).reshape(-1)

    def free_masks(self, free_names) -> np.ndarray:
        """bool [padded domains, rows, cols]: a cell is free iff its node
        is in ``free_names`` (holes — coordinates with no node — and the
        padding domains stay False)."""
        mask = np.zeros((self.padded_domains, self.rows, self.cols), dtype=bool)
        for name in free_names:
            coord = self.coord_of.get(name)
            if coord is not None:
                mask[(self.domain_of[name], *coord)] = True
        return mask

    def free_mask(self, free_names) -> np.ndarray:
        """bool [rows, cols] of a mesh of one domain (see
        :meth:`free_masks`)."""
        if len(self.domains) > 1:
            raise ValueError(
                f"the mesh spans {len(self.domains)} ICI domains: one grid "
                "cannot hold them (free_masks)"
            )
        return self.free_masks(free_names)[0]

    def names_for(self, cells, domain: int = 0) -> Optional[List[str]]:
        """The node names at ``cells`` (row-major) of one domain; None
        when any cell is a hole."""
        names = []
        for row, col in cells:
            name = self.name_at.get((domain, row, col))
            if name is None:
                return None
            names.append(name)
        return names


def best_slice(
    mesh: MeshView,
    free_names,
    shape: Tuple[int, int],
    use_device: bool = True,
) -> Optional[Tuple[List[str], Tuple[int, int, int, int], str]]:
    """The gang reservation's solve: ``(names in row-major slice order,
    (row, col, h, w), domain label)`` of the best ``h x w`` slice, either
    orientation, over the free nodes of every ICI domain — one device
    program per orientation (:func:`best_domain_anchor`); None when no
    slice fits."""
    found = best_slice_in(mesh, mesh.free_masks(free_names), shape, use_device)
    return None if found is None else found[:3]


def best_slice_in(
    mesh: MeshView,
    free: np.ndarray,
    shape: Tuple[int, int],
    use_device: bool = True,
    span=trace.NULL_SPAN,
):
    """:func:`best_slice` over a ready bool ``[padded domains, rows, cols]``
    free mask: ``(names, (row, col, h, w), domain label, int32 flat cells
    of the slice)``, or None.  The solve's steps are stages on ``span``
    (:func:`best_domain_anchor`)."""
    h, w = shape
    shapes = [(h, w)] if h == w else [(h, w), (w, h)]
    found = best_domain_anchor(free, shapes, use_device, span)
    if found is None:
        return None
    hh, ww, d, i, j = found
    # every cell of the window is free, so every one has its node
    names = mesh.names_for(slice_cells(i, j, hh, ww), domain=d)
    return names, (i, j, hh, ww), mesh.domains[d], mesh.window_cells(
        d, i, j, hh, ww
    )
