"""The seeded world of the ``tpu-v5e-fleet-12k`` deployment (a fleet of Cloud
TPU v5e pods carved on demand into multi-host training slices, in front of
TAS with ``--gang=on``): what its driver, its assembler and its plain
reference all derive from the configuration and the seed — the hosts and
their ICI coordinates, the jobs of the mix and their pods, the fleet's state
at the start, and the slice rule the history is placed by.  NumPy and the
standard library; never JAX, never the program.

**The fleet.** ``domains`` TPU pods, each a ``domain_rows x domain_cols``
mesh of hosts; host ``index`` is ``domain * rows * cols + row * cols +
col``, so a slice's hosts in row-major order are its hosts in index order.
A host is one node that allocates the host's four chips: one pod a host.

**A job** is a shape of the mix: ``1x1`` a single-host pod (no gang label),
any other ``h x w`` a gang of ``h * w`` pods labelled with the job's group,
its size and its topology.  All pods of a job carry one ``telemetry-policy``.

**The slice rule** (:func:`place`): among every anchor of every domain and
both orientations (``h x w`` first), the windows whose cells are all free;
of those the one that leaves the fewest free cells in the one-cell ring
around it inside its own domain; ties to the lowest (orientation, domain,
row, col).  The free cells of a window and of its ring are counted directly,
window by window.
"""

from __future__ import annotations

import numpy as np

from generator import bench_pod_name, node_names, rng

STREAM_JOB_SHAPES, STREAM_JOB_POLICIES, STREAM_HISTORY = 41, 42, 43
STREAM_HISTORY_DELETES, STREAM_CHURN = 44, 45
# the wire format of the labels this deployment's pods and nodes carry
COORD_LABEL, DOMAIN_LABEL = "pas-tpu-coord", "pas-tpu-domain"
GROUP_LABEL, SIZE_LABEL, TOPOLOGY_LABEL = (
    "pas-workload-group", "pas-gang-size", "pas-gang-topology")
POLICY_LABEL = "telemetry-policy"
NAMESPACE = "default"


def grid(config: dict) -> tuple:
    """(domains, rows, cols) of the fleet."""
    return config["domains"], config["domain_rows"], config["domain_cols"]


def host_cell(config: dict, index: int) -> tuple:
    """(domain, row, col) of host ``index``."""
    _, rows, cols = grid(config)
    domain, rest = divmod(int(index), rows * cols)
    return domain, rest // cols, rest % cols


def domain_label(domain: int) -> str:
    """A domain's label: zero-padded, so the labels sort in domain order."""
    return f"pod-{domain:03d}"


def node_raw(config: dict, index: int, name: str) -> dict:
    """One host: its ICI domain and coordinate, and its allocatable."""
    domain, row, col = host_cell(config, index)
    resources = dict(config["node_allocatable"])
    return {"metadata": {"name": name, "labels": {
                DOMAIN_LABEL: domain_label(domain), COORD_LABEL: f"{row},{col}"}},
            "status": {"allocatable": resources, "capacity": resources,
                       "phase": "Running"}}


def shapes(config: dict) -> list:
    """(h, w) of each entry of the job mix, in the file's order."""
    return [tuple(int(x) for x in entry["shape"].split("x"))
            for entry in config["job_mix"]]


def shares(config: dict) -> np.ndarray:
    weights = np.array([entry["share"] for entry in config["job_mix"]], dtype=float)
    return weights / weights.sum()


def is_gang(shape: tuple) -> bool:
    return shape[0] * shape[1] > 1


# -- the slice rule -------------------------------------------------------------


def window_counts(cells: np.ndarray, h: int, w: int) -> np.ndarray:
    """[D, M-h+1, N-w+1]: the cells counted in every ``h x w`` window of
    every domain, one offset of the window at a time."""
    _, rows, cols = cells.shape
    out = np.zeros((cells.shape[0], rows - h + 1, cols - w + 1), dtype=np.int32)
    for a in range(h):
        for b in range(w):
            out += cells[:, a: a + rows - h + 1, b: b + cols - w + 1]
    return out


def anchor_scores(free: np.ndarray, h: int, w: int) -> np.ndarray:
    """int32 [D, M-h+1, N-w+1]: for every anchor of every domain, the free
    cells in the one-cell ring around the ``h x w`` window when the window is
    wholly free, else -1: the window's free cells, and those of the window
    one cell wider all round (the grid given a border of empty cells) less
    them."""
    cells = free.astype(np.int32)
    inside = window_counts(cells, h, w)
    bordered = np.pad(cells, ((0, 0), (1, 1), (1, 1)))
    around = window_counts(bordered, h + 2, w + 2) - inside
    return np.where(inside == h * w, around, -1)


def place(free: np.ndarray, shape: tuple):
    """``(h, w, domain, row, col)`` of the slice the rule gives a job of
    ``shape`` over the bool [D, M, N] ``free`` mask, or None."""
    h, w = shape
    _, rows, cols = free.shape
    best = None
    for index, (hh, ww) in enumerate([(h, w)] if h == w else [(h, w), (w, h)]):
        if hh > rows or ww > cols:
            continue
        scores = anchor_scores(free, hh, ww)
        fits = scores >= 0
        if not fits.any():
            continue
        ranked = np.where(fits, scores, np.iinfo(np.int32).max)
        flat = int(np.argmin(ranked))  # the first: the lowest (domain, row, col)
        d, i, j = np.unravel_index(flat, ranked.shape)
        key = (int(ranked.reshape(-1)[flat]), index)
        if best is None or key < best[0]:
            best = (key, (hh, ww, int(d), int(i), int(j)))
    return None if best is None else best[1]


def slice_hosts(config: dict, found: tuple) -> np.ndarray:
    """Host indices of a placed slice, in row-major (= index) order."""
    h, w, d, i, j = found
    _, rows, cols = grid(config)
    r, c = np.meshgrid(np.arange(i, i + h), np.arange(j, j + w), indexing="ij")
    return (d * rows * cols + r * cols + c).reshape(-1).astype(np.int64)


def free_mask(config: dict, free_hosts: np.ndarray) -> np.ndarray:
    """bool [D, M, N] of a bool [hosts] vector."""
    return free_hosts.reshape(grid(config))


# -- the jobs -------------------------------------------------------------------


class Job:
    __slots__ = ("name", "shape", "policy", "pods", "hosts")

    def __init__(self, name: str, shape: tuple, policy: int, pods: list,
                 hosts=None):
        self.name, self.shape, self.policy, self.pods = name, shape, policy, pods
        self.hosts = hosts  # where its pods run, once they do

    @property
    def size(self) -> int:
        return self.shape[0] * self.shape[1]

    @property
    def gang_id(self) -> str:
        return f"{NAMESPACE}/{self.name}"


def pod_raw(config: dict, name: str, job: Job, policy_name: str,
            node: str = "") -> dict:
    """One of a job's pods: the host's chips, the job's policy and, for a
    gang, its group, size and topology."""
    labels = {"app": "bench", POLICY_LABEL: policy_name}
    if is_gang(job.shape):
        labels.update({GROUP_LABEL: job.name, SIZE_LABEL: str(job.size),
                       TOPOLOGY_LABEL: f"{job.shape[0]}x{job.shape[1]}"})
    raw = {
        "metadata": {"name": name, "namespace": NAMESPACE, "uid": f"uid-{name}",
                     "labels": labels},
        "spec": {"schedulerName": "default-scheduler", "containers": [{
            "name": "trainer", "image": "registry.example.com/train:v1",
            "resources": {"requests": dict(config["pod_requests"]),
                          "limits": dict(config["pod_requests"])},
        }]},
        "status": {"phase": "Running" if node else "Pending"},
    }
    if node:
        raw["spec"]["nodeName"] = node
    return raw


def backlog(config: dict, seed: int) -> list:
    """The jobs pending at the start, in creation order; their pods are
    ``bench-<position>`` with a job's members consecutive."""
    kinds = shapes(config)
    drawn = rng(seed, STREAM_JOB_SHAPES).choice(
        len(kinds), size=config["measure_jobs"], p=shares(config))
    policies = rng(seed, STREAM_JOB_POLICIES).integers(
        0, len(config["policies"]), size=config["measure_jobs"])
    jobs, position = [], 0
    for number, (kind, policy) in enumerate(zip(drawn, policies)):
        shape = kinds[int(kind)]
        size = shape[0] * shape[1]
        pods = [bench_pod_name(p) for p in range(position, position + size)]
        jobs.append(Job(f"job-{number:05d}", shape, int(policy), pods))
        position += size
    return jobs


def pod_jobs(jobs: list) -> list:
    """For each backlog pod position: (its job's index, its member index)."""
    return [(k, m) for k, job in enumerate(jobs) for m in range(job.size)]


def history(config: dict, seed: int) -> list:
    """The jobs running at the start: jobs of the mix placed one by one by
    the slice rule until ``start_busy_share`` of the hosts are busy (a job
    that does not fit is passed over), then deleted in a seeded order until
    ``start_kept_share`` are — the fragmented free hosts of a fleet that has
    churned.  Their pods are ``init-<job>-<member>``."""
    d, rows, cols = grid(config)
    total = d * rows * cols
    free = np.ones((d, rows, cols), dtype=bool)
    kinds, weights = shapes(config), shares(config)
    gen = rng(seed, STREAM_HISTORY)
    placed, busy, passed_over = [], 0, 0
    while busy < config["start_busy_share"] * total and passed_over < 1000:
        shape = kinds[int(gen.choice(len(kinds), p=weights))]
        policy = int(gen.integers(0, len(config["policies"])))
        found = place(free, shape)
        if found is None:
            passed_over += 1
            continue
        hosts = slice_hosts(config, found)
        free.reshape(-1)[hosts] = False
        busy += len(hosts)
        placed.append((shape, policy, hosts))
    kept = np.ones(len(placed), dtype=bool)
    for k in rng(seed, STREAM_HISTORY_DELETES).permutation(len(placed)):
        if busy <= config["start_kept_share"] * total:
            break
        kept[k] = False
        busy -= len(placed[k][2])
    jobs = []
    for k in np.flatnonzero(kept):
        shape, policy, hosts = placed[int(k)]
        name = f"init-{int(k):05d}"
        jobs.append(Job(name, shape, policy,
                        [f"{name}-{m:02d}" for m in range(len(hosts))], hosts))
    return jobs


def free_at_start(config: dict, running: list) -> np.ndarray:
    """bool [hosts]: the hosts no running job holds."""
    free = np.ones(config["nodes"], dtype=bool)
    for job in running:
        free[job.hosts] = False
    return free


def warm_jobs(config: dict) -> list:
    """The warm-up's jobs: one gang of every gang shape of the mix, then a
    single-host pod of every policy — every program the window runs is
    compiled by them (a gang's reservation solves both orientations).  The
    gangs carry the warm-up's own policy, whose ``dontschedule`` no host
    meets, so that a warm gang binds whole or never reserves."""
    jobs, position = [], 0
    warm_policy = len(config["policies"])  # after the cell's own
    for shape in shapes(config):
        if not is_gang(shape):
            continue
        size = shape[0] * shape[1]
        jobs.append(Job(f"warm-{shape[0]}x{shape[1]}", shape, warm_policy,
                        [f"warm-{p:05d}" for p in range(position, position + size)]))
        position += size
    for policy in range(len(config["policies"])):
        jobs.append(Job(f"warm-single-{policy}", (1, 1), policy,
                        [f"warm-{position:05d}"]))
        position += 1
    return jobs


def policy_names(config: dict) -> list:
    """The cell's policies, then the warm-up's own."""
    return [p["name"] for p in config["policies"]] + [config["warm_policy"]["name"]]


def hosts(config: dict) -> list:
    return node_names(config["node_prefix"], config["nodes"])
