"""The ``backlog-gang`` driver: the ``backlog`` driver's kube-scheduler over a
fleet of TPU hosts whose pending pods are training jobs, most of them gangs
that need a whole slice (``gang_world``).

The loop, the two verbs, the short readers and the binding connection are
``drivers/backlog.py``'s, loaded through ``plugins.load`` and not copied.
What differs:

* **Candidates.** kube-scheduler's Fit on a host that allocates its four
  chips to one pod: every free host, in host order.  A host leaves when a pod
  is assumed on it, and comes back when the job running there is deleted.
* **Requeue.** A pod no host passed goes back to the queue and is tried
  again once its backoff (1 s, doubling to 10 s) has passed, before the
  next pod that was never tried.  A record is one attempt; ``attempt``
  counts the ones before it.  Where the extender scored none of the hosts
  that passed, the scheduler takes the first of them (every host scores 0,
  the first wins the tie).
* **Churn.** On the binding connection, once a job's last pod is bound, one
  running job (those of the fleet's start and those completed since, in that
  order) drawn uniformly by the seed is deleted whole: a ``DELETE`` of each
  of its pods to the played kube API.  Its hosts come back to the
  scheduling thread, which takes them in before its next Filter.
* **Warm-up.** One gang of every gang shape and a single-host pod of every
  policy, serially, bindings included; then every warm-up pod is deleted.

Beyond the base driver's, a record holds ``job`` and ``attempt``, and
``changes``: how many entries of the window's ``changes`` log — (0, host)
a host taken by a pod, (1, job, hosts) a deleted job's hosts given back,
from the start of the warm-up on — the Filter's candidates reflect.  The
window also gives ``deleted`` ([job, first DELETE sent, last answered]) and
``completed`` ([job, its last binding acknowledged]).
"""

from __future__ import annotations

import heapq
import json
import os
import queue
import threading
import time

import numpy as np

import batch_world
import gang_world
import generator
import plugins

base = plugins.load("drivers", "backlog")
TRAFFIC_KEYS = base.TRAFFIC_KEYS
INITIAL_BACKOFF_S, MAX_BACKOFF_S = 1.0, 10.0


class ApiClient(base.ApiClient):
    """The binding connection, which also deletes pods."""

    def delete(self, pod: str) -> tuple:
        head = (f"DELETE /api/v1/namespaces/{gang_world.NAMESPACE}/pods/{pod} "
                "HTTP/1.1\r\nHost: kube\r\nContent-Length: 0\r\n\r\n").encode()
        return self.exchange(head)


class Driver(base.Driver):

    def __init__(self, job: dict):
        config = job["config"]
        # the base driver reads batch_world's init pods and backlog size:
        # here the fleet's start and the jobs are gang_world's
        super().__init__({**job, "config": {**config, "init_pods": 0,
                                            "measure_pods": 0}})
        self.config = config
        self.policies = gang_world.policy_names(config)
        self.running = gang_world.history(config, self.seed)
        self.feasible = gang_world.free_at_start(config, self.running)
        self.jobs = gang_world.backlog(config, self.seed)
        self.pod_of = gang_world.pod_jobs(self.jobs)
        self.warm_list = gang_world.warm_jobs(config)
        self.warm_of = [(k, m) for k, j in enumerate(self.warm_list)
                        for m in range(j.size)]
        self.changes = []  # (0, host) taken; (1, job, hosts) given back
        self.freed = queue.Queue()  # (job, hosts) from the binding thread
        self.landed = {}  # job name -> hosts its bound pods landed on
        self.churning = False
        self.churn = generator.rng(self.seed, gang_world.STREAM_CHURN)
        self.deleted, self.completed = [], []
        self._bytes = {}

    # -- one pod's requests -------------------------------------------------------

    def job_of(self, index: int, warm: bool) -> tuple:
        """(the job, its number, the member) of a backlog or warm-up pod."""
        k, member = (self.warm_of if warm else self.pod_of)[index]
        return (self.warm_list if warm else self.jobs)[k], k, member

    def pod(self, index: int, warm: bool) -> tuple:
        job, _k, member = self.job_of(index, warm)
        name = job.pods[member]
        if name not in self._bytes:
            self._bytes[name] = generator.compact(gang_world.pod_raw(
                self.config, name, job, self.policies[job.policy]))
        return name, self._bytes[name], job.policy

    def take_in_frees(self) -> None:
        """The hosts of the jobs deleted since the last Filter come back."""
        while True:
            try:
                job, hosts = self.freed.get_nowait()
            except queue.Empty:
                return
            self.feasible[hosts] = True
            self.changes.append((1, job, [int(h) for h in hosts]))
            self._rendered = None

    def first_verb(self, index: int, warm: bool = False) -> tuple:
        """Filter over every free host."""
        self.take_in_frees()
        name, pod_bytes, which = self.pod(index, warm)
        if self._rendered is None:
            self._candidates = np.flatnonzero(self.feasible).astype(np.int32)
            self._rendered = b",".join(self.name_bytes[i] for i in self._candidates)
        request = self.client.render("filter", [
            b'{"Pod":', pod_bytes, self.open_list, self._rendered, self.close_list])
        t0, t1, status, body = self.client.exchange(request)
        record = {
            "index": index, "which": which, "job": self.job_of(index, warm)[1],
            "start": 0, "count": len(self._candidates), "changes": len(self.changes),
            "t": [t0, t1, np.nan, np.nan], "status": [status, 0],
            "second": "", "node": -1, "error": "", "attempt": 0,
            "bind_t": [np.nan, np.nan], "bind_status": 0,
        }
        if status == 200:
            try:
                record["passed"], record["failed"] = self.read_filter(body)
            except (ValueError, KeyError) as exc:
                record["error"] = f"filter answer unreadable: {exc!r}"
        else:
            record["error"] = f"filter status {status}"
        return record, name, pod_bytes

    def pick(self, record: dict, name: str, pod_bytes: bytes, keep: bool) -> bool:
        """Prioritize over the hosts that passed; the top-scored host, or the
        first that passed where none was scored.  The host is taken at once."""
        if record["error"] or not len(record["passed"]):
            return False
        t2, t3, status, body = self.client.exchange(self.client.render(
            "prioritize", [b'{"Pod":', pod_bytes, self.open_list, self._passed,
                           self.close_list]))
        record["t"][2:] = [t2, t3]
        record["status"][1] = status
        record["second"] = "prioritize"
        if status != 200:
            record["error"] = f"prioritize status {status}: {body[:200]!r}"
            return False
        try:
            if keep:
                answer = json.loads(body)
                hosts = np.array([self.index[e["Host"]] for e in answer], dtype=np.int32)
                scores = np.array([e["Score"] for e in answer], dtype=np.int32)
                record["order"], record["scores"] = hosts, scores
            else:
                hosts, scores = self.read_priorities(body, len(record["passed"]))
            node = int(hosts[scores.argmax()]) if len(hosts) else int(
                record["passed"][0])
        except (ValueError, KeyError, TypeError) as exc:
            record["error"] = f"prioritize answer unreadable: {exc!r}"
            return False
        record["node"] = node
        self.feasible[node] = False
        self.changes.append((0, node))
        self._rendered = None
        return True

    # -- the binding connection -----------------------------------------------------

    def connect(self, port: int = 0) -> None:
        if self.api is None:
            self.api = ApiClient(batch_world.bind_address(os.getppid()))
        super().connect(port)

    def bind(self, record: dict, name: str, warm: bool = False) -> None:
        super().bind(record, name)
        if record["bind_status"] != 201:
            return
        job = self.job_of(record["index"], warm)[0]
        landed = self.landed.setdefault(job.name, [])
        landed.append(record["node"])
        if len(landed) == job.size:
            job.hosts = np.array(sorted(landed), dtype=np.int64)
            if self.churning:
                try:
                    self.complete(job)
                except Exception as exc:  # noqa: BLE001 — the queue must keep draining
                    record["error"] = f"churn after {job.name} failed: {exc!r}"

    def complete(self, job) -> None:
        """A job runs whole: one running job, drawn by the seed, is deleted."""
        self.completed.append([job.name, time.monotonic()])
        self.running.append(job)
        victim = self.running.pop(int(self.churn.integers(0, len(self.running))))
        self.delete(victim)
        self.freed.put((victim.name, victim.hosts))

    def delete(self, job) -> None:
        first = time.monotonic()
        for pod in job.pods:
            _sent, _answered, status, body = self.api.delete(pod)
            if status != 200:
                raise RuntimeError(f"delete of {pod}: {status} {body[:200]!r}")
        self.deleted.append([job.name, first, time.monotonic()])

    # -- the parent's commands ---------------------------------------------------------

    def warm_pods(self) -> int:
        return len(self.warm_of)

    def warm(self) -> list:
        records = []
        for index in range(self.warm_pods()):
            record, name, pod_bytes = self.first_verb(index, warm=True)
            if self.pick(record, name, pod_bytes, keep=False):
                self.bind(record, name, warm=True)
            records.append(record)
        for job in self.warm_list:  # every warm-up pod goes, bound or not
            for pod in job.pods:
                self.api.delete(pod)
            landed = self.landed.pop(job.name, [])
            if landed:
                self.feasible[landed] = True
                self.changes.append((1, job.name, [int(h) for h in landed]))
                self._rendered = None
        return records

    def window(self, seconds: float) -> dict:
        self.connect()  # the server drops a connection silent for 5 s
        self.churning = True
        handoff = queue.Queue(maxsize=self.bind_depth)
        binder = threading.Thread(target=self.bind_loop, args=(handoff,))
        binder.start()
        records, retries, fresh = [], [], 0
        began = time.monotonic()
        deadline = began + seconds
        try:
            while True:
                now = time.monotonic()
                if retries and retries[0][0] <= now:
                    _due, index, attempt = heapq.heappop(retries)
                elif fresh < len(self.pod_of):
                    index, attempt, fresh = fresh, 0, fresh + 1
                elif retries:
                    time.sleep(max(min(retries[0][0], deadline) - now, 0))
                    if time.monotonic() >= deadline:
                        break
                    continue
                else:
                    break  # the backlog's end
                keep = len(records) % self.keep_every == self.keep_phase
                record, name, pod_bytes = self.first_verb(index)
                record["attempt"] = attempt
                records.append(record)
                if self.pick(record, name, pod_bytes, keep):
                    handoff.put((record, name))
                elif not record["error"]:
                    backoff = min(INITIAL_BACKOFF_S * 2 ** attempt, MAX_BACKOFF_S)
                    heapq.heappush(
                        retries, (time.monotonic() + backoff, index, attempt + 1))
                if time.monotonic() >= deadline:
                    break
        finally:
            handoff.put(None)
            binder.join()
            ended = time.monotonic()
        return {"began": began, "ended": ended, "records": records,
                "changes": list(self.changes), "deleted": list(self.deleted),
                "completed": list(self.completed),
                "read_whole": list(self.read_whole)}
