"""The ``backlog`` driver: one kube-scheduler draining a backlog of pending
pods through Filter and Prioritize, and binding as kube-scheduler does.

Closed loop, one scheduler, concurrency 1 on the scheduling connection.
Every pod of the backlog is pending when the window opens; pod order is
creation order.  For pod *i* the scheduling thread sends Filter over every
node that kube-scheduler's own NodeResourcesFit still passes, in node order
(``percentage_of_nodes_to_score`` 100), then Prioritize over the nodes that
passed, picks the top-scored host (the first on a tie), assumes the pod on
it — the node leaves the candidates once it holds what its allocatable fits —
and hands (pod, node) to the binding thread, which writes ``pods/binding`` to
the played kube API (``batch_world.bind_address``: the API server, not an
extender verb) on a keep-alive connection of its own while Filter of pod
*i+1* is sent.  A pod counts once its binding is acknowledged: the window
ends at its deadline or with the backlog's last pod, and ``ended`` is stamped
after the last binding in flight is answered.

The warm-up sends serial cycles, bindings included, on warm pods of their
own.  The records are the serial driver's (``t`` = Filter sent, Filter
answered, Prioritize sent, Prioritize answered) plus ``gone`` (how many
nodes had left the candidates when the Filter was sent; the window lists
them in order as ``left``), ``node`` (the host picked), ``bind_t``
(binding sent, acknowledged), ``bind_status`` and, on every
``keep_every``-th cycle, ``order`` and ``scores`` (the Prioritize answer
whole, as node indices).  The window also says how many answers its short
readers (``read_filter``, ``read_priorities``) had to read whole.
"""

from __future__ import annotations

import json
import os
import queue
import socket
import threading
import time

import numpy as np

import batch_world
import generator

TRAFFIC_KEYS = {"percentage_of_nodes_to_score": int, "bind_connections": int,
                "bind_queue_depth": int}
# every byte that is no part of a whole number becomes a blank
NUMBERS_ONLY = bytes(b if chr(b) in "0123456789-" else 32 for b in range(256))


class ApiClient(generator.Client):
    """A keep-alive connection to the played kube API's binding endpoint."""

    def __init__(self, address: str):
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.connect(address)
        self.buf = bytearray()

    def bind(self, pod: str, node: str) -> tuple:
        body = generator.compact({
            "apiVersion": "v1", "kind": "Binding", "metadata": {"name": pod},
            "target": {"apiVersion": "v1", "kind": "Node", "name": node}})
        head = (f"POST {batch_world.binding_path(pod)} HTTP/1.1\r\nHost: kube\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        return self.exchange(head + body)


class Driver(generator.Cycles):

    def __init__(self, job: dict):
        super().__init__(job)
        config, traffic = job["config"], job["traffic"]
        if (traffic["cycle"] != ["filter", "prioritize"]
                or traffic["wire"] != "names"
                or traffic["percentage_of_nodes_to_score"] != 100
                or traffic["bind_connections"] != 1
                or traffic["bind_queue_depth"] < 1):
            raise ValueError(
                f"traffic {traffic['name']!r}: the backlog driver takes the "
                "cycle filter, prioritize on the names wire, every feasible "
                "node scored, one binding connection (bindings go out in pod "
                "order) and a hand-off queue at least one deep")
        self.config = config
        n = config["nodes"]
        self.count = n  # candidates of the first Filter: every node
        self.fit = batch_world.fit_per_node(config)
        self.held = np.bincount(
            batch_world.init_pod_nodes(config, self.seed), minlength=n)
        self.feasible = self.held < self.fit
        self.left = [int(i) for i in np.flatnonzero(~self.feasible)]
        self.policy_of = batch_world.pod_policies(config, self.seed)
        self.bind_depth = traffic["bind_queue_depth"]
        self.api = None
        # the candidates as sent (indices, names), until one of them leaves;
        # the names that passed the last Filter, as Prioritize is sent them
        self._candidates = self._rendered = self._passed = None
        self.read_whole = [0, 0]  # Filter, Prioritize answers not read short
        self.prefix = (config["node_prefix"] + "-").encode()

    # -- one pod's requests -----------------------------------------------------

    def pod(self, index: int, warm: bool) -> tuple:
        name = f"warm-{index:05d}" if warm else generator.bench_pod_name(index)
        which = (index % len(self.policies) if warm
                 else int(self.policy_of[index]))
        raw = batch_world.pod_raw(self.config, name, self.policies[which])
        return name, generator.compact(raw), which

    def first_verb(self, index: int, warm: bool = False) -> tuple:
        """Filter over every node kube-scheduler's Fit still passes."""
        name, pod_bytes, which = self.pod(index, warm)
        if self._rendered is None:
            self._candidates = np.flatnonzero(self.feasible).astype(np.int32)
            self._rendered = b",".join(
                self.name_bytes[i] for i in self._candidates)
        request = self.client.render("filter", [
            b'{"Pod":', pod_bytes, self.open_list, self._rendered,
            self.close_list])
        gone = len(self.left)
        t0, t1, status, body = self.client.exchange(request)
        record = {
            "index": index, "which": which, "start": 0,
            "count": len(self.names) - gone, "gone": gone,
            "t": [t0, t1, np.nan, np.nan], "status": [status, 0],
            "second": "", "node": -1, "error": "",
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

    def read_filter(self, body: bytes) -> tuple:
        """(passed, failed) node indices of a FilterResult, in answer order.
        The short way reads ``FailedNodes`` alone and holds ``NodeNames``,
        byte for byte, to the candidates less those: kube-scheduler reads
        the answer in Go, and 5,000 names through ``json.loads`` would make
        the client the slowest part of the cycle.  Any other answer is read
        whole."""
        names_at, failed_at = body.find(b'"NodeNames"'), body.rfind(b'"FailedNodes"')
        if 0 <= names_at < failed_at:
            tail = json.loads(b"{" + body[failed_at:])
            failed = np.array(
                [self.index[f] for f in tail.get("FailedNodes") or ()],
                dtype=np.int32)
            passed = self._candidates[
                ~np.isin(self._candidates, failed, assume_unique=True)]
            said = body[body.find(b"[", names_at) + 1: body.rfind(b"]", 0, failed_at)]
            parts = [self.name_bytes[i] for i in passed]
            if said in (b", ".join(parts), b",".join(parts)):
                self._passed = said  # Prioritize is sent what Filter passed
                return passed, failed
        self.read_whole[0] += 1
        passed, failed = self.parse_filter(body)
        self._passed = b",".join(self.name_bytes[self.index[p]] for p in passed)
        return (np.array([self.index[p] for p in passed], dtype=np.int32),
                np.array([self.index[f] for f in failed], dtype=np.int32))

    def read_priorities(self, body: bytes, expected: int) -> tuple:
        """(hosts as node indices, scores) of a HostPriorityList, in answer
        order.  The short way reads the numbers alone — a node's index is
        the number in its name — and is taken only where every host carries
        the prefix and the counts agree; any other answer is read whole."""
        numbers = np.fromstring(
            body.translate(NUMBERS_ONLY).decode(), dtype=np.int64, sep=" ")
        if (len(numbers) == 2 * expected
                and body.count(b'"' + self.prefix) == expected):
            hosts = -numbers[0::2]
            if hosts.min() >= 0 and hosts.max() < len(self.names):
                return hosts.astype(np.int32), numbers[1::2].astype(np.int32)
        self.read_whole[1] += 1
        answer = json.loads(body)
        return (np.array([self.index[e["Host"]] for e in answer], dtype=np.int32),
                np.array([e["Score"] for e in answer], dtype=np.int32))

    def pick(self, record: dict, name: str, pod_bytes: bytes, keep: bool) -> bool:
        """Prioritize over the nodes that passed, then kube-scheduler's
        choice: the top-scored host, the first on a tie; the pod is assumed
        on it at once.  False where the pod got no node."""
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
            if keep:  # a kept answer is read whole, name by name
                answer = json.loads(body)
                hosts = np.array(
                    [self.index[e["Host"]] for e in answer], dtype=np.int32)
                scores = np.array([e["Score"] for e in answer], dtype=np.int32)
                record["order"], record["scores"] = hosts, scores
            else:
                hosts, scores = self.read_priorities(body, len(record["passed"]))
            node = int(hosts[scores.argmax()])  # the first of the top-scored
        except (ValueError, KeyError, TypeError) as exc:
            record["error"] = f"prioritize answer unreadable: {exc!r}"
            return False
        record["node"] = node
        self.held[node] += 1
        if self.held[node] >= self.fit:
            self.feasible[node] = False
            self.left.append(node)
            self._rendered = None
        return True

    def bind(self, record: dict, name: str) -> None:
        try:
            sent, acked, status, body = self.api.bind(
                name, self.names[record["node"]])
        except Exception as exc:  # noqa: BLE001 — the queue must keep draining
            record["error"] = f"binding not answered: {exc!r}"
            return
        record["bind_t"] = [sent, acked]
        record["bind_status"] = status
        if status != 201:
            record["error"] = f"binding status {status}: {body[:200]!r}"

    # -- the parent's commands ----------------------------------------------------

    def connect(self, port: int = 0) -> None:
        super().connect(port)
        if self.api is None:
            self.api = ApiClient(batch_world.bind_address(os.getppid()))

    def warm(self) -> list:
        records = []
        for index in range(self.warm_pods()):
            record, name, pod_bytes = self.first_verb(index, warm=True)
            if self.pick(record, name, pod_bytes, keep=False):
                self.bind(record, name)
            records.append(record)
        return records

    def bind_loop(self, handoff: queue.Queue) -> None:
        while True:
            handed = handoff.get()
            if handed is None:
                return
            self.bind(*handed)

    def window(self, seconds: float) -> dict:
        self.connect()  # the server drops a connection silent for 5 s
        handoff = queue.Queue(maxsize=self.bind_depth)
        binder = threading.Thread(target=self.bind_loop, args=(handoff,))
        binder.start()
        records = []
        began = time.monotonic()
        deadline = began + seconds
        try:
            for index in range(self.config["measure_pods"]):
                keep = index % self.keep_every == self.keep_phase
                record, name, pod_bytes = self.first_verb(index)
                records.append(record)
                if self.pick(record, name, pod_bytes, keep):
                    handoff.put((record, name))
                if time.monotonic() >= deadline:
                    break
        finally:
            handoff.put(None)
            binder.join()
            ended = time.monotonic()
        return {"began": began, "ended": ended, "records": records,
                "left": list(self.left), "read_whole": list(self.read_whole)}

    def probe(self) -> list:
        return []
