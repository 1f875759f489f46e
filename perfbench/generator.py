"""The one general traffic generator: a kube-scheduler's serial scheduling
cycle, driven by the parameters of a configuration file and a traffic file.

Two halves, neither of which ever imports JAX or anything of the program:

* the seeded world (node names, ``v1.Node`` objects, pods, telemetry rounds,
  candidate windows) — imported by the parent, the reference and this
  module's own child process, so all three see the same data for a seed;
* the child process (``python perfbench/generator.py``): one keep-alive
  socket, pre-rendered request bytes, ``time.monotonic()`` stamps (one clock
  for every process on Linux).  The parent talks to it in JSON lines on
  stdin/stdout; the window's records go back as one pickle.

A scheduling cycle is closed and serial, as kube-scheduler's is: Filter over
the pod's candidates, then the second verb (Prioritize over the nodes that
passed, or Bind onto the first of them), and only then the next pod.
"""

from __future__ import annotations

import json
import pickle
import socket
import sys
import time

import numpy as np

SEQUENCE_LENGTH = 1 << 18  # cycles drawn per seed; the loop wraps past it
STREAM_STARTS, STREAM_NODES, STREAM_ROUNDS, STREAM_PODS, STREAM_SHAPES = (
    1, 2, 3, 4, 5,
)


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


# -- the candidate rule -------------------------------------------------------


def candidates_to_find(num_nodes: int) -> int:
    """kube-scheduler's numFeasibleNodesToFind with percentageOfNodesToScore
    unset: all below 100 nodes, else ``50 - N/125`` percent (integer
    division), never under 5% or 100 nodes."""
    if num_nodes < 100:
        return num_nodes
    adaptive = max(50 - num_nodes // 125, 5)
    return max(num_nodes * adaptive // 100, 100)


def window_starts(seed: int, num_nodes: int, count: int, extra_max: int,
                  stream: int = 0) -> np.ndarray:
    """Start index of every cycle's candidate window: kube-scheduler's
    round-robin, advancing by the number of nodes it examined (the
    candidates it found plus ``0..extra_max`` it skipped as infeasible by
    its own filters), wrapping."""
    gen = rng(seed, STREAM_STARTS, stream)
    first = int(gen.integers(0, num_nodes))
    examined = count + gen.integers(0, extra_max + 1, size=SEQUENCE_LENGTH)
    starts = (first + np.concatenate(([0], np.cumsum(examined)[:-1])))
    return (starts % num_nodes).astype(np.int64)


def node_names(prefix: str, num_nodes: int) -> list:
    return [f"{prefix}-{i:05d}" for i in range(num_nodes)]


TRAFFIC_KEYS = {
    "name": str, "cycle": list, "wire": str, "candidates": dict,
    "churn": dict, "warm_cycles": int, "keep_every": int, "deep_cycles": int,
    "probe_after_window": bool,
}


def checked_traffic(traffic: dict) -> dict:
    """The traffic file, refused where it sets what this generator does not
    implement: a parameter that no code reads would run as something else
    than the file says."""
    for key, value in traffic.items():
        if key not in TRAFFIC_KEYS:
            raise ValueError(f"traffic {traffic.get('name')!r}: no parameter {key!r}")
        if not isinstance(value, TRAFFIC_KEYS[key]):
            raise ValueError(f"traffic {traffic.get('name')!r}: {key!r} is not "
                             f"a {TRAFFIC_KEYS[key].__name__}")
    cycle = traffic["cycle"]
    if cycle not in (["filter", "prioritize"], ["filter", "bind"]):
        raise ValueError(f"traffic {traffic['name']!r}: no cycle {cycle!r}")
    if traffic["wire"] not in ("names", "nodes"):
        raise ValueError(f"traffic {traffic['name']!r}: no wire {traffic['wire']!r}")
    if set(traffic["candidates"]) != {"examined_extra_max"}:
        raise ValueError(f"traffic {traffic['name']!r}: candidates takes "
                         "examined_extra_max and nothing else")
    if set(traffic.get("churn", {})) - {"pending_ahead"}:
        raise ValueError(f"traffic {traffic['name']!r}: churn takes "
                         "pending_ahead and nothing else")
    return traffic


def sized(config: dict, rehearse: bool) -> dict:
    """The configuration as run: the file's sizes, or — for a CPU rehearsal
    only — its ``rehearsal`` block laid over them."""
    if not rehearse:
        return config
    return {**config, **config.get("rehearsal", {})}


# -- TAS world ----------------------------------------------------------------


def metric_round(seed: int, round_index: int, metric_index: int,
                 num_nodes: int, step: int) -> np.ndarray:
    """One metric's values in one telemetry round: a seeded permutation times
    ``step`` plus the round number — every column moves every round, and no
    two nodes tie."""
    gen = rng(seed, STREAM_ROUNDS, round_index, metric_index)
    return gen.permutation(num_nodes).astype(np.int64) * step + round_index


def tas_policies(config: dict) -> list:
    """The configuration's policies with thresholds resolved against the top
    metric value: [{name, strategies: {type: [(metric, operator, target)]}}]."""
    top = (config["nodes"] - 1) * config["value_step"]
    return [
        {
            "name": policy["name"],
            "strategies": {
                kind: [
                    (r["metric"], r["operator"], int(top * r["top_share"]))
                    for r in rules
                ]
                for kind, rules in policy["strategies"].items()
            },
        }
        for policy in config["policies"]
    ]


CONDITIONS = (  # the kubelet's four, and the route controller's
    ("NetworkUnavailable", "False", "RouteCreated", "RouteController created a route"),
    ("MemoryPressure", "False", "KubeletHasSufficientMemory",
     "kubelet has sufficient memory available"),
    ("DiskPressure", "False", "KubeletHasNoDiskPressure", "kubelet has no disk pressure"),
    ("PIDPressure", "False", "KubeletHasSufficientPID",
     "kubelet has sufficient PID available"),
    ("Ready", "True", "KubeletReady", "kubelet is posting ready status"),
)


def node_object(seed: int, index: int, name: str, shape: dict) -> dict:
    """A ``v1.Node`` as a kubelet on a cloud reports it, sized by what the
    configuration's ``node_object`` cites: ``images`` entries of
    ``image_names`` names each under ``status.images``, the kubelet's and the
    cloud provider's well-known labels, ``conditions`` conditions."""
    gen = rng(seed, STREAM_NODES, index)
    zone, region = f"region-1{'abc'[index % 3]}", "region-1"
    kind = f"m{int(gen.integers(4, 9))}.4xlarge"
    labels = {
        "kubernetes.io/hostname": name,
        "kubernetes.io/os": "linux", "beta.kubernetes.io/os": "linux",
        "kubernetes.io/arch": "amd64", "beta.kubernetes.io/arch": "amd64",
        "node.kubernetes.io/instance-type": kind,
        "beta.kubernetes.io/instance-type": kind,
        "topology.kubernetes.io/zone": zone,
        "failure-domain.beta.kubernetes.io/zone": zone,
        "topology.kubernetes.io/region": region,
        "failure-domain.beta.kubernetes.io/region": region,
    }
    resources = {
        "cpu": "64", "memory": f"{int(gen.integers(200, 260)) * 1000000}Ki",
        "ephemeral-storage": "1843269236Ki", "hugepages-1Gi": "0",
        "hugepages-2Mi": "0", "pods": "110",
    }
    stamp = "2026-01-01T00:00:00Z"
    conditions = [
        {"type": kind, "status": status, "lastHeartbeatTime": stamp,
         "lastTransitionTime": stamp, "reason": reason, "message": message}
        for kind, status, reason, message in CONDITIONS[-shape["conditions"]:]
    ]
    count = shape["images"]
    teams = gen.integers(0, 40, size=count).tolist()
    digests = gen.integers(0, 2**62, size=(count, 2)).tolist()
    versions = gen.integers(1, 99, size=count).tolist()
    sizes = gen.integers(5_000_000, 900_000_000, size=count).tolist()
    images = [
        {"names": [
            f"registry.example.com/team-{teams[k]}/service-{k}"
            f"@sha256:{digests[k][0]:032x}{digests[k][1]:032x}",
            f"registry.example.com/team-{teams[k]}/service-{k}:v{versions[k]}",
            *[f"mirror-{m}.example.com/team-{teams[k]}/service-{k}:v{versions[k]}"
              for m in range(shape["image_names"] - 2)],
        ][: shape["image_names"]], "sizeBytes": sizes[k]}
        for k in range(count)
    ]
    return {
        "metadata": {
            "name": name, "uid": f"{int(gen.integers(0, 2**62)):032x}",
            "resourceVersion": str(int(gen.integers(1, 10**8))),
            "creationTimestamp": stamp, "labels": labels,
            "annotations": {
                "node.alpha.kubernetes.io/ttl": "0",
                "volumes.kubernetes.io/controller-managed-attach-detach": "true",
            },
        },
        "spec": {"podCIDR": f"10.{index // 256}.{index % 256}.0/24",
                 "providerID": f"provider://{zone}/{name}"},
        "status": {
            "capacity": resources, "allocatable": resources,
            "conditions": conditions,
            "addresses": [
                {"type": "InternalIP", "address": f"10.200.{index // 256}.{index % 256}"},
                {"type": "Hostname", "address": name},
            ],
            "daemonEndpoints": {"kubeletEndpoint": {"Port": 10250}},
            "nodeInfo": {
                "machineID": f"{int(gen.integers(0, 2**62)):032x}",
                "kernelVersion": "6.1.0", "osImage": "Linux",
                "containerRuntimeVersion": "containerd://1.7.2",
                "kubeletVersion": "v1.29.3", "kubeProxyVersion": "v1.29.3",
                "operatingSystem": "linux", "architecture": "amd64",
            },
            "images": images,
        },
    }


def tas_pod(policy: str, name: str) -> dict:
    return {
        "metadata": {"name": name, "namespace": "default",
                     "uid": f"uid-{name}",
                     "labels": {"app": "bench", "telemetry-policy": policy}},
        "spec": {"schedulerName": "default-scheduler", "containers": [{
            "name": "main", "image": "registry.example.com/team/service:v1",
            "resources": {"requests": {"cpu": "500m", "memory": "1Gi"},
                          "limits": {"telemetry/scheduling": "1"}},
        }]},
        "status": {"phase": "Pending"},
    }


# -- GAS world ----------------------------------------------------------------

GAS_RESOURCES = (
    "gpu.intel.com/i915", "gpu.intel.com/millicores", "gpu.intel.com/memory.max",
)


def gas_cards(config: dict, seed: int) -> np.ndarray:
    """Cards per node: the configuration's shapes dealt out by the seed."""
    n = config["nodes"]
    cards = np.empty(n, dtype=np.int64)
    at = 0
    for k, shape in enumerate(config["node_shapes"]):
        last = k == len(config["node_shapes"]) - 1
        count = n - at if last else int(round(n * shape["share"]))
        cards[at: at + count] = shape["cards"]
        at += count
    return rng(seed, STREAM_SHAPES).permutation(cards)


def gas_templates(config: dict) -> list:
    """[[{resource: amount} per container]] in the file's order."""
    return [
        [
            {GAS_RESOURCES[0]: c["i915"], GAS_RESOURCES[1]: c["millicores"],
             GAS_RESOURCES[2]: c["memory"]}
            for c in template["containers"]
        ]
        for template in config["pod_templates"]
    ]


def gas_template_sequence(config: dict, seed: int, stream: int = 0) -> np.ndarray:
    weights = np.array(
        [t["weight"] for t in config["pod_templates"]], dtype=float
    )
    return rng(seed, STREAM_PODS, stream).choice(
        len(weights), size=SEQUENCE_LENGTH, p=weights / weights.sum()
    )


def gas_pod(name: str, containers: list, node: str = "",
            annotations: dict = None, phase: str = "Pending") -> dict:
    raw = {
        "metadata": {"name": name, "namespace": "default",
                     "uid": f"uid-{name}", "labels": {"app": "bench"}},
        "spec": {"containers": [
            {"name": f"c{k}", "resources": {
                "requests": {key: str(value) for key, value in requests.items()}
            }}
            for k, requests in enumerate(containers)
        ]},
        "status": {"phase": phase},
    }
    if node:
        raw["spec"]["nodeName"] = node
    if annotations:
        raw["metadata"]["annotations"] = dict(annotations)
    return raw


def gas_node(name: str, cards: int, per_card: dict) -> dict:
    return {
        "metadata": {"name": name, "labels": {
            "gpu.intel.com/cards": ".".join(f"card{k}" for k in range(cards)),
        }},
        "status": {"allocatable": {
            key: str(value * cards) for key, value in per_card.items()
        }},
    }


def bench_pod_name(index: int) -> str:
    return f"bench-{index:07d}"


def cycle_span(record: dict) -> float:
    """Seconds from the first byte of a pod's first verb sent to the last
    byte of its last verb's answer received; a pod that no node fits ends
    its cycle at its Filter."""
    t = record["t"]
    return (t[1] if np.isnan(t[3]) else t[3]) - t[0]


# -- the HTTP client (a copy of benchmarks/http_load.drive's raw socket) --------


class Client:
    """One keep-alive connection with pre-rendered request heads: http.client
    would add ~0.2 ms of object churn per call and file it under the server."""

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = bytearray()
        self.heads = {
            verb: (
                f"POST /scheduler/{verb} HTTP/1.1\r\nHost: bench\r\n"
                "Content-Type: application/json\r\nContent-Length: "
            ).encode()
            for verb in ("filter", "prioritize", "bind")
        }

    def render(self, verb: str, parts: list) -> bytes:
        length = sum(len(p) for p in parts)
        return b"".join(
            [self.heads[verb], str(length).encode(), b"\r\n\r\n", *parts]
        )

    def exchange(self, request: bytes):
        """(sent at, answered at, status, body): stamps around the first
        byte out and the last byte in."""
        sent = time.monotonic()
        self.sock.sendall(request)
        buf = self.buf
        while True:
            end = buf.find(b"\r\n\r\n")
            if end >= 0:
                break
            chunk = self.sock.recv(1 << 16)
            if not chunk:
                raise ConnectionError("server closed mid-response")
            buf += chunk
        header = bytes(buf[:end])
        status = int(header.split(b" ", 2)[1])
        length = 0
        for line in header.split(b"\r\n")[1:]:
            if line[:15].lower() == b"content-length:":
                length = int(line[15:])
        need = end + 4 + length
        while len(buf) < need:
            chunk = self.sock.recv(min(1 << 20, max(need - len(buf), 1 << 16)))
            if not chunk:
                raise ConnectionError("server closed mid-body")
            buf += chunk
        answered = time.monotonic()
        with memoryview(buf) as view:
            body = bytes(view[end + 4: need])
        del buf[:need]
        return sent, answered, status, body

    def close(self) -> None:
        self.sock.close()


class Ring:
    """Pre-rendered JSON array elements laid out twice over, so any contiguous
    wrapping window of candidates is one slice of one bytes object."""

    def __init__(self, elements: list, longest: int):
        doubled = elements + elements[:longest]
        self.offsets = np.concatenate(
            ([0], np.cumsum([len(e) + 1 for e in doubled]))
        )
        self.data = b",".join(doubled) + b","

    def window(self, start: int, count: int) -> bytes:
        return self.data[self.offsets[start]: self.offsets[start + count] - 1]


# -- the cycles -----------------------------------------------------------------


def compact(obj) -> bytes:
    return json.dumps(obj, separators=(",", ":")).encode()


class Cycles:
    """Pre-rendered pieces for one cell, and one scheduling cycle over them."""

    def __init__(self, job: dict):
        self.job = job
        config, traffic = job["config"], job["traffic"]
        self.seed = job["seed"]
        self.kind = config["assembler"]
        self.second = traffic["cycle"][1]
        self.wire = traffic["wire"]
        n = config["nodes"]
        self.names = node_names(config["node_prefix"], n)
        self.index = {name: i for i, name in enumerate(self.names)}
        self.name_bytes = [json.dumps(name).encode() for name in self.names]
        self.count = candidates_to_find(n)
        extra = traffic["candidates"]["examined_extra_max"]
        self.starts = {
            stream: window_starts(self.seed, n, self.count, extra, stream)
            for stream in (0, 1)
        }
        if self.wire == "nodes":
            shape = config["node_object"]
            self.node_bytes = [
                compact(node_object(self.seed, i, name, shape))
                for i, name in enumerate(self.names)
            ]
            self.ring = Ring(self.node_bytes, self.count)
            self.open_list, self.close_list = b',"Nodes":{"items":[', b"]}}"
        else:
            self.node_bytes = self.name_bytes
            self.ring = Ring(self.name_bytes, self.count)
            self.open_list, self.close_list = b',"NodeNames":[', b"]}"
        if self.kind == "tas":
            self.policies = [p["name"] for p in config["policies"]]
        else:
            self.templates = gas_templates(config)
            self.sequence = gas_template_sequence(config, self.seed)
        self.keep_every = max(int(traffic.get("keep_every", 1)), 1)
        self.keep_phase = int(rng(self.seed, 9).integers(0, self.keep_every))
        self.client = None
        self.port = 0

    def connect(self, port: int = 0) -> None:
        """Open the connection anew: the server drops a keep-alive
        connection that stays silent for its 5 s read timeout."""
        self.port = port or self.port
        if self.client is not None:
            self.client.close()
        self.client = Client(self.port)

    def pod(self, index: int, warm: bool) -> tuple:
        """(pod name, pod JSON bytes, which policy or template): the
        window's pods follow the seed, warm-up and probe pods take every
        policy or template in turn."""
        name = f"warm-{index:05d}" if warm else bench_pod_name(index)
        if self.kind == "tas":
            which = index % len(self.policies)
            return name, compact(tas_pod(self.policies[which], name)), which
        which = (index % len(self.templates) if warm
                 else int(self.sequence[index % SEQUENCE_LENGTH]))
        return name, compact(gas_pod(name, self.templates[which])), which

    def parse_filter(self, body: bytes) -> tuple:
        """(passed names, failed names) of a FilterResult; a Nodes-wire
        answer is read from its NodeNames tail, not its echoed objects."""
        if self.wire == "nodes":
            at = body.rfind(b'"NodeNames"')
            answer = json.loads(b"{" + body[at:]) if at >= 0 else {}
        else:
            answer = json.loads(body)
        passed = [name for name in answer.get("NodeNames") or () if name]
        return passed, list(answer.get("FailedNodes") or ())

    def run(self, index: int, warm: bool = False, candidates: tuple = None,
            second: bool = True, keep: bool = False, deep: bool = False) -> dict:
        """One pod's scheduling cycle; returns its record."""
        name, pod_bytes, which = self.pod(index, warm)
        if candidates is None:
            start = int(self.starts[int(warm)][index % SEQUENCE_LENGTH])
            count = self.count
        else:
            start, count = candidates
        request = self.client.render("filter", [
            b'{"Pod":', pod_bytes, self.open_list,
            self.ring.window(start, count), self.close_list,
        ])
        t0, t1, status, body = self.client.exchange(request)
        record = {
            "index": index, "which": which, "start": start, "count": count,
            "t": [t0, t1, np.nan, np.nan], "status": [status, 0],
            "second": "", "node": -1, "error": "",
        }
        passed = failed = ()
        if status == 200:
            try:
                passed, failed = self.parse_filter(body)
                record["passed"] = np.array(
                    [self.index[p] for p in passed], dtype=np.int32)
                record["failed"] = np.array(
                    [self.index[f] for f in failed], dtype=np.int32)
            except (ValueError, KeyError) as exc:
                record["error"] = f"filter answer unreadable: {exc!r}"
        else:
            record["error"] = f"filter status {status}"
        if deep:
            record["filter_body"] = body
        if record["error"] or not second or not len(passed):
            return record
        if self.second == "prioritize":
            request = self.client.render("prioritize", [
                b'{"Pod":', pod_bytes, self.open_list,
                b",".join([self.node_bytes[i] for i in record["passed"]]),
                self.close_list,
            ])
        else:
            record["node"] = int(record["passed"][0])
            request = self.client.render("bind", [compact({
                "PodName": name, "PodNamespace": "default",
                "PodUID": f"uid-{name}", "Node": passed[0],
            })])
        t2, t3, status, body = self.client.exchange(request)
        record["t"][2:] = [t2, t3]
        record["status"][1] = status
        record["second"] = self.second
        if status != 200:
            record["error"] = f"{self.second} status {status}: {body[:200]!r}"
        elif self.second == "bind":
            try:
                error = json.loads(body).get("Error") or ""
            except ValueError:
                error = f"bind answer unreadable: {body[:200]!r}"
            record["error"] = error and f"bind error: {error}"
        elif keep:
            record["second_body"] = body
        elif len(body) < 3:
            record["error"] = "prioritize answer empty"
        return record

    def kinds(self) -> int:
        return len(self.policies if self.kind == "tas" else self.templates)

    def warm(self) -> list:
        """Every policy or template ``warm_cycles`` times over, so every
        request shape of the cell compiles before the window."""
        passes = int(self.job["traffic"].get("warm_cycles", 1))
        return [self.run(index, warm=True)
                for index in range(passes * self.kinds())]

    def window(self, seconds: float) -> dict:
        deep = set()
        want_deep = int(self.job["traffic"].get("deep_cycles", 0))
        records = []
        index = 0
        began = time.monotonic()
        deadline = began + seconds
        while True:
            keep = index % self.keep_every == self.keep_phase
            is_deep = keep and len(deep) < want_deep
            if is_deep:
                deep.add(index)
            records.append(self.run(index, keep=keep, deep=is_deep))
            index += 1
            if time.monotonic() >= deadline:
                break
        return {"began": began, "ended": time.monotonic(), "records": records}

    def probe(self) -> list:
        """After the window and once the system has settled: one Filter per
        policy or template over EVERY node, never followed by a second verb."""
        self.connect()
        return [
            self.run(index, warm=True, candidates=(0, len(self.names)),
                     second=False)
            for index in range(self.kinds())
        ]


def reply(obj: dict, payload: bytes = b"") -> None:
    """One JSON line, then ``payload`` (its length is in the line)."""
    out = sys.stdout.buffer
    out.write(compact({**obj, "payload": len(payload)}) + b"\n")
    out.write(payload)
    out.flush()


def child_main() -> int:
    assert "jax" not in sys.modules, "the generator must never import JAX"
    job = json.loads(sys.stdin.readline())
    cycles = Cycles(job)
    reply({"ready": True, "candidates": cycles.count})
    for line in sys.stdin:
        command = json.loads(line)
        what = command["cmd"]
        if what == "connect":
            cycles.connect(command["port"])
            reply({"connected": True})
        elif what == "warm":
            reply({"done": "warm"}, pickle.dumps(cycles.warm()))
        elif what == "window":
            result = cycles.window(command["seconds"])
            assert "jax" not in sys.modules
            reply({"done": "window"}, pickle.dumps(result, protocol=4))
        elif what == "probe":
            reply({"done": "probe"}, pickle.dumps(cycles.probe()))
        elif what == "quit":
            break
    if cycles.client is not None:
        cycles.client.close()
    return 0


if __name__ == "__main__":
    sys.exit(child_main())
