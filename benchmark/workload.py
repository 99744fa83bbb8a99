"""The one general generator: a configuration (cluster, tenants, job
shapes) and a traffic mix (arrivals, completions) in, store objects out.

Everything is drawn from the seed, and every seed gets the same set of
sizes in another order: job kinds come in blocks that hold each kind as
often as its ``share``, request combinations come in blocks that hold
each combination once, and open-loop gaps come in blocks of
exponential quantiles. Each block is shuffled by the seed.

The generator keeps its own plain record of what it created (``Ledger``),
which is what the reference reads: the reference never looks at the
program's objects.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field

import numpy as np

MI = 1024 * 1024
GPU = "nvidia.com/gpu"
NS = "default"
TS0 = 1_000_000.0  # creation timestamps: TS0 + creation sequence, unique


@dataclass
class PodRec:
    key: str          # "namespace/name"
    name: str
    job: str          # pod group name
    cpu: float        # the request as handed to the program (cores)
    mem: float        # bytes
    gpu: float        # devices
    ts: float         # creation timestamp
    node: str = ""    # bound node ("" pending)
    running: bool = False


@dataclass
class JobRec:
    name: str
    queue: str
    min_member: int
    ts: float
    pods: list = field(default_factory=list)  # pod keys, creation order


def _blocks(rng: np.random.Generator, items: list):
    """Endless stream of ``items``, each block a seeded permutation."""
    while True:
        for i in rng.permutation(len(items)):
            yield items[i]


class Generator:
    """Job and arrival streams for one (config, traffic, seed)."""

    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        self.config = config
        self.traffic = traffic
        ss = np.random.SeedSequence([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
        kind_rng, combo_rng, gap_rng, place_rng, tenant_rng = (
            np.random.default_rng(s) for s in ss.spawn(5)
        )
        kinds = []
        for k, kind in enumerate(config["jobs"]):
            kinds += [k] * int(kind["share"])
        self._kinds = _blocks(kind_rng, kinds)
        self._combos = []
        for kind in config["jobs"]:
            w = kind["worker"]
            combos = list(itertools.product(w["cpu_milli"], w["memory_mi"], w["gpus"]))
            self._combos.append(_blocks(combo_rng, combos))
        self.place_rng = place_rng
        self.gap_rng = gap_rng
        q = config["queues"]
        self.queues = [f"q{i:03d}" for i in range(q["count"])]
        self.weights = [q["weights"][i % len(q["weights"])] for i in range(q["count"])]
        skew = float(traffic.get("tenant_skew", 0.0))
        p = np.array([(i + 1.0) ** -skew for i in range(q["count"])])
        self._tenant_p = p / p.sum()
        self.tenant_rng = tenant_rng
        self.seq = 0

    def _ts(self) -> float:
        self.seq += 1
        return TS0 + self.seq

    def next_job(self, ledger: "Ledger") -> JobRec:
        k = next(self._kinds)
        kind = self.config["jobs"][k]
        name = f"j{self.seq:08d}"
        queue = self.queues[int(self.tenant_rng.choice(len(self.queues), p=self._tenant_p))]
        n_pods = kind["workers"] + (1 if kind["ps"] else 0)
        mm = n_pods if kind["min_member"] == "all" else int(kind["min_member"])
        job = JobRec(name, queue, mm, self._ts())
        specs = []
        if kind["ps"]:
            specs.append(("ps", kind["ps"]["cpu_milli"], kind["ps"]["memory_mi"], 0))
        for w in range(kind["workers"]):
            cpu, mem, gpu = next(self._combos[k])
            specs.append((f"w{w}", cpu, mem, gpu))
        for suffix, cpu, mem, gpu in specs:
            pname = f"{name}-{suffix}"
            rec = PodRec(f"{NS}/{pname}", pname, name, cpu / 1000.0, float(mem * MI),
                         float(gpu), self._ts())
            job.pods.append(rec.key)
            ledger.pods[rec.key] = rec
            ledger.all_pods[rec.key] = rec
        ledger.jobs[name] = job
        ledger.all_jobs[name] = job
        return job

    def gaps(self, rate: float):
        """Endless open-loop inter-arrival gaps: blocks of exponential
        quantiles, each block a seeded permutation."""
        b = int(self.traffic.get("gap_block", 64))
        base = np.array([-math.log(1.0 - (i + 0.5) / b) for i in range(b)]) / rate
        while True:
            yield from base[self.gap_rng.permutation(b)]


class Ledger:
    """The generator's own record of the cluster: what it created, and
    the binds and evictions it saw land in the store. Plain data only."""

    def __init__(self, config: dict) -> None:
        n = config["nodes"]
        self.nodes = [f"n{i:05d}" for i in range(n["count"])]
        self.node_alloc = {
            "cpu": n["cpu_milli"] / 1000.0,
            "mem": float(n["memory_mi"] * MI),
            "gpu": float(n["gpus"]),
            "pods": int(n["pods"]),
        }
        self.pods: dict[str, PodRec] = {}      # live
        self.jobs: dict[str, JobRec] = {}      # live
        self.all_pods: dict[str, PodRec] = {}  # everything ever created
        self.all_jobs: dict[str, JobRec] = {}
        self.queues: list[tuple[str, int, float]] = []  # (name, weight, ts)
        self.log: list[tuple] = []  # ordered events, see cluster.py


def pod_object(rec: PodRec, job: JobRec):
    from kube_batch_tpu.apis.types import Container, ObjectMeta, Pod, PodPhase
    from kube_batch_tpu.apis.types import GROUP_NAME_ANNOTATION_KEY

    req = {"cpu": rec.cpu, "memory": rec.mem}
    if rec.gpu:
        req[GPU] = rec.gpu
    return Pod(
        metadata=ObjectMeta(
            name=rec.name, namespace=NS, uid=f"{NS}-{rec.name}",
            annotations={GROUP_NAME_ANNOTATION_KEY: job.name},
            creation_timestamp=rec.ts,
        ),
        phase=PodPhase.RUNNING if rec.running else PodPhase.PENDING,
        containers=[Container(requests=req)],
        node_name=rec.node,
    )


def pod_group_object(job: JobRec, phase: str):
    from kube_batch_tpu.apis.types import (
        ObjectMeta, PodGroup, PodGroupPhase, PodGroupSpec, PodGroupStatus,
    )

    return PodGroup(
        metadata=ObjectMeta(name=job.name, namespace=NS, uid=f"pg-{NS}-{job.name}",
                            creation_timestamp=job.ts),
        spec=PodGroupSpec(min_member=job.min_member, queue=job.queue),
        status=PodGroupStatus(phase=PodGroupPhase(phase)),
    )


def node_object(name: str, alloc: dict):
    from kube_batch_tpu.apis.types import Node, ObjectMeta

    rl = {"cpu": alloc["cpu"], "memory": alloc["mem"], "pods": float(alloc["pods"])}
    if alloc["gpu"]:
        rl[GPU] = alloc["gpu"]
    return Node(metadata=ObjectMeta(name=name, uid=name), allocatable=rl,
                capacity=dict(rl))


def queue_object(name: str, weight: int, ts: float):
    from kube_batch_tpu.apis.types import ObjectMeta, Queue, QueueSpec

    return Queue(metadata=ObjectMeta(name=name, uid=f"q-{name}", creation_timestamp=ts),
                 spec=QueueSpec(weight=weight))
