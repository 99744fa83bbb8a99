"""The benchmark's side of the cluster: it seeds the store, makes the
store writes that the traffic mix (benchmark/loops/<loop>.py) asks for at
each cycle boundary, and records every bind and eviction that lands in
the store, with the time it landed.

All writes go through the store's create/update/delete calls, so the
scheduler cache's event handlers do their real work. The event log
(``ledger.log``) is what the reference replays:

    ("job+", name, phase)         ("job-", name)
    ("pod+", key, node, running)  ("pod-", key)     ("run", key)
    ("cycle", index)              ("bind", key, node)   ("evict", key)
"""

from __future__ import annotations

import time
from collections import deque

from benchmark.workload import (
    NS, Generator, JobRec, Ledger, PodRec, node_object, pod_group_object,
    pod_object, queue_object,
)


class Cluster:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        from kube_batch_tpu.cache import ClusterStore

        self.config = config
        self.gen = Generator(config, traffic, seed)
        self.ledger = Ledger(config)
        self.store = ClusterStore()
        self.cycle = -1
        self.bound = 0             # pods with a node, all jobs
        self.bind_time: dict[str, float] = {}
        self.due: dict[str, float] = {}   # job -> due arrival time (open loop)
        self.binds_per_cycle: dict[int, int] = {}
        self.evictions = 0
        self.write_errors = 0
        self._deleting: str | None = None
        self.order: deque = deque()       # live jobs, creation order

    # -- store writes ---------------------------------------------------

    def create_job(self, job: JobRec, phase: str = "Pending") -> None:
        self.store.create_pod_group(pod_group_object(job, phase))
        self.ledger.log.append(("job+", job.name, phase))
        for key in job.pods:
            self._create_pod(self.ledger.pods[key], job)
        self.order.append(job.name)

    def _create_pod(self, rec: PodRec, job: JobRec) -> None:
        self.store.create_pod(pod_object(rec, job))
        self.ledger.log.append(("pod+", rec.key, rec.node, rec.running))
        if rec.node:
            self.bound += 1

    def delete_job(self, name: str) -> None:
        job = self.ledger.jobs.pop(name)
        for key in job.pods:
            rec = self.ledger.pods.pop(key)
            self._deleting = key
            try:
                self.store.delete_pod(NS, rec.name)
            finally:
                self._deleting = None
            self.ledger.log.append(("pod-", key))
            if rec.node:
                self.bound -= 1
        self.store.delete_pod_group(NS, name)
        self.ledger.log.append(("job-", name))

    # -- what lands in the store ----------------------------------------

    def watch(self) -> None:
        """Subscribe to pod events. Registered after the scheduler's cache,
        so the store's replay of existing pods costs this handler nothing."""
        from kube_batch_tpu.cache.store import PODS, EventHandler

        self.store.add_event_handler(
            PODS, EventHandler(on_update=self._on_update, on_delete=self._on_delete)
        )

    def _on_update(self, old, new) -> None:
        if old.node_name or not new.node_name:
            return
        t = time.perf_counter()
        key = f"{new.namespace}/{new.name}"
        rec = self.ledger.pods.get(key)
        if rec is None:
            return
        rec.node = new.node_name
        self.bound += 1
        self.bind_time[key] = t
        self.binds_per_cycle[self.cycle] = self.binds_per_cycle.get(self.cycle, 0) + 1
        self.ledger.log.append(("bind", key, new.node_name))

    def _on_delete(self, old) -> None:
        key = f"{old.namespace}/{old.name}"
        if key == self._deleting:
            return
        rec = self.ledger.pods.get(key)
        if rec is None:
            return
        self.evictions += 1
        self.ledger.log.append(("evict", key))
        if rec.node:
            self.bound -= 1
        self.ledger.jobs[rec.job].pods.remove(key)
        del self.ledger.pods[key]

    # -- set-up ---------------------------------------------------------

    def seed(self) -> None:
        """Nodes, queues and the resident jobs, pre-bound and Running."""
        led, gen = self.ledger, self.gen
        for i, (name, weight) in enumerate(zip(gen.queues, gen.weights)):
            ts = float(i + 1)
            self.store.create_queue(queue_object(name, weight, ts))
            led.queues.append((name, weight, ts))
        for name in led.nodes:
            self.store.create_node(node_object(name, led.node_alloc))
        res = self.config["residents"]
        for job in self._place_residents(res):
            self.create_job(job, res["pod_group_phase"])

    def _place_residents(self, res: dict) -> list:
        """Draw resident jobs and place them over a seeded node order,
        first fit; a job that does not fit whole is dropped."""
        led, rng = self.ledger, self.gen.place_rng
        a = led.node_alloc
        n = len(led.nodes)
        free = [[a["cpu"], a["mem"], a["gpu"], a["pods"]] for _ in range(n)]
        jobs, pods = [], 0
        while pods < res["pods"]:
            job = self.gen.next_job(led)
            order = rng.permutation(n).tolist()
            placed, p = [], 0
            for key in job.pods:
                rec = led.pods[key]
                for step in range(n):  # first fit from the last pod's node on
                    i = order[(p + step) % n]
                    f = free[i]
                    if f[0] >= rec.cpu and f[1] >= rec.mem and f[2] >= rec.gpu and f[3] >= 1:
                        break
                else:
                    break
                p = (p + step + 1) % n
                f[0] -= rec.cpu
                f[1] -= rec.mem
                f[2] -= rec.gpu
                f[3] -= 1
                placed.append((rec, i))
            if len(placed) < len(job.pods):
                for rec, i in placed:
                    f = free[i]
                    f[0] += rec.cpu
                    f[1] += rec.mem
                    f[2] += rec.gpu
                    f[3] += 1
                for key in job.pods:
                    del led.pods[key]
                del led.jobs[job.name]
                continue
            for rec, i in placed:
                rec.node = led.nodes[i]
                rec.running = True
            pods += len(job.pods)
            jobs.append(job)
        return jobs

    def fully_bound(self, name: str) -> bool:
        job = self.ledger.jobs[name]
        pods = self.ledger.pods
        return all(pods[k].node for k in job.pods)

    def start_cycle(self) -> None:
        self.cycle += 1
        self.ledger.log.append(("cycle", self.cycle))

    def pending_pods(self) -> int:
        return len(self.ledger.pods) - self.bound

    def pending_jobs(self) -> int:
        pods = self.ledger.pods
        return sum(1 for j in self.ledger.jobs.values() if any(not pods[k].node for k in j.pods))
