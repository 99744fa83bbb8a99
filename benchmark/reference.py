"""The plain reference: one scheduling session of the configured policy,
written from its description and independent of the program.

The policy is the scheduler conf the configurations run
(`benchmark/confs/scheduler-conf-tpu.yaml`): actions enqueue, reclaim,
allocate, backfill, preempt; plugins priority, gang, conformance, drf,
predicates, proportion and node order (least-requested plus
balanced-resource). It follows kube-batch's semantics with the repo's
stated choices: ties between equal node scores go to the first node in
name order, a priority queue pops the item that is best at the moment
it pops, and derived quotients (shares, balanced fractions, water-filled
deserved) are compared in the configuration's precision (float32).

It reads only the benchmark's own ledger of the cluster (benchmark/
workload.py): it imports nothing of the program and takes nothing the
program made, beyond the binds and evictions that earlier cycles landed
in the store, which are the state the next cycle starts from.

Inputs with a feature this reference does not model (zero requests,
affinity, priorities other than the default) cannot come from the
configurations; `World` refuses them.
"""

from __future__ import annotations

import heapq

import numpy as np

MAX_PRIORITY = 10
MIN_CPU = 10.0
MIN_MEM = 10.0 * 1024 * 1024
MIN_SC = 10.0
GPU = "nvidia.com/gpu"


# -- resources: milli-cpu, bytes, and scalar map (presence matters) -------


class Res:
    __slots__ = ("c", "m", "s")

    def __init__(self, c: float = 0.0, m: float = 0.0, s: dict | None = None) -> None:
        self.c = c
        self.m = m
        self.s = dict(s) if s else {}

    def clone(self) -> "Res":
        return Res(self.c, self.m, self.s)

    def add(self, r: "Res") -> "Res":
        self.c += r.c
        self.m += r.m
        for k, q in r.s.items():
            self.s[k] = self.s.get(k, 0.0) + q
        return self

    def sub(self, r: "Res") -> "Res":
        if not le(r, self):
            raise ValueError("reference: resource underflow")
        self.c -= r.c
        self.m -= r.m
        if self.s:
            for k, q in r.s.items():
                self.s[k] = self.s.get(k, 0.0) - q
        return self

    def names(self) -> list:
        return ["cpu", "memory", *self.s.keys()]

    def get(self, name: str) -> float:
        if name == "cpu":
            return self.c
        if name == "memory":
            return self.m
        return self.s.get(name, 0.0)


def le(a: Res, b: Res, dt=None) -> bool:
    """a <= b within the per-dimension epsilon; a scalar on the left with
    no scalars at all on the right fails."""
    if dt is None:
        ac, bc, am, bm = a.c, b.c, a.m, b.m
    else:
        ac, bc, am, bm = float(dt(a.c)), float(dt(b.c)), float(dt(a.m)), float(dt(b.m))
    if not (ac < bc or abs(bc - ac) < MIN_CPU):
        return False
    if not (am < bm or abs(bm - am) < MIN_MEM):
        return False
    for k, q in a.s.items():
        if not b.s:
            return False
        bq = b.s.get(k, 0.0)
        if dt is not None:
            q, bq = float(dt(q)), float(dt(bq))
        if not (q < bq or abs(bq - q) < MIN_SC):
            return False
    return True


def lt(a: Res, b: Res) -> bool:
    """Strictly less everywhere; with no scalars on either side, False."""
    if not (a.c < b.c and a.m < b.m):
        return False
    if not a.s:
        return bool(b.s)
    for k, q in a.s.items():
        if not b.s:
            return False
        if q >= b.s.get(k, 0.0):
            return False
    return True


def is_empty(a: Res) -> bool:
    if not (a.c < MIN_CPU and a.m < MIN_MEM):
        return False
    return all(q < MIN_SC for q in a.s.values())


def res_min(a: Res, b: Res) -> Res:
    out = Res(min(a.c, b.c), min(a.m, b.m))
    if not a.s or not b.s:
        return out
    for k, q in a.s.items():
        out.s[k] = min(q, b.s.get(k, 0.0))
    return out


def share(l: float, r: float, dt) -> float:
    if r == 0:
        return 0.0 if l == 0 else 1.0
    return float(dt(l) / dt(r))


# -- the world at a cycle's start ------------------------------------------

PENDING, ALLOCATED, PIPELINED, BINDING, BOUND, RUNNING, RELEASING = (
    "Pending", "Allocated", "Pipelined", "Binding", "Bound", "Running", "Releasing",
)
HOLDS = (ALLOCATED, BINDING, BOUND, RUNNING)  # statuses that hold resources


class Task:
    __slots__ = ("key", "job", "req", "ts", "uid", "status", "node")

    def __init__(self, key, job, req, ts, status, node):
        self.key = key
        self.job = job
        self.req = req
        self.ts = ts
        self.uid = key.replace("/", "-")
        self.status = status
        self.node = node


class Job:
    __slots__ = ("name", "queue", "min", "ts", "uid", "tasks", "alloc", "share")

    def __init__(self, name, queue, mm, ts):
        self.name = name
        self.queue = queue
        self.min = mm
        self.ts = ts
        self.uid = "default/" + name
        self.tasks: dict[str, Task] = {}
        self.alloc = Res()
        self.share = 0.0

    def count(self, *statuses) -> int:
        return sum(1 for t in self.tasks.values() if t.status in statuses)

    def ready(self) -> bool:
        return self.count(*HOLDS) >= self.min

    def pipelined(self) -> bool:
        return self.count(PIPELINED, *HOLDS) >= self.min


class World:
    """Cluster state at the start of a cycle, replayed from the ledger."""

    def __init__(self, ledger) -> None:
        self.ledger = ledger
        a = ledger.node_alloc
        self.node_names = sorted(ledger.nodes)
        self.alloc = Res(float(a["cpu"]) * 1000.0, float(a["mem"]),
                         {GPU: float(a["gpu"]) * 1000.0} if a["gpu"] else None)
        self.max_pods = int(a["pods"])
        self.queues = {name: (w, ts) for name, w, ts in ledger.queues}
        self.jobs: dict[str, tuple] = {}    # name -> (queue, min, ts), creation order
        self.pods: dict[str, list] = {}     # key -> [job, req, ts, node, running]
        self.on_node: dict[str, dict] = {n: {} for n in self.node_names}
        self.pos = 0
        self.cycle = -1

    def advance(self, cycle: int) -> tuple[dict, dict]:
        """Replay the log up to the start of ``cycle``; returns the binds
        {pod: node} and evictions {pod: job} that landed during the cycle
        before it."""
        log, led = self.ledger.log, self.ledger
        binds, evicts = {}, {}
        if cycle == self.cycle:
            return binds, evicts
        while self.pos < len(log):
            ev = log[self.pos]
            kind = ev[0]
            if kind == "cycle":
                if ev[1] == cycle:
                    self.pos += 1
                    self.cycle = cycle
                    return binds, evicts
                binds, evicts = {}, {}
            elif kind == "job+":
                job = led.all_jobs[ev[1]]
                self.jobs[ev[1]] = (job.queue, job.min_member, job.ts)
            elif kind == "job-":
                del self.jobs[ev[1]]
            elif kind == "pod+":
                rec = led.all_pods[ev[1]]
                req = Res(float(rec.cpu) * 1000.0, float(rec.mem),
                          {GPU: float(rec.gpu) * 1000.0} if rec.gpu else None)
                self.pods[ev[1]] = [rec.job, req, rec.ts, ev[2], ev[3]]
                if ev[2]:
                    self.on_node[ev[2]][ev[1]] = True
            elif kind in ("pod-", "evict"):
                p = self.pods.pop(ev[1])
                if p[3]:
                    del self.on_node[p[3]][ev[1]]
                if kind == "evict":
                    evicts[ev[1]] = p[0]
            elif kind == "bind":
                p = self.pods[ev[1]]
                p[3] = ev[2]
                self.on_node[ev[2]][ev[1]] = True
                binds[ev[1]] = ev[2]
            elif kind == "run":
                p = self.pods[ev[1]]
                p[4] = True
                node = self.on_node[p[3]]
                del node[ev[1]]
                node[ev[1]] = True   # the cache re-adds an updated pod at the end
            self.pos += 1
        self.cycle = cycle
        return binds, evicts


# -- one session ------------------------------------------------------------


class Session:
    """One scheduling session over a World's state; ``run()`` returns
    the binds {pod: node} and evictions {pod} it would land."""

    def __init__(self, world: World, dtype=np.float32) -> None:
        self.dt = dtype
        names = world.node_names
        self.names = names
        n = len(names)
        self.idx = {name: i for i, name in enumerate(names)}
        a = world.alloc
        has_sc = bool(a.s)
        self.cap_c = np.full(n, a.c)
        self.cap_m = np.full(n, a.m)
        self.max_pods = world.max_pods
        self.idle = [a.clone() for _ in range(n)]
        self.rel = [Res() for _ in range(n)]
        self.used = [Res() for _ in range(n)]
        self.node_tasks: list[dict] = [dict() for _ in range(n)]
        self.used_c = np.zeros(n)
        self.used_m = np.zeros(n)
        self.idle_c = np.full(n, a.c)
        self.idle_m = np.full(n, a.m)
        self.idle_g = np.full(n, a.s.get(GPU, 0.0))
        self.idle_sc = np.full(n, has_sc)
        self.rel_c = np.zeros(n)
        self.rel_m = np.zeros(n)
        self.rel_g = np.zeros(n)
        self.rel_sc = np.zeros(n, dtype=bool)
        self.ntasks = np.zeros(n, dtype=np.int64)
        self._keys: dict[tuple, int] = {}
        self._table = np.zeros((0, n))
        self._kc = np.zeros(0)
        self._km = np.zeros(0)
        self._dirty: set[int] = set()
        self.queues = dict(world.queues)
        self.jobs: dict[str, Job] = {}
        self.tasks: dict[str, Task] = {}
        for name, (queue, mm, ts) in world.jobs.items():
            if queue in self.queues:
                self.jobs[name] = Job(name, queue, mm, ts)
        for key, (job, req, ts, node, running) in world.pods.items():
            if job not in self.jobs:
                continue
            if is_empty(req):
                raise ValueError(f"reference: {key} requests nothing (backfill is not modelled)")
            status = (RUNNING if running else BOUND) if node else PENDING
            t = Task(key, job, req, ts, status, node)
            self.jobs[job].tasks[key] = t
            self.tasks[key] = t
        # node residents, in the order the scheduler's cache holds them
        for node, keys in world.on_node.items():
            i = self.idx[node]
            for key in keys:
                t = self.tasks.get(key)
                if t is not None:
                    self._node_add(i, t)
        self.binds: dict[str, str] = {}
        self.evicts: set[str] = set()
        self.total = Res()
        for _ in range(n):
            self.total.add(a)
        for job in self.jobs.values():
            valid = job.count(PIPELINED, PENDING, *HOLDS)
            if valid < job.min:
                raise ValueError(f"reference: job {job.name} has {valid} of {job.min} pods")
        self._open_drf()
        self._open_proportion()

    # -- node accounting ----------------------------------------------------

    def _sync(self, i: int) -> None:
        idle, rel, used = self.idle[i], self.rel[i], self.used[i]
        self.idle_c[i], self.idle_m[i] = idle.c, idle.m
        self.idle_g[i] = idle.s.get(GPU, 0.0)
        self.idle_sc[i] = bool(idle.s)
        self.rel_c[i], self.rel_m[i] = rel.c, rel.m
        self.rel_g[i] = rel.s.get(GPU, 0.0)
        self.rel_sc[i] = bool(rel.s)
        self.used_c[i], self.used_m[i] = used.c, used.m
        self.ntasks[i] = len(self.node_tasks[i])
        self._dirty.add(i)

    def _node_add(self, i: int, t: Task, strict: bool = False) -> None:
        if t.status == RELEASING:
            self.rel[i].add(t.req)
            self._take(self.idle[i], t.req, strict)
        elif t.status == PIPELINED:
            self._take(self.rel[i], t.req, strict)
        else:
            self._take(self.idle[i], t.req, strict)
        self.used[i].add(t.req)
        self.node_tasks[i][t.key] = t
        self._sync(i)

    @staticmethod
    def _take(r: Res, req: Res, strict: bool) -> None:
        if strict:
            r.sub(req)
            return
        r.c -= req.c
        r.m -= req.m
        if r.s:
            for k, q in req.s.items():
                r.s[k] = r.s.get(k, 0.0) - q

    def _node_remove(self, i: int, t: Task) -> None:
        if t.status == RELEASING:
            self.rel[i].sub(t.req)
            self.idle[i].add(t.req)
        elif t.status == PIPELINED:
            self.rel[i].add(t.req)
        else:
            self.idle[i].add(t.req)
        self.used[i].sub(t.req)
        del self.node_tasks[i][t.key]
        self._sync(i)

    # -- plugins: drf and proportion ------------------------------------------

    def _drf_share(self, alloc: Res) -> float:
        res = 0.0
        for name in self.total.names():
            s = share(alloc.get(name), self.total.get(name), self.dt)
            if s > res:
                res = s
        return res

    def _open_drf(self) -> None:
        for job in self.jobs.values():
            for t in job.tasks.values():
                if t.status in HOLDS:
                    job.alloc.add(t.req)
            job.share = self._drf_share(job.alloc)

    def _open_proportion(self) -> None:
        dt = self.dt
        self.qattr: dict[str, dict] = {}
        for job in self.jobs.values():
            if job.queue not in self.qattr:
                self.qattr[job.queue] = {
                    "weight": self.queues[job.queue][0], "deserved": Res(),
                    "alloc": Res(), "request": Res(), "share": 0.0,
                }
            attr = self.qattr[job.queue]
            for t in job.tasks.values():
                if t.status in HOLDS:
                    attr["alloc"].add(t.req)
                    attr["request"].add(t.req)
                elif t.status == PENDING:
                    attr["request"].add(t.req)
        remaining = self.total.clone()
        met: set = set()
        while True:
            total_weight = sum(a["weight"] for q, a in self.qattr.items() if q not in met)
            if total_weight == 0:
                break
            this_round = Res()
            for q, attr in self.qattr.items():
                if q in met:
                    continue
                old = attr["deserved"].clone()
                part = remaining.clone()
                ratio = attr["weight"] / total_weight
                part.c *= ratio
                part.m *= ratio
                for k in part.s:
                    part.s[k] *= ratio
                attr["deserved"].add(part)
                if not le(attr["deserved"], attr["request"]):
                    attr["deserved"] = res_min(attr["deserved"], attr["request"])
                    met.add(q)
                self._queue_share(attr)
                this_round.add(attr["deserved"].clone().sub(old))
            remaining.sub(this_round)
            if is_empty(remaining):
                break
        for attr in self.qattr.values():
            d = attr["deserved"]
            d.c = float(dt(d.c))
            d.m = float(dt(d.m))
            for k in d.s:
                d.s[k] = float(dt(d.s[k]))
            self._queue_share(attr)

    def _queue_share(self, attr: dict) -> None:
        res = 0.0
        d, a = attr["deserved"], attr["alloc"]
        for name in d.names():
            s = share(a.get(name), d.get(name), self.dt)
            if s > res:
                res = s
        attr["share"] = res

    def _on_allocate(self, t: Task) -> None:
        job = self.jobs[t.job]
        job.alloc.add(t.req)
        job.share = self._drf_share(job.alloc)
        attr = self.qattr[job.queue]
        attr["alloc"].add(t.req)
        self._queue_share(attr)

    def _on_deallocate(self, t: Task) -> None:
        job = self.jobs[t.job]
        job.alloc.sub(t.req)
        job.share = self._drf_share(job.alloc)
        attr = self.qattr[job.queue]
        attr["alloc"].sub(t.req)
        self._queue_share(attr)

    def overused(self, queue: str) -> bool:
        attr = self.qattr.get(queue)
        if attr is None:
            return False
        return le(attr["deserved"], attr["alloc"], self.dt)

    # -- orders -------------------------------------------------------------

    def job_key(self, job: Job) -> tuple:
        return (0, job.ready(), job.share, job.ts, job.uid)

    def queue_key(self, q: str) -> tuple:
        attr = self.qattr.get(q)
        return (attr["share"] if attr else 0.0, self.queues[q][1], "q-" + q)

    @staticmethod
    def task_key(t: Task) -> tuple:
        return (-1, t.ts, t.uid)

    # -- predicates and scores, over the whole node axis --------------------

    def _fits(self, req: Res, c, m, g, sc) -> np.ndarray:
        ok = ((req.c < c) | (np.abs(c - req.c) < MIN_CPU)) & (
            (req.m < m) | (np.abs(m - req.m) < MIN_MEM))
        if req.s:
            q = req.s.get(GPU, 0.0)
            ok &= sc & ((q < g) | (np.abs(g - q) < MIN_SC))
        return ok

    def fits_idle(self, req: Res) -> np.ndarray:
        return self._fits(req, self.idle_c, self.idle_m, self.idle_g, self.idle_sc)

    def fits_releasing(self, req: Res) -> np.ndarray:
        return self._fits(req, self.rel_c, self.rel_m, self.rel_g, self.rel_sc)

    def predicate(self) -> np.ndarray:
        return self.ntasks < self.max_pods

    def scores(self, req: Res) -> np.ndarray:
        """Node scores for ``req`` over every node. One row is kept per
        distinct request, refreshed only at the nodes whose usage changed;
        every entry is the same elementwise float computation."""
        if self._dirty:
            idx = np.fromiter(self._dirty, dtype=np.int64)
            if self._keys:
                self._table[:, idx] = self._score_vec(self._kc[:, None], self._km[:, None], idx)
            self._dirty.clear()
        key = (req.c, req.m)
        row = self._keys.get(key)
        if row is None:
            new = self._score_vec(np.array([[req.c]]), np.array([[req.m]]), slice(None))
            self._table = new if not self._keys else np.vstack([self._table, new])
            self._kc = np.append(self._kc, req.c)
            self._km = np.append(self._km, req.m)
            row = self._keys[key] = len(self._keys)
        return self._table[row]

    def _score_vec(self, c: np.ndarray, m: np.ndarray, idx) -> np.ndarray:
        """Scores of requests (c, m) (column vectors) at nodes ``idx``."""
        dt = self.dt
        rq_c = np.asarray(self.used_c[idx][None, :] + c, dt)
        rq_m = np.asarray(self.used_m[idx][None, :] + m, dt)
        cp_c = np.asarray(self.cap_c[idx], dt)[None, :]
        cp_m = np.asarray(self.cap_m[idx], dt)[None, :]

        def least_dim(rq, cp):
            safe = np.where(cp == 0.0, 1.0, cp).astype(dt)
            sc = np.floor_divide((cp - rq) * dt(MAX_PRIORITY), safe)
            return np.where((cp == 0.0) | (rq > cp), dt(0.0), sc)

        least = np.floor_divide(least_dim(rq_c, cp_c) + least_dim(rq_m, cp_m), dt(2.0))
        cpu_f = np.where(cp_c != 0.0, rq_c / np.where(cp_c == 0.0, 1.0, cp_c).astype(dt), dt(1.0))
        mem_f = np.where(cp_m != 0.0, rq_m / np.where(cp_m == 0.0, 1.0, cp_m).astype(dt), dt(1.0))
        balanced = np.where(
            (cpu_f >= 1.0) | (mem_f >= 1.0), dt(0.0),
            np.trunc(dt(MAX_PRIORITY) - np.abs(cpu_f - mem_f) * dt(MAX_PRIORITY)),
        )
        return least.astype(np.float64) + balanced.astype(np.float64)

    # -- session mutations --------------------------------------------------

    def allocate(self, t: Task, i: int) -> None:
        t.status = ALLOCATED
        t.node = self.names[i]
        self._node_add(i, t, strict=True)
        self._on_allocate(t)
        job = self.jobs[t.job]
        if job.ready():
            for u in job.tasks.values():
                if u.status == ALLOCATED:
                    u.status = BINDING
                    self.binds[u.key] = u.node

    def pipeline(self, t: Task, i: int) -> None:
        t.status = PIPELINED
        t.node = self.names[i]
        self._node_add(i, t, strict=True)
        self._on_allocate(t)

    def unpipeline(self, t: Task) -> None:
        i = self.idx[t.node]
        self._node_remove(i, t)
        t.status = PENDING
        t.node = ""
        self._on_deallocate(t)

    def evict_session(self, t: Task) -> None:
        i = self.idx[t.node]
        self._node_remove(i, t)
        t.status = RELEASING
        self._node_add(i, t)
        self._on_deallocate(t)

    def unevict(self, t: Task) -> None:
        i = self.idx[t.node]
        self._node_remove(i, t)
        t.status = RUNNING
        self._node_add(i, t)
        self._on_allocate(t)

    # -- actions ------------------------------------------------------------

    def run(self) -> tuple[dict, set]:
        # enqueue admits every job: each one has its pods, and no pod group
        # states minResources
        self.reclaim()
        self.allocate_action()
        self.preempt()
        return self.binds, self.evicts

    def _pending(self, job: Job) -> list:
        return [t for t in job.tasks.values() if t.status == PENDING]

    def allocate_action(self) -> None:
        live: set = set()
        heaps: dict[str, list] = {}
        seq = 0
        for job in self.jobs.values():
            live.add(job.queue)
            if not self._pending(job):
                continue  # popping a job with nothing pending changes nothing
            heaps.setdefault(job.queue, [])
            heapq.heappush(heaps[job.queue], (self.job_key(job), seq, job.name))
            seq += 1
        task_heaps: dict[str, list] = {}
        while live:
            q = min(live, key=self.queue_key)
            heap = heaps.get(q)
            if self.overused(q) or not heap:
                live.discard(q)
                continue
            _, _, name = heapq.heappop(heap)
            job = self.jobs[name]
            if name not in task_heaps:
                task_heaps[name] = sorted(self._pending(job), key=self.task_key, reverse=True)
            tasks = task_heaps[name]
            while tasks:
                t = tasks.pop()
                fit = (self.fits_idle(t.req) | self.fits_releasing(t.req)) & self.predicate()
                cand = np.flatnonzero(fit)
                if cand.size == 0:
                    break
                sc = self.scores(t.req)[cand]
                i = int(cand[int(np.argmax(sc))])
                if le(t.req, self.idle[i]):
                    self.allocate(t, i)
                elif le(t.req, self.rel[i]):
                    self.pipeline(t, i)
                if job.ready():
                    heapq.heappush(heap, (self.job_key(job), seq, name))
                    seq += 1
                    break

    def _pop(self, items: list, key):
        best = min(range(len(items)), key=lambda j: key(items[j]))
        return items.pop(best)

    def _victims(self, fns_by_tier, actor: Task, cands: list) -> list:
        if not cands:
            return []
        for fns in fns_by_tier:
            victims = None
            for fn in fns:
                got = fn(actor, cands)
                if victims is None:
                    victims = list(got)
                else:
                    keep = {v.key for v in got}
                    victims = [v for v in victims if v.key in keep]
            if victims:
                return victims
        return []

    def _gang_ok(self, actor: Task, cands: list) -> list:
        out = []
        for v in cands:
            job = self.jobs[v.job]
            occupied = job.count(*HOLDS)
            if job.min <= occupied - 1 or job.min == 1:
                out.append(v)
        return out

    @staticmethod
    def _conformance_ok(actor: Task, cands: list) -> list:
        return list(cands)  # no critical pods and no kube-system namespace here

    def _drf_ok(self, actor: Task, cands: list) -> list:
        out = []
        lalloc = self.jobs[actor.job].alloc.clone().add(actor.req)
        ls = self._drf_share(lalloc)
        allocs: dict[str, Res] = {}
        for v in cands:
            if v.job not in allocs:
                allocs[v.job] = self.jobs[v.job].alloc.clone()
            ralloc = allocs[v.job].sub(v.req)
            rs = self._drf_share(ralloc)
            if ls < rs or abs(ls - rs) <= 1e-6:
                out.append(v)
        return out

    def _proportion_ok(self, actor: Task, cands: list) -> list:
        out = []
        allocs: dict[str, Res] = {}
        for v in cands:
            job = self.jobs[v.job]
            attr = self.qattr[job.queue]
            if job.queue not in allocs:
                allocs[job.queue] = attr["alloc"].clone()
            alloc = allocs[job.queue]
            if lt(alloc, v.req):
                continue
            alloc.sub(v.req)
            if le(attr["deserved"], alloc, self.dt):
                out.append(v)
        return out

    def reclaim(self) -> None:
        tiers = [[self._gang_ok, self._conformance_ok], [self._proportion_ok]]
        queues: list = []
        preemptors: dict[str, list] = {}
        ptasks: dict[str, list] = {}
        for job in self.jobs.values():
            if job.queue not in queues:
                queues.append(job.queue)
            pend = self._pending(job)
            if pend:
                preemptors.setdefault(job.queue, []).append(job)
                ptasks[job.name] = list(pend)
        while queues:
            q = self._pop(queues, self.queue_key)
            if self.overused(q):
                continue
            jobs = preemptors.get(q)
            if not jobs:
                continue
            job = self._pop(jobs, self.job_key)
            tasks = ptasks.get(job.name)
            if not tasks:
                continue
            task = self._pop(tasks, self.task_key)
            assigned = False
            for i in np.flatnonzero(self.predicate()):
                i = int(i)
                cands = [
                    r for r in self.node_tasks[i].values()
                    if r.status == RUNNING and r.job in self.jobs
                    and self.jobs[r.job].queue != job.queue
                ]
                victims = self._victims(tiers, task, cands)
                if not victims:
                    continue
                total = Res()
                for v in victims:
                    total.add(v.req)
                if lt(total, task.req):
                    continue
                reclaimed = Res()
                for v in victims:
                    self.evict_session(v)
                    self.evicts.add(v.key)
                    reclaimed.add(v.req)
                    if le(task.req, reclaimed):
                        break
                if le(task.req, reclaimed):
                    self.pipeline(task, i)
                    assigned = True
                    break
            if assigned:
                queues.append(q)

    def _candidates(self, t: Task) -> list:
        ok = np.flatnonzero(self.predicate())
        sc = self.scores(t.req)[ok]
        order = np.argsort(-sc, kind="stable")
        return [int(i) for i in ok[order]]

    def _preempt_one(self, stmt: list, actor: Task, keep) -> bool:
        tiers = [[self._gang_ok, self._conformance_ok], [self._drf_ok]]
        for i in self._candidates(actor):
            cands = [r for r in self.node_tasks[i].values() if keep(r)]
            victims = self._victims(tiers, actor, cands)
            if not victims:
                continue
            total = Res()
            for v in victims:
                total.add(v.req)
            if lt(total, actor.req):
                continue
            order = sorted(victims, key=self.task_key, reverse=True)
            freed = Res()
            for v in order:
                self.evict_session(v)
                stmt.append(("evict", v))
                freed.add(v.req)
                if le(actor.req, freed):
                    break
            if le(actor.req, freed):
                self.pipeline(actor, i)
                stmt.append(("pipeline", actor))
                return True
        return False

    def _commit(self, stmt: list) -> None:
        for op, t in stmt:
            if op == "evict":
                self.evicts.add(t.key)

    def _discard(self, stmt: list) -> None:
        for op, t in reversed(stmt):
            if op == "evict":
                self.unevict(t)
            else:
                self.unpipeline(t)

    def preempt(self) -> None:
        preemptors: dict[str, list] = {}
        ptasks: dict[str, list] = {}
        under: list = []
        queues: list = []
        for job in self.jobs.values():
            if job.queue not in queues:
                queues.append(job.queue)
            pend = self._pending(job)
            if pend:
                preemptors.setdefault(job.queue, []).append(job)
                under.append(job)
                ptasks[job.name] = list(pend)
        for q in queues:
            while True:
                jobs = preemptors.get(q)
                if not jobs:
                    break
                pj = self._pop(jobs, self.job_key)
                stmt: list = []
                assigned = False
                while ptasks[pj.name]:
                    actor = self._pop(ptasks[pj.name], self.task_key)

                    def other_job(r, pj=pj, actor=actor):
                        return (r.status == RUNNING and r.job in self.jobs
                                and self.jobs[r.job].queue == pj.queue and r.job != actor.job)

                    if self._preempt_one(stmt, actor, other_job):
                        assigned = True
                    if pj.pipelined():
                        break
                if pj.pipelined():
                    self._commit(stmt)
                else:
                    self._discard(stmt)
                    continue
                if assigned:
                    jobs.append(pj)
            for job in under:
                while ptasks.get(job.name):
                    actor = self._pop(ptasks[job.name], self.task_key)

                    def same_job(r, actor=actor):
                        return r.status == RUNNING and r.job == actor.job

                    stmt = []
                    assigned = self._preempt_one(stmt, actor, same_job)
                    self._commit(stmt)
                    if not assigned:
                        break
