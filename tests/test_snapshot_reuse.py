"""Snapshot clone reuse: ``SchedulerCache.snapshot`` hands the next
session the node and job clones that neither the cache nor the last
session changed, told apart by their ``rev`` counters.

- equivalence: seeded random cache events and whole scheduling cycles
  (serial and device actions, a discarded session and a session-less
  snapshot in between); every snapshot must equal a fresh full clone of
  the mirror field for field and in order, and reuse must engage;
- revisions: each ``NodeInfo``/``JobInfo`` mutator bumps ``rev``; a clone
  that any action, session or Statement API, or the bulk replay changed
  reads a changed ``rev`` and is cloned afresh; a snapshot no closed
  session settled is followed by a whole clone;
- counter: ``snapshot_objects_total`` and the snapshot spans' ``reused`` /
  ``cloned`` attributes on a cluster with a known set of changed objects.
"""

from __future__ import annotations

import dataclasses
import pathlib
import random
from concurrent.futures import Future

import pytest

from kube_batch_tpu import metrics, obs
from kube_batch_tpu.api.job_info import JobInfo, TaskInfo
from kube_batch_tpu.api.node_info import NodeInfo
from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.apis.types import (
    Affinity,
    ObjectMeta,
    PodAffinityTerm,
    PodDisruptionBudget,
    PodGroupPhase,
    PodPhase,
    PriorityClass,
)
from kube_batch_tpu.cache import ClusterStore, SchedulerCache
from kube_batch_tpu.cache.store import PRIORITY_CLASSES
from kube_batch_tpu.conf import parse_scheduler_conf
from kube_batch_tpu.framework import close_session, get_action, open_session
from kube_batch_tpu.scheduler import Scheduler
from kube_batch_tpu.testing import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource_list,
)

EXAMPLES = pathlib.Path(__file__).resolve().parent.parent / "examples"
SERIAL = "scheduler-conf.yaml"
DEVICE = "scheduler-conf-tpu.yaml"


def tiers(conf: str):
    return parse_scheduler_conf((EXAMPLES / conf).read_text()).tiers


# -- the cluster ---------------------------------------------------------------


class Cluster:
    """A small seeded cluster written through the store: resident gangs
    bound and Running, pending gangs arriving, and the churn the cache
    sees in production."""

    def __init__(self, store: ClusterStore, rng: random.Random) -> None:
        self.store, self.rng = store, rng
        self.gangs = 0
        store.create_queue(build_queue("default"))
        store.create_priority_class(
            PriorityClass(metadata=ObjectMeta(name="high", uid="pc-high"), value=10)
        )
        for i in range(6):
            store.create_node(build_node(
                f"n{i}", build_resource_list(cpu=8, memory="16Gi", pods=20),
                labels={"zone": f"z{i % 2}"},
            ))
        for _ in range(6):
            self.gang(resident=True)

    def gang(self, resident: bool = False, queue: str = "default") -> None:
        rng = self.rng
        name = f"g{self.gangs}"
        self.gangs += 1
        size = rng.randint(1, 4)
        pg = build_pod_group(name, queue=queue, min_member=rng.randint(1, size))
        if rng.random() < 0.3:
            pg.spec.priority_class_name = "high"
        if resident:
            pg.status.phase = PodGroupPhase.RUNNING
        # pod affinity routes a pending gang through the device path's
        # host step (one task at a time against the live session)
        affinity = None
        if not resident and rng.random() < 0.25:
            affinity = Affinity(pod_affinity_preferred=[(5, PodAffinityTerm({"app": "web"}, "zone"))])
        self.store.create_pod_group(pg)
        for k in range(size):
            pod = build_pod(
                name=f"{name}-{k}", group_name=name,
                node_name=f"n{rng.randrange(6)}" if resident else "",
                phase=PodPhase.RUNNING if resident else PodPhase.PENDING,
                req=build_resource_list(cpu=rng.choice([0.5, 1, 2]),
                                        memory=rng.choice(["512Mi", "1Gi"])),
                labels={"app": "web"},
            )
            self.store.create_pod(dataclasses.replace(pod, affinity=affinity))

    def churn(self, cache: SchedulerCache) -> None:
        """One random cache event."""
        rng, store = self.rng, self.store
        pods = store.list("pods")
        kind = rng.choice([
            "arrive", "arrive", "delete_pod", "run", "node", "podgroup",
            "priority", "queue", "evict",
        ])
        if kind == "arrive":
            queue = "b" if store.get("queues", "b") is not None and rng.random() < 0.3 else "default"
            self.gang(queue=queue)
        elif kind == "delete_pod" and pods:
            pod = rng.choice(pods)
            store.delete_pod(pod.namespace, pod.name)
        elif kind == "run":
            bound = [p for p in pods if p.node_name and p.phase == PodPhase.PENDING]
            if bound:
                store.update_pod(dataclasses.replace(rng.choice(bound), phase=PodPhase.RUNNING))
        elif kind == "node":
            node = rng.choice(store.list("nodes"))
            labels = dict(node.metadata.labels, touched=str(rng.random()))
            store.update_node(dataclasses.replace(
                node, metadata=dataclasses.replace(node.metadata, labels=labels)
            ))
        elif kind == "podgroup":
            pgs = store.list("podgroups")
            if pgs:
                pg = rng.choice(pgs)
                spec = dataclasses.replace(
                    pg.spec,
                    priority_class_name="" if pg.spec.priority_class_name else "high",
                )
                store.update_pod_group(dataclasses.replace(pg, spec=spec))
        elif kind == "priority":
            pc = store.get(PRIORITY_CLASSES, "high")
            store.update(PRIORITY_CLASSES, dataclasses.replace(pc, value=pc.value + 1))
        elif kind == "queue":
            if store.get("queues", "b") is None:
                store.create_queue(build_queue("b"))
            else:
                store.delete_queue("b")
        elif kind == "evict":
            with cache._mutex:
                running = [
                    t for j in cache.jobs.values()
                    for t in j.task_status_index.get(TaskStatus.RUNNING, {}).values()
                ]
            if running:
                cache.evict(rng.choice(running), "churn")


# -- a fresh full clone, and the comparison ---------------------------------


def vec(r):
    return (r.milli_cpu, r.memory, dict(r.scalars), r.max_task_num)


def task_fields(t: TaskInfo) -> tuple:
    return (t.uid, t.job, t.name, t.namespace, t.node_name, t.status, t.priority,
            t.volume_ready, id(t.pod), vec(t.resreq), vec(t.init_resreq))


def node_fields(n: NodeInfo) -> tuple:
    return (n.name, id(n.node), id(n.other), vec(n.idle), vec(n.used),
            vec(n.releasing), vec(n.allocatable), vec(n.capability),
            [(k, task_fields(t)) for k, t in n.tasks.items()])


def job_fields(j: JobInfo) -> tuple:
    return (j.uid, j.name, j.namespace, j.queue, j.priority, j.min_available,
            dict(j.node_selector), j.creation_timestamp, id(j.pod_group), id(j.pdb),
            {k: vec(v) for k, v in j.nodes_fit_delta.items()},
            [(k, task_fields(t)) for k, t in j.tasks.items()],
            [(s, list(d)) for s, d in j.task_status_index.items()],
            vec(j.allocated), vec(j.total_request))


def full_clone(cache: SchedulerCache) -> tuple[dict, dict]:
    """What a snapshot clones from the mirror when it reuses nothing,
    with the job priorities resolved from the priority classes."""
    with cache._mutex:
        nodes = {name: n.clone() for name, n in cache.nodes.items()}
        jobs = {}
        for uid, job in cache.jobs.items():
            if job.pod_group is None and job.pdb is None:
                continue
            if job.queue not in cache.queues:
                continue
            clone = job.clone()
            if job.pod_group is not None:
                pc = cache.priority_classes.get(job.pod_group.spec.priority_class_name)
                clone.priority = pc.value if pc is not None else cache._default_priority
            jobs[uid] = clone
    return nodes, jobs


def assert_same_world(snap, cache: SchedulerCache) -> None:
    nodes, jobs = full_clone(cache)
    assert list(snap.nodes) == list(nodes)
    assert list(snap.jobs) == list(jobs)
    for name, want in nodes.items():
        got = snap.nodes[name]
        assert node_fields(got) == node_fields(want), name
        src = cache.nodes[name]
        assert got is not src
        assert all(t is not src.tasks[k] for k, t in got.tasks.items())
    for uid, want in jobs.items():
        got = snap.jobs[uid]
        assert job_fields(got) == job_fields(want), uid
        src = cache.jobs[uid]
        assert got is not src
        for s, d in got.task_status_index.items():
            for tid, t in d.items():
                assert t is got.tasks[tid] and t.status == s
                assert t is not src.tasks[tid]
                node = snap.nodes.get(t.node_name)
                assert node is None or all(t is not nt for nt in node.tasks.values())


def reused_total() -> tuple[float, float]:
    value = metrics.snapshot_objects.value
    return (value({"kind": "node", "how": "reused"}),
            value({"kind": "job", "how": "reused"}))


# -- equivalence --------------------------------------------------------------


@pytest.mark.parametrize("conf", [SERIAL, DEVICE])
@pytest.mark.parametrize("seed", [11, 2654435761])
def test_reused_snapshot_equals_a_full_clone(conf, seed):
    rng = random.Random(seed)
    store = ClusterStore()
    cache = SchedulerCache(store)
    cluster = Cluster(store, rng)
    sched = Scheduler(cache, scheduler_conf=str(EXAMPLES / conf))
    real_snapshot = cache.snapshot
    state = {"settled": False, "snapshots": 0}

    def checked_snapshot():
        before = reused_total()
        snap = real_snapshot()
        state["snapshots"] += 1
        assert_same_world(snap, cache)
        if not state["settled"]:
            assert reused_total() == before, "reused clones of an unsettled snapshot"
        state["settled"] = False
        return snap

    cache.snapshot = checked_snapshot
    start = reused_total()
    try:
        for step in range(24):
            for _ in range(rng.randint(1, 5)):
                cluster.churn(cache)
            if step % 8 == 5:
                # a discarded session: its clones changed, nothing settles
                ssn = open_session(cache, tiers(SERIAL))
                resident = [t for j in ssn.jobs.values()
                            for t in j.task_status_index.get(TaskStatus.RUNNING, {}).values()]
                if resident:
                    ssn.statement().evict(resident[0], "discarded")
                close_session(ssn, discard=True)
            elif step % 8 == 7:
                cache.snapshot()  # no session: nothing settles
            else:
                sched.run_once()
                state["settled"] = True
    finally:
        cache.stop()
    assert state["snapshots"] == 24
    nodes, jobs = reused_total()
    assert nodes > start[0] and jobs > start[1], "reuse never engaged"


# -- revisions ------------------------------------------------------------------


def _node() -> NodeInfo:
    ni = NodeInfo(build_node("n0", build_resource_list(cpu=8, memory="8Gi", pods=10)))
    ni.add_task(TaskInfo(build_pod(name="a", node_name="n0", phase=PodPhase.RUNNING,
                                   req=build_resource_list(cpu=1))))
    return ni


def _job() -> JobInfo:
    job = JobInfo("default/g", TaskInfo(build_pod(name="a", group_name="g",
                                                  req=build_resource_list(cpu=1))))
    job.set_pod_group(build_pod_group("g"))
    return job


def _pdb() -> PodDisruptionBudget:
    return PodDisruptionBudget(metadata=ObjectMeta(name="g", namespace="default"))


NODE_MUTATORS = {
    "add_task": lambda n: n.add_task(TaskInfo(build_pod(name="b", node_name="n0",
                                                        phase=PodPhase.RUNNING))),
    "remove_task": lambda n: n.remove_task(next(iter(n.tasks.values()))),
    "update_task": lambda n: n.update_task(next(iter(n.tasks.values()))),
    "set_node": lambda n: n.set_node(build_node("n0", build_resource_list(cpu=4))),
}

JOB_MUTATORS = {
    "add_task_info": lambda j: j.add_task_info(TaskInfo(build_pod(name="b", group_name="g"))),
    "delete_task_info": lambda j: j.delete_task_info(next(iter(j.tasks.values()))),
    "update_task_status": lambda j: j.update_task_status(
        next(iter(j.tasks.values())), TaskStatus.ALLOCATED),
    "set_pod_group": lambda j: j.set_pod_group(build_pod_group("g", min_member=2)),
    "unset_pod_group": lambda j: j.unset_pod_group(),
    "set_pdb": lambda j: j.set_pdb(_pdb()),
    "unset_pdb": lambda j: j.unset_pdb(),
}


@pytest.mark.parametrize("mutator", sorted(NODE_MUTATORS))
def test_node_mutator_bumps_rev(mutator):
    node = _node()
    rev = node.rev
    NODE_MUTATORS[mutator](node)
    assert node.rev > rev


@pytest.mark.parametrize("mutator", sorted(JOB_MUTATORS))
def test_job_mutator_bumps_rev(mutator):
    job = _job()
    rev = job.rev
    JOB_MUTATORS[mutator](job)
    assert job.rev > rev


def test_pod_group_redelivered_unchanged_keeps_rev():
    """The session's status write-back re-delivers the PodGroup a job
    already holds; that changes nothing a clone copies."""
    job = _job()
    rev = job.rev
    job.set_pod_group(job.pod_group)
    assert job.rev == rev
    job.pod_group.spec.queue = ""
    job.set_pod_group(job.pod_group, default_queue="default")
    assert job.rev == rev and job.queue == "default"
    job.set_pod_group(job.pod_group, default_queue="other")
    assert job.rev > rev and job.queue == "other"


def _fingerprints(ssn) -> tuple[dict, dict]:
    return ({name: (n.rev, node_fields(n)) for name, n in ssn.nodes.items()},
            {uid: (j.rev, job_fields(j)) for uid, j in ssn.jobs.items()})


def _assert_changes_bumped(before: dict, objects: dict) -> int:
    """Every object whose fields changed reads another ``rev``; returns
    how many changed."""
    changed = 0
    for key, (rev, fields) in before.items():
        obj = objects[key]
        now = node_fields(obj) if isinstance(obj, NodeInfo) else job_fields(obj)
        if now != fields:
            changed += 1
            assert obj.rev != rev, f"{key} changed without a rev bump"
    return changed


def _contended_cluster(store: ClusterStore) -> None:
    """Low-priority residents fill queue a; a high-priority gang in a
    preempts, queue b's job reclaims, and a gang that cannot complete
    leaves Allocated tasks behind."""
    store.create_queue(build_queue("a"))
    store.create_queue(build_queue("b"))
    for i in range(5):
        store.create_node(build_node(f"n{i}", build_resource_list(cpu=2, memory="4Gi", pods=10)))
    low = build_pod_group("low", queue="a", min_member=1)
    low.status.phase = PodGroupPhase.RUNNING
    store.create_pod_group(low)
    for i in range(7):
        store.create_pod(build_pod(
            name=f"low-{i}", group_name="low", node_name=f"n{i // 2}",
            phase=PodPhase.RUNNING, priority=1,
            req=build_resource_list(cpu=1, memory="1Gi"),
        ))
    store.create_pod_group(build_pod_group("high", queue="a", min_member=2))
    for i in range(2):
        store.create_pod(build_pod(name=f"high-{i}", group_name="high", priority=9,
                                   req=build_resource_list(cpu=1, memory="1Gi")))
    store.create_pod_group(build_pod_group("other", queue="b", min_member=1))
    store.create_pod(build_pod(name="other-0", group_name="other",
                               req=build_resource_list(cpu="1001m", memory="1Gi")))
    # pod affinity: the device path steps this gang on the host
    store.create_pod_group(build_pod_group("big", queue="b", min_member=3))
    affinity = Affinity(pod_affinity_preferred=[(5, PodAffinityTerm({"app": "web"}, "zone"))])
    for i in range(3):
        pod = build_pod(name=f"big-{i}", group_name="big", labels={"app": "web"},
                        req=build_resource_list(cpu="1500m", memory="1Gi"))
        store.create_pod(dataclasses.replace(pod, affinity=affinity))


@pytest.mark.parametrize("conf", [SERIAL, DEVICE])
def test_every_action_bumps_what_it_changes(conf):
    """Each action of the serial and the device pipeline, the bulk replay
    among them, runs on a snapshot; every clone it changed reads a new
    ``rev``, and the next snapshot clones each of those afresh."""
    store = ClusterStore()
    cache = SchedulerCache(store)
    try:
        _contended_cluster(store)
        parsed = parse_scheduler_conf((EXAMPLES / conf).read_text())
        ssn = open_session(cache, parsed.tiers)
        opened = {**{("node", k): (n, n.rev) for k, n in ssn.nodes.items()},
                  **{("job", k): (j, j.rev) for k, j in ssn.jobs.items()}}
        changed = 0
        for name in [a.strip() for a in parsed.actions.split(",")]:
            nodes, jobs = _fingerprints(ssn)
            get_action(name).execute(ssn)
            changed += _assert_changes_bumped(nodes, ssn.nodes)
            changed += _assert_changes_bumped(jobs, ssn.jobs)
        assert changed, "the actions changed nothing"
        close_session(ssn)
        snap = cache.snapshot()
        for (kind, key), (clone, rev) in opened.items():
            world = snap.nodes if kind == "node" else snap.jobs
            if clone.rev != rev and key in world:
                assert world[key] is not clone, key
        assert_same_world(snap, cache)
    finally:
        cache.stop()


def _resident_cluster(store: ClusterStore, gangs: int = 3) -> None:
    store.create_queue(build_queue("default"))
    for i in range(gangs):
        store.create_node(build_node(f"n{i}", build_resource_list(cpu=8, memory="8Gi", pods=10)))
    for g in range(gangs):
        pg = build_pod_group(f"r{g}", min_member=1)
        pg.status.phase = PodGroupPhase.RUNNING
        store.create_pod_group(pg)
        for k in range(2):
            store.create_pod(build_pod(
                name=f"r{g}-{k}", group_name=f"r{g}", node_name=f"n{g}",
                phase=PodPhase.RUNNING, req=build_resource_list(cpu=1, memory="1Gi"),
            ))


def _first_task(ssn, job: str) -> TaskInfo:
    return next(iter(ssn.jobs[job].tasks.values()))


# Each changes one job clone and its node through a session or Statement
# API and then puts the clone back where it was (statuses resident), so
# only the revisions can tell the clone changed.
SESSION_APIS = {
    "statement.evict+discard": lambda ssn, t: _evict_discard(ssn, t),
    "statement.pipeline+discard": lambda ssn, t: _pipeline_discard(ssn, t),
    "session.pipeline": lambda ssn, t: _pipeline(ssn, t),
    "session.allocate": lambda ssn, t: ssn.allocate(t, "n2"),
    "session.evict": lambda ssn, t: ssn.evict(t, "api"),
}


def _evict_discard(ssn, task: TaskInfo) -> None:
    stmt = ssn.statement()
    stmt.evict(task, "api")
    stmt.discard()


def _pipeline(ssn, task: TaskInfo) -> None:
    """Pipelined onto what an eviction on n2 releases."""
    ssn.evict(_first_task(ssn, "default/r2"), "api")
    ssn.pipeline(task, "n2")


def _pipeline_discard(ssn, task: TaskInfo) -> None:
    stmt = ssn.statement()
    stmt.evict(_first_task(ssn, "default/r2"), "api")
    stmt.pipeline(task, "n2")
    stmt.discard()


@pytest.mark.parametrize("api", sorted(SESSION_APIS))
def test_clone_changed_through_a_session_api_is_cloned_afresh(api):
    store = ClusterStore()
    cache = SchedulerCache(store)
    try:
        _resident_cluster(store)
        if api in ("session.pipeline", "session.allocate", "statement.pipeline+discard"):
            # a pending gang that stays short of its minMember: no dispatch
            pg = build_pod_group("p", min_member=3)
            store.create_pod_group(pg)
            for k in range(3):
                store.create_pod(build_pod(name=f"p-{k}", group_name="p",
                                           req=build_resource_list(cpu=1)))
            job = "default/p"
        else:
            job = "default/r0"
        conf = tiers(SERIAL)
        close_session(open_session(cache, conf))  # settles a first world
        ssn = open_session(cache, conf)
        first_nodes, first_jobs = dict(ssn.nodes), dict(ssn.jobs)
        task = _first_task(ssn, job)
        node = task.node_name or "n2"
        SESSION_APIS[api](ssn, task)
        close_session(ssn)
        snap = cache.snapshot()
        assert snap.nodes[node] is not first_nodes[node]
        assert snap.jobs[job] is not first_jobs[job]
        # the clones nobody changed are handed out again
        assert snap.nodes["n1"] is first_nodes["n1"]
        assert snap.jobs["default/r1"] is first_jobs["default/r1"]
        assert_same_world(snap, cache)
    finally:
        cache.stop()


def _settled(cache, conf) -> dict:
    ssn = open_session(cache, conf)
    world = dict(ssn.nodes)
    close_session(ssn)
    return world


def test_snapshot_without_a_session_settles_nothing():
    store = ClusterStore()
    cache = SchedulerCache(store)
    _resident_cluster(store)
    conf = tiers(SERIAL)
    world = _settled(cache, conf)
    direct = cache.snapshot()  # reuses the settled world...
    assert direct.nodes["n0"] is world["n0"]
    again = cache.snapshot()  # ...but settles nothing itself
    assert all(again.nodes[n] is not direct.nodes[n] for n in direct.nodes)
    assert all(again.jobs[j] is not direct.jobs[j] for j in direct.jobs)


def test_discarded_session_settles_nothing():
    store = ClusterStore()
    cache = SchedulerCache(store)
    _resident_cluster(store)
    conf = tiers(SERIAL)
    _settled(cache, conf)
    ssn = open_session(cache, conf)
    world = (dict(ssn.nodes), dict(ssn.jobs))
    close_session(ssn, discard=True)
    snap = cache.snapshot()
    assert all(snap.nodes[n] is not world[0][n] for n in snap.nodes)
    assert all(snap.jobs[j] is not world[1][j] for j in snap.jobs)


def test_unjoined_pipelined_session_settles_nothing():
    """A session whose deferred dispatch is still in flight at the next
    snapshot: that snapshot clones everything, and closing the older
    session afterwards settles nothing either."""
    store = ClusterStore()
    cache = SchedulerCache(store)
    _resident_cluster(store)
    conf = tiers(SERIAL)
    _settled(cache, conf)
    ssn = open_session(cache, conf)
    ssn.deferred_dispatch = Future()
    first = dict(ssn.nodes)
    mid = cache.snapshot()
    assert all(mid.nodes[n] is not first[n] for n in first)
    ssn.deferred_dispatch.set_result(None)
    close_session(ssn)
    last = cache.snapshot()
    assert all(last.nodes[n] is not first[n] and last.nodes[n] is not mid.nodes[n]
               for n in first)


def test_clone_changed_after_its_session_settled_is_cloned_afresh():
    """Streaming micro-cycles keep changing the node table the last full
    session left, after that session settled it."""
    store = ClusterStore()
    cache = SchedulerCache(store)
    _resident_cluster(store)
    world = _settled(cache, tiers(SERIAL))
    world["n0"].add_task(TaskInfo(build_pod(name="late", node_name="n0",
                                            phase=PodPhase.RUNNING)))
    snap = cache.snapshot()
    assert snap.nodes["n0"] is not world["n0"]
    assert snap.nodes["n1"] is world["n1"]
    assert_same_world(snap, cache)


def test_node_replaced_under_its_name_is_cloned_afresh():
    """A node deleted and added again is another mirror object, whatever
    its revision reads."""
    store = ClusterStore()
    cache = SchedulerCache(store)
    _resident_cluster(store)
    store.create_node(build_node("spare", build_resource_list(cpu=8, pods=10)))
    world = _settled(cache, tiers(SERIAL))
    store.delete_node("spare")
    store.create_node(build_node("spare", build_resource_list(cpu=4, pods=10)))
    assert cache.nodes["spare"].rev == world["spare"].rev == 0
    snap = cache.snapshot()
    assert snap.nodes["spare"] is not world["spare"]
    assert snap.nodes["n0"] is world["n0"]
    assert_same_world(snap, cache)


def test_job_with_unplaced_tasks_is_always_cloned_afresh():
    """A gang no node fits keeps its Pending tasks, and an unchanged
    revision: still, the jobs an action works on are never reused."""
    store = ClusterStore()
    cache = SchedulerCache(store)
    _resident_cluster(store)
    store.create_pod_group(build_pod_group("huge", min_member=1))
    store.create_pod(build_pod(name="huge-0", group_name="huge",
                               req=build_resource_list(cpu=100)))
    conf = tiers(SERIAL)
    for _ in range(2):
        ssn = open_session(cache, conf)
        pending = ssn.jobs["default/huge"]
        resident = ssn.jobs["default/r0"]
        close_session(ssn)
    snap = cache.snapshot()
    assert snap.jobs["default/huge"] is not pending
    assert snap.jobs["default/r0"] is resident


# -- counter ------------------------------------------------------------------


@pytest.fixture
def tracing(monkeypatch, tmp_path):
    monkeypatch.setenv(obs.ENV, "1")
    monkeypatch.setenv(obs.RECORDER_ENV, str(tmp_path / "flight"))
    obs.configure()
    obs.recorder.clear()
    yield
    obs.configure("off")
    obs.recorder.clear()


def test_counter_and_span_attributes_report_reuse(tracing):
    """Three resident gangs on three nodes; one pod of gang r0 (on n0) is
    deleted between two cycles: n0 and r0 are cloned, the rest reused."""
    store = ClusterStore()
    cache = SchedulerCache(store)
    _resident_cluster(store)
    conf = tiers(SERIAL)
    value = metrics.snapshot_objects.value
    labels = [(k, h) for k in ("node", "job") for h in ("reused", "cloned")]

    def counts():
        return {(k, h): value({"kind": k, "how": h}) for k, h in labels}

    c0 = counts()
    _settled(cache, conf)
    c1 = counts()
    assert {k: c1[k] - c0[k] for k in labels} == {
        ("node", "reused"): 0, ("node", "cloned"): 3,
        ("job", "reused"): 0, ("job", "cloned"): 3,
    }
    store.delete_pod("default", "r0-0")
    obs.recorder.clear()
    _settled(cache, conf)
    c2 = counts()
    assert {k: c2[k] - c1[k] for k in labels} == {
        ("node", "reused"): 2, ("node", "cloned"): 1,
        ("job", "reused"): 2, ("job", "cloned"): 1,
    }
    spans = {s["name"]: s["attrs"] for s in obs.recorder.spans()}
    assert spans["snapshot.nodes"] == {"objects": 3, "tasks": 5, "reused": 2, "cloned": 1}
    assert spans["snapshot.jobs"] == {"objects": 3, "tasks": 5, "reused": 2, "cloned": 1}
