"""SchedulerCache unit tests: feed store mutations, assert the mirror
(the pattern of reference cache/cache_test.go:128-227, extended to the
write side, resync, GC, and snapshot policy)."""

from __future__ import annotations

import pathlib
import time

import pytest

from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.apis.types import (
    GROUP_NAME_ANNOTATION_KEY,
    ObjectMeta,
    PodDisruptionBudget,
    PodGroupPhase,
    PodPhase,
    PriorityClass,
)
from kube_batch_tpu.cache import ClusterStore, SchedulerCache, shadow_pod_group
from kube_batch_tpu.scheduler import Scheduler
from kube_batch_tpu.testing import (
    FakeEvictor,
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource,
    build_resource_list,
)


def wait_until(pred, timeout=5.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.fixture
def store():
    return ClusterStore()


@pytest.fixture
def cache(store):
    sc = SchedulerCache(store)
    yield sc
    sc.stop()


def test_add_pod_accounts_on_node(store, cache):
    store.create_node(build_node("n1", build_resource_list(cpu=8, memory="16Gi", pods=100)))
    store.create_pod(
        build_pod(name="p1", node_name="n1", phase=PodPhase.RUNNING,
                  req=build_resource_list(cpu=2, memory="4Gi"))
    )
    ni = cache.nodes["n1"]
    assert ni.used == build_resource(cpu=2, memory="4Gi")
    assert ni.idle == build_resource(cpu=6, memory="12Gi")
    assert len(ni.tasks) == 1


def test_node_arriving_after_pods_replays_accounting(store, cache):
    """Pods seen before their node: accounting lands once the node shows
    up (reference event_handlers.go:70-88 + node_info SetNode)."""
    store.create_pod(
        build_pod(name="p1", node_name="n1", phase=PodPhase.RUNNING,
                  req=build_resource_list(cpu=2))
    )
    assert cache.nodes["n1"].node is None  # placeholder, no capacity yet
    store.create_node(build_node("n1", build_resource_list(cpu=8)))
    ni = cache.nodes["n1"]
    assert ni.used == build_resource(cpu=2)
    assert ni.idle == build_resource(cpu=6)


def test_shadow_pod_group_for_annotationless_pod(store, cache):
    store.create_pod(build_pod(name="solo", req=build_resource_list(cpu=1)))
    assert len(cache.jobs) == 1
    job = next(iter(cache.jobs.values()))
    assert shadow_pod_group(job.pod_group)
    assert job.min_available == 1
    assert job.queue == "default"
    assert job.pod_group.status.phase == PodGroupPhase.INQUEUE


def test_shadow_group_shares_controller(store, cache):
    """Sibling pods of one controller share one shadow job
    (reference cache/util.go:43-49 GetController)."""
    for i in range(3):
        pod = build_pod(name=f"rs-{i}", req=build_resource_list(cpu=1))
        pod.metadata.owner_job = "rs-frontend"
        store.create_pod(pod)
    assert len(cache.jobs) == 1
    assert len(next(iter(cache.jobs.values())).tasks) == 3


def test_other_scheduler_pending_pod_filtered(store, cache):
    store.create_pod(build_pod(name="alien", scheduler_name="default-scheduler"))
    assert not cache.jobs


def test_other_scheduler_running_pod_occupies_node(store, cache):
    """Non-pending pods pass the filter regardless of scheduler — they
    hold node resources (reference cache.go:245-266)."""
    store.create_node(build_node("n1", build_resource_list(cpu=8)))
    store.create_pod(
        build_pod(name="alien", node_name="n1", phase=PodPhase.RUNNING,
                  scheduler_name="default-scheduler", req=build_resource_list(cpu=3))
    )
    assert cache.nodes["n1"].idle == build_resource(cpu=5)
    assert not cache.jobs  # no shadow job for foreign pods


def test_pod_group_binds_tasks_and_default_queue(store, cache):
    store.create_pod_group(build_pod_group("pg1", min_member=2))
    store.create_pod(build_pod(name="m1", group_name="pg1", req=build_resource_list(cpu=1)))
    store.create_pod(build_pod(name="m2", group_name="pg1", req=build_resource_list(cpu=1)))
    job = cache.jobs["default/pg1"]
    assert job.min_available == 2
    assert len(job.tasks) == 2
    assert job.queue == "default"  # empty spec.queue -> defaultQueue


def test_pdb_gang_source(store, cache):
    pdb = PodDisruptionBudget(
        metadata=ObjectMeta(name="pdb1", namespace="default"), min_available=2
    )
    store.create_pdb(pdb)
    job = cache.jobs["default/pdb1"]
    assert job.pdb is pdb
    assert job.min_available == 2
    assert job.queue == "default"


def test_snapshot_priority_class_resolution(store, cache):
    store.create_queue(build_queue("default"))
    store.create_priority_class(
        PriorityClass(metadata=ObjectMeta(name="high"), value=1000)
    )
    store.create_priority_class(
        PriorityClass(metadata=ObjectMeta(name="base"), value=7, global_default=True)
    )
    pg_hi = build_pod_group("hi")
    pg_hi.spec.priority_class_name = "high"
    store.create_pod_group(pg_hi)
    store.create_pod_group(build_pod_group("lo"))
    store.create_pod(build_pod(name="h", group_name="hi"))
    store.create_pod(build_pod(name="l", group_name="lo"))

    snap = cache.snapshot()
    assert snap.jobs["default/hi"].priority == 1000
    assert snap.jobs["default/lo"].priority == 7  # global default

    store.delete_priority_class("base")
    snap = cache.snapshot()
    assert snap.jobs["default/lo"].priority == 0


def test_snapshot_skips_job_with_missing_queue(store, cache):
    store.create_queue(build_queue("default"))
    pg = build_pod_group("orphan", queue="nonexistent")
    store.create_pod_group(pg)
    store.create_pod(build_pod(name="o", group_name="orphan"))
    snap = cache.snapshot()
    assert "default/orphan" not in snap.jobs
    # ...and jobs in a live queue survive.
    store.create_pod_group(build_pod_group("ok", queue="default"))
    store.create_pod(build_pod(name="k", group_name="ok"))
    assert "default/ok" in cache.snapshot().jobs


def test_snapshot_is_deep_clone(store, cache):
    store.create_queue(build_queue("default"))
    store.create_node(build_node("n1", build_resource_list(cpu=8)))
    store.create_pod(build_pod(name="p", req=build_resource_list(cpu=1)))
    snap = cache.snapshot()
    job = next(iter(snap.jobs.values()))
    task = next(iter(job.tasks.values()))
    job.update_task_status(task, TaskStatus.ALLOCATED)
    snap.nodes["n1"].add_task(task)
    # The cache mirror is untouched by session mutations.
    cached = next(iter(cache.jobs.values()))
    assert next(iter(cached.tasks.values())).status == TaskStatus.PENDING
    assert cache.nodes["n1"].idle == build_resource(cpu=8)


def test_bind_round_trip(store, cache):
    """bind() flips the mirror to Binding, the async store write sets
    pod.node_name, and the resulting update event lands the task Bound
    on the node (reference cache.go:404-448)."""
    cache.run()
    store.create_node(build_node("n1", build_resource_list(cpu=8)))
    store.create_pod(build_pod(name="p1", req=build_resource_list(cpu=2)))
    job = next(iter(cache.jobs.values()))
    task = next(iter(job.tasks.values()))

    cache.bind(task, "n1")
    wait_until(
        lambda: store.get_pod("default", "p1").node_name == "n1",
        what="bind write-back",
    )
    wait_until(
        lambda: next(iter(next(iter(cache.jobs.values())).tasks.values())).status
        == TaskStatus.BOUND,
        what="Binding -> Bound round trip",
    )
    assert cache.nodes["n1"].used == build_resource(cpu=2)
    assert len(cache.nodes["n1"].tasks) == 1


class FailingBinder:
    def __init__(self, store, fail_times):
        self._inner_store = store
        self.fail_times = fail_times
        self.calls = 0

    def bind(self, pod, hostname):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise RuntimeError("injected bind failure")
        import dataclasses

        self._inner_store.update_pod(dataclasses.replace(pod, node_name=hostname))


def test_failed_bind_resyncs_task(store):
    """A failed bind re-enters through errTasks: the task returns to
    Pending and is schedulable again (reference cache.go:512-534)."""
    binder = FailingBinder(store, fail_times=10**9)
    sc = SchedulerCache(store, binder=binder)
    sc.run()
    try:
        store.create_node(build_node("n1", build_resource_list(cpu=8)))
        store.create_pod(build_pod(name="p1", req=build_resource_list(cpu=2)))
        task = next(iter(next(iter(sc.jobs.values())).tasks.values()))
        sc.bind(task, "n1")
        wait_until(lambda: binder.calls >= 1, what="binder attempt")
        wait_until(
            lambda: next(iter(next(iter(sc.jobs.values())).tasks.values())).status
            == TaskStatus.PENDING,
            what="resync back to Pending",
        )
        # Node accounting rolled back too.
        assert sc.nodes["n1"].used == build_resource()
        assert store.get_pod("default", "p1").node_name == ""
    finally:
        sc.stop()


def test_evict_releases_then_deletes(store, cache):
    cache.run()
    store.create_node(build_node("n1", build_resource_list(cpu=8)))
    store.create_pod(
        build_pod(name="p1", node_name="n1", phase=PodPhase.RUNNING,
                  req=build_resource_list(cpu=2))
    )
    task = next(iter(next(iter(cache.jobs.values())).tasks.values()))
    cache.evict(task, "preempted")
    wait_until(lambda: store.get_pod("default", "p1") is None, what="evict delete")
    wait_until(lambda: not cache.nodes["n1"].tasks, what="node cleanup")
    assert cache.nodes["n1"].idle == build_resource(cpu=8)


def test_terminated_job_gc(store, cache):
    """Deleting the PodGroup and all pods garbage-collects the job
    through the deletedJobs queue (reference cache.go:480-510)."""
    cache.run()
    store.create_pod_group(build_pod_group("pg1"))
    store.create_pod(build_pod(name="m1", group_name="pg1"))
    assert "default/pg1" in cache.jobs
    store.delete_pod("default", "m1")
    store.delete_pod_group("default", "pg1")
    wait_until(lambda: "default/pg1" not in cache.jobs, what="job GC")


def test_shadow_job_gc_after_pod_delete(store, cache):
    """Shadow jobs are GC'd once their last pod goes away — the shadow
    PodGroup lives only in the cache, so it counts as absent for
    job_terminated (divergence from reference api/helpers.go:101-106)."""
    cache.run()
    store.create_pod(build_pod(name="solo", req=build_resource_list(cpu=1)))
    assert len(cache.jobs) == 1
    store.delete_pod("default", "solo")
    wait_until(lambda: not cache.jobs, what="shadow job GC")


def test_pdb_does_not_stomp_podgroup_queue(store, cache):
    pg = build_pod_group("pg1", queue="research")
    store.create_pod_group(pg)
    pdb = PodDisruptionBudget(
        metadata=ObjectMeta(name="pdb1", namespace="default", owner_job="default/pg1"),
        min_available=2,
    )
    store.create_pdb(pdb)
    assert cache.jobs["default/pg1"].queue == "research"


def test_unschedulable_condition_writes_through_store(store, cache):
    """record_job_status_event posts PodScheduled=False through the
    store, not onto a possibly-stale cached pod object."""
    store.create_queue(build_queue("default"))
    store.create_pod(build_pod(name="p1", req=build_resource_list(cpu=1)))
    job = next(iter(cache.jobs.values()))
    cache.record_job_status_event(job)
    conds = store.get_pod("default", "p1").conditions
    assert any(c.type == "PodScheduled" and c.status == "False" for c in conds)


def test_node_update_reconciles_resources(store, cache):
    node = build_node("n1", build_resource_list(cpu=8))
    store.create_node(node)
    store.create_pod(
        build_pod(name="p1", node_name="n1", phase=PodPhase.RUNNING,
                  req=build_resource_list(cpu=2))
    )
    bigger = build_node("n1", build_resource_list(cpu=16))
    store.update_node(bigger)
    ni = cache.nodes["n1"]
    assert ni.idle == build_resource(cpu=14)
    assert ni.used == build_resource(cpu=2)


def test_delete_node(store, cache):
    store.create_node(build_node("n1", build_resource_list(cpu=8)))
    store.delete_node("n1")
    assert "n1" not in cache.nodes


def test_pod_update_resize_reaccounts(store, cache):
    store.create_node(build_node("n1", build_resource_list(cpu=8)))
    pod = build_pod(name="p1", node_name="n1", phase=PodPhase.RUNNING,
                    req=build_resource_list(cpu=2))
    store.create_pod(pod)
    resized = build_pod(name="p1", node_name="n1", phase=PodPhase.RUNNING,
                        req=build_resource_list(cpu=4))
    resized.metadata.uid = pod.metadata.uid
    store.update_pod(resized)
    assert cache.nodes["n1"].used == build_resource(cpu=4)
    job = next(iter(cache.jobs.values()))
    assert len(job.tasks) == 1


def test_shadow_job_member_delete_does_not_strand(store, cache):
    """Deleting a shadow-group pod removes it from the job too (the
    reference leaks these, event_handlers.go:160-180; see
    cache._resolve_shadow_job)."""
    store.create_pod(build_pod(name="solo", req=build_resource_list(cpu=1)))
    job = next(iter(cache.jobs.values()))
    assert len(job.tasks) == 1
    store.delete_pod("default", "solo")
    assert not job.tasks


def test_terminated_pod_lifecycle_does_not_strand_task(store, cache):
    """A Succeeded pod (never resident on the node mirror) can still be
    updated and deleted: update keeps the task, delete GCs the job."""
    cache.run()
    store.create_node(build_node("n1", build_resource_list(cpu=8)))
    pod = build_pod(name="p1", node_name="n1", phase=PodPhase.RUNNING,
                    req=build_resource_list(cpu=2))
    store.create_pod(pod)
    done = build_pod(name="p1", node_name="n1", phase=PodPhase.SUCCEEDED,
                     req=build_resource_list(cpu=2))
    done.metadata.uid = pod.metadata.uid
    store.update_pod(done)
    assert cache.nodes["n1"].idle == build_resource(cpu=8)  # released
    job = next(iter(cache.jobs.values()))
    assert len(job.tasks) == 1  # task survives in Succeeded
    # Another update (e.g. a condition append) must not strand it.
    store.update_pod(done)
    assert len(next(iter(cache.jobs.values())).tasks) == 1
    store.delete_pod("default", "p1")
    wait_until(lambda: not cache.jobs, what="terminated shadow job GC")


def test_node_condition_change_reaches_mirror(store, cache):
    """Ready/pressure flips refresh the cached Node even when nothing
    else changed, so predicates see them next snapshot."""
    from kube_batch_tpu.apis.types import NodeCondition

    store.create_node(build_node("n1", build_resource_list(cpu=8)))
    broken = build_node("n1", build_resource_list(cpu=8))
    broken.conditions = [NodeCondition(type="Ready", status="False")]
    store.update_node(broken)
    assert not cache.nodes["n1"].node.ready()


def test_cache_stop_then_run_resyncs_again(store):
    """stop() then run() must leave the resync machinery live (the
    retry queues reopen)."""
    # past the in-place retry budget (KBT_WRITE_RETRIES, default 2), so
    # the failure reaches the errTasks resync machinery under test —
    # fewer failures would now be absorbed by the retry-with-jitter rung
    binder = FailingBinder(store, fail_times=3)
    sc = SchedulerCache(store, binder=binder)
    sc.run()
    sc.stop()
    sc.run()
    try:
        store.create_node(build_node("n1", build_resource_list(cpu=8)))
        store.create_pod(build_pod(name="p1", req=build_resource_list(cpu=2)))
        task = next(iter(next(iter(sc.jobs.values())).tasks.values()))
        sc.bind(task, "n1")  # first attempt fails -> resync -> retried later
        wait_until(lambda: binder.calls >= 1, what="first bind attempt")
        wait_until(
            lambda: next(iter(next(iter(sc.jobs.values())).tasks.values())).status
            == TaskStatus.PENDING,
            what="resync after restart",
        )
    finally:
        sc.stop()


def test_group_annotation_requires_podgroup_to_snapshot(store, cache):
    """An annotated pod whose PodGroup never arrives builds a spec-less
    job that snapshot() skips (reference cache.go:545-552)."""
    store.create_queue(build_queue("default"))
    pod = build_pod(name="waiting", group_name="late-pg")
    store.create_pod(pod)
    assert "default/late-pg" in cache.jobs
    assert "default/late-pg" not in cache.snapshot().jobs
    store.create_pod_group(build_pod_group("late-pg"))
    assert "default/late-pg" in cache.snapshot().jobs


def test_annotated_pod_survives_group_annotation(store, cache):
    pod = build_pod(name="g1", group_name="pg1", req=build_resource_list(cpu=1))
    assert GROUP_NAME_ANNOTATION_KEY in pod.metadata.annotations
    store.create_pod_group(build_pod_group("pg1"))
    store.create_pod(pod)
    assert len(cache.jobs["default/pg1"].tasks) == 1


EXAMPLES = pathlib.Path(__file__).resolve().parent.parent / "examples"


def _vector_bits(r):
    return (r.milli_cpu, r.memory, dict(r.scalars), r.max_task_num)


@pytest.mark.parametrize("conf", ["scheduler-conf.yaml", "scheduler-conf-tpu.yaml"])
def test_scheduling_cycle_never_mutates_task_vectors(store, conf):
    """Snapshot clones share each cached task's ``resreq``/``init_resreq``
    with the cache's own TaskInfo, which is exact only while no code
    mutates a task's resource vectors in place. One full cycle that
    enqueues, reclaims, allocates, backfills and preempts must leave every
    cached task's vectors reading what they read before it."""
    evictor = FakeEvictor()
    sc = SchedulerCache(store, evictor=evictor)
    try:
        store.create_queue(build_queue("a"))
        store.create_queue(build_queue("b"))
        for i in range(4):
            store.create_node(build_node(f"n{i}", build_resource_list(cpu=2, memory="4Gi", pods=10)))
        # queue a fills the cluster with low-priority residents
        store.create_pod_group(build_pod_group("low", queue="a", min_member=1))
        for i in range(8):
            store.create_pod(build_pod(
                name=f"low-{i}", group_name="low", node_name=f"n{i // 2}",
                phase=PodPhase.RUNNING, priority=1,
                req=build_resource_list(cpu=1, memory="1Gi", **{"nvidia.com/gpu": 0}),
            ))
        # a high-priority gang in queue a preempts; queue b's job reclaims
        store.create_pod_group(build_pod_group("high", queue="a", min_member=2))
        for i in range(2):
            store.create_pod(build_pod(name=f"high-{i}", group_name="high", priority=9,
                                       req=build_resource_list(cpu=1, memory="1Gi")))
        store.create_pod_group(build_pod_group("other", queue="b", min_member=1))
        store.create_pod(build_pod(name="other-0", group_name="other",
                                   req=build_resource_list(cpu="1001m", memory="1Gi")))

        def cached_tasks():
            with sc._mutex:
                return [t for j in sc.jobs.values() for t in j.tasks.values()] + [
                    t for n in sc.nodes.values() for t in n.tasks.values()
                ]

        def vectors(t):
            return _vector_bits(t.resreq), _vector_bits(t.init_resreq)

        tasks = cached_tasks()
        before = [vectors(t) for t in tasks]
        by_uid = {t.uid: vectors(t) for t in tasks}
        assert len(by_uid) == 11

        Scheduler(sc, scheduler_conf=str(EXAMPLES / conf)).run_once()
        wait_until(lambda: len(evictor.evicts) > 0, what="an eviction")

        assert [vectors(t) for t in tasks] == before
        after = cached_tasks()
        assert {t.uid for t in after} == set(by_uid)
        for t in after:
            assert vectors(t) == by_uid[t.uid], t.uid
    finally:
        sc.stop()
