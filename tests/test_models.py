"""Sanity checks on the synthetic workload generators (the config
shapes of BASELINE.md) — counts, queues, scalar resources, determinism."""

from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.models import (
    GPU,
    TPU,
    gang_example,
    multi_queue,
    multi_tenant_ml,
    preempt_mix,
    synthetic,
)


def pending_count(cluster) -> int:
    return sum(
        len(j.task_status_index.get(TaskStatus.PENDING, {}))
        for j in cluster.jobs.values()
    )


def test_gang_example_shape():
    c = gang_example()
    assert len(c.nodes) == 3
    assert pending_count(c) == 3
    (job,) = c.jobs.values()
    assert job.min_available == 3


def test_synthetic_shape():
    c = synthetic(200, 20)
    assert len(c.nodes) == 20
    assert pending_count(c) == 200
    assert len(c.queues) == 1


def test_multi_queue_shape():
    c = multi_queue(400, 40, n_queues=4, tasks_per_job=8)
    assert len(c.queues) == 4
    assert pending_count(c) == 400
    queues_used = {j.queue for j in c.jobs.values()}
    assert queues_used == {f"q{i}" for i in range(4)}


def test_preempt_mix_has_residents():
    c = preempt_mix(500, 50, tasks_per_job=10)
    assert pending_count(c) == 500
    running = sum(
        len(j.task_status_index.get(TaskStatus.RUNNING, {}))
        for j in c.jobs.values()
    )
    releasing = sum(
        len(j.task_status_index.get(TaskStatus.RELEASING, {}))
        for j in c.jobs.values()
    )
    assert running + releasing == 25  # one victim per 2 nodes
    assert any(n.used.milli_cpu > 0 for n in c.nodes.values())


def test_multi_tenant_ml_scalars():
    c = multi_tenant_ml(n_jobs=10, n_nodes=20, n_queues=5)
    assert len(c.queues) == 5
    accels = set()
    for j in c.jobs.values():
        for t in j.task_status_index.get(TaskStatus.PENDING, {}).values():
            accels.update(t.resreq.scalars)
    assert accels <= {GPU, TPU} and accels
    gpu_nodes = [n for n in c.nodes.values() if GPU in n.allocatable.scalars]
    tpu_nodes = [n for n in c.nodes.values() if TPU in n.allocatable.scalars]
    assert gpu_nodes and tpu_nodes


def test_generators_deterministic():
    a, b = synthetic(100, 10, seed=5), synthetic(100, 10, seed=5)
    assert sorted(a.jobs) == sorted(b.jobs)
    ta = {t.uid: t.resreq.milli_cpu for j in a.jobs.values() for t in j.tasks.values()}
    tb = {t.uid: t.resreq.milli_cpu for j in b.jobs.values() for t in j.tasks.values()}
    assert ta == tb
