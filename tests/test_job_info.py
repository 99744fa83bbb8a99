"""TaskInfo/JobInfo bookkeeping invariants
(reference pkg/scheduler/api/job_info_test.go)."""

import pytest

from kube_batch_tpu.api import JobInfo, Resource, TaskStatus
from kube_batch_tpu.api.job_info import get_job_id
from kube_batch_tpu.apis.types import PodPhase
from kube_batch_tpu.testing import build_pod, build_resource_list, build_task


def rl(cpu, mem):
    return build_resource_list(cpu, mem)


class TestTaskInfo:
    def test_new_task_from_pending_pod(self):
        t = build_task(name="p1", req=rl("1", "1G"))
        assert t.status == TaskStatus.PENDING
        assert t.resreq == Resource.from_resource_list(rl("1", "1G"))
        assert t.priority == 1  # default (job_info.go:80)

    def test_status_from_phase_and_node(self):
        assert build_task(phase=PodPhase.RUNNING, node_name="n1").status == TaskStatus.RUNNING
        assert build_task(phase=PodPhase.PENDING, node_name="n1").status == TaskStatus.BOUND
        assert build_task(phase=PodPhase.SUCCEEDED).status == TaskStatus.SUCCEEDED
        assert build_task(phase=PodPhase.FAILED).status == TaskStatus.FAILED

    def test_releasing_when_deleting(self):
        pod = build_pod(name="doomed", phase=PodPhase.RUNNING, node_name="n1")
        pod.metadata.deletion_timestamp = 123.0
        from kube_batch_tpu.api.job_info import TaskInfo

        assert TaskInfo(pod).status == TaskStatus.RELEASING

    def test_job_id_from_annotation(self):
        pod = build_pod(namespace="ns", name="p", group_name="pg1")
        assert get_job_id(pod) == "ns/pg1"
        assert get_job_id(build_pod(name="orphan")) == ""

    def test_clone_isolates_resources(self):
        t = build_task(req=rl("1", "1G"))
        c = t.clone()
        c.resreq.add(Resource(milli_cpu=1))
        assert t.resreq != c.resreq


class TestJobInfo:
    def test_add_task_updates_aggregates(self):
        """reference job_info_test.go TestAddTaskInfo."""
        job = JobInfo("ns/j1")
        t1 = build_task(name="p1", req=rl("1", "1G"), group_name="j1")
        t2 = build_task(name="p2", req=rl("2", "2G"), group_name="j1", node_name="n1",
                        phase=PodPhase.RUNNING)
        job.add_task_info(t1)
        job.add_task_info(t2)

        assert len(job.tasks) == 2
        assert job.total_request == Resource.from_resource_list(rl("3", "3G"))
        # only the running task is allocated
        assert job.allocated == Resource.from_resource_list(rl("2", "2G"))
        assert set(job.task_status_index) == {TaskStatus.PENDING, TaskStatus.RUNNING}

    def test_delete_task_restores_aggregates(self):
        """reference job_info_test.go TestDeleteTaskInfo."""
        job = JobInfo("ns/j1")
        t1 = build_task(name="p1", req=rl("1", "1G"))
        t2 = build_task(name="p2", req=rl("2", "2G"), node_name="n1", phase=PodPhase.RUNNING)
        job.add_task_info(t1)
        job.add_task_info(t2)
        job.delete_task_info(t2)

        assert len(job.tasks) == 1
        assert job.total_request == Resource.from_resource_list(rl("1", "1G"))
        assert job.allocated.is_empty()
        assert TaskStatus.RUNNING not in job.task_status_index

    def test_delete_missing_raises(self):
        job = JobInfo("ns/j1")
        with pytest.raises(KeyError):
            job.delete_task_info(build_task(name="ghost"))

    def test_update_task_status_moves_index(self):
        job = JobInfo("ns/j1")
        t = build_task(name="p1", req=rl("1", "1G"))
        job.add_task_info(t)
        job.update_task_status(t, TaskStatus.ALLOCATED)
        assert TaskStatus.PENDING not in job.task_status_index
        assert t.uid in job.task_status_index[TaskStatus.ALLOCATED]
        assert job.allocated == Resource.from_resource_list(rl("1", "1G"))

    def test_gang_predicates(self):
        job = JobInfo("ns/j1")
        job.min_available = 2
        t1 = build_task(name="p1", req=rl("1", "1G"))
        t2 = build_task(name="p2", req=rl("1", "1G"))
        job.add_task_info(t1)
        job.add_task_info(t2)

        assert job.valid_task_num() == 2
        assert job.ready_task_num() == 0
        assert not job.ready()

        job.update_task_status(t1, TaskStatus.ALLOCATED)
        assert job.ready_task_num() == 1
        assert not job.ready()
        job.update_task_status(t2, TaskStatus.PIPELINED)
        assert job.waiting_task_num() == 1
        assert job.pipelined()  # ready + waiting >= min
        assert not job.ready()

        job.update_task_status(t2, TaskStatus.BOUND)
        assert job.ready()

    def test_fit_error_histogram(self):
        job = JobInfo("ns/j1")
        job.nodes_fit_delta = {
            "n1": Resource(milli_cpu=-10),
            "n2": Resource(milli_cpu=-10, memory=-1),
        }
        msg = job.fit_error()
        assert "0/2 nodes are available" in msg
        assert "2 insufficient cpu" in msg
        assert "1 insufficient memory" in msg
        assert JobInfo("ns/empty").fit_error() == "0 nodes are available"

    def test_clone(self):
        job = JobInfo("ns/j1")
        job.min_available = 2
        job.queue = "q1"
        job.add_task_info(build_task(name="p1", req=rl("1", "1G")))
        c = job.clone()
        assert c.uid == job.uid and c.queue == "q1" and c.min_available == 2
        assert len(c.tasks) == 1
        # mutating the clone must not affect the original
        c.update_task_status(next(iter(c.tasks.values())), TaskStatus.ALLOCATED)
        assert job.ready_task_num() == 0


# -- the snapshot clone against the add_task_info replay it stands in for ------

GPU = "nvidia.com/gpu"


def _task(name, cpu, mem, status, **scalars):
    t = build_task(name=name, req=build_resource_list(cpu, mem, **scalars), group_name="j1")
    t.status = status
    return t


def _running_only():
    job = JobInfo("default/j1")
    for i, (cpu, mem) in enumerate([("100m", "128Mi"), ("250m", "256Mi"), ("500m", "512Mi")] * 3):
        job.add_task_info(_task(f"r{i}", cpu, mem, TaskStatus.RUNNING))
    return job


def _every_status():
    job = JobInfo("default/j1")
    for i, status in enumerate([TaskStatus.PENDING, TaskStatus.RUNNING, TaskStatus.RELEASING,
                                TaskStatus.PIPELINED, TaskStatus.ALLOCATED, TaskStatus.BOUND,
                                TaskStatus.SUCCEEDED, TaskStatus.PENDING, TaskStatus.RUNNING]):
        job.add_task_info(_task(f"s{i}", "500m", "512Mi", status))
    return job


def _fractional_history():
    job = JobInfo("default/j1")
    tasks = [
        _task(f"f{i}", cpu, "100Mi", status, **{GPU: gpu})
        for i, (cpu, gpu, status) in enumerate([
            (1.001, 0.3, TaskStatus.RUNNING), (0.333, 0.7, TaskStatus.PENDING),
            (2.017, 0.1, TaskStatus.RUNNING), (1.001, 0.3, TaskStatus.ALLOCATED),
            (0.129, 0.9, TaskStatus.RELEASING), (3.003, 0.3, TaskStatus.PENDING),
        ])
    ]
    for t in tasks:
        job.add_task_info(t)
    for t in tasks[1::2]:
        job.delete_task_info(t)
    job.update_task_status(tasks[0], TaskStatus.RELEASING)
    job.add_task_info(_task("late", 0.777, "1Gi", TaskStatus.BOUND, **{GPU: 0.3}))
    job.add_task_info(tasks[3])
    return job


JOB_MIXES = {
    "running_only": _running_only,
    "every_status": _every_status,
    "fractional_history": _fractional_history,
}


def _replayed_clone(job):
    """The snapshot clone as an add_task_info replay of fresh task copies."""
    info = JobInfo(job.uid)
    for task in job.tasks.values():
        info.add_task_info(task.clone())
    return info


def _bits(r):
    return (r.milli_cpu, r.memory, list(r.scalars.items()), r.max_task_num)


class TestSnapshotClone:
    @pytest.mark.parametrize("mix", sorted(JOB_MIXES))
    def test_clone_matches_add_task_info_replay_bit_for_bit(self, mix):
        job = JOB_MIXES[mix]()
        got, want = job.clone(), _replayed_clone(job)
        assert _bits(got.total_request) == _bits(want.total_request)
        assert _bits(got.allocated) == _bits(want.allocated)
        assert list(got.tasks) == list(want.tasks) == list(job.tasks)
        assert [(s, list(ts)) for s, ts in got.task_status_index.items()] == [
            (s, list(ts)) for s, ts in want.task_status_index.items()
        ]
        for uid, ti in got.tasks.items():
            exp = want.tasks[uid]
            assert (ti.status, ti.node_name, ti.pod) == (exp.status, exp.node_name, exp.pod)
            assert _bits(ti.resreq) == _bits(exp.resreq)
            assert got.task_status_index[ti.status][uid] is ti

    @pytest.mark.parametrize("mix", sorted(JOB_MIXES))
    def test_clone_shares_vectors_but_not_status(self, mix):
        job = JOB_MIXES[mix]()
        before = (_bits(job.total_request), _bits(job.allocated))
        c = job.clone()
        for uid, ti in c.tasks.items():
            src = job.tasks[uid]
            assert ti is not src
            assert ti.resreq is src.resreq and ti.init_resreq is src.init_resreq
        uid, ti = next((u, t) for u, t in c.tasks.items() if t.status == TaskStatus.RUNNING)
        c.update_task_status(ti, TaskStatus.RELEASING)
        assert job.tasks[uid].status == TaskStatus.RUNNING
        assert uid in job.task_status_index[TaskStatus.RUNNING]
        assert uid not in job.task_status_index.get(TaskStatus.RELEASING, {})
        assert (_bits(job.total_request), _bits(job.allocated)) == before
