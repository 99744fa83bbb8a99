"""Parity tests for the native (C++) hot loops vs their Python twins.

The native module (kube_batch_tpu/native/_hotloops.cpp) reimplements
the replay path's per-event session surgery; these tests pin its
semantics to the pure-Python loop it replaces: identical status
flips, node_name sets, residency-clone sharing rules
(api/job_info.py clone_for_residency), status-index dict contents,
and the mutation-free volume-guard prepass. Skipped wholesale when
the toolchain cannot build the module (the framework then runs the
Python loops — same results, slower)."""

from __future__ import annotations

import numpy as np
import pytest

from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.native import lib
from kube_batch_tpu.testing import build_task

pytestmark = pytest.mark.skipif(lib is None, reason="native module unavailable")


def _mk_tasks(n):
    return [
        build_task(namespace="ns", name=f"p{i}", req={"cpu": 1.0}) for i in range(n)
    ]


def _run_python_twin(tasks, tkeys, node_tasks, node_names, rows, nrows, allocs, counts):
    """The exact loop _Replayer._assign_segments_py runs for volume-less
    rows (volume rows never reach bulk_assign — the guard test below)."""
    segments = []
    pos = 0
    for cnt in counts:
        alloc_d, pipe_d = {}, {}
        for i in range(pos, pos + cnt):
            task = tasks[rows[i]]
            if allocs[i]:
                task.volume_ready = True
                task.status = TaskStatus.ALLOCATED
                alloc_d[task.uid] = task
            else:
                task.status = TaskStatus.PIPELINED
                pipe_d[task.uid] = task
            task.node_name = node_names[nrows[i]]
            node_tasks[nrows[i]][tkeys[rows[i]]] = task.clone_for_residency()
        pos += cnt
        segments.append((alloc_d, pipe_d))
    return segments


class TestBulkAssign:
    def test_matches_python_twin(self):
        rng = np.random.default_rng(7)
        n, n_nodes = 200, 7
        rows = rng.permutation(n).tolist()
        nrows = rng.integers(0, n_nodes, n).tolist()
        allocs = rng.integers(0, 2, n).astype(np.uint8)
        counts = [50, 100, 0, 50]

        tasks_a, tasks_b = _mk_tasks(n), _mk_tasks(n)
        tkeys = [f"{t.namespace}/{t.name}" for t in tasks_a]
        nt_a = [dict() for _ in range(n_nodes)]
        nt_b = [dict() for _ in range(n_nodes)]
        nn = [f"node-{i}" for i in range(n_nodes)]

        seg_n = lib.bulk_assign(
            tasks_a, tkeys, nt_a, nn, rows, nrows, allocs.tobytes(), counts,
            TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
        )
        seg_p = _run_python_twin(
            tasks_b, tkeys, nt_b, nn, rows, nrows, allocs.tolist(), counts
        )

        assert len(seg_n) == len(seg_p) == 4
        for (an, pn), (ap, pp) in zip(seg_n, seg_p):
            assert list(an) == list(ap)  # same uids, same insertion order
            assert list(pn) == list(pp)
        for ta, tb in zip(tasks_a, tasks_b):
            assert ta.status is tb.status
            assert ta.node_name == tb.node_name
            assert ta.volume_ready == tb.volume_ready
        for da, db in zip(nt_a, nt_b):
            assert list(da) == list(db)
            for k in da:
                ca, cb = da[k], db[k]
                assert ca.status is cb.status and ca.node_name == cb.node_name

    def test_clone_shares_resources_and_detaches_status(self):
        tasks = _mk_tasks(1)
        nt = [dict()]
        lib.bulk_assign(
            tasks, ["ns/p0"], nt, ["n0"], [0], [0], bytes([1]), [1],
            TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
        )
        clone = nt[0]["ns/p0"]
        t = tasks[0]
        assert clone is not t
        assert clone.resreq is t.resreq and clone.init_resreq is t.init_resreq
        assert clone.pod is t.pod and clone.uid == t.uid
        assert clone.status is TaskStatus.ALLOCATED
        t.status = TaskStatus.BINDING  # later dispatch flip
        assert clone.status is TaskStatus.ALLOCATED  # resident unaffected

    def test_volume_rows_raise_without_mutation(self):
        tasks = _mk_tasks(2)
        tasks[1].pod.volumes = ["claim-1"]
        before = [(t.status, t.node_name, t.volume_ready) for t in tasks]
        nt = [dict()]
        with pytest.raises(ValueError, match="volume"):
            lib.bulk_assign(
                tasks, ["ns/p0", "ns/p1"], nt, ["n0"], [0, 1], [0, 0],
                bytes([1, 1]), [2], TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
            )
        # prepass fired before any event applied: nothing changed
        assert [(t.status, t.node_name, t.volume_ready) for t in tasks] == before
        assert not nt[0]

    def test_pipelined_rows_skip_volume_guard(self):
        # only Allocated events bind volumes; a Pipelined volume row is fine
        tasks = _mk_tasks(1)
        tasks[0].pod.volumes = ["claim-1"]
        nt = [dict()]
        lib.bulk_assign(
            tasks, ["ns/p0"], nt, ["n0"], [0], [0], bytes([0]), [1],
            TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
        )
        assert tasks[0].status is TaskStatus.PIPELINED
        assert not tasks[0].volume_ready

    def test_clone_survives_collection(self):
        import gc

        tasks = _mk_tasks(3)
        nt = [dict()]
        lib.bulk_assign(
            tasks, [f"ns/p{i}" for i in range(3)], nt, ["n0"], [0, 1, 2],
            [0, 0, 0], bytes([1, 1, 1]), [3],
            TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
        )
        clones = dict(nt[0])
        del tasks, nt
        gc.collect()  # clones are GC-untracked; refcounting must keep them
        for k, c in clones.items():
            assert c.uid and c.status is TaskStatus.ALLOCATED

    def test_length_mismatch_rejected(self):
        tasks = _mk_tasks(1)
        with pytest.raises(ValueError):
            lib.bulk_assign(
                tasks, ["ns/p0"], [{}], ["n0"], [0, 0], [0], bytes([1]), [1],
                TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
            )
        with pytest.raises(IndexError):
            lib.bulk_assign(
                tasks, ["ns/p0"], [{}], ["n0"], [5], [0], bytes([1]), [1],
                TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
            )


class TestCollectPending:
    def _python_twin(self, jobs):
        from kube_batch_tpu.api.types import TaskStatus

        out = []
        for job in jobs:
            pending = [
                t
                for t in job.task_status_index.get(TaskStatus.PENDING, {}).values()
                if not t.resreq.is_empty()
            ]
            pending.sort(
                key=lambda t: (-t.priority, t.pod.metadata.creation_timestamp, t.uid)
            )
            out.append(pending)
        return out

    def _jobs(self):
        import random

        from kube_batch_tpu.api.job_info import JobInfo
        from kube_batch_tpu.api.types import TaskStatus

        rng = random.Random(11)
        jobs = []
        for j in range(6):
            job = JobInfo(uid=f"job-{j}")
            for i in range(rng.randint(0, 12)):
                t = build_task(
                    namespace="ns",
                    name=f"j{j}t{i}",
                    req=rng.choice([{"cpu": 1.0}, {"cpu": 0.5}, None]),
                    priority=rng.choice([None, 1, 5, 9]),
                )
                t.pod.metadata.creation_timestamp = rng.choice([100.0, 200.0, 300.0])
                if rng.random() < 0.3:
                    t.pod.node_selector["zone"] = "a"
                if rng.random() < 0.2:
                    t.pod.containers[0].ports = [8080]
                job.add_task_info(t)
            jobs.append(job)
        return jobs

    def test_matches_python_extraction(self):
        from kube_batch_tpu.api.resource_info import (
            MIN_MEMORY,
            MIN_MILLI_CPU,
            MIN_MILLI_SCALAR,
        )
        from kube_batch_tpu.api.types import TaskStatus

        jobs = self._jobs()
        native = lib.collect_pending(
            jobs, TaskStatus.PENDING, MIN_MILLI_CPU, MIN_MEMORY, MIN_MILLI_SCALAR
        )
        python = self._python_twin(jobs)
        assert len(native) == len(python)
        for (n_tasks, flags), p_tasks in zip(native, python):
            assert [t.uid for t in n_tasks] == [t.uid for t in p_tasks]
            for t, fl in zip(n_tasks, flags):
                plain = (
                    not t.pod.node_selector
                    and t.pod.affinity is None
                    and not t.pod.tolerations
                    and not t.pod.volumes
                    and len(t.pod.containers) == 1
                    and not t.pod.containers[0].ports
                )
                assert bool(fl) == plain, t.uid

    def test_empty_resreq_excluded(self):
        from kube_batch_tpu.api.job_info import JobInfo
        from kube_batch_tpu.api.resource_info import (
            MIN_MEMORY,
            MIN_MILLI_CPU,
            MIN_MILLI_SCALAR,
        )
        from kube_batch_tpu.api.types import TaskStatus

        job = JobInfo(uid="j")
        job.add_task_info(build_task(namespace="ns", name="be", req=None))
        job.add_task_info(build_task(namespace="ns", name="real", req={"cpu": 1.0}))
        (tasks, flags), = lib.collect_pending(
            [job], TaskStatus.PENDING, MIN_MILLI_CPU, MIN_MEMORY, MIN_MILLI_SCALAR
        )
        assert [t.name for t in tasks] == ["real"]

    def test_encode_with_and_without_native_agree(self, monkeypatch):
        import numpy as np

        import kube_batch_tpu.ops.encode as E
        from kube_batch_tpu.models import multi_tenant_ml
        from kube_batch_tpu.testing import FakeCache

        # one world, snapshotted per encode: the clusters must be equal
        # to the timestamp (task_created rides the arrays now)
        fc = FakeCache(multi_tenant_ml())

        def enc():
            cluster = fc.snapshot()
            return E.encode_session(cluster.jobs, cluster.nodes, cluster.queues)

        a = enc()
        monkeypatch.setattr(E, "_native", None)
        b = enc()
        assert [t.uid for t in a.tasks] == [t.uid for t in b.tasks]
        for k in a.arrays:
            np.testing.assert_array_equal(
                np.asarray(a.arrays[k]), np.asarray(b.arrays[k]), err_msg=k
            )


class TestHalfInitializedGuards:
    """r4 advisor findings: unset __slots__ members must surface as
    AttributeError (with the exception actually set), never a segfault
    or a bare SystemError; counts inconsistencies must raise before any
    mutation."""

    def test_bulk_assign_counts_mismatch_raises_premutation(self):
        tasks = _mk_tasks(3)
        before = [(t.status, t.node_name, t.volume_ready) for t in tasks]
        nt = [dict()]
        for bad_counts in ([2], [4], [2, 2]):  # under / over / over-split
            with pytest.raises(ValueError, match="count"):
                lib.bulk_assign(
                    tasks, [f"ns/p{i}" for i in range(3)], nt, ["n0"],
                    [0, 1, 2], [0, 0, 0], bytes([1, 1, 1]), bad_counts,
                    TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
                )
            assert [(t.status, t.node_name, t.volume_ready) for t in tasks] == before
            assert not nt[0]

    def test_bulk_assign_null_pod_slot_raises(self):
        tasks = _mk_tasks(2)
        del tasks[1].pod  # unset the slot: C-level member is now NULL
        nt = [dict()]
        with pytest.raises(AttributeError, match="pod"):
            lib.bulk_assign(
                tasks, ["ns/p0", "ns/p1"], nt, ["n0"], [0, 1], [0, 0],
                bytes([1, 1]), [2], TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
            )
        assert not nt[0]  # prepass: nothing mutated

    def test_bulk_assign_null_uid_slot_raises(self):
        tasks = _mk_tasks(2)
        del tasks[0].uid  # would be a NULL dict key in the mutation loop
        nt = [dict()]
        with pytest.raises(AttributeError, match="uid"):
            lib.bulk_assign(
                tasks, ["ns/p0", "ns/p1"], nt, ["n0"], [0, 1], [0, 0],
                bytes([0, 0]), [2], TaskStatus.ALLOCATED, TaskStatus.PIPELINED,
            )
        assert not nt[0]
        assert tasks[1].status is not TaskStatus.PIPELINED  # prepass: no mutation

    def test_collect_pending_null_pod_slot_raises(self):
        from kube_batch_tpu.api.job_info import JobInfo
        from kube_batch_tpu.api.resource_info import (
            MIN_MEMORY,
            MIN_MILLI_CPU,
            MIN_MILLI_SCALAR,
        )

        job = JobInfo(uid="j")
        t = build_task(namespace="ns", name="ghost", req={"cpu": 1.0})
        job.add_task_info(t)
        del t.pod
        with pytest.raises(AttributeError):
            lib.collect_pending(
                [job], TaskStatus.PENDING, MIN_MILLI_CPU, MIN_MEMORY,
                MIN_MILLI_SCALAR,
            )

    def test_extract_task_columns_null_scalars_slot_raises(self):
        t = build_task(namespace="ns", name="t0", req={"cpu": 1.0})
        t.job = "j"
        del t.resreq.scalars
        req = np.zeros((1, 2), np.float32)
        res = np.zeros((1, 2), np.float32)
        job_out = np.zeros(1, np.int32)
        hs = np.zeros(1, np.uint8)
        rhs = np.zeros(1, np.uint8)
        with pytest.raises(AttributeError, match="scalars"):
            lib.extract_task_columns([t], {"j": 0}, req, res, job_out, hs, rhs)


class TestBulkSetSlot:
    def test_sets_every_object(self):
        tasks = _mk_tasks(50)
        lib.bulk_set_slot(tasks, "status", TaskStatus.BINDING)
        assert all(t.status is TaskStatus.BINDING for t in tasks)

    def test_non_slot_attr_rejected(self):
        with pytest.raises(AttributeError):
            lib.bulk_set_slot(_mk_tasks(1), "not_a_slot", 1)
        with pytest.raises(TypeError):
            # exists on the type but is a method, not a member slot
            lib.bulk_set_slot(_mk_tasks(1), "clone", 1)

    def test_empty_list_ok(self):
        lib.bulk_set_slot([], "status", TaskStatus.BINDING)


class TestHistogramNdarrayPath:
    def test_matches_scalar_observe(self):
        from kube_batch_tpu.metrics import Histogram

        buckets = [0.1, 1.0, 10.0]
        h1, h2 = Histogram("a", "", buckets), Histogram("b", "", buckets)
        vals = [0.05, 0.1, 0.5, 1.0, 5.0, 50.0]
        for v in vals:
            h1.observe(v)
        h2.observe_many(np.asarray(vals))
        assert h1.snapshot() == h2.snapshot()


class TestActionUsesNative:
    def test_xla_allocate_with_and_without_native_agree(self, monkeypatch):
        """The full action, native path vs forced-Python path, must
        produce identical binds and session state on a gang cluster."""
        import kube_batch_tpu.actions.xla_allocate as XA
        from kube_batch_tpu.conf import parse_scheduler_conf
        from kube_batch_tpu.framework import close_session, get_action, open_session
        from kube_batch_tpu.models import synthetic
        from kube_batch_tpu.testing import FakeCache
        from test_xla_allocate import DEFAULT_TIERS_YAML

        def run():
            cache = FakeCache(synthetic(120, 16))
            ssn = open_session(cache, parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers)
            get_action("xla_allocate").execute(ssn)
            binds = dict(cache.binder.binds)
            close_session(ssn)
            return binds

        native_binds = run()
        monkeypatch.setattr(XA, "_native", None)
        python_binds = run()
        assert native_binds == python_binds and len(native_binds) > 0


class TestR5PrepassContracts:
    """r5 native additions: every mutating entry point must fail
    PRE-mutation so the Python fallbacks never double-apply."""

    def test_bulk_dispatch_bad_index_raises_premutation(self):
        from kube_batch_tpu.api.job_info import JobInfo

        good = JobInfo(uid="g")
        t = build_task(namespace="ns", name="t0", req={"cpu": 1.0})
        good.add_task_info(t)
        good.update_task_status(t, TaskStatus.ALLOCATED)

        class Weird:
            task_status_index = "not-a-dict"

        with pytest.raises(TypeError, match="task_status_index"):
            lib.bulk_dispatch(
                [good, Weird()], bytes([1, 1]),
                TaskStatus.ALLOCATED, TaskStatus.BINDING,
            )
        # prepass fired before any bucket moved
        assert TaskStatus.ALLOCATED in good.task_status_index
        assert TaskStatus.BINDING not in good.task_status_index

    def test_bulk_dispatch_moves_buckets_and_returns_tasks(self):
        from kube_batch_tpu.api.job_info import JobInfo

        jobs = []
        for j in range(3):
            job = JobInfo(uid=f"j{j}")
            for i in range(4):
                t = build_task(namespace="ns", name=f"j{j}t{i}", req={"cpu": 1.0})
                job.add_task_info(t)
                job.update_task_status(t, TaskStatus.ALLOCATED)
            jobs.append(job)
        out = lib.bulk_dispatch(
            jobs, bytes([1, 0, 1]), TaskStatus.ALLOCATED, TaskStatus.BINDING
        )
        assert [t.name for t in out] == [
            f"j{j}t{i}" for j in (0, 2) for i in range(4)
        ]
        for j, job in enumerate(jobs):
            if j == 1:
                assert TaskStatus.ALLOCATED in job.task_status_index
            else:
                assert TaskStatus.ALLOCATED not in job.task_status_index
                assert len(job.task_status_index[TaskStatus.BINDING]) == 4

    def test_bulk_dispatch_merges_into_existing_binding_bucket(self):
        from kube_batch_tpu.api.job_info import JobInfo

        job = JobInfo(uid="j")
        pre = build_task(namespace="ns", name="pre", req={"cpu": 1.0})
        job.add_task_info(pre)
        job.update_task_status(pre, TaskStatus.BINDING)  # existing bucket
        t = build_task(namespace="ns", name="t0", req={"cpu": 1.0})
        job.add_task_info(t)
        job.update_task_status(t, TaskStatus.ALLOCATED)
        out = lib.bulk_dispatch(
            [job], bytes([1]), TaskStatus.ALLOCATED, TaskStatus.BINDING
        )
        assert [x.name for x in out] == ["t0"]
        binding = job.task_status_index[TaskStatus.BINDING]
        assert set(binding) == {pre.uid, t.uid}  # merged, not replaced
        assert TaskStatus.ALLOCATED not in job.task_status_index

    def test_bulk_res_axpy_mixed_types_raise_premutation(self):
        from kube_batch_tpu.api.resource_info import Resource

        a = Resource(milli_cpu=1000.0, memory=2048.0)
        b = object()  # not a Resource at all
        deltas = np.asarray([[100.0, 10.0], [100.0, 10.0]], np.float64)
        with pytest.raises(TypeError):
            lib.bulk_res_axpy([a, b], deltas, 1)
        assert a.milli_cpu == 1000.0 and a.memory == 2048.0  # untouched

    def test_bulk_res_axpy_applies_dense_dims(self):
        from kube_batch_tpu.api.resource_info import Resource

        rs = [Resource(milli_cpu=1000.0, memory=2048.0) for _ in range(3)]
        deltas = np.asarray(
            [[100.0, 10.0], [200.0, 20.0], [300.0, 30.0]], np.float64
        )
        lib.bulk_res_axpy(rs, deltas, -1)
        assert [r.milli_cpu for r in rs] == [900.0, 800.0, 700.0]
        assert [r.memory for r in rs] == [2038.0, 2028.0, 2018.0]

    def test_finish_columns_matches_python_builds(self):
        tasks = _mk_tasks(5)
        for i, t in enumerate(tasks):
            t.node_name = f"node-{i}"
            t.pod.metadata.creation_timestamp = 100.0 + i
        row_of = {t.uid: r for r, t in enumerate(tasks)}
        task_keys = [f"{t.namespace}/{t.name}" for t in tasks]
        rb, cb, keys, hostnames = lib.finish_columns(
            tasks, row_of, task_keys, TaskStatus.BINDING
        )
        assert np.frombuffer(rb, np.int64).tolist() == list(range(5))
        assert np.frombuffer(cb, np.float64).tolist() == [100.0 + i for i in range(5)]
        assert keys == task_keys
        assert hostnames == [f"node-{i}" for i in range(5)]
        assert all(t.status is TaskStatus.BINDING for t in tasks)

    def test_finish_columns_unencoded_task_keys_lazily(self):
        tasks = _mk_tasks(2)
        for t in tasks:
            t.node_name = "n0"
        row_of = {tasks[0].uid: 0}  # tasks[1] unknown to this encode
        rb, cb, keys, hostnames = lib.finish_columns(
            tasks, row_of, ["ns/p0"], None
        )
        rows = np.frombuffer(rb, np.int64).tolist()
        assert rows == [0, -1]
        assert keys == ["ns/p0", "ns/p1"]
        # None = no flip: status must be UNCHANGED (build_task default)
        assert tasks[0].status is TaskStatus.PENDING
        assert tasks[1].status is TaskStatus.PENDING
