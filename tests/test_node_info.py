"""NodeInfo accounting invariants (reference pkg/scheduler/api/node_info_test.go)."""

import pytest

from kube_batch_tpu.api import NodeInfo, Resource, TaskStatus
from kube_batch_tpu.apis.types import PodPhase
from kube_batch_tpu.testing import build_node, build_resource_list, build_task


def rl(cpu, mem):
    return build_resource_list(cpu, mem)


def make_node(cpu="8", mem="8G"):
    return NodeInfo(build_node("n1", rl(cpu, mem)))


class TestAddRemove:
    def test_add_task_consumes_idle(self):
        """reference node_info_test.go TestNodeInfo_AddPod."""
        ni = make_node()
        ni.add_task(build_task(name="p1", req=rl("1", "1G"), node_name="n1",
                               phase=PodPhase.RUNNING))
        ni.add_task(build_task(name="p2", req=rl("2", "2G"), node_name="n1",
                               phase=PodPhase.RUNNING))
        assert ni.idle == Resource.from_resource_list(rl("5", "5G"))
        assert ni.used == Resource.from_resource_list(rl("3", "3G"))
        assert len(ni.tasks) == 2

    def test_remove_task_restores_idle(self):
        """reference node_info_test.go TestNodeInfo_RemovePod."""
        ni = make_node()
        t1 = build_task(name="p1", req=rl("1", "1G"), node_name="n1", phase=PodPhase.RUNNING)
        t2 = build_task(name="p2", req=rl("2", "2G"), node_name="n1", phase=PodPhase.RUNNING)
        ni.add_task(t1)
        ni.add_task(t2)
        ni.remove_task(t1)
        assert ni.idle == Resource.from_resource_list(rl("6", "6G"))
        assert ni.used == Resource.from_resource_list(rl("2", "2G"))

    def test_add_duplicate_raises(self):
        ni = make_node()
        t = build_task(name="p1", req=rl("1", "1G"), node_name="n1", phase=PodPhase.RUNNING)
        ni.add_task(t)
        with pytest.raises(KeyError):
            ni.add_task(t)

    def test_remove_missing_raises(self):
        with pytest.raises(KeyError):
            make_node().remove_task(build_task(name="ghost", node_name="n1"))


class TestStatusAccounting:
    def test_releasing_task(self):
        """Releasing consumes idle AND is tracked in releasing
        (node_info.go:120-123)."""
        ni = make_node()
        t = build_task(name="p1", req=rl("2", "2G"), node_name="n1", phase=PodPhase.RUNNING)
        t.status = TaskStatus.RELEASING
        ni.add_task(t)
        assert ni.idle == Resource.from_resource_list(rl("6", "6G"))
        assert ni.releasing == Resource.from_resource_list(rl("2", "2G"))
        assert ni.used == Resource.from_resource_list(rl("2", "2G"))
        ni.remove_task(t)
        assert ni.idle == Resource.from_resource_list(rl("8", "8G"))
        assert ni.releasing.is_empty()

    def test_pipelined_task_rides_releasing(self):
        """Pipelined subtracts from releasing, not idle (node_info.go:124-125)."""
        ni = make_node()
        rel = build_task(name="victim", req=rl("2", "2G"), node_name="n1",
                         phase=PodPhase.RUNNING)
        rel.status = TaskStatus.RELEASING
        ni.add_task(rel)
        pipe = build_task(name="incoming", req=rl("2", "2G"), node_name="n1")
        pipe.status = TaskStatus.PIPELINED
        ni.add_task(pipe)
        assert ni.releasing.is_empty()  # 2G releasing - 2G pipelined
        assert ni.idle == Resource.from_resource_list(rl("6", "6G"))

    def test_task_clone_isolation(self):
        """Node holds a clone: caller status flips don't corrupt accounting
        (node_info.go:117)."""
        ni = make_node()
        t = build_task(name="p1", req=rl("1", "1G"), node_name="n1", phase=PodPhase.RUNNING)
        ni.add_task(t)
        t.status = TaskStatus.RELEASING  # mutate caller's copy
        ni.remove_task(t)  # looked up by key; node's clone still RUNNING
        assert ni.idle == Resource.from_resource_list(rl("8", "8G"))
        assert ni.releasing.is_empty()


class TestSetNodeClone:
    def test_set_node_recomputes(self):
        """reference node_info_test.go TestNodeInfo_SetNode."""
        ni = make_node("4", "4G")
        ni.add_task(build_task(name="p1", req=rl("1", "1G"), node_name="n1",
                               phase=PodPhase.RUNNING))
        bigger = build_node("n1", rl("16", "16G"))
        ni.set_node(bigger)
        assert ni.allocatable == Resource.from_resource_list(rl("16", "16G"))
        assert ni.idle == Resource.from_resource_list(rl("15", "15G"))
        assert ni.used == Resource.from_resource_list(rl("1", "1G"))

    def test_clone(self):
        ni = make_node()
        ni.add_task(build_task(name="p1", req=rl("1", "1G"), node_name="n1",
                               phase=PodPhase.RUNNING))
        c = ni.clone()
        assert c.idle == ni.idle and c.used == ni.used and len(c.tasks) == 1
        c.add_task(build_task(name="p2", req=rl("1", "1G"), node_name="n1",
                              phase=PodPhase.RUNNING))
        assert len(ni.tasks) == 1  # original untouched


# -- the snapshot clone against the add_task replay it stands in for -----------

GPU = "nvidia.com/gpu"


def _task(name, cpu, mem, status=TaskStatus.RUNNING, **scalars):
    t = build_task(name=name, req=build_resource_list(cpu, mem, **scalars),
                   node_name="n1", phase=PodPhase.RUNNING)
    t.status = status
    return t


def _running_only():
    ni = make_node("16", "32Gi")
    for i, (cpu, mem) in enumerate([("100m", "128Mi"), ("250m", "256Mi"), ("500m", "512Mi")] * 3):
        ni.add_task(_task(f"r{i}", cpu, mem))
    return ni


def _releasing_and_pipelined():
    ni = make_node("16", "32Gi")
    ni.add_task(_task("run", "1", "1Gi"))
    ni.add_task(_task("rel-a", "2", "2Gi", TaskStatus.RELEASING))
    ni.add_task(_task("rel-b", "500m", "512Mi", TaskStatus.RELEASING))
    ni.add_task(_task("pipe", "1500m", "1Gi", TaskStatus.PIPELINED))
    ni.add_task(_task("bound", "250m", "256Mi", TaskStatus.BOUND))
    return ni


def _overcommitted():
    ni = make_node("2", "2Gi")
    for i in range(3):
        ni.add_task(_task(f"o{i}", "1", "1Gi"), overcommit=True)
    assert ni.idle.milli_cpu < 0 and ni.idle.memory < 0
    return ni


def _fractional_history():
    ni = NodeInfo(build_node("n1", build_resource_list("64", "256Gi", **{GPU: 8})))
    tasks = [
        _task(f"f{i}", cpu, "100Mi", **{GPU: gpu})
        for i, (cpu, gpu) in enumerate([(1.001, 0.3), (0.333, 0.7), (2.017, 0.1),
                                        (1.001, 0.3), (0.129, 0.9), (3.003, 0.3)])
    ]
    for t in tasks:
        ni.add_task(t)
    for t in tasks[1::2]:
        ni.remove_task(t)
    ni.add_task(_task("late", 0.777, "1Gi", TaskStatus.RELEASING, **{GPU: 0.3}))
    ni.add_task(tasks[3])
    return ni


def _no_node():
    ni = NodeInfo()
    ni.add_task(_task("a", "1", "1Gi"))
    ni.add_task(_task("b", "2", "2Gi", TaskStatus.RELEASING))
    return ni


NODE_MIXES = {
    "running_only": _running_only,
    "releasing_and_pipelined": _releasing_and_pipelined,
    "overcommitted": _overcommitted,
    "fractional_history": _fractional_history,
    "no_node": _no_node,
}


def _replayed_clone(ni):
    """The snapshot clone as a full add_task replay of fresh task copies."""
    res = NodeInfo(ni.node)
    for task in ni.tasks.values():
        res.add_task(task.clone(), overcommit=True)
    res.other = ni.other
    return res


def _bits(r):
    return (r.milli_cpu, r.memory, list(r.scalars.items()), r.max_task_num)


class TestSnapshotClone:
    @pytest.mark.parametrize("mix", sorted(NODE_MIXES))
    def test_clone_matches_add_task_replay_bit_for_bit(self, mix):
        ni = NODE_MIXES[mix]()
        ni.other = object()
        got, want = ni.clone(), _replayed_clone(ni)
        for field in ("idle", "used", "releasing", "allocatable", "capability"):
            assert _bits(getattr(got, field)) == _bits(getattr(want, field)), field
        assert list(got.tasks) == list(want.tasks) == list(ni.tasks)
        for key, ti in got.tasks.items():
            exp = want.tasks[key]
            assert (ti.uid, ti.status, ti.node_name, ti.pod) == (exp.uid, exp.status, exp.node_name, exp.pod)
            assert _bits(ti.resreq) == _bits(exp.resreq)
        assert (got.name, got.node, got.other) == (want.name, want.node, want.other)

    @pytest.mark.parametrize("mix", sorted(NODE_MIXES))
    def test_clone_shares_vectors_but_not_status(self, mix):
        ni = NODE_MIXES[mix]()
        before = [_bits(getattr(ni, f)) for f in ("idle", "used", "releasing")]
        c = ni.clone()
        for key, ti in c.tasks.items():
            src = ni.tasks[key]
            assert ti is not src
            assert ti.resreq is src.resreq and ti.init_resreq is src.init_resreq
        key = next(k for k, t in c.tasks.items() if t.status == TaskStatus.RUNNING)
        flipped = c.tasks[key].clone_for_residency()
        flipped.status = TaskStatus.RELEASING
        # update_task's remove + add, tolerant so the overcommitted mix flips too
        c.remove_task(flipped)
        c.add_task(flipped, overcommit=True)
        assert c.tasks[key].status == TaskStatus.RELEASING
        assert ni.tasks[key].status == TaskStatus.RUNNING
        assert [_bits(getattr(ni, f)) for f in ("idle", "used", "releasing")] == before
