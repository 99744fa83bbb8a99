"""The cyclic collector's policy (kube_batch_tpu/utils/collector.py): off
inside a scheduling cycle, a collection and a freeze at its boundary,
a full pass by the interpreter's own quarter rule, and a gc hook that
takes no lock."""

from __future__ import annotations

import gc
import threading
import weakref

import pytest

from kube_batch_tpu import metrics, obs
from kube_batch_tpu.scheduler import Scheduler
from kube_batch_tpu.testing import (
    FakeCache,
    build_cluster,
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource_list,
)
from kube_batch_tpu.utils import collector


@pytest.fixture
def policy(monkeypatch):
    """A fresh policy in the process's place, the collector enabled; the
    collector's state and the frozen heap are put back afterwards."""
    fresh = collector.BoundaryCollector()
    monkeypatch.setattr(collector, "policy", fresh)
    was_enabled = gc.isenabled()
    gc.enable()
    try:
        yield fresh
    finally:
        fresh.uninstall()
        gc.unfreeze()
        if not was_enabled:
            gc.disable()


@pytest.fixture
def tracing(monkeypatch, tmp_path):
    monkeypatch.setenv(obs.ENV, "1")
    monkeypatch.setenv(obs.RECORDER_ENV, str(tmp_path / "flight"))
    obs.configure()
    obs.recorder.clear()
    yield
    obs.configure("off")
    obs.recorder.clear()


class _Node:
    """A reference cycle the collector, not refcounting, has to free."""

    def __init__(self) -> None:
        self.me = self


class _Probe:
    """An action that records whether the collector was on while it ran
    (under a registered action's name, so its span is a declared one)."""

    name = "enqueue"

    def __init__(self, then=None) -> None:
        self.seen: list[bool] = []
        self.then = then

    def execute(self, ssn) -> None:
        self.seen.append(gc.isenabled())
        if self.then is not None:
            self.then()


def _scheduler(*actions) -> Scheduler:
    pods = [
        build_pod(name=f"g-p{i}", group_name="g",
                  req=build_resource_list(cpu=1, memory="512Mi"))
        for i in range(2)
    ]
    nodes = [build_node("n0", build_resource_list(cpu=8, memory="8Gi", pods=16))]
    cluster = build_cluster(pods, nodes, [build_pod_group("g", min_member=2)],
                            [build_queue("default")])
    sched = Scheduler(FakeCache(cluster))
    sched.actions = list(actions)
    return sched


TPU_CONF = """
actions: "enqueue, xla_reclaim, xla_allocate, xla_backfill, xla_preempt"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: tensorscore
"""


def _count(when: str) -> float:
    return metrics.gc_collections.value({"when": when})


def _raise() -> None:
    raise RuntimeError("action failed")


@pytest.mark.parametrize("outcome", ["completes", "action_raises", "hard_budget_abort"])
def test_off_inside_run_once_and_restored_after(policy, monkeypatch, tmp_path, outcome):
    monkeypatch.setenv(obs.RECORDER_ENV, str(tmp_path / "flight"))
    probe = _Probe(then=_raise if outcome == "action_raises" else None)
    after = _Probe()
    sched = _scheduler(probe, after)
    overruns = metrics.cycle_overruns.value({"kind": "hard"})
    if outcome == "hard_budget_abort":
        sched._hard_deadline = 1e-9  # the check after the first action aborts
    if outcome == "action_raises":
        with pytest.raises(RuntimeError, match="action failed"):
            sched.run_once()
    else:
        sched.run_once()
    assert probe.seen == [False]
    assert gc.isenabled()
    assert policy.state()["depth"] == 0
    if outcome == "hard_budget_abort":
        assert after.seen == []
        assert metrics.cycle_overruns.value({"kind": "hard"}) == overruns + 1
    else:
        assert after.seen == ([] if outcome == "action_raises" else [False])


def test_a_collector_disabled_before_entry_stays_disabled_and_untouched(policy):
    probe = _Probe()
    sched = _scheduler(probe)
    full, boundary = _count("full"), _count("boundary")
    frozen = gc.get_freeze_count()
    gc.disable()
    sched.run_once()
    assert probe.seen == [False]
    assert not gc.isenabled()
    # no boundary ran: nothing collected or frozen on the embedding's behalf
    assert (_count("full"), _count("boundary")) == (full, boundary)
    assert gc.get_freeze_count() == frozen
    assert policy.state()["frozen_base"] is None


def test_nested_and_overlapping_entries_reenable_only_at_the_outermost_exit(policy):
    with policy.cycle():
        with policy.cycle():
            assert not gc.isenabled()
        assert not gc.isenabled()
    assert gc.isenabled()

    # overlapping entries from two threads: the first to leave does not
    # re-enable the collector under the one still inside
    entered, leave = threading.Event(), threading.Event()

    def other():
        with policy.cycle():
            entered.set()
            leave.wait(10)

    t = threading.Thread(target=other)
    with policy.cycle():
        t.start()
        assert entered.wait(10)
    assert not gc.isenabled(), "the first exit re-enabled the collector early"
    leave.set()
    t.join(10)
    assert not t.is_alive()
    assert gc.isenabled()
    assert policy.state()["depth"] == 0


def test_a_cycle_dropped_inside_a_cycle_is_reclaimed_at_its_boundary(policy):
    with policy.cycle():
        pass  # the first boundary (a full pass) is out of the way
    with policy.cycle():
        node = _Node()
        ref = weakref.ref(node)
        del node
        assert ref() is not None, "refcounting alone cannot free a cycle"
    assert ref() is None


def test_the_first_boundary_is_a_full_pass_and_later_ones_young(policy):
    full, boundary = _count("full"), _count("boundary")
    with policy.cycle():
        pass
    assert (_count("full"), _count("boundary")) == (full + 1, boundary)
    state = policy.state()
    assert state["frozen_base"] > 0
    assert gc.get_freeze_count() == pytest.approx(state["frozen_base"], rel=0.01)
    assert state["frozen_since"] == 0
    with policy.cycle():
        pass
    assert (_count("full"), _count("boundary")) == (full + 1, boundary + 1)


def test_a_frozen_cycle_is_reclaimed_once_the_quarter_rule_fires(policy):
    with policy.cycle():
        pass  # the first full pass sets the frozen base
    node = _Node()
    ref = weakref.ref(node)
    with policy.cycle():
        pass  # a young boundary freezes it
    del node
    with policy.cycle():
        pass
    assert ref() is not None, "a young boundary must not walk the frozen heap"

    base = policy.state()["frozen_base"]
    kept = [[] for _ in range(int(base * collector.FULL_PASS_SHARE) + 1000)]
    full = _count("full")
    with policy.cycle():
        pass  # freezes `kept`: past a quarter of the base
    assert policy.state()["frozen_since"] > base * collector.FULL_PASS_SHARE
    assert ref() is not None
    with policy.cycle():
        pass  # the quarter rule: a full pass
    assert _count("full") == full + 1
    assert ref() is None
    assert policy.state()["frozen_since"] == 0
    del kept


def test_no_automatic_collection_inside_a_cycle(policy):
    garbage = []

    def allocate():  # far past the young generation's threshold
        garbage.extend([i] for i in range(50 * gc.get_threshold()[0]))

    probe = _Probe(then=allocate)
    sched = _scheduler(probe)
    sched.run_once()
    inside = _count("cycle")
    sched.run_once()
    assert _count("cycle") == inside
    assert len(garbage) > 0


def test_counters_and_spans_with_tracing_on(policy, tracing):
    sched = _scheduler(_Probe())
    sched.run_once()  # the first, full, boundary
    between = _count("between")
    seconds = metrics.gc_pause_seconds.value({"when": "between"})
    obs.recorder.clear()
    gc.collect(0)  # a collection outside a cycle, not the boundary's own
    sched.run_once()
    assert _count("between") >= between + 1
    assert metrics.gc_pause_seconds.value({"when": "between"}) > seconds

    spans = obs.recorder.spans()
    assert obs.check_tree(spans) == []
    by_id = {s["span_id"]: s for s in spans}
    (boundary,) = [s for s in spans if s["name"] == "gc"]
    assert by_id[boundary["parent_id"]]["name"] == "cycle"
    assert boundary["attrs"]["full"] is False
    assert boundary["attrs"]["collected"] >= 0
    assert boundary["attrs"]["frozen"] >= 0
    pauses = [s for s in spans if s["name"] == "gc.pause"]
    assert pauses and all(p["parent_id"] == boundary["span_id"] for p in pauses)
    assert {p["attrs"]["when"] for p in pauses} == {"between"}
    assert {p["attrs"]["generation"] for p in pauses} >= {0}


def test_no_span_with_tracing_off(policy):
    assert not obs.enabled()
    obs.recorder.clear()
    sched = _scheduler(_Probe())
    boundaries = _count("full") + _count("boundary")
    sched.run_once()
    gc.collect(0)
    sched.run_once()
    assert obs.recorder.spans() == []
    assert _count("full") + _count("boundary") == boundaries + 2


@pytest.mark.parametrize("lock", ["recorder", "metrics"])
def test_the_gc_hook_takes_no_lock(policy, tracing, lock):
    """A collection that starts while its thread holds the flight
    recorder's or a metric's lock must not wait on that lock."""
    held = obs.recorder._lock if lock == "recorder" else metrics.gc_collections._lock
    with policy.cycle():
        pass  # the hook is installed
    done = threading.Event()

    def collect_under_lock():
        with held:
            gc.collect(0)
        done.set()

    t = threading.Thread(target=collect_under_lock, daemon=True)
    t.start()
    t.join(10)
    assert done.is_set() and not t.is_alive(), "the gc hook deadlocked on a held lock"
    between = _count("between")
    with policy.cycle():
        pass  # the boundary reports what the hook recorded
    assert _count("between") >= between + 1


@pytest.mark.parametrize("conf", ["default", "tpu"])
def test_close_session_frees_its_world_by_refcount(conf):
    """The boundary's young pass is cheap only if the session's clones
    are gone before it: closing a session drops every plugin callback
    that closes over the session and its nodes, jobs and tasks, so the
    world dies by refcount while the session object itself lives on."""
    from kube_batch_tpu.conf import parse_scheduler_conf
    from kube_batch_tpu.framework import close_session, open_session
    from kube_batch_tpu.scheduler import DEFAULT_SCHEDULER_CONF

    tiers = parse_scheduler_conf(TPU_CONF if conf == "tpu" else DEFAULT_SCHEDULER_CONF).tiers
    cache = _scheduler().cache
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        ssn = open_session(cache, tiers)
        assert ssn.node_order_fns or ssn.predicate_fns
        world = [weakref.ref(x) for x in (*ssn.nodes.values(), *ssn.jobs.values())]
        assert world
        close_session(ssn)
        assert [r for r in world if r() is not None] == []
        assert ssn is not None
    finally:
        if was_enabled:
            gc.enable()
