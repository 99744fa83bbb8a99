"""Incremental cross-cycle encoder (ISSUE 5): parity, invalidation,
arena, and chaos coverage for ops/encode_cache.py.

The contract under test: with ``KBT_ENCODE_CACHE`` on (the default), a
warm encode — and a churned re-encode — is **byte-identical** to a cold
encode of the same world, and every scheduling path (serial action, XLA
twin, the mesh rungs at {1,2,4,8} devices) places bind-for-bind
identically to the cache-off path.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from kube_batch_tpu import actions  # noqa: F401  (registers actions)
from kube_batch_tpu import plugins  # noqa: F401  (registers plugins)
from kube_batch_tpu import faults, metrics
from kube_batch_tpu.conf import parse_scheduler_conf
from kube_batch_tpu.framework import close_session, get_action, open_session
from kube_batch_tpu.models import multi_queue, synthetic
from kube_batch_tpu.ops import encode_cache
from kube_batch_tpu.ops.encode import _bucket, _job_bucket, encode_session
from kube_batch_tpu.testing import (
    FakeCache,
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource_list,
)

CONF = """
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
"""


@pytest.fixture(autouse=True)
def _fresh_cache():
    """Every test starts cold and leaves no armed faults behind."""
    encode_cache.get().invalidate_all("test")
    faults.registry.reset()
    yield
    encode_cache.get().invalidate_all("test")
    faults.registry.reset()
    os.environ.pop("KBT_ENCODE_CACHE", None)


def _tiers():
    return parse_scheduler_conf(CONF).tiers


def _encode(ssn, dtype=np.float64):
    return encode_session(
        ssn.jobs, ssn.nodes, ssn.queues, dtype=dtype,
        drf=ssn.plugins.get("drf"), proportion=ssn.plugins.get("proportion"),
        session=ssn,
    )


def _assert_arrays_equal(a, b, what=""):
    assert set(a.arrays) == set(b.arrays)
    for k in a.arrays:
        x, y = np.asarray(a.arrays[k]), np.asarray(b.arrays[k])
        assert x.shape == y.shape and x.dtype == y.dtype, f"{what} arrays[{k}]"
        assert np.array_equal(x, y), f"{what} arrays[{k}] diverges"


# -- encode-level parity -----------------------------------------------------


def test_warm_encode_byte_identical_to_cold():
    ssn = open_session(FakeCache(multi_queue(400, 64)), _tiers())
    cold = _encode(ssn)
    warm = _encode(ssn)
    _assert_arrays_equal(cold, warm, "warm")
    assert encode_cache.get().warm_fraction > 0.5
    assert [t.uid for t in warm.tasks] == [t.uid for t in cold.tasks]
    close_session(ssn)


def test_churned_encode_identical_to_fresh_cold():
    """Node churn (label flip via set_node — the watch-event shape) must
    invalidate exactly the churned rows: the re-encode equals a fully
    cold encode of the churned world."""
    ssn = open_session(FakeCache(multi_queue(400, 64)), _tiers())
    _encode(ssn)
    for name in sorted(ssn.nodes)[:3]:
        ssn.nodes[name].set_node(
            build_node(
                name,
                build_resource_list(cpu=8, memory="16Gi", pods=110),
                labels={"churn/zone": "z1"},
            )
        )
    churn = _encode(ssn)
    encode_cache.get().invalidate_all("test")
    cold = _encode(ssn)
    _assert_arrays_equal(cold, churn, "churn")
    close_session(ssn)


def test_session_mutation_invalidates_task_block():
    """state_seq is the task block's freshness key: after the session
    mutates (an allocate), the re-encode must see the shrunken pending
    set, not the cached rows."""
    ssn = open_session(FakeCache(multi_queue(120, 16)), _tiers())
    enc1 = _encode(ssn)
    task = enc1.tasks[0]
    node = next(iter(ssn.nodes.values()))
    ssn.allocate(task, node.name)
    enc2 = _encode(ssn)
    assert enc2.n_tasks == enc1.n_tasks - 1
    encode_cache.get().invalidate_all("test")
    cold = _encode(ssn)
    _assert_arrays_equal(cold, enc2, "post-mutation")
    close_session(ssn)


def test_selector_affinity_world_parity():
    """Signature-heavy world (selectors + labeled nodes): the pair memo
    must reproduce the compat/affinity products exactly."""
    nodes = [
        build_node(
            f"n{i:03d}",
            build_resource_list(cpu=4, memory="8Gi", pods=20),
            labels={"disk": "ssd" if i % 2 else "hdd", "zone": f"z{i % 3}"},
        )
        for i in range(24)
    ]
    pods, pgs = [], []
    for j in range(12):
        name = f"job{j:02d}"
        pgs.append(build_pod_group(name, min_member=1))
        for t in range(4):
            pods.append(
                build_pod(
                    name=f"{name}-t{t}",
                    group_name=name,
                    req=build_resource_list(cpu=1, memory="1Gi"),
                    node_selector={"disk": "ssd"} if j % 2 else None,
                )
            )
    from kube_batch_tpu.testing import build_cluster

    cluster = build_cluster(pods, nodes, pgs, [build_queue("default")])
    ssn = open_session(FakeCache(cluster), _tiers())
    cold = _encode(ssn)
    warm = _encode(ssn)
    _assert_arrays_equal(cold, warm, "selector")
    # churn one node into a new signature group
    ssn.nodes["n001"].set_node(
        build_node(
            "n001",
            build_resource_list(cpu=4, memory="8Gi", pods=20),
            labels={"disk": "nvme", "zone": "z9"},
        )
    )
    churn = _encode(ssn)
    encode_cache.get().invalidate_all("test")
    _assert_arrays_equal(_encode(ssn), churn, "selector-churn")
    close_session(ssn)


# -- action-level placement parity (serial + mesh {1,2,4,8}) -----------------


def _run_action(cluster, action_args=None, env=None):
    saved = {}
    for k, v in (env or {}).items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    try:
        cache = FakeCache(cluster)
        ssn = open_session(cache, _tiers(), action_args)
        get_action("xla_allocate").execute(ssn)
        state = {
            t.uid: (t.status, t.node_name)
            for j in ssn.jobs.values()
            for d in j.task_status_index.values()
            for t in d.values()
        }
        binds = dict(cache.binder.binds)
        close_session(ssn)
        return state, binds
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.mark.parametrize("mesh", [None, 1, 2, 4, 8])
def test_placements_identical_cache_on_vs_off(mesh):
    """The acceptance pin: warm-path placements bind-for-bind identical
    to the cache-off path — serial-eligible snapshot, XLA twin, and the
    mesh rungs at {1,2,4,8} devices."""
    args = {"xla_allocate": {"mesh": f"cpu:{mesh}"}} if mesh else None
    make = lambda: synthetic(300, 64)  # noqa: E731
    state_off, binds_off = _run_action(make(), args, env={"KBT_ENCODE_CACHE": "0"})
    # cache on, twice (second run hits the per-object memos)
    state_on, binds_on = _run_action(make(), args, env={"KBT_ENCODE_CACHE": "1"})
    state_on2, binds_on2 = _run_action(make(), args, env={"KBT_ENCODE_CACHE": "1"})
    assert binds_on == binds_off and binds_on2 == binds_off
    assert state_on == state_off and state_on2 == state_off


def test_serial_action_untouched_by_cache():
    """The serial allocate does not encode: cache on/off cannot differ."""
    make = lambda: synthetic(120, 16)  # noqa: E731
    results = []
    for flag in ("0", "1"):
        os.environ["KBT_ENCODE_CACHE"] = flag
        cache = FakeCache(make())
        ssn = open_session(cache, _tiers())
        get_action("allocate").execute(ssn)
        results.append(dict(cache.binder.binds))
        close_session(ssn)
    assert results[0] == results[1]


# -- chaos: encode.cache fault + churn with the mutation detector on ---------


@pytest.mark.chaos
def test_encode_cache_fault_and_churn_binds_identical(monkeypatch):
    """Fire `encode.cache` mid-run and churn nodes between cycles with
    the mutation detector on: binds over the whole run must equal the
    cache-off twin's, and the fault must drop the cache (cold encode)."""
    monkeypatch.setenv("KBT_CACHE_MUTATION_DETECTOR", "1")
    monkeypatch.setenv("KBT_MIN_DEVICE_PAIRS", "0")

    from kube_batch_tpu.cache import ClusterStore, SchedulerCache
    from kube_batch_tpu.scheduler import Scheduler

    def drive(cache_flag: str, arm_fault: bool):
        monkeypatch.setenv("KBT_ENCODE_CACHE", cache_flag)
        encode_cache.get().invalidate_all("test")
        faults.registry.reset()
        store = ClusterStore()
        store.create_queue(build_queue("default"))
        for i in range(8):
            store.create_node(
                build_node(
                    f"n{i}", build_resource_list(cpu=16, memory="32Gi", pods=64)
                )
            )
        cache = SchedulerCache(store)
        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            conf = os.path.join(tmp, "conf.yaml")
            with open(conf, "w", encoding="utf-8") as fh:
                fh.write('actions: "enqueue, xla_allocate"\n' + CONF)
            sched = Scheduler(cache, scheduler_conf=conf, schedule_period=0.01)
            for cycle in range(4):
                for m in range(4):
                    store.create_pod(
                        build_pod(
                            name=f"c{cycle}-p{m}", group_name=f"g{cycle}",
                            req=build_resource_list(cpu=1, memory="512Mi"),
                        )
                    )
                store.create_pod_group(build_pod_group(f"g{cycle}", min_member=4))
                if arm_fault and cycle == 2:
                    faults.registry.arm("encode.cache", count=1)
                if cycle == 2:
                    # node churn between cycles: label flip through the
                    # store (the real watch-event path -> dirty feed)
                    n = store.get("nodes", "n0")
                    import dataclasses

                    store.update("nodes", dataclasses.replace(
                        n, metadata=dataclasses.replace(
                            n.metadata, labels={"churn": "1"}
                        )
                    ))
                sched.run_once()
        binds = {
            key: pod.node_name
            for key, pod in (
                (f"{p.namespace}/{p.name}", p) for p in store.list("pods")
            )
        }
        return binds

    binds_off = drive("0", arm_fault=False)
    fired0 = metrics.fault_injections.value({"point": "encode.cache"})
    binds_on = drive("1", arm_fault=True)
    fired1 = metrics.fault_injections.value({"point": "encode.cache"})
    assert binds_on == binds_off
    assert all(v for v in binds_on.values()), "unbound pods left behind"
    assert fired1 == fired0 + 1, "encode.cache fault did not fire"


# -- dirty feed + metrics ----------------------------------------------------


def test_dirty_feed_drops_entries_and_meters():
    ec = encode_cache.get()
    ssn = open_session(FakeCache(multi_queue(60, 8)), _tiers())
    _encode(ssn)
    assert ec._node_static, "node memo empty after encode"
    name = next(iter(ec._node_static))
    v0 = ec.version
    before = metrics.encode_cache_invalidations.value({"reason": "nodes"})
    encode_cache.note_store_event("nodes", name)
    assert name not in ec._node_static
    assert ec.version == v0 + 1
    assert metrics.encode_cache_invalidations.value({"reason": "nodes"}) == before + 1
    close_session(ssn)


def test_warm_fraction_metric_set():
    ssn = open_session(FakeCache(multi_queue(60, 8)), _tiers())
    _encode(ssn)
    _encode(ssn)
    assert metrics.encode_warm_fraction.value() > 0.5
    assert metrics.encode_cache_hits.value() > 0
    close_session(ssn)


def test_disabled_cache_is_inert():
    os.environ["KBT_ENCODE_CACHE"] = "0"
    ec = encode_cache.get()
    ec.invalidate_all("test")
    ssn = open_session(FakeCache(multi_queue(60, 8)), _tiers())
    _encode(ssn)
    _encode(ssn)
    assert ec._task_block is None and not ec._node_static
    close_session(ssn)


# -- tensor arena ------------------------------------------------------------


def test_arena_reuse_and_row_delta():
    import jax  # noqa: F401  (device path)

    arena = encode_cache.TensorArena()
    host = np.arange(32.0).reshape(8, 4)
    d1 = arena.upload("node_idle", host)
    assert arena.full_uploads == 1
    # identical content, different object -> buffer reuse, no upload
    d2 = arena.upload("node_idle", host.copy())
    assert arena.reuses == 1 and d2 is d1
    # one changed row -> in-place row scatter, not a full transfer
    churn = host.copy()
    churn[3] = [100.0, 101.0, 102.0, 103.0]
    d3 = arena.upload("node_idle", churn)
    assert arena.row_updates == 1 and arena.rows_uploaded == 1
    np.testing.assert_array_equal(np.asarray(d3), churn)
    # many changed rows -> full re-upload
    big = churn * 7.0
    d4 = arena.upload("node_idle", big)
    assert arena.full_uploads == 2
    np.testing.assert_array_equal(np.asarray(d4), big)
    # shape change -> fresh buffer
    grown = np.ones((16, 4))
    d5 = arena.upload("node_idle", grown)
    assert arena.full_uploads == 3
    np.testing.assert_array_equal(np.asarray(d5), grown)


def test_arena_device_view_passthrough():
    arena = encode_cache.TensorArena()
    arrays = {
        "node_idle": np.ones((8, 4)),
        "compat": np.ones((2, 3), bool),
        "node_gid": np.zeros(8, np.int32),  # unmanaged: passes through
    }
    view = arena.device_view(arrays)
    assert view["node_gid"] is arrays["node_gid"]
    np.testing.assert_array_equal(np.asarray(view["node_idle"]), arrays["node_idle"])
    np.testing.assert_array_equal(np.asarray(view["compat"]), arrays["compat"])


@pytest.mark.parametrize("gangs", [117, 127, 128, 136, 204])
def test_job_bucket_follows_the_task_bucket(gangs):
    """Gangs of ten whose count dips under a power of two keep the kernel
    shape of their task bucket: (2048, 256) for 1,025-2,048 tasks, the
    pair a cycle of 136 gangs compiles."""
    T = _bucket(gangs * 10)
    assert (T, _job_bucket(gangs, T)) == (2048, 256)


@pytest.mark.parametrize("tasks,jobs,want", [
    (5, 5, 8), (30, 3, 4), (64, 2, 8), (4096, 231, 256), (4096, 299, 512),
    (16384, 898, 1024), (16384, 1197, 2048), (65536, 10, 256), (300, 300, 512),
])
def test_job_bucket_is_a_power_of_two_over_a_floor(tasks, jobs, want):
    assert _job_bucket(jobs, _bucket(tasks)) == want
