"""Blocked sharded-Pallas solver ≡ single-chip solve ≡ serial.

The blocked path (parallel/sharded_pallas.ShardedPallasSolver) runs the
fused block kernel per shard with one argmax exchange per gang
iteration; these tests pin it, decision for decision, against the XLA
while-loop twin (itself pinned against the serial oracle in
test_xla_allocate) at mesh sizes {1, 2, 4, 8} on the virtual CPU mesh,
and bind-for-bind against the serial action through the real
xla_allocate routing — including the segmented pod-affinity
pause/resume hybrid and the per-shard VMEM envelope gate.
"""

import os

import numpy as np
import pytest

import kube_batch_tpu.actions  # noqa: F401  (registers actions)
import kube_batch_tpu.plugins  # noqa: F401  (registers plugins)
from kube_batch_tpu import faults
from kube_batch_tpu.conf import parse_scheduler_conf
from kube_batch_tpu.framework import close_session, open_session
from kube_batch_tpu.models import multi_queue, synthetic
from kube_batch_tpu.ops import pallas_solve
from kube_batch_tpu.ops.encode import encode_session
from kube_batch_tpu.ops.kernels import solve_allocate_state
from kube_batch_tpu.parallel import make_mesh
from kube_batch_tpu.parallel.sharded_pallas import ShardedPallasSolver
from kube_batch_tpu.testing import FakeCache

DEFAULT_TIERS_YAML = """
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


def f32_arrays(cluster, drf=True, proportion=True):
    ssn = open_session(
        FakeCache(cluster), parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers
    )
    enc = encode_session(
        ssn.jobs,
        ssn.nodes,
        ssn.queues,
        dtype=np.float32,
        drf=ssn.plugins.get("drf") if drf else None,
        proportion=ssn.plugins.get("proportion") if proportion else None,
    )
    close_session(ssn)
    a = dict(enc.arrays)
    for k in ("w_least", "w_balanced", "w_aff", "w_podaff"):
        a[k] = np.float32(1)
    return a


def assert_assignment_equal(ref, got, ctx=""):
    np.testing.assert_array_equal(
        np.asarray(ref.assigned_node), np.asarray(got.assigned_node),
        err_msg=f"{ctx}: node",
    )
    np.testing.assert_array_equal(
        np.asarray(ref.assigned_kind), np.asarray(got.assigned_kind),
        err_msg=f"{ctx}: kind",
    )
    np.testing.assert_array_equal(
        np.asarray(ref.assign_pos), np.asarray(got.assign_pos),
        err_msg=f"{ctx}: pos",
    )
    np.testing.assert_array_equal(
        np.asarray(ref.ready_cnt), np.asarray(got.ready_cnt),
        err_msg=f"{ctx}: ready",
    )
    np.testing.assert_array_equal(
        np.asarray(ref.job_active), np.asarray(got.job_active),
        err_msg=f"{ctx}: active",
    )
    np.testing.assert_array_equal(
        np.asarray(ref.q_dropped), np.asarray(got.q_dropped),
        err_msg=f"{ctx}: q_dropped",
    )
    assert int(ref.step) == int(got.step), f"{ctx}: step"
    np.testing.assert_allclose(
        np.asarray(ref.idle), np.asarray(got.idle), err_msg=f"{ctx}: idle"
    )
    np.testing.assert_allclose(
        np.asarray(ref.used), np.asarray(got.used), err_msg=f"{ctx}: used"
    )


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_blocked_sharded_matches_xla_twin(n_devices):
    """The same f32 snapshot through the XLA while-loop twin and the
    blocked sharded solver (jnp block backend on the CPU mesh) must
    agree on every assignment and on the final node state."""
    a = f32_arrays(synthetic(120, 24, seed=3))
    ref = solve_allocate_state(a, None, enable_drf=True, enable_proportion=True)
    got = ShardedPallasSolver(
        a, make_mesh(n_devices), enable_drf=True, enable_proportion=True
    ).solve(None)
    assert_assignment_equal(ref, got, ctx=f"mesh {n_devices}")


@pytest.mark.parametrize("n_devices", [2, 8])
def test_blocked_sharded_multi_queue(n_devices):
    a = f32_arrays(multi_queue(96, 16, n_queues=3, tasks_per_job=6, seed=7))
    ref = solve_allocate_state(a, None, enable_drf=True, enable_proportion=True)
    got = ShardedPallasSolver(
        a, make_mesh(n_devices), enable_drf=True, enable_proportion=True
    ).solve(None)
    assert_assignment_equal(ref, got, ctx=f"mesh {n_devices}")


@pytest.mark.parametrize("n_devices", [2, 4])
def test_blocked_interpret_kernel_matches(n_devices):
    """The actual Pallas block kernel through the interpreter — the code
    the TPU mesh compiles with Mosaic — against the XLA twin."""
    a = f32_arrays(synthetic(80, 16, seed=5))
    ref = solve_allocate_state(a, None, enable_drf=True, enable_proportion=True)
    got = ShardedPallasSolver(
        a, make_mesh(n_devices), enable_drf=True, enable_proportion=True,
        block_impl="interpret",
    ).solve(None)
    assert_assignment_equal(ref, got, ctx=f"interpret mesh {n_devices}")


# -- through the real action: routing, serial parity, pause/resume -------


def run_action(cluster_fn, mesh_spec, env=None):
    from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction

    saved = {}
    for k, v in (env or {}).items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    try:
        cache = FakeCache(cluster_fn())
        ssn = open_session(
            cache,
            parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers,
            {"xla_allocate": {"mesh": mesh_spec}},
        )
        action = XlaAllocateAction(dtype=np.float32)
        action.execute(ssn)
        close_session(ssn)
        return (
            dict(cache.binder.binds),
            action.last_solver_tier,
            action.last_mesh_size,
        )
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_serial(cluster_fn):
    from kube_batch_tpu.framework import get_action

    cache = FakeCache(cluster_fn())
    ssn = open_session(cache, parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers)
    get_action("allocate").execute(ssn)
    close_session(ssn)
    return dict(cache.binder.binds)


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_action_mesh_pallas_binds_match_serial(n_devices):
    """Bind-for-bind identity with the serial path through the real
    action at every mesh size; sizes > 1 must actually take the
    mesh_pallas rung (loud failure, never a silent downgrade)."""
    def mk():
        return multi_queue(600, 64, n_queues=3, tasks_per_job=6, seed=11)

    spec = f"cpu:{n_devices}" if n_devices > 1 else "off"
    binds, tier, mesh_n = run_action(mk, spec)
    if n_devices > 1:
        assert mesh_n == n_devices
        assert tier == "mesh_pallas", f"expected mesh_pallas rung, got {tier}"
    serial = run_serial(mk)
    assert binds == serial and len(binds) == 600


def _pod_affinity_cluster():
    from kube_batch_tpu.apis.types import Affinity, PodAffinityTerm, PodPhase
    from kube_batch_tpu.testing import (
        build_cluster,
        build_node,
        build_pod,
        build_pod_group,
        build_queue,
        build_resource_list,
    )

    anchor = build_pod(
        name="anchor",
        node_name="n0",
        phase=PodPhase.RUNNING,
        req=build_resource_list(cpu=1, memory="128Mi"),
        labels={"app": "db"},
    )
    pods, groups = [anchor], []
    for i in range(12):
        p = build_pod(
            name=f"p{i}",
            group_name=f"g{i}",
            req=build_resource_list(cpu=1, memory="256Mi"),
        )
        p.metadata.creation_timestamp = float(i)
        if i in (4, 9):  # two host-only tasks -> two pause/resume trips
            p.affinity = Affinity(
                pod_affinity_required=[PodAffinityTerm(label_selector={"app": "db"})]
            )
        pg = build_pod_group(f"g{i}", min_member=1)
        pg.metadata.creation_timestamp = float(i)
        pods.append(p)
        groups.append(pg)
    nodes = [
        build_node(f"n{i}", build_resource_list(cpu=8, memory="8Gi", pods=20))
        for i in range(4)
    ]
    return build_cluster(pods, nodes, groups, [build_queue("default")])


@pytest.mark.parametrize("n_devices", [2, 4])
def test_action_mesh_pallas_pause_resume_parity(n_devices):
    """The segmented pod-affinity hybrid on the mesh_pallas rung: the
    paused state is gathered to host, serial-stepped, and re-enters the
    blocked sharded resume program — binds must match the serial path
    and the single-chip run."""
    binds, tier, mesh_n = run_action(_pod_affinity_cluster, f"cpu:{n_devices}")
    assert mesh_n == n_devices
    assert tier == "mesh_pallas"
    single, _, _ = run_action(_pod_affinity_cluster, "off")
    serial = run_serial(_pod_affinity_cluster)
    assert binds == single == serial and len(binds) == 12


# -- the per-shard VMEM envelope ------------------------------------------


def test_block_vmem_scales_with_mesh():
    a = f32_arrays(multi_queue(600, 640, n_queues=3, tasks_per_job=6, seed=2))
    b1 = pallas_solve.block_vmem_bytes(a, 1)
    b4 = pallas_solve.block_vmem_bytes(a, 4)
    b8 = pallas_solve.block_vmem_bytes(a, 8)
    assert b1 > b4 > b8 > 0
    # ceil-division over folded 128-lane rows: within 2x of linear
    assert b1 <= 4 * b4 <= 8 * b1


def test_mesh_supported_beyond_single_chip_envelope(monkeypatch):
    """The capacity story: pick a budget between the per-shard block
    claim and the single-chip claim — the single-chip gate must refuse
    while the 8-shard mesh gate admits. (Needs > 128 nodes: one folded
    128-lane row is the minimum block and cannot subdivide.)"""
    a = f32_arrays(multi_queue(600, 640, n_queues=3, tasks_per_job=6, seed=2))
    lo = pallas_solve.block_vmem_bytes(a, 8)
    hi = pallas_solve.block_vmem_bytes(a, 1)
    assert lo < hi
    monkeypatch.setenv("KBT_VMEM_BUDGET", str((lo + hi) // 2))
    assert pallas_solve.mesh_supported(a, 8)
    assert not pallas_solve.mesh_supported(a, 1)


def test_action_beyond_envelope_stays_on_pallas_rung(monkeypatch):
    """Through the action: a budget too small for the single-chip Pallas
    claim still engages the mesh_pallas rung when the node block divided
    over the mesh fits — instead of degrading to the XLA twin."""
    def mk():
        return multi_queue(600, 640, n_queues=3, tasks_per_job=6, seed=2)

    a = f32_arrays(mk())
    lo = pallas_solve.block_vmem_bytes(a, 8)
    hi = pallas_solve.block_vmem_bytes(a, 1)
    assert lo < hi
    budget = str((lo + hi) // 2)
    monkeypatch.setenv("KBT_VMEM_BUDGET", budget)
    # beyond the single-chip envelope (the full-snapshot claim only
    # grows from the node-block claim), within the 8-shard envelope
    assert not pallas_solve.supported(a)
    assert pallas_solve.mesh_supported(a, 8)
    binds, tier, mesh_n = run_action(mk, "cpu:8")
    assert mesh_n == 8
    assert tier == "mesh_pallas"
    serial = run_serial(mk)
    assert binds == serial and len(binds) == 600


# -- degradation: the mesh_pallas breaker rung ----------------------------


def test_mesh_pallas_fault_degrades_to_sharded_xla():
    """An injected mesh_pallas solve failure must degrade to the mesh
    XLA rung within the cycle (binds still land, still correct) and
    record against the mesh_pallas breaker."""
    def mk():
        return multi_queue(600, 64, n_queues=3, tasks_per_job=6, seed=11)

    faults.registry.reset()
    faults.solver_ladder.reset()
    breaker = faults.solver_ladder.breakers["mesh_pallas"]
    try:
        faults.registry.arm("solve.mesh_pallas", count=1)
        binds, tier, mesh_n = run_action(mk, "cpu:8")
        assert mesh_n == 8
        assert tier == "sharded_xla", f"expected mesh XLA rung, got {tier}"
        assert breaker.failures >= 1
        serial = run_serial(mk)
        assert binds == serial and len(binds) == 600
    finally:
        faults.registry.reset()
        faults.solver_ladder.reset()


# -- K-deep batched exchange (ISSUE 13) -----------------------------------


@pytest.mark.parametrize("n_devices,k", [(2, 2), (4, 4), (8, 4)])
def test_batched_exchange_matches_xla_twin(n_devices, k):
    """The speculative K-deep exchange (one all-gather per K gang
    iterations, owner-shard validation replaying only invalidated
    iterations) must agree with the XLA twin on every assignment and
    actually commit iterations from batches."""
    a = f32_arrays(synthetic(120, 24, seed=3))
    ref = solve_allocate_state(a, None, enable_drf=True, enable_proportion=True)
    sp = ShardedPallasSolver(
        a, make_mesh(n_devices), enable_drf=True, enable_proportion=True,
        exchange_batch=k,
    )
    got = sp.solve(None)
    assert_assignment_equal(ref, got, ctx=f"mesh {n_devices} K={k}")
    assert sp.batched_iters > 0, "no gang iteration committed from a batch"


@pytest.mark.parametrize("n_devices", [2, 8])
def test_batched_exchange_multi_queue(n_devices):
    a = f32_arrays(multi_queue(96, 16, n_queues=3, tasks_per_job=6, seed=7))
    ref = solve_allocate_state(a, None, enable_drf=True, enable_proportion=True)
    sp = ShardedPallasSolver(
        a, make_mesh(n_devices), enable_drf=True, enable_proportion=True,
        exchange_batch=4,
    )
    got = sp.solve(None)
    assert_assignment_equal(ref, got, ctx=f"mq mesh {n_devices} K=4")
    assert sp.batched_iters > 0


def test_batched_exchange_pause_resume_through_action():
    """KBT_PIPELINE + KBT_EXCHANGE_BATCH through the real action routing,
    including the segmented pod-affinity pause/resume hybrid: binds must
    match the serial path, and the action must account the amortized
    iterations."""
    from kube_batch_tpu import pipeline
    from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction

    saved = {k: os.environ.get(k) for k in ("KBT_PIPELINE", "KBT_EXCHANGE_BATCH")}
    os.environ["KBT_PIPELINE"] = "1"
    os.environ["KBT_EXCHANGE_BATCH"] = "4"
    pipeline.reset()
    try:
        cache = FakeCache(_pod_affinity_cluster())
        ssn = open_session(
            cache,
            parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers,
            {"xla_allocate": {"mesh": "cpu:4"}},
        )
        action = XlaAllocateAction(dtype=np.float32)
        action.execute(ssn)
        close_session(ssn)
        assert action.last_mesh_size == 4
        assert action.last_solver_tier == "mesh_pallas"
        assert action.last_batched_iters > 0
        serial = run_serial(_pod_affinity_cluster)
        assert dict(cache.binder.binds) == serial and len(serial) == 12
    finally:
        pipeline.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_default_exchange_batch_env():
    """K defaults to 1 (no speculation) outside pipelined mode; inside,
    KBT_EXCHANGE_BATCH with a sane default and a clamp."""
    from kube_batch_tpu import pipeline
    from kube_batch_tpu.parallel.sharded_pallas import _default_exchange_batch

    saved = {k: os.environ.get(k) for k in ("KBT_PIPELINE", "KBT_EXCHANGE_BATCH")}
    try:
        os.environ.pop("KBT_PIPELINE", None)
        os.environ["KBT_EXCHANGE_BATCH"] = "8"
        assert _default_exchange_batch() == 1, "K>1 must require KBT_PIPELINE"
        os.environ["KBT_PIPELINE"] = "1"
        assert _default_exchange_batch() == 8
        os.environ.pop("KBT_EXCHANGE_BATCH", None)
        assert _default_exchange_batch() == 4
        os.environ["KBT_EXCHANGE_BATCH"] = "200"
        assert _default_exchange_batch() == 64
        os.environ["KBT_EXCHANGE_BATCH"] = "banana"
        assert _default_exchange_batch() == 4
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
