"""Pallas fused solve ≡ XLA while-loop solve.

The XLA kernel (ops/kernels.py) is itself pinned against the serial
oracle (tests/test_xla_allocate.py); these tests pin the fused Pallas
kernel (ops/pallas_solve.py) against the XLA kernel, decision for
decision, on the same float32 snapshots. On CPU the Pallas kernel runs
in interpreter mode; on the real chip the compiled kernel is covered by
chip_smoke.py's bind parity with the XLA twin and the serial actions
(the action auto-selects the Pallas path on TPU).
"""

import numpy as np
import pytest

from kube_batch_tpu import actions  # noqa: F401  (registers actions)
from kube_batch_tpu import plugins  # noqa: F401  (registers plugins)
from kube_batch_tpu.conf import parse_scheduler_conf
from kube_batch_tpu.framework import close_session, open_session
from kube_batch_tpu.models import multi_tenant_ml, synthetic
from kube_batch_tpu.ops.encode import encode_session
from kube_batch_tpu.ops.kernels import solve_allocate_state
from kube_batch_tpu.ops.pallas_solve import PallasSolver, supported
from kube_batch_tpu.testing import FakeCache

from test_xla_allocate import DEFAULT_TIERS_YAML, gen_cluster


def solve_both(cluster, drf=True, proportion=True):
    """Encode once (float32), run the XLA and interpret-mode Pallas
    solvers on identical arrays; return both final states."""
    cache = FakeCache(cluster)
    ssn = open_session(cache, parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers)
    enc = encode_session(
        ssn.jobs,
        ssn.nodes,
        ssn.queues,
        dtype=np.float32,
        drf=ssn.plugins.get("drf") if drf else None,
        proportion=ssn.plugins.get("proportion") if proportion else None,
    )
    close_session(ssn)
    if not enc.tasks:
        return None, None
    a = dict(enc.arrays)
    a["w_least"] = np.float32(1)
    a["w_balanced"] = np.float32(1)
    a["w_aff"] = np.float32(1)
    a["w_podaff"] = np.float32(1)
    assert supported(a)
    lax_state = solve_allocate_state(a, None, enable_drf=drf, enable_proportion=proportion)
    pallas_state = PallasSolver(a, drf, proportion, interpret=True, fetch_f32=True).solve(None)
    return lax_state, pallas_state


def assert_states_equal(lax_state, pallas_state, ctx=""):
    l, p = lax_state, pallas_state
    assert int(l.step) == int(p.step), f"{ctx}: step"
    np.testing.assert_array_equal(np.asarray(l.assigned_node), p.assigned_node, err_msg=f"{ctx}: node")
    np.testing.assert_array_equal(np.asarray(l.assigned_kind), p.assigned_kind, err_msg=f"{ctx}: kind")
    np.testing.assert_array_equal(np.asarray(l.assign_pos), p.assign_pos, err_msg=f"{ctx}: pos")
    np.testing.assert_array_equal(np.asarray(l.ready_cnt), p.ready_cnt, err_msg=f"{ctx}: ready")
    np.testing.assert_array_equal(np.asarray(l.ptr), p.ptr, err_msg=f"{ctx}: ptr")
    np.testing.assert_array_equal(np.asarray(l.job_active), p.job_active, err_msg=f"{ctx}: active")
    np.testing.assert_array_equal(np.asarray(l.q_dropped), p.q_dropped, err_msg=f"{ctx}: q_dropped")
    np.testing.assert_allclose(np.asarray(l.idle), p.idle, err_msg=f"{ctx}: idle")
    np.testing.assert_allclose(np.asarray(l.used), p.used, err_msg=f"{ctx}: used")
    np.testing.assert_allclose(np.asarray(l.job_alloc), p.job_alloc, err_msg=f"{ctx}: job_alloc")
    np.testing.assert_allclose(np.asarray(l.q_alloc), p.q_alloc, err_msg=f"{ctx}: q_alloc")


def test_synthetic_small():
    assert_states_equal(*solve_both(synthetic(40, 5)))


def test_synthetic_medium():
    assert_states_equal(*solve_both(synthetic(200, 20)))


def test_scalar_resources_multi_tenant():
    """GPU/TPU scalar slots exercise the has-scalar gates and the Go
    nil-scalar-map parity bits inside the kernel."""
    assert_states_equal(
        *solve_both(multi_tenant_ml(n_jobs=8, n_nodes=8, n_queues=3))
    )


def test_no_drf_no_proportion_variant():
    lax_state, pallas_state = solve_both(synthetic(60, 6), drf=False, proportion=False)
    assert_states_equal(lax_state, pallas_state)


@pytest.mark.parametrize("batch", range(3))
def test_property_pallas_equals_xla(batch):
    """Random snapshots (gang jobs, priorities, selectors, taints,
    residents, multi-queue) — the fused kernel must match the XLA kernel
    decision for decision under the default conf."""
    for seed in range(batch * 4, (batch + 1) * 4):
        lax_state, pallas_state = solve_both(gen_cluster(seed))
        if lax_state is None:
            continue
        assert_states_equal(lax_state, pallas_state, ctx=f"seed {seed}")


def test_action_uses_pallas_in_interpret_mode(monkeypatch):
    """End-to-end through the action: KBT_PALLAS=interpret must produce
    the exact lax-path session outcome (binds and task states)."""
    from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction

    def run(mode):
        monkeypatch.setenv("KBT_PALLAS", mode)
        cache = FakeCache(synthetic(80, 8))
        ssn = open_session(cache, parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers)
        XlaAllocateAction(dtype=np.float32).execute(ssn)
        state = {}
        for job in ssn.jobs.values():
            for tasks in job.task_status_index.values():
                for t in tasks.values():
                    state[t.uid] = (t.status, t.node_name)
        close_session(ssn)
        return state, dict(cache.binder.binds)

    lax_state, lax_binds = run("0")
    pallas_state, pallas_binds = run("interpret")
    assert pallas_state == lax_state
    assert pallas_binds == lax_binds


def test_fold_boundary_exact_128_tasks():
    """T exactly at the fold boundary (128 tasks -> one full row)."""
    assert_states_equal(*solve_both(synthetic(128, 4, tasks_per_job=8)))


def test_fold_boundary_129_tasks():
    """T one past the fold boundary (129 -> two rows, second nearly empty).
    synthetic() builds n_pods//tasks_per_job jobs; 129 with 3-task jobs
    gives 43 jobs x 3 = 129 tasks exactly."""
    assert_states_equal(*solve_both(synthetic(129, 5, tasks_per_job=3)))


def test_single_node_single_job():
    assert_states_equal(*solve_both(synthetic(6, 1, tasks_per_job=6)))


def test_more_tasks_than_capacity():
    """Oversubscribed: most tasks must stay pending, gang barrier holds."""
    lax_state, pallas_state = solve_both(synthetic(300, 2, tasks_per_job=10))
    assert_states_equal(lax_state, pallas_state)
    assert int(pallas_state.step) < 300


def test_supported_envelope_edges():
    """Out-of-envelope snapshots must be detected so the action routes to
    the XLA kernel instead of failing in Mosaic."""
    import numpy as np

    from kube_batch_tpu.ops import pallas_solve

    def base(T=64, N=16, R=2, P=1, GT=1):
        return {
            "task_req": np.zeros((T, R), np.float32),
            "task_res": np.zeros((T, R), np.float32),
            "task_gid": np.zeros(T, np.int32),
            "task_has_sc": np.zeros(T, bool),
            "task_res_has_sc": np.zeros(T, bool),
            "task_host_only": np.zeros(T, bool),
            "task_ports": np.zeros((T, P), bool),
            "compat": np.zeros((GT, 4), bool),
            "node_idle": np.zeros((N, R), np.float32),
            "job_min": np.zeros(8, np.int32),
            "queue_rank": np.zeros(2, np.int32),
        }

    assert pallas_solve.supported(base())
    assert not pallas_solve.supported(base(R=9))  # resource rank beyond R8
    assert not pallas_solve.supported(base(P=40))  # > 31 distinct host ports
    # compat expansion past the VMEM budget (GT x N too large)
    assert not pallas_solve.supported(base(GT=4096, N=8192))


def test_vmem_budget_is_device_aware(monkeypatch):
    """v5e-class cores (128 MiB VMEM) get the wide budget, older TPU
    cores the conservative one, a TPU missing from the table raises,
    the CPU (interpret path) keeps the conservative value, and
    KBT_VMEM_BUDGET overrides them all."""
    import jax

    from kube_batch_tpu.ops import pallas_solve

    class Dev:
        def __init__(self, kind, platform="tpu"):
            self.device_kind = kind
            self.platform = platform

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v5 lite")])
    assert pallas_solve.vmem_budget() == 96 * 1024 * 1024
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v3")])
    assert pallas_solve.vmem_budget() == pallas_solve._DEFAULT_VMEM_BUDGET
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        pallas_solve.vmem_budget()
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("cpu", "cpu")])
    assert pallas_solve.vmem_budget() == pallas_solve._DEFAULT_VMEM_BUDGET
    monkeypatch.setenv("KBT_VMEM_BUDGET", str(7 * 1024 * 1024))
    assert pallas_solve.vmem_budget() == 7 * 1024 * 1024


def test_many_scalar_resources_falls_back_to_lax(monkeypatch):
    """A cluster with 7+ distinct scalar resources (R > 8) runs the XLA
    kernel via the action and still matches serial."""
    import numpy as np

    from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction
    from kube_batch_tpu.testing import (
        build_cluster,
        build_node,
        build_pod,
        build_pod_group,
        build_queue,
        build_resource_list,
    )

    scalars = {f"vendor{i}.com/dev": 2 for i in range(7)}

    def mk():
        pods = [
            build_pod(
                name=f"p{i}",
                group_name="pg",
                req=build_resource_list(cpu=1, memory="1Gi", **scalars),
            )
            for i in range(3)
        ]
        nodes = [
            build_node(
                f"n{i}", build_resource_list(cpu=4, memory="8Gi", pods=10, **scalars)
            )
            for i in range(2)
        ]
        return build_cluster(
            pods, nodes, [build_pod_group("pg", min_member=1)], [build_queue("default")]
        )

    monkeypatch.setenv("KBT_PALLAS", "interpret")  # would use pallas if eligible

    def run(action):
        cache = FakeCache(mk())
        ssn = open_session(cache, parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers)
        if action == "serial":
            from kube_batch_tpu.actions.allocate import AllocateAction

            AllocateAction().execute(ssn)
        else:
            XlaAllocateAction(dtype=np.float32).execute(ssn)
        binds = dict(cache.binder.binds)
        close_session(ssn)
        return binds

    assert run("xla") == run("serial") != {}


def test_pod_affinity_keeps_pallas_kernel(monkeypatch):
    """VERDICT r3 item 7: live InterPodAffinity no longer forces the XLA
    kernel. A cluster with affinity pods (two host-stepped pauses) runs
    the Pallas solver across every segment — its affinity static
    re-folded per resume — and matches the serial action exactly."""
    from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction
    from kube_batch_tpu.apis.types import Affinity, PodAffinityTerm, PodPhase
    from kube_batch_tpu.ops import pallas_solve
    from kube_batch_tpu.testing import (
        build_cluster,
        build_node,
        build_pod,
        build_pod_group,
        build_queue,
        build_resource_list,
    )

    def mk():
        pods, groups = [], []
        for i in (0, 1):
            pods.append(
                build_pod(
                    name=f"anchor{i}",
                    node_name=f"n{i}",
                    phase=PodPhase.RUNNING,
                    req=build_resource_list(cpu=1, memory="128Mi"),
                    labels={"app": "db"},
                )
            )

        def gang(name, pod, ts):
            pod.metadata.creation_timestamp = ts
            pg = build_pod_group(name, min_member=1)
            pg.metadata.creation_timestamp = ts
            pods.append(pod)
            groups.append(pg)

        for i, ts in ((0, 0.0), (1, 10.0)):
            aff = build_pod(
                name=f"aff{i}", group_name=f"g-aff{i}",
                req=build_resource_list(cpu=1, memory="256Mi"),
            )
            aff.affinity = Affinity(
                pod_affinity_required=[PodAffinityTerm(label_selector={"app": "db"})]
            )
            gang(f"g-aff{i}", aff, ts)
        for i in range(6):
            gang(
                f"g-fill{i}",
                build_pod(
                    name=f"fill{i}", group_name=f"g-fill{i}",
                    req=build_resource_list(cpu=2, memory="2Gi"),
                ),
                1.0 + i,
            )
        nodes = [
            build_node(f"n{i}", build_resource_list(cpu=8, memory="8Gi", pods=20))
            for i in range(3)
        ]
        return build_cluster(pods, nodes, groups, [build_queue("default")])

    monkeypatch.setenv("KBT_PALLAS", "interpret")
    solve_calls = {"n": 0}
    orig_solve = pallas_solve.PallasSolver.solve

    def counting_solve(self, state=None):
        solve_calls["n"] += 1
        return orig_solve(self, state)

    monkeypatch.setattr(pallas_solve.PallasSolver, "solve", counting_solve)

    def run(action):
        cache = FakeCache(mk())
        ssn = open_session(cache, parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers)
        if action == "serial":
            from kube_batch_tpu.actions.allocate import AllocateAction

            AllocateAction().execute(ssn)
        else:
            XlaAllocateAction(dtype=np.float32).execute(ssn)
        close_session(ssn)
        return dict(cache.binder.binds)

    serial_binds = run("serial")
    xla_binds = run("xla")
    assert xla_binds == serial_binds
    assert len(serial_binds) == 8
    # initial segment + a resume per host-stepped affinity pod
    assert solve_calls["n"] >= 3, f"pallas did not drive the hybrid ({solve_calls})"


class TestClassDedupParity:
    """ADVICE r5 (low): the native class_dedup numbers classes in
    first-occurrence order, the np.unique fallback in sorted-key order.
    Class id order is documented as meaningless — these tests pin that
    the two paths produce the SAME task partition and the SAME binds, so
    a future consumer tie-breaking on class id cannot diverge undetected
    between KBT_NATIVE=0 and native runs."""

    def _arrays(self):
        """A snapshot with real class structure: duplicate pods (one
        class), a distinct-resource pod, and port/gang variation."""
        cache = FakeCache(synthetic(96, 8, tasks_per_job=6))
        ssn = open_session(cache, parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers)
        enc = encode_session(
            ssn.jobs, ssn.nodes, ssn.queues, dtype=np.float32,
            drf=ssn.plugins.get("drf"), proportion=ssn.plugins.get("proportion"),
        )
        close_session(ssn)
        return dict(enc.arrays)

    def test_partition_and_reconstruction_parity(self):
        from kube_batch_tpu import faults
        from kube_batch_tpu.native import lib as native_lib
        from kube_batch_tpu.ops import pallas_solve as PS

        if native_lib is None or not hasattr(native_lib, "class_dedup"):
            pytest.skip("native class_dedup unavailable in this image")
        a = self._arrays()

        PS._class_inv_slot = None  # drop the per-cycle memo
        tports_n, first_n, inv_n = PS._class_inverse(a)

        faults.registry.arm("native.class_dedup")  # force the fallback
        try:
            PS._class_inv_slot = None
            tports_f, first_f, inv_f = PS._class_inverse(a)
        finally:
            faults.registry.reset()
            PS._class_inv_slot = None

        assert np.array_equal(tports_n, tports_f)
        assert first_n.shape == first_f.shape  # same class count
        # each representative index reconstructs its own class id
        assert np.array_equal(inv_n[first_n], np.arange(first_n.shape[0]))
        assert np.array_equal(inv_f[first_f], np.arange(first_f.shape[0]))

        # the task partition (which tasks share a class) is identical,
        # independent of class numbering
        def partition(inv):
            groups: dict[int, list[int]] = {}
            for task_row, cls in enumerate(inv.tolist()):
                groups.setdefault(cls, []).append(task_row)
            return sorted(tuple(g) for g in groups.values())

        assert partition(inv_n) == partition(inv_f)

    def test_binds_identical_native_vs_fallback(self, monkeypatch):
        """Same snapshot through the full action (interpret-mode pallas,
        which consumes the class tables) with and without the native
        dedup: identical binds."""
        from kube_batch_tpu import faults
        from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction
        from kube_batch_tpu.ops import pallas_solve as PS

        monkeypatch.setenv("KBT_PALLAS", "interpret")

        def run():
            PS._class_inv_slot = None
            cache = FakeCache(synthetic(80, 8))
            ssn = open_session(cache, parse_scheduler_conf(DEFAULT_TIERS_YAML).tiers)
            XlaAllocateAction(dtype=np.float32).execute(ssn)
            close_session(ssn)
            return dict(cache.binder.binds)

        native_binds = run()
        faults.registry.arm("native.class_dedup")
        try:
            fallback_binds = run()
        finally:
            faults.registry.reset()
            PS._class_inv_slot = None
        assert native_binds == fallback_binds != {}
