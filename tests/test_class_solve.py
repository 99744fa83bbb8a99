"""Node-class compressed solve (ISSUE 20): the node axis folded into
equivalence classes, feasibility+score+argmax at class granularity,
concrete placement replayed through the serial tiebreak.

The invariants pinned here:

- **parity**: compressed ≡ uncompressed ≡ serial, bind for bind, on the
  heterogeneous-pool world — single chip, every mesh size, the
  pod-affinity pause/resume hybrid, and a streaming micro-cycle over a
  resident table that absorbed a peer shard's occupancy patch;
- **dynamics**: in-solve splits (a bound node leaves its class), the
  segment iteration cap forcing a mid-solve re-pack, and re-merges of
  bound-alike nodes all demonstrably fire, with the power-of-two slot
  bucket sticky across cycles;
- **degrade, never drop**: the ``solve.class_table`` fault point drops
  the cycle to the uncompressed tier with identical binds and a metered
  degrade;
- **zero warm recompiles**: 1%-churn sessions run under a
  CompileSentinel budget of zero;
- **tooling**: the wide-key native ``class_dedup`` agrees with the
  np.unique fallback, and the class explain path is byte-identical to
  the per-node one.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import kube_batch_tpu.actions  # noqa: F401
import kube_batch_tpu.plugins  # noqa: F401
from kube_batch_tpu import faults, metrics
from kube_batch_tpu.conf import parse_scheduler_conf
from kube_batch_tpu.framework import close_session, open_session
from kube_batch_tpu.ops.class_solve import ENV, _smoke_world, dedup_rows
from kube_batch_tpu.testing import FakeCache


# The reference's default conf: drf + proportion fold into the loop
# state, so the class key carries the fairness planes too.
TIERS_YAML = """
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

N_SMOKE_NODES = 4 * 18


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.registry.reset()
    faults.solver_ladder.reset()
    yield
    faults.registry.reset()
    faults.solver_ladder.reset()


def run_xla(cluster, compress, mesh=None, action=None, tiers=TIERS_YAML):
    from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction

    saved = os.environ.get(ENV)
    os.environ[ENV] = "1" if compress else "0"
    try:
        action = action or XlaAllocateAction()
        args = {"xla_allocate": {"mesh": mesh}} if mesh else {}
        cache = FakeCache(cluster)
        ssn = open_session(cache, parse_scheduler_conf(tiers).tiers, args)
        try:
            action.execute(ssn)
        finally:
            close_session(ssn)
        return dict(cache.binder.binds), action
    finally:
        if saved is None:
            os.environ.pop(ENV, None)
        else:
            os.environ[ENV] = saved


def run_serial(cluster, tiers=TIERS_YAML):
    from kube_batch_tpu.actions.allocate import AllocateAction

    cache = FakeCache(cluster)
    ssn = open_session(cache, parse_scheduler_conf(tiers).tiers)
    try:
        AllocateAction().execute(ssn)
    finally:
        close_session(ssn)
    return dict(cache.binder.binds)


@pytest.fixture(scope="module")
def smoke_sides():
    """Serial / uncompressed / compressed over the heterogeneous-pool
    world, computed once for the whole module."""
    serial = run_serial(_smoke_world())
    plain, _ = run_xla(_smoke_world(), compress=False)
    comp, action = run_xla(_smoke_world(), compress=True)
    return {
        "serial": serial,
        "plain": plain,
        "comp": comp,
        "tier": action.last_solver_tier,
        "stats": dict(action.last_class_stats or {}),
    }


# -- parity ------------------------------------------------------------------


def test_compressed_parity_vs_uncompressed_and_serial(smoke_sides):
    assert smoke_sides["comp"] == smoke_sides["plain"] == smoke_sides["serial"]
    assert len(smoke_sides["comp"]) > 0
    assert smoke_sides["tier"] == "class_xla"
    s = smoke_sides["stats"]
    assert 0 < s["class_count"] < N_SMOKE_NODES
    assert s["compression_ratio"] > 5


@pytest.mark.parametrize("n_devices", [2, 4, 8])
def test_mesh_parity(smoke_sides, n_devices):
    """The class kernel under GSPMD: the slot axis is replicated, the
    member replay stays on host — every mesh size must reproduce the
    single-chip (mesh-off) binds exactly."""
    comp, action = run_xla(
        _smoke_world(), compress=True, mesh=f"cpu:{n_devices}"
    )
    assert action.last_mesh_size == n_devices, "sharded path did not engage"
    # blocked mesh rung by default, plain GSPMD when KBT_MESH_PALLAS=off
    assert action.last_solver_tier in ("class_mesh_pallas", "class_sharded_xla")
    assert comp == smoke_sides["plain"]


def _affinity_world():
    """test_parallel's pause/resume world with a duplicated node pool:
    4 byte-identical nodes (one diverges by carrying the anchor), two
    pod-affinity host-only tasks forcing two pause/resume trips through
    the segmented hybrid."""
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
        if i in (4, 9):
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


def test_pod_affinity_pause_resume_hybrid_parity():
    """Host-only tasks pause the class kernel too: the gathered state
    is serial-stepped and re-enters the compressed resume program, on
    and off a mesh, with binds identical to the uncompressed tiers."""
    plain, _ = run_xla(_affinity_world(), compress=False)
    comp, a1 = run_xla(_affinity_world(), compress=True)
    comp4, a4 = run_xla(_affinity_world(), compress=True, mesh="cpu:4")
    assert a1.last_solver_tier.startswith("class_")
    assert a4.last_mesh_size == 4
    assert comp == comp4 == plain and len(plain) == 12


# -- split / re-merge / segment-cap dynamics ---------------------------------


def test_split_remerge_segment_dynamics_across_cycles():
    # extra arrivals push the solve past one segment's iteration budget
    # (c_pad/2 = 64 here), forcing the in-solve re-pack
    comp, action = run_xla(_smoke_world(arrivals=12), compress=True)
    s1 = dict(action.last_class_stats)
    world2 = lambda: _smoke_world(bound=comp, arrivals=18)  # noqa: E731
    comp2, _ = run_xla(world2(), compress=True, action=action)
    s2 = dict(action.last_class_stats)
    plain2, _ = run_xla(world2(), compress=False)
    assert comp2 == plain2

    # a bound node's occupancy diverges from its class mid-solve
    assert s1["splits"] > 0
    # the segment iteration cap forces >=1 in-solve re-pack, where
    # bound-alike singletons collapse back into shared classes
    assert s1["segments"] >= 2
    assert s1["remerges"] + s2["remerges"] > 0
    # power-of-two slot bucket, sticky across cycles (never shrinks)
    for s in (s1, s2):
        assert s["c_pad"] & (s["c_pad"] - 1) == 0 and s["c_pad"] > 0
    assert s2["c_pad"] >= s1["c_pad"]
    assert 0 < s2["class_count"] <= s2["c_pad"]


# -- chaos: degrade, never drop ----------------------------------------------


def test_class_table_fault_degrades_loudly_with_parity(smoke_sides):
    """solve.class_table: a poisoned class table drops the cycle to the
    uncompressed tier — binds identical, degrade + injection metered."""
    labels = {"tier": "class_solve", "reason": "class_table"}
    before = metrics.degraded_cycles.value(labels)
    faults.registry.arm("solve.class_table", count=1)
    binds, action = run_xla(_smoke_world(), compress=True)
    assert binds == smoke_sides["plain"]
    assert action.last_solver_tier == "xla"  # fell to the wrapped rung
    assert action.last_class_stats is None
    assert metrics.degraded_cycles.value(labels) == before + 1
    assert metrics.fault_injections.value({"point": "solve.class_table"}) >= 1


# -- wide-key dedup: native vs fallback --------------------------------------


def test_dedup_rows_native_vs_fallback_partition_parity():
    """Multi-slab keys (f64 matrix + i32 + bool matrix + 1-D i64)
    through the native multi-buffer hash and the np.unique fallback
    (forced by the native.class_dedup fault): class ORDER differs by
    contract, the partition into classes must not."""
    from kube_batch_tpu.native import lib as native

    assert native is not None and hasattr(native, "class_dedup")

    rng = np.random.default_rng(0)
    n = 4096
    slabs = [
        (rng.integers(0, 3, (n, 5)) * 0.5).astype(np.float64),
        rng.integers(0, 4, n).astype(np.int32),
        rng.integers(0, 2, (n, 3)).astype(bool),
        rng.integers(0, 2, n).astype(np.int64),
    ]
    first_n, inv_n = dedup_rows(slabs)
    faults.registry.arm("native.class_dedup")
    first_f, inv_f = dedup_rows(slabs)
    _, _, fired = faults.registry.active()["native.class_dedup"]
    assert fired >= 1, "fallback path never engaged"

    def partition(first, inv):
        groups: dict[int, list[int]] = {}
        for row, cls in enumerate(inv):
            groups.setdefault(int(cls), []).append(row)
        for cls, members in groups.items():
            # the representative is a member of its own class
            assert int(first[cls]) in members
        return sorted(tuple(m) for m in groups.values())

    assert partition(first_n, inv_n) == partition(first_f, inv_f)
    assert len(first_n) == len(first_f) < n
    assert first_n.dtype == np.int64 and inv_n.dtype == np.int32


# -- class-granularity explain -----------------------------------------------


def test_explain_class_path_byte_identical():
    """explain_batch_classes must reproduce explain_batch exactly —
    eliminations, feasible counts, would-fit bits and the top-k
    near-miss list (same argmax tie contract) — from one evaluated row
    per class."""
    from kube_batch_tpu.ops import explain as ops_explain
    from kube_batch_tpu.ops.encode import encode_session
    from kube_batch_tpu.ops.kernels import solve_allocate_state

    ssn = open_session(
        FakeCache(_smoke_world()), parse_scheduler_conf(TIERS_YAML).tiers
    )
    enc = encode_session(ssn.jobs, ssn.nodes, ssn.queues, dtype=np.float64)
    close_session(ssn, discard=True)
    arrays = dict(enc.arrays)
    arrays.update(
        w_least=np.float64(1), w_balanced=np.float64(1),
        w_aff=np.float64(1), w_podaff=np.float64(1),
    )
    state = solve_allocate_state(arrays)

    rep_rows = ops_explain.pad_rows(
        [int(arrays["job_start"][j])
         for j in range(len(enc.jobs)) if arrays["job_valid"][j]]
    )
    st = tuple(
        np.asarray(getattr(state, f))
        for f in ("idle", "rel", "used", "ntasks", "nports")
    )
    base = ops_explain.explain_batch(arrays, *st, rep_rows)
    comp = ops_explain.explain_batch_classes(arrays, *st, rep_rows)
    real = np.asarray(rep_rows) >= 0
    assert real.sum() > 0
    for b, c in zip(base, comp):
        np.testing.assert_array_equal(np.asarray(b)[real], np.asarray(c)[real])


# -- streaming micro-cycle over an absorbed peer patch -----------------------


def test_streaming_micro_cycle_absorb_patch_class_parity():
    """Federated streaming shape: a full cycle adopts the resident node
    table, a peer shard's bind lands as an absorb-mode occupancy patch
    (not a degrade), and the next micro-cycle solves fresh arrivals over
    the patched residents — compressed vs uncompressed must agree bind
    for bind on both the full cycle and the micro-cycle."""
    from kube_batch_tpu.apis.types import PodPhase
    from kube_batch_tpu.streaming import StreamState, open_micro_session
    from kube_batch_tpu.testing import (
        build_cluster,
        build_node,
        build_pod,
        build_pod_group,
        build_queue,
        build_resource_list,
    )

    def arrival_jobs():
        pods, pgs = [], []
        for g in range(2):
            name = f"arrival-{g}"
            pgs.append(build_pod_group(name, min_member=2))
            for m in range(2):
                pods.append(
                    build_pod(
                        name=f"{name}-t{m}", group_name=name,
                        req=build_resource_list(cpu="1", memory="2Gi"),
                    )
                )
        scratch = build_cluster(
            pods, [build_node("scratch", build_resource_list(cpu=1))],
            pgs, [build_queue("default")],
        )
        ssn = open_session(
            FakeCache(scratch), parse_scheduler_conf(TIERS_YAML).tiers
        )
        jobs, queues = dict(ssn.jobs), dict(ssn.queues)
        close_session(ssn, discard=True)
        return jobs, queues

    def side(compress):
        from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction

        saved = os.environ.get(ENV)
        os.environ[ENV] = "1" if compress else "0"
        try:
            action = XlaAllocateAction()
            tiers = parse_scheduler_conf(TIERS_YAML).tiers
            cache = FakeCache(_smoke_world())
            ssn = open_session(cache, tiers)
            action.execute(ssn)
            st = StreamState()
            st.adopt_full_cycle(ssn)
            close_session(ssn)
            full = dict(cache.binder.binds)

            # peer shard binds a pod that fills large-000 down to
            # 500m/1Gi — absorbed as an occupancy patch (table stays
            # valid), and consequential: the 1cpu/2Gi arrivals can no
            # longer land there
            idle = st.nodes["large-000"].idle
            peer = build_pod(
                name="peer-0", node_name="large-000",
                phase=PodPhase.RUNNING,
                req=build_resource_list(
                    cpu=f"{int(idle.milli_cpu) - 500}m",
                    memory=f"{int(idle.memory // 2**20) - 1024}Mi",
                ),
            )
            assert st.apply_bound_patches([("add", "default/peer-0", peer)])
            assert st.valid

            jobs, queues = arrival_jobs()
            mssn = open_micro_session(cache, tiers, {}, jobs, st.nodes, queues)
            mssn.micro_cycle = True
            action.execute(mssn)
            close_session(mssn)
            micro = {
                k: v for k, v in cache.binder.binds.items() if k not in full
            }
            return full, micro, action.last_solver_tier
        finally:
            if saved is None:
                os.environ.pop(ENV, None)
            else:
                os.environ[ENV] = saved

    full_c, micro_c, tier = side(True)
    full_p, micro_p, _ = side(False)
    assert tier.startswith("class_"), "micro-cycle did not solve at class level"
    assert full_c == full_p
    assert micro_c == micro_p and len(micro_c) == 4
    # the absorbed peer occupancy was consequential: nothing else fits
    # on large-000 after a 28-cpu resident landed there
    assert "large-000" not in micro_c.values()


# -- zero warm recompiles under churn ----------------------------------------


def test_warm_churn_sessions_zero_recompiles():
    """1%-class node churn (the resident shape moves with the salt)
    must re-key classes without moving the power-of-two class bucket —
    warm sessions compile nothing."""
    from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction
    from kube_batch_tpu.analysis.trace.sentinel import CompileSentinel
    from kube_batch_tpu.models import uniform_pool

    action = XlaAllocateAction()
    world = lambda salt: uniform_pool(  # noqa: E731
        800, 100, churn=0.02, churn_salt=salt
    )
    for salt in (0, 1):  # compile + warm the sticky bucket
        run_xla(world(salt), compress=True, action=action)
    with CompileSentinel("class solve warm churn", budget=0) as cs:
        for salt in (2, 3):
            _, action = run_xla(world(salt), compress=True, action=action)
    assert cs.compiles == 0
    assert action.last_solver_tier.startswith("class_")
    assert action.last_class_stats["compression_ratio"] > 10
