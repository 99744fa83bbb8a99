"""Fused Pallas TPU kernel for the gang-aware allocate solve.

Same algorithm, same policy, same float32 arithmetic as the XLA
`lax.while_loop` kernel (ops/kernels.py `solve_allocate_step`) — but the
*entire* loop runs inside one Mosaic kernel with every array resident in
VMEM, so one solver iteration costs ~2-3us instead of the ~70us of
per-HLO-op dispatch the XLA while loop pays at these (tiny-tensor)
shapes. That difference is the whole ballgame: a 50k-task snapshot is
>50k dependent iterations (reference allocate.go:94-190 is an inherently
sequential greedy loop — each assignment changes the node state the next
decision reads), so the serial spine cannot be batched away without
changing policy; it can only be made cheap. This kernel makes it cheap.

Layout strategy (Mosaic supports dynamic indexing on sublane/leading
dims, NOT on the lane dim — probed, see git history):

- per-task fields fold to ``[T/128, 128]`` (row = t >> 7, lane = t & 127);
  a task access is one dynamic-sublane row load + a lane-mask reduce, and
  a result write is a row read-modify-write — both O(1) vregs;
- task resource vectors dedup into *classes* (unique (req, res, group,
  flags, ports) combinations — a 50k-pod job collapses to a handful), so
  the kernel carries a ``[T/128, 128]`` class id plus tiny
  ``[8, C/128, 128]`` class tables instead of 2x ``[8, T]`` megabytes;
- node arrays fold to ``[8, N/128, 128]`` (resource dim in sublanes);
  feasibility/score are full-array VPU ops, but the *assignment* update
  touches only the 128-lane slab holding the chosen node — a full-array
  RMW measured ~6us/iter, the slab RMW is free;
- job/queue fields fold like tasks; the per-queue "has active jobs" set
  (a scatter over jobs in the XLA kernel) is maintained *incrementally*
  as an active-job counter per queue, updated on the single job/queue
  retirement any iteration can cause;
- the (queue, job) selection block — only needed when the current job
  was retired — sits under `lax.cond` so task-pop iterations skip it.

Equivalence contract: identical op-for-op float32 formulas and identical
lexicographic tie-breaks as ops/kernels.py, pinned by the pallas ≡ XLA
property tests (interpret mode on CPU, real kernel on TPU via bench's
serial-vs-xla bind assertions). The pause/resume protocol for host-only
(pod-affinity) tasks is identical: the kernel exits with ``paused_at``
set, the action serial-steps the task and re-enters with patched state.

Out-of-envelope snapshots (resource rank > 8, > 31 distinct host ports,
a compat matrix too large for VMEM) fall back to the XLA kernel — never
to serial Python.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import lru_cache

import jax
import numpy as np

from kube_batch_tpu.ops.kernels import SolveState, ieee_div as _ieee_div

R8 = 8  # padded resource rank (milli-cpu, memory, <=6 scalar resources)
LANES = 128
INT_MAX = np.iinfo(np.int32).max

# VMEM the fused solve may claim (bytes), by TPU generation: matched as
# a substring of the lower-cased ``device_kind``, first match wins.
_TPU_VMEM_BUDGET = (
    ("v5", 96 * 1024 * 1024),  # v5e ("TPU v5 lite"), v5p: 128 MiB cores
    ("v6", 96 * 1024 * 1024),
    ("v7", 96 * 1024 * 1024),
    ("v4", 12 * 1024 * 1024),  # 16 MiB-class cores
    ("v3", 12 * 1024 * 1024),
    ("v2", 12 * 1024 * 1024),
)
# Off the TPU (CPU, Pallas interpret mode) the gate keeps the
# conservative 16 MiB-class value.
_DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024


def vmem_budget() -> int:
    """Per-core VMEM the solve may claim, by device generation.

    A TPU whose ``device_kind`` is missing from `_TPU_VMEM_BUDGET` is an
    error, not a default: a wrong guess either refuses snapshots the
    chip can hold or sends the kernel a claim it cannot compile. The
    96 MiB entries admit a 400k-task x 40k-node snapshot (~33 MiB
    estimated resident) to the fused kernel; its time on the chip is not
    measured on current code. ``KBT_VMEM_BUDGET`` (bytes) overrides."""
    env = os.environ.get("KBT_VMEM_BUDGET")
    if env:
        try:
            return int(env)
        except ValueError:
            import logging

            logging.getLogger("kube_batch_tpu.ops.pallas_solve").warning(
                "KBT_VMEM_BUDGET=%r is not an integer byte count; "
                "using the device default",
                env,
            )
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _DEFAULT_VMEM_BUDGET
    kind = dev.device_kind.lower()
    for tag, budget in _TPU_VMEM_BUDGET:
        if tag in kind:
            return budget
    raise ValueError(
        f"no VMEM budget for TPU device_kind {dev.device_kind!r}; add it "
        "to pallas_solve._TPU_VMEM_BUDGET or set KBT_VMEM_BUDGET"
    )


def _rows(n: int) -> int:
    return max((n + LANES - 1) // LANES, 1)


def _fold1(x: np.ndarray, rows: int, dtype, pad=0) -> np.ndarray:
    out = np.full(rows * LANES, pad, dtype)
    out[: x.shape[0]] = x
    return out.reshape(rows, LANES)


def _fold2(x: np.ndarray, rows: int, dtype) -> np.ndarray:
    """[X, R] -> [R8, rows, 128] (resource dim to sublanes, X folded)."""
    X, R = x.shape
    out = np.zeros((R8, rows * LANES), dtype)
    out[:R, :X] = np.ascontiguousarray(x.T)
    return out.reshape(R8, rows, LANES)


def _unfold1(x, n: int):
    return np.asarray(x).reshape(-1)[:n]


def _unfold2(x, n: int, r: int):
    return np.ascontiguousarray(np.asarray(x).reshape(R8, -1).T[:n, :r])


def _ports_mask(ports_bool: np.ndarray) -> np.ndarray:
    """[X, P] bool -> int32 bitmask (caller guarantees P <= 31)."""
    P = ports_bool.shape[1]
    bits = (1 << np.arange(P, dtype=np.int64))[None, :]
    return (ports_bool.astype(np.int64) * bits).sum(axis=1).astype(np.int32)


@dataclass
class _Packed:
    """Folded static inputs + initial dynamic state + dims."""

    dims: tuple  # (Tr, Nr, Jr, Qr, Cr, GT, R, max_iter)
    statics: list  # ordered static input arrays
    tcls: np.ndarray
    n_tasks_pad: int  # lax-padded T (for parity of indices)
    n_jobs_pad: int
    n_nodes_pad: int
    n_queues_pad: int


_class_inv_slot: tuple | None = None  # (input arrays, result) single-cycle memo
_CLASS_KEYS = (
    "task_req", "task_res", "task_gid", "task_has_sc",
    "task_res_has_sc", "task_host_only", "task_ports",
)


def _class_inverse(a: dict):
    """Dedup tasks into classes by (req, res, gid, flags, ports): returns
    (tports, first_indices, inverse) as np.unique does. Shared by pack()
    and supported() so the VMEM gate sees the real class count. The last
    result is memoized, keyed on the identity of *every* input array (the
    slot holds strong refs, so `is` comparisons cannot alias freed
    buffers), so the O(T log T) dedup runs once per cycle, not once per
    caller; the memo must stay *outside* the arrays dict, which is a jit
    pytree argument."""
    global _class_inv_slot
    inputs = tuple(a[k] for k in _CLASS_KEYS)
    if _class_inv_slot is not None and all(
        x is y for x, y in zip(_class_inv_slot[0], inputs)
    ):
        return _class_inv_slot[1]
    tports = _ports_mask(np.asarray(a["task_ports"]))
    key = np.concatenate(
        [
            np.asarray(a["task_req"], np.float64),
            np.asarray(a["task_res"], np.float64),
            np.asarray(a["task_gid"], np.float64)[:, None],
            np.asarray(a["task_has_sc"], np.float64)[:, None],
            np.asarray(a["task_res_has_sc"], np.float64)[:, None],
            np.asarray(a["task_host_only"], np.float64)[:, None],
            tports.astype(np.float64)[:, None],
        ],
        axis=1,
    )
    key = np.ascontiguousarray(key)
    from kube_batch_tpu import faults as _faults
    from kube_batch_tpu.native import lib as _native

    if (
        _native is not None
        and hasattr(_native, "class_dedup")
        and not _faults.should_fire("native.class_dedup")
    ):
        # O(T) hash pass, classes in first-occurrence order (~10x the
        # void-sort below at 400k). Any consistent (first, inverse)
        # pairing is equivalent — class order carries no meaning in the
        # packed layout.
        first_b, inv_b = _native.class_dedup(key)
        first = np.frombuffer(first_b, np.int64)
        inv = np.frombuffer(inv_b, np.int32).astype(np.int64)
    else:
        void = key.view(np.dtype((np.void, key.dtype.itemsize * key.shape[1])))
        _, first, inv = np.unique(void.ravel(), return_index=True, return_inverse=True)
    _class_inv_slot = (inputs, (tports, first, inv))
    return tports, first, inv


def supported(a: dict) -> bool:
    """Envelope check for the pallas path (beyond kernel_supported).

    The VMEM estimate accounts for every buffer resident during the solve
    (round-3 advisor finding: the old estimate omitted the class tables,
    jalloc/qalloc, and the doubled state from the manual in->out copy
    that works around Mosaic's aliasing semantics): all packed statics,
    plus the dynamic state twice — once as the aliased inputs, once as
    the output copies the kernel writes at entry."""
    R = a["task_req"].shape[1]
    if R > R8:
        return False
    if a["task_ports"].shape[1] > 31:
        return False
    GT = a["compat"].shape[0]
    N = a["node_idle"].shape[0]
    T = a["task_req"].shape[0]
    J = a["job_min"].shape[0]
    Q = a["queue_rank"].shape[0]
    _, first, _ = _class_inverse(a)
    C = first.shape[0]
    T_pad, N_pad, J_pad, Q_pad, C_pad = (
        _rows(T) * LANES,
        _rows(N) * LANES,
        _rows(J) * LANES,
        _rows(Q) * LANES,
        _rows(C) * LANES,
    )
    # elements (4 bytes each), mirroring _Packed.statics exactly
    statics = (
        T_pad  # tcls
        + 2 * R8 * C_pad  # creq, cres
        + 5 * C_pad  # cgid, chs, crhs, cho, cpt
        + 2 * GT * N_pad  # cnode, affw
        + R8 * N_pad  # nalloc
        + 3 * N_pad  # nmax, nihs, nrhs
        + 6 * J_pad  # jstart/jend/jmin/jprio/jqueue/jvalid
        + 2 * R8 * Q_pad  # qdes, qdim
        + 16 + 2 * R8  # fscal, drft, drfd
        + LANES  # iscal
    )
    # dynamic state, mirroring the kernel's in/out ref lists
    state = (
        3 * T_pad  # tnode, tkind, tpos
        + 3 * R8 * N_pad  # idle, rel, used
        + 2 * N_pad  # ntasks, nports
        + 3 * J_pad  # jptr, jready, jactive
        + 2 * Q_pad  # qdropped, qcount
        + R8 * J_pad  # jalloc
        + R8 * Q_pad  # qalloc
        + Q_pad  # qahs
        + LANES  # oscal
    )
    vmem = (statics + 2 * state) * 4
    return vmem <= vmem_budget()


def block_vmem_bytes(a: dict, mesh_size: int) -> int:
    """Per-shard VMEM the *blocked* sharded solve claims (bytes).

    The blocked path (parallel/sharded_pallas.ShardedPallasSolver) keeps
    only the node-axis slab resident in the block kernel's VMEM: the
    fused feasibility+score kernel reads the local node block of the
    statics (cnode, affw, nalloc, nmax, nihs, nrhs) and the dynamic node
    state (idle, rel, used, ntasks, nports). Task/job/queue state stays
    replicated in XLA-land (HBM/registers), so — unlike the single-chip
    fused kernel, whose envelope is dominated by the task fold at large
    T — the blocked envelope scales with N / mesh_size only. That is the
    capacity story: a snapshot that overflows `vmem_budget()` on one
    chip stays on the Pallas rung when its node block divided over the
    mesh fits.
    """
    N = a["node_idle"].shape[0]
    GT = a["compat"].shape[0]
    Nr = _rows(N)
    n_loc = -(-Nr // max(mesh_size, 1)) * LANES  # folded columns per shard
    # elements (4 bytes each): cnode+affw [GT,...] statics, nalloc +
    # idle/rel/used [R8,...], nmax/nihs/nrhs/ntasks/nports flat, plus the
    # candidate/score scratch the kernel materializes (~4 flat arrays).
    elems = n_loc * (2 * GT + 4 * R8 + 5 + 4)
    return elems * 4


def mesh_supported(a: dict, mesh_size: int) -> bool:
    """Envelope check for the blocked sharded-Pallas path: same static
    limits as the single-chip kernel (resource rank, host ports), but the
    VMEM gate is per shard — `block_vmem_bytes(a, mesh_size)` against the
    device budget."""
    if a["task_req"].shape[1] > R8:
        return False
    if a["task_ports"].shape[1] > 31:
        return False
    return block_vmem_bytes(a, mesh_size) <= vmem_budget()


def fold_affinity_scores(a: dict, Nr: int) -> np.ndarray:
    """[GT, Nr, 128] combined static score term: preferred node-affinity
    plus live InterPodAffinity, each pre-weighted (the kernel multiplies
    by 1). Re-folded by PallasSolver.solve when the action refreshes
    a["pod_sc"] between pause/resume segments — a [GT, N] multiply-add,
    not a re-pack."""
    f32 = np.float32
    node_gid = np.asarray(a["node_gid"], np.int64)
    N = node_gid.shape[0]
    full = np.asarray(a["aff_sc"], f32)[:, node_gid] * f32(a["w_aff"])
    pod_sc = np.asarray(a.get("pod_sc"), f32)
    if pod_sc.ndim == 2 and pod_sc.any():
        full = full + pod_sc * f32(a["w_podaff"])
    GT = full.shape[0]
    affw = np.zeros((GT, Nr, LANES), f32)
    affw[:, : (N + LANES - 1) // LANES, :].reshape(GT, -1)[:, :N] = full
    return affw


def pack(a: dict, enable_drf: bool, enable_proportion: bool) -> _Packed:
    """Fold the encoder's SoA snapshot into the kernel's VMEM layout."""
    f32, i32 = np.float32, np.int32
    T, R = a["task_req"].shape
    N = a["node_idle"].shape[0]
    J = a["job_min"].shape[0]
    Q = a["queue_rank"].shape[0]
    Tr, Nr, Jr, Qr = _rows(T), _rows(N), _rows(J), _rows(Q)

    # -- task classes: unique (req, res, gid, flags, ports) rows ----------
    tports, first, inv = _class_inverse(a)
    C = first.shape[0]
    Cr = _rows(C)
    tcls = _fold1(inv.astype(i32), Tr, i32)

    creq = _fold2(np.asarray(a["task_req"], f32)[first], Cr, f32)
    cres = _fold2(np.asarray(a["task_res"], f32)[first], Cr, f32)
    cgid = _fold1(np.asarray(a["task_gid"], i32)[first], Cr, i32)
    chs = _fold1(np.asarray(a["task_has_sc"], i32)[first], Cr, i32)
    crhs = _fold1(np.asarray(a["task_res_has_sc"], i32)[first], Cr, i32)
    cho = _fold1(np.asarray(a["task_host_only"], i32)[first], Cr, i32)
    cpt = _fold1(tports[first], Cr, i32)

    # -- node statics: compat/affinity expanded per node ------------------
    node_gid = np.asarray(a["node_gid"], np.int64)
    okv = np.asarray(a["node_ok"] & a["node_valid"])
    cnode_full = np.asarray(a["compat"])[:, node_gid] & okv[None, :]  # [GT,N]
    GT = cnode_full.shape[0]
    cnode = np.zeros((GT, Nr, LANES), i32)
    cnode[:, : (N + LANES - 1) // LANES, :].reshape(GT, -1)[:, :N] = cnode_full
    affw = fold_affinity_scores(a, Nr)

    nalloc = _fold2(np.asarray(a["node_alloc"], f32), Nr, f32)
    nmax = _fold1(np.asarray(a["node_max_tasks"], i32), Nr, i32)
    nihs = _fold1(np.asarray(a["node_idle_has_sc"], i32), Nr, i32)
    nrhs = _fold1(np.asarray(a["node_rel_has_sc"], i32), Nr, i32)

    # -- job / queue statics ----------------------------------------------
    jstart = _fold1(np.asarray(a["job_start"], i32), Jr, i32)
    jend = _fold1(np.asarray(a["job_end"], i32), Jr, i32)
    jmin = _fold1(np.asarray(a["job_min"], i32), Jr, i32)
    jprio = _fold1(np.asarray(a["job_prio"], i32), Jr, i32)
    jqueue = _fold1(np.asarray(a["job_queue"], i32), Jr, i32)
    jvalid = _fold1(np.asarray(a["job_valid"], i32), Jr, i32)
    qdes = _fold2(np.asarray(a["q_deserved"], f32), Qr, f32)
    qdim = _fold2(np.asarray(a["q_dims"], i32), Qr, f32)  # as f32 0/1

    # Pad rows (r >= R) carry req=0 and idle=0; eps must be positive there
    # so the all-dims fit check sees 0 < 0 + eps and ignores them.
    eps = np.ones(R8, f32)
    eps[:R] = np.asarray(a["eps"], f32)
    fscal = np.zeros(16, f32)
    fscal[:R8] = eps
    fscal[8] = np.float32(a["w_least"])
    fscal[9] = np.float32(a["w_balanced"])
    # The affinity weights (w_aff AND w_podaff) are baked into the affw
    # matrix at fold time (fold_affinity_scores), so the kernel's single
    # multiplier is 1 — this is what lets live InterPodAffinity scores
    # refresh between pause/resume segments without a kernel change.
    fscal[10] = np.float32(1.0)
    drft = np.zeros(R8, f32)
    drfd = np.zeros(R8, i32)
    if enable_drf:
        drft[:R] = np.asarray(a["drf_total"], f32)
        drfd[:R] = np.asarray(a["drf_dims"], i32)

    max_iter = T + J + Q + 1 + int(np.asarray(a["task_host_only"]).sum())

    statics = [
        tcls, creq, cres, cgid, chs, crhs, cho, cpt,
        cnode, affw, nalloc, nmax, nihs, nrhs,
        jstart, jend, jmin, jprio, jqueue, jvalid,
        qdes, qdim, fscal, drft, drfd,
    ]
    return _Packed(
        dims=(Tr, Nr, Jr, Qr, Cr, GT, R, max_iter),
        statics=statics,
        tcls=tcls,
        n_tasks_pad=T,
        n_jobs_pad=J,
        n_nodes_pad=N,
        n_queues_pad=Q,
    )


def _initial_state(a: dict, enable_drf: bool, enable_proportion: bool) -> SolveState:
    """Numpy twin of kernels.init_state (fresh solve)."""
    f32, i32 = np.float32, np.int32
    T, R = a["task_req"].shape
    J = a["job_min"].shape[0]
    Q = a["queue_rank"].shape[0]
    return SolveState(
        it=i32(0),
        step=i32(0),
        cur=i32(-1),
        ptr=np.asarray(a["job_start"], i32).copy(),
        assigned_node=np.full(T, -1, i32),
        assigned_kind=np.zeros(T, i32),
        assign_pos=np.full(T, -1, i32),
        idle=np.asarray(a["node_idle"], f32).copy(),
        rel=np.asarray(a["node_rel"], f32).copy(),
        used=np.asarray(a["node_used"], f32).copy(),
        ntasks=np.asarray(a["node_ntasks"], i32).copy(),
        nports=np.asarray(a["node_ports"], bool).copy(),
        ready_cnt=np.asarray(a["job_ready0"], i32).copy(),
        job_active=np.asarray(a["job_valid"], bool).copy(),
        q_dropped=np.zeros(Q, bool),
        job_alloc=(
            np.asarray(a["job_alloc0"], f32).copy()
            if enable_drf
            else np.zeros((J, R), f32)
        ),
        q_alloc=(
            np.asarray(a["q_alloc0"], f32).copy()
            if enable_proportion
            else np.zeros((Q, R), f32)
        ),
        q_alloc_has_sc=(
            np.asarray(a["q_alloc_has_sc0"], bool).copy()
            if enable_proportion
            else np.zeros(Q, bool)
        ),
        paused_at=i32(-1),
    )


@lru_cache(maxsize=64)
def _build(
    Tr: int, Nr: int, Jr: int, Qr: int, Cr: int, GT: int, R: int,
    enable_drf: bool, enable_proportion: bool, interpret: bool,
):
    """Compile (cached per shape bucket) the fused solve kernel."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    MAX_PRIORITY = 10
    import os as _os
    _DEBUG = _os.environ.get("KBT_PALLAS_DEBUG") == "1"
    T_pad, N_pad, J_pad, Q_pad = Tr * LANES, Nr * LANES, Jr * LANES, Qr * LANES
    NINF = float("-inf")  # python floats: jnp weak types, no captured consts
    PINF = float("inf")

    def kernel(
        # statics (order = _Packed.statics)
        tcls_ref, creq_ref, cres_ref, cgid_ref, chs_ref, crhs_ref, cho_ref,
        cpt_ref, cnode_ref, affw_ref, nalloc_ref, nmax_ref, nihs_ref,
        nrhs_ref, jstart_ref, jend_ref, jmin_ref, jprio_ref, jqueue_ref,
        jvalid_ref, qdes_ref, qdim_ref, fscal_ref, drft_ref, drfd_ref,
        iscal_ref,
        # state inputs (aliased to outputs)
        tnode_in, tkind_in, tpos_in, idle_in, rel_in, used_in, ntasks_in,
        nports_in, jptr_in, jready_in, jactive_in, qdropped_in, qcount_in,
        jalloc_in, qalloc_in, qahs_in,
        # outputs
        oscal_ref, tnode_ref, tkind_ref, tpos_ref, idle_ref, rel_ref,
        used_ref, ntasks_ref, nports_ref, jptr_ref, jready_ref, jactive_ref,
        qdropped_ref, qcount_ref, jalloc_ref, qalloc_ref, qahs_ref,
    ):
        # Copy the incoming state into the output refs and operate on those
        # — Mosaic does not expose aliased input values through output refs,
        # so in/out aliasing alone is not enough (measured: garbage reads).
        tnode_ref[:, :] = tnode_in[:, :]
        tkind_ref[:, :] = tkind_in[:, :]
        tpos_ref[:, :] = tpos_in[:, :]
        idle_ref[:, :, :] = idle_in[:, :, :]
        rel_ref[:, :, :] = rel_in[:, :, :]
        used_ref[:, :, :] = used_in[:, :, :]
        ntasks_ref[:, :] = ntasks_in[:, :]
        nports_ref[:, :] = nports_in[:, :]
        jptr_ref[:, :] = jptr_in[:, :]
        jready_ref[:, :] = jready_in[:, :]
        jactive_ref[:, :] = jactive_in[:, :]
        qdropped_ref[:, :] = qdropped_in[:, :]
        qcount_ref[:, :] = qcount_in[:, :]
        jalloc_ref[:, :, :] = jalloc_in[:, :, :]
        qalloc_ref[:, :, :] = qalloc_in[:, :, :]
        qahs_ref[:, :] = qahs_in[:, :]

        lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        lane3 = lane[None]  # [1,1,128]
        nidx = (
            lax.broadcasted_iota(jnp.int32, (Nr, LANES), 0) * LANES
            + lax.broadcasted_iota(jnp.int32, (Nr, LANES), 1)
        )
        jidx = (
            lax.broadcasted_iota(jnp.int32, (Jr, LANES), 0) * LANES
            + lax.broadcasted_iota(jnp.int32, (Jr, LANES), 1)
        )
        qidx = (
            lax.broadcasted_iota(jnp.int32, (Qr, LANES), 0) * LANES
            + lax.broadcasted_iota(jnp.int32, (Qr, LANES), 1)
        )

        # loop-invariant scalars / small vectors
        eps_v = jnp.concatenate(
            [jnp.full((1, 1), fscal_ref[i], jnp.float32) for i in range(R8)]
        )  # [R8,1]
        eps3 = eps_v[:, :, None]
        w_least = fscal_ref[8]
        w_bal = fscal_ref[9]
        w_aff = fscal_ref[10]
        max_iter = iscal_ref[5]

        def exti(ref, idx):
            r, l = idx // LANES, idx % LANES
            # dtype pinned: under jax x64 (CPU interpret tests) jnp.sum
            # would promote int32 to int64 and break the carry types
            return jnp.sum(jnp.where(lane == l, ref[pl.ds(r, 1), :], 0), dtype=jnp.int32)

        def extcol(ref3, idx, zero=0.0):
            r, l = idx // LANES, idx % LANES
            slab = ref3[:, pl.ds(r, 1), :]
            return jnp.sum(jnp.where(lane3 == l, slab, zero), axis=2)  # [R8,1]

        def extdim(ref3, idx, r):
            """Scalar of resource dim r at folded column idx. Mosaic cannot
            do i1 vector ops at [8,1], so per-dim gates are scalar-unrolled."""
            rr, l = idx // LANES, idx % LANES
            return jnp.sum(jnp.where(lane == l, ref3[r, pl.ds(rr, 1), :], 0.0))

        def rmw_set(ref, idx, val):
            r, l = idx // LANES, idx % LANES
            row = ref[pl.ds(r, 1), :]
            ref[pl.ds(r, 1), :] = jnp.where(lane == l, val, row)

        def rmw_add(ref, idx, val):
            r, l = idx // LANES, idx % LANES
            ref[pl.ds(r, 1), :] = ref[pl.ds(r, 1), :] + jnp.where(lane == l, val, 0)

        def rmw_add3(ref3, idx, col):
            r, l = idx // LANES, idx % LANES
            slab = ref3[:, pl.ds(r, 1), :]
            ref3[:, pl.ds(r, 1), :] = slab + jnp.where(
                lane3 == l, col[:, :, None], 0.0
            )

        def lex_argmin(mask, keys, idx, pad):
            m = mask
            for k in keys:
                sent = PINF if jnp.issubdtype(k.dtype, jnp.floating) else INT_MAX
                kmin = jnp.min(jnp.where(m, k, sent))
                m = m & (k == kmin)
            return jnp.min(jnp.where(m, idx, pad))

        def drf_share():
            # _share_rows over jobs: max over masked dims of alloc/total
            s = jnp.full((Jr, LANES), NINF, jnp.float32)
            for r in range(R8):
                denom = drft_ref[r]
                alloc_r = jalloc_ref[r, :, :]
                sr = jnp.where(
                    denom == 0.0,
                    # dtype-pinned 0/1 branch (trace-audit KBT-P002)
                    (alloc_r != 0.0).astype(alloc_r.dtype),
                    _ieee_div(alloc_r, jnp.where(denom == 0.0, 1.0, denom)),
                )
                s = jnp.where(drfd_ref[r] != 0, jnp.maximum(s, sr), s)
            return jnp.maximum(s, 0.0)

        def q_share():
            s = jnp.full((Qr, LANES), NINF, jnp.float32)
            for r in range(R8):
                d = qdes_ref[r, :, :]
                al = qalloc_ref[r, :, :]
                sr = jnp.where(
                    d == 0.0,
                    # dtype-pinned 0/1 branch (trace-audit KBT-P002)
                    (al != 0.0).astype(al.dtype),
                    _ieee_div(al, jnp.where(d == 0.0, 1.0, d)),
                )
                s = jnp.where(qdim_ref[r, :, :] != 0.0, jnp.maximum(s, sr), s)
            return jnp.maximum(s, 0.0)

        def select():
            """Queue + job selection (lax kernel body lines 'queue + job
            selection'); returns (qsel, drop_q, jsel, sel_ok)."""
            q_has = (qcount_ref[:, :] > 0) & (qdropped_ref[:, :] == 0)
            if enable_proportion:
                qsel = lex_argmin(q_has, [q_share(), qidx], qidx, Q_pad)
            else:
                qsel = lex_argmin(q_has, [qidx], qidx, Q_pad)
            q_any = qsel < Q_pad
            qsel_c = jnp.minimum(qsel, Q_pad - 1)

            if enable_proportion:
                # Overused gate (proportion.go:188-199 + the Go
                # nil-scalar-map branch), scalar-unrolled per dim.
                has_sc_q = exti(qahs_ref, qsel_c) != 0
                overused = jnp.bool_(True)
                for r in range(R8):
                    d_r = extdim(qdes_ref, qsel_c, r)
                    a_r = extdim(qalloc_ref, qsel_c, r)
                    m_r = extdim(qdim_ref, qsel_c, r)
                    ok_r = (d_r < a_r) | (jnp.abs(a_r - d_r) < fscal_ref[r])
                    if r >= 2:
                        ok_r = ok_r & has_sc_q
                    overused = overused & jnp.where(m_r != 0.0, ok_r, True)
            else:
                overused = jnp.bool_(False)

            jmask = (jactive_ref[:, :] != 0) & (jqueue_ref[:, :] == qsel_c)
            ready_bit = (jready_ref[:, :] >= jmin_ref[:, :]).astype(jnp.int32)
            keys = [-jprio_ref[:, :], ready_bit]
            if enable_drf:
                keys.append(drf_share())
            keys.append(jidx)
            jsel = lex_argmin(jmask, keys, jidx, J_pad)
            j_any = jsel < J_pad
            sel_ok = q_any & ~overused & j_any
            drop_q = q_any & overused
            return qsel_c, drop_q, jnp.minimum(jsel, J_pad - 1), sel_ok

        def body(carry):
            it, step, cur, paused, n_active = carry
            need_sel = cur < 0

            qsel, drop_q, jsel, sel_ok = lax.cond(
                need_sel,
                select,
                lambda: (jnp.int32(0), jnp.bool_(False), jnp.int32(0), jnp.bool_(False)),
            )
            cur = jnp.where(need_sel, jnp.where(sel_ok, jsel, -1), cur)

            qsel_cnt = exti(qcount_ref, qsel)

            @pl.when(drop_q)
            def _():
                # overused queue retires all its jobs for the cycle
                jactive_ref[:, :] = jnp.where(
                    jqueue_ref[:, :] == qsel, 0, jactive_ref[:, :]
                )
                rmw_set(qdropped_ref, qsel, 1)
                rmw_set(qcount_ref, qsel, 0)

            n_active = n_active - jnp.where(drop_q, qsel_cnt, 0)

            # -- pop the current job's next pending task (O(1) pointer) --
            cur_c = jnp.maximum(cur, 0)
            t = exti(jptr_ref, cur_c)
            if _DEBUG:
                jax.debug.print(
                    "it={} cur={} qsel={} drop_q={} sel_ok={} t={} jend={} nact={}",
                    it, cur, qsel, drop_q, sel_ok, t, exti(jend_ref, cur_c), n_active,
                )
            t_any = (cur >= 0) & (t < exti(jend_ref, cur_c))
            t = jnp.minimum(t, T_pad - 1)
            drop = (cur >= 0) & ~t_any
            cls = exti(tcls_ref, t)
            pause = t_any & (exti(cho_ref, cls) != 0)
            proc = t_any & ~pause

            # -- feasibility over the node axis (vectorized) -------------
            req = extcol(creq_ref, cls)  # [R8,1]
            res = extcol(cres_ref, cls)
            has_sc = exti(chs_ref, cls) != 0
            gid = jnp.minimum(exti(cgid_ref, cls), GT - 1)
            tports = exti(cpt_ref, cls)

            req3 = req[:, :, None]  # [R8,1,1]
            fits_idle = jnp.all(req3 < idle_ref[:, :, :] + eps3, axis=0) & ~(
                has_sc & (nihs_ref[:, :] == 0)
            )
            fits_rel = jnp.all(req3 < rel_ref[:, :, :] + eps3, axis=0) & ~(
                has_sc & (nrhs_ref[:, :] == 0)
            )
            static_ok = cnode_ref[pl.ds(gid, 1), :, :][0] != 0
            room = ntasks_ref[:, :] < nmax_ref[:, :]
            port_ok = (nports_ref[:, :] & tports) == 0
            cand = static_ok & room & port_ok & (fits_idle | fits_rel)

            # -- score + deterministic best node -------------------------
            req_cpu = used_ref[0, :, :] + res[0, 0]
            req_mem = used_ref[1, :, :] + res[1, 0]
            cap_cpu = nalloc_ref[0, :, :]
            cap_mem = nalloc_ref[1, :, :]

            def least_dim(rq, cp):
                safe = jnp.where(cp == 0.0, 1.0, cp)
                sc = jnp.floor(
                    _ieee_div((cp - rq) * MAX_PRIORITY, safe)
                ).astype(jnp.int32)
                return jnp.where((cp == 0.0) | (rq > cp), 0, sc)

            least = (least_dim(req_cpu, cap_cpu) + least_dim(req_mem, cap_mem)) // 2
            cpu_f = jnp.where(
                cap_cpu != 0.0,
                _ieee_div(req_cpu, jnp.where(cap_cpu == 0.0, 1.0, cap_cpu)),
                1.0,
            )
            mem_f = jnp.where(
                cap_mem != 0.0,
                _ieee_div(req_mem, jnp.where(cap_mem == 0.0, 1.0, cap_mem)),
                1.0,
            )
            balanced = jnp.where(
                (cpu_f >= 1.0) | (mem_f >= 1.0),
                0,
                (MAX_PRIORITY - jnp.abs(cpu_f - mem_f) * MAX_PRIORITY).astype(
                    jnp.int32
                ),
            )
            score = (
                least.astype(jnp.float32) * w_least
                + balanced.astype(jnp.float32) * w_bal
                + affw_ref[pl.ds(gid, 1), :, :][0] * w_aff
            )
            if _DEBUG:
                jax.debug.print(
                    "  cls={} gid={} req0={} req1={} static={} room={} port={} fi={} fr={}",
                    cls, gid, req[0, 0], req[1, 0], jnp.sum(static_ok),
                    jnp.sum(room), jnp.sum(port_ok), jnp.sum(fits_idle),
                    jnp.sum(fits_rel),
                )
            big = jnp.max(jnp.where(cand, score, NINF))
            any_cand = big > NINF
            nb = jnp.min(jnp.where(cand & (score == big), nidx, N_pad))
            nb = jnp.minimum(nb, N_pad - 1)
            abandon = proc & ~any_cand
            assign = proc & any_cand

            # fits-idle at the chosen node (scalar recompute from slab,
            # per-dim unrolled — see extdim)
            nr, nl = nb // LANES, nb % LANES
            fits_idle_nb = ~(has_sc & (exti(nihs_ref, nb) == 0))
            for r in range(R8):
                req_r = extdim(creq_ref, cls, r)
                idle_r = extdim(idle_ref, nb, r)
                fits_idle_nb = fits_idle_nb & (req_r < idle_r + fscal_ref[r])
            do_alloc = assign & fits_idle_nb

            @pl.when(assign)
            def _():
                col_alloc = jnp.where(do_alloc, res, 0.0)
                col_pipe = jnp.where(do_alloc, 0.0, res)
                lmask = lane3 == nl
                idle_ref[:, pl.ds(nr, 1), :] = idle_ref[:, pl.ds(nr, 1), :] - jnp.where(
                    lmask, col_alloc[:, :, None], 0.0
                )
                rel_ref[:, pl.ds(nr, 1), :] = rel_ref[:, pl.ds(nr, 1), :] - jnp.where(
                    lmask, col_pipe[:, :, None], 0.0
                )
                used_ref[:, pl.ds(nr, 1), :] = used_ref[:, pl.ds(nr, 1), :] + jnp.where(
                    lmask, res[:, :, None], 0.0
                )
                rmw_add(ntasks_ref, nb, 1)
                nports_ref[pl.ds(nr, 1), :] = nports_ref[pl.ds(nr, 1), :] | jnp.where(
                    lane == nl, tports, 0
                )
                rmw_set(tnode_ref, t, nb)
                rmw_set(tkind_ref, t, jnp.where(do_alloc, 1, 2))
                rmw_set(tpos_ref, t, step)
                rmw_add(jready_ref, cur_c, jnp.where(do_alloc, 1, 0))
                if enable_drf:
                    rmw_add3(jalloc_ref, cur_c, res)
                if enable_proportion:
                    qcur = exti(jqueue_ref, cur_c)
                    rmw_add3(qalloc_ref, qcur, res)
                    res_has_sc = exti(crhs_ref, cls) != 0
                    rmw_set(
                        qahs_ref,
                        qcur,
                        jnp.where(res_has_sc, 1, exti(qahs_ref, qcur)),
                    )

            @pl.when(proc)
            def _():
                rmw_add(jptr_ref, cur_c, 1)

            retire = drop | abandon

            @pl.when(retire)
            def _():
                rmw_set(jactive_ref, cur_c, 0)
                rmw_add(qcount_ref, exti(jqueue_ref, cur_c), -1)

            n_active = n_active - jnp.where(retire, 1, 0)

            # -- gang barrier / next current job -------------------------
            ready_c = exti(jready_ref, cur_c)  # post-update value
            ready_now = ready_c >= exti(jmin_ref, cur_c)
            cur_next = jnp.where(retire | (proc & ready_now), -1, cur)

            return (
                it + 1,
                step + assign.astype(jnp.int32),
                cur_next,
                jnp.where(pause, t, -1),
                n_active,
            )

        def cond(carry):
            it, step, cur, paused, n_active = carry
            return ((cur >= 0) | (n_active > 0)) & (it < max_iter) & (paused < 0)

        it, step, cur, paused, n_active = lax.while_loop(
            cond,
            body,
            (iscal_ref[0], iscal_ref[1], iscal_ref[2], jnp.int32(-1), iscal_ref[4]),
        )
        oscal_ref[0] = it
        oscal_ref[1] = step
        oscal_ref[2] = cur
        oscal_ref[3] = paused
        oscal_ref[4] = n_active

    f32, i32 = jnp.float32, jnp.int32
    state_shapes = [
        ((Tr, LANES), i32),  # tnode
        ((Tr, LANES), i32),  # tkind
        ((Tr, LANES), i32),  # tpos
        ((R8, Nr, LANES), f32),  # idle
        ((R8, Nr, LANES), f32),  # rel
        ((R8, Nr, LANES), f32),  # used
        ((Nr, LANES), i32),  # ntasks
        ((Nr, LANES), i32),  # nports
        ((Jr, LANES), i32),  # jptr
        ((Jr, LANES), i32),  # jready
        ((Jr, LANES), i32),  # jactive
        ((Qr, LANES), i32),  # qdropped
        ((Qr, LANES), i32),  # qcount
        ((R8, Jr, LANES), f32),  # jalloc
        ((R8, Qr, LANES), f32),  # qalloc
        ((Qr, LANES), i32),  # qahs
    ]
    out_shape = [jax.ShapeDtypeStruct((16,), i32)] + [
        jax.ShapeDtypeStruct(s, d) for s, d in state_shapes
    ]
    in_specs = (
        [pl.BlockSpec(memory_space=pltpu.VMEM)] * 22
        + [pl.BlockSpec(memory_space=pltpu.SMEM)] * 4  # fscal, drft, drfd, iscal
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 16
    )
    out_specs = tuple(
        [pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 16
    )
    call = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret,
    )

    def wrapped(*args):
        """Concatenate the 17 outputs into one i32 + one f32 device
        buffer, so the host pays one device->host round trip per solve
        instead of 17. The f32 buffer is only materialized on
        pause/resume or in tests."""
        (
            oscal, tnode, tkind, tpos, idle, rel, used, ntasks, nports,
            jptr, jready, jactive, qdropped, qcount, jalloc, qalloc, qahs,
        ) = call(*args)
        icat = jnp.concatenate(
            [
                oscal, tnode.ravel(), tkind.ravel(), tpos.ravel(),
                jptr.ravel(), jready.ravel(), jactive.ravel(),
                ntasks.ravel(), nports.ravel(), qdropped.ravel(),
                qcount.ravel(), qahs.ravel(),
            ]
        )
        fcat = jnp.concatenate(
            [
                idle.ravel(), rel.ravel(), used.ravel(),
                jalloc.ravel(), qalloc.ravel(),
            ]
        )
        return icat, fcat

    return jax.jit(wrapped)


# -- blocked sharded-Pallas entry (parallel/sharded_pallas) ---------------
#
# The block step is the per-shard half of one gang iteration: the fused
# feasibility + score + block-local argmax over the shard's node block,
# in the same folded [R8, Nr_loc, 128] VMEM layout and with the same
# float32 formulas as the single-chip fused kernel above. The caller
# (ShardedPallasSolver) exchanges the returned (best score, global node
# index, fits-idle bit) triple across the mesh axis per iteration and
# applies the winning capacity update on the owning shard only.
#
# fvec layout (f32, 32): [0:8] padded task req, [8:16] padded task res,
# [16:24] padded eps (pad dims carry 1.0 so the all-dims fit check
# ignores them), [24] w_least, [25] w_balanced (affinity weights are
# baked into affw at fold time, as in the single-chip kernel).
# ivec layout (i32, 8): [0] gid (pre-clamped to GT-1), [1] task has_sc,
# [2] task port bitmask, [3] global folded index offset of this shard's
# block, [4] the "no candidate" index sentinel (global padded N).

FVEC_LEN = 32
IVEC_LEN = 8


@lru_cache(maxsize=64)
def _build_block_step(Nr_loc: int, GT: int, interpret: bool):
    """Compile (cached per local block shape) the fused block step."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    MAX_PRIORITY = 10
    NINF = float("-inf")

    def kernel(
        ivec_ref, fvec_ref,
        cnode_ref, affw_ref, nalloc_ref, nmax_ref, nihs_ref, nrhs_ref,
        idle_ref, rel_ref, used_ref, ntasks_ref, nports_ref,
        oscore_ref, oidx_ref,
    ):
        gid = ivec_ref[0]
        has_sc = ivec_ref[1] != 0
        tports = ivec_ref[2]
        off = ivec_ref[3]
        sentinel = ivec_ref[4]

        lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        nidx = (
            lax.broadcasted_iota(jnp.int32, (Nr_loc, LANES), 0) * LANES
            + lax.broadcasted_iota(jnp.int32, (Nr_loc, LANES), 1)
        )

        req_v = jnp.concatenate(
            [jnp.full((1, 1), fvec_ref[i], jnp.float32) for i in range(R8)]
        )
        eps_v = jnp.concatenate(
            [jnp.full((1, 1), fvec_ref[16 + i], jnp.float32) for i in range(R8)]
        )
        req3 = req_v[:, :, None]
        eps3 = eps_v[:, :, None]

        # -- feasibility over the local node block (== single-chip kernel) --
        fits_idle = jnp.all(req3 < idle_ref[:, :, :] + eps3, axis=0) & ~(
            has_sc & (nihs_ref[:, :] == 0)
        )
        fits_rel = jnp.all(req3 < rel_ref[:, :, :] + eps3, axis=0) & ~(
            has_sc & (nrhs_ref[:, :] == 0)
        )
        static_ok = cnode_ref[pl.ds(gid, 1), :, :][0] != 0
        room = ntasks_ref[:, :] < nmax_ref[:, :]
        port_ok = (nports_ref[:, :] & tports) == 0
        cand = static_ok & room & port_ok & (fits_idle | fits_rel)

        # -- score + deterministic block-local best ------------------------
        req_cpu = used_ref[0, :, :] + fvec_ref[8]
        req_mem = used_ref[1, :, :] + fvec_ref[9]
        cap_cpu = nalloc_ref[0, :, :]
        cap_mem = nalloc_ref[1, :, :]

        def least_dim(rq, cp):
            safe = jnp.where(cp == 0.0, 1.0, cp)
            sc = jnp.floor(
                _ieee_div((cp - rq) * MAX_PRIORITY, safe)
            ).astype(jnp.int32)
            return jnp.where((cp == 0.0) | (rq > cp), 0, sc)

        least = (least_dim(req_cpu, cap_cpu) + least_dim(req_mem, cap_mem)) // 2
        cpu_f = jnp.where(
            cap_cpu != 0.0,
            _ieee_div(req_cpu, jnp.where(cap_cpu == 0.0, 1.0, cap_cpu)),
            1.0,
        )
        mem_f = jnp.where(
            cap_mem != 0.0,
            _ieee_div(req_mem, jnp.where(cap_mem == 0.0, 1.0, cap_mem)),
            1.0,
        )
        balanced = jnp.where(
            (cpu_f >= 1.0) | (mem_f >= 1.0),
            0,
            (MAX_PRIORITY - jnp.abs(cpu_f - mem_f) * MAX_PRIORITY).astype(
                jnp.int32
            ),
        )
        score = (
            least.astype(jnp.float32) * fvec_ref[24]
            + balanced.astype(jnp.float32) * fvec_ref[25]
            + affw_ref[pl.ds(gid, 1), :, :][0]
        )
        big = jnp.max(jnp.where(cand, score, NINF))
        any_c = big > NINF
        nb = jnp.min(jnp.where(cand & (score == big), nidx, INT_MAX))
        nb = jnp.minimum(nb, Nr_loc * LANES - 1)

        # fits-idle at the block-local best (scalar recompute per dim —
        # Mosaic cannot do i1 vector extraction at [8,1], same idiom as
        # the single-chip kernel's extdim unroll)
        def exti(ref, idx):
            r, l = idx // LANES, idx % LANES
            return jnp.sum(
                jnp.where(lane == l, ref[pl.ds(r, 1), :], 0), dtype=jnp.int32
            )

        def extdim(ref3, idx, r):
            rr, l = idx // LANES, idx % LANES
            return jnp.sum(jnp.where(lane == l, ref3[r, pl.ds(rr, 1), :], 0.0))

        fits_nb = ~(has_sc & (exti(nihs_ref, nb) == 0))
        for r in range(R8):
            fits_nb = fits_nb & (
                fvec_ref[r] < extdim(idle_ref, nb, r) + fvec_ref[16 + r]
            )

        oscore_ref[0] = jnp.where(any_c, big, NINF)
        oidx_ref[0] = jnp.where(any_c, nb + off, sentinel)
        oidx_ref[1] = (any_c & fits_nb).astype(jnp.int32)

    call = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        ),
        in_specs=(
            [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
            + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 11
        ),
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        interpret=interpret,
    )

    def step(ivec, fvec, *blocks):
        oscore, oidx = call(ivec, fvec, *blocks)
        return oscore[0], oidx[0], oidx[1]

    return step


def block_step_jnp(ivec, fvec, cnode, affw, nalloc, nmax, nihs, nrhs,
                   idle, rel, used, ntasks, nports):
    """jnp twin of the fused block step — identical folded layout and
    float32 formulas, as plain XLA ops. The fast compiled path on
    non-TPU meshes (virtual-CPU parity tests and bench rows) and the
    cross-check oracle the interpret-mode kernel is pinned against."""
    import jax.numpy as jnp
    from jax import lax

    MAX_PRIORITY = 10
    gid = ivec[0]
    has_sc = ivec[1] != 0
    tports = ivec[2]
    off = ivec[3]
    sentinel = ivec[4]
    Nr_loc = nmax.shape[0]

    req3 = fvec[:R8][:, None, None]
    eps3 = fvec[16:24][:, None, None]
    fits_idle = jnp.all(req3 < idle + eps3, axis=0) & ~(has_sc & (nihs == 0))
    fits_rel = jnp.all(req3 < rel + eps3, axis=0) & ~(has_sc & (nrhs == 0))
    static_ok = cnode[gid] != 0
    room = ntasks < nmax
    port_ok = (nports & tports) == 0
    cand = static_ok & room & port_ok & (fits_idle | fits_rel)

    req_cpu = used[0] + fvec[8]
    req_mem = used[1] + fvec[9]
    cap_cpu = nalloc[0]
    cap_mem = nalloc[1]

    def least_dim(rq, cp):
        safe = jnp.where(cp == 0.0, 1.0, cp)
        sc = jnp.floor(_ieee_div((cp - rq) * MAX_PRIORITY, safe)).astype(jnp.int32)
        return jnp.where((cp == 0.0) | (rq > cp), 0, sc)

    least = (least_dim(req_cpu, cap_cpu) + least_dim(req_mem, cap_mem)) // 2
    cpu_f = jnp.where(
        cap_cpu != 0.0,
        _ieee_div(req_cpu, jnp.where(cap_cpu == 0.0, 1.0, cap_cpu)),
        1.0,
    )
    mem_f = jnp.where(
        cap_mem != 0.0,
        _ieee_div(req_mem, jnp.where(cap_mem == 0.0, 1.0, cap_mem)),
        1.0,
    )
    balanced = jnp.where(
        (cpu_f >= 1.0) | (mem_f >= 1.0),
        0,
        (MAX_PRIORITY - jnp.abs(cpu_f - mem_f) * MAX_PRIORITY).astype(jnp.int32),
    )
    score = (
        least.astype(jnp.float32) * fvec[24]
        + balanced.astype(jnp.float32) * fvec[25]
        + affw[gid]
    )
    nidx = (
        lax.broadcasted_iota(jnp.int32, (Nr_loc, LANES), 0) * LANES
        + lax.broadcasted_iota(jnp.int32, (Nr_loc, LANES), 1)
    )
    NINF = jnp.float32(-jnp.inf)
    big = jnp.max(jnp.where(cand, score, NINF))
    any_c = big > NINF
    nb = jnp.min(jnp.where(cand & (score == big), nidx, INT_MAX))
    nb = jnp.minimum(nb, Nr_loc * LANES - 1)
    rr, l = nb // LANES, nb % LANES
    fits_nb = ~(has_sc & (nihs[rr, l] == 0)) & jnp.all(
        fvec[:R8] < idle[:, rr, l] + fvec[16:24]
    )
    return (
        jnp.where(any_c, big, NINF),
        jnp.where(any_c, nb + off, sentinel),
        (any_c & fits_nb).astype(jnp.int32),
    )


class PallasSolver:
    """Per-execute driver: pack once, then solve / resume.

    Speaks the same `SolveState` protocol as ops.kernels so the action's
    segmented pod-affinity hybrid works unchanged.
    """

    def __init__(
        self,
        a: dict,
        enable_drf: bool,
        enable_proportion: bool,
        interpret: bool = False,
        fetch_f32: bool = False,
    ) -> None:
        self.a = a
        self.enable_drf = enable_drf
        self.enable_proportion = enable_proportion
        self._fetch_f32 = fetch_f32  # tests compare idle/used; replay doesn't
        self.packed = pack(a, enable_drf, enable_proportion)
        self._pod_sc = a.get("pod_sc")  # identity marker for refresh
        Tr, Nr, Jr, Qr, Cr, GT, R, self.max_iter = self.packed.dims
        self.fn = _build(
            Tr, Nr, Jr, Qr, Cr, GT, R, enable_drf, enable_proportion, interpret
        )

    _AFFW_IDX = 9  # affw's position in _Packed.statics

    def trace_args(self, state: SolveState | None = None) -> tuple:
        """The concrete argument tuple ``solve`` passes to the traced
        program ``self.fn``. Public so the trace auditor
        (analysis/trace) can walk the fused kernel's jaxpr on these
        arguments' avals without executing it."""
        if state is None:
            state = _initial_state(self.a, self.enable_drf, self.enable_proportion)
        return self._program_args(state)

    def _program_args(self, state: SolveState) -> tuple:
        p = self.packed
        Tr, Nr, Jr, Qr, Cr, GT, R, max_iter = p.dims
        f32, i32 = np.float32, np.int32
        job_active = np.asarray(state.job_active, bool)
        job_queue = np.asarray(self.a["job_queue"], np.int64)
        qcount = np.bincount(
            job_queue[job_active], minlength=p.n_queues_pad
        ).astype(i32)
        n_active = int(job_active.sum())

        iscal = np.zeros(16, i32)
        iscal[0] = int(state.it)
        iscal[1] = int(state.step)
        iscal[2] = int(state.cur)
        iscal[3] = -1
        iscal[4] = n_active
        iscal[5] = max_iter

        nports_bits = _ports_mask(np.asarray(state.nports, bool))
        folded_state = [
            _fold1(np.asarray(state.assigned_node, i32), Tr, i32, pad=-1),
            _fold1(np.asarray(state.assigned_kind, i32), Tr, i32),
            _fold1(np.asarray(state.assign_pos, i32), Tr, i32, pad=-1),
            _fold2(np.asarray(state.idle, f32), Nr, f32),
            _fold2(np.asarray(state.rel, f32), Nr, f32),
            _fold2(np.asarray(state.used, f32), Nr, f32),
            _fold1(np.asarray(state.ntasks, i32), Nr, i32),
            _fold1(nports_bits, Nr, i32),
            _fold1(np.asarray(state.ptr, i32), Jr, i32),
            _fold1(np.asarray(state.ready_cnt, i32), Jr, i32),
            _fold1(job_active.astype(i32), Jr, i32),
            _fold1(np.asarray(state.q_dropped, i32), Qr, i32),
            _fold1(qcount, Qr, i32),
            _fold2(np.asarray(state.job_alloc, f32), Jr, f32),
            _fold2(np.asarray(state.q_alloc, f32), Qr, f32),
            _fold1(np.asarray(state.q_alloc_has_sc, i32), Qr, i32),
        ]
        return (*p.statics, iscal, *folded_state)

    def solve(self, state: SolveState | None = None) -> SolveState:
        p = self.packed
        Tr, Nr, Jr, Qr, Cr, GT, R, max_iter = p.dims
        if self.a.get("pod_sc") is not self._pod_sc:
            # The action recomputed live InterPodAffinity scores after a
            # host-stepped pod landed (VERDICT r3 item 7): re-fold just
            # the affinity static and resume with the fresh scores.
            self._pod_sc = self.a.get("pod_sc")
            p.statics[self._AFFW_IDX] = fold_affinity_scores(self.a, Nr)
        if state is None:
            state = _initial_state(self.a, self.enable_drf, self.enable_proportion)
        icat_d, fcat_d = self.fn(*self._program_args(state))
        icat = np.asarray(icat_d)  # ONE round-trip for everything integer

        TL, NL, JL, QL = Tr * LANES, Nr * LANES, Jr * LANES, Qr * LANES
        T, J, Q, N = p.n_tasks_pad, p.n_jobs_pad, p.n_queues_pad, p.n_nodes_pad
        pos = [0]

        def take(n):
            s = icat[pos[0] : pos[0] + n]
            pos[0] += n
            return s

        oscal = take(16)
        tnode = take(TL)[:T]
        tkind = take(TL)[:T]
        tpos = take(TL)[:T]
        jptr = take(JL)[:J]
        jready = take(JL)[:J]
        jactive = take(JL)[:J]
        ntasks = take(NL)[:N]
        nport_bits = take(NL)[:N]
        qdropped = take(QL)[:Q]
        take(QL)  # qcount (derived; recomputed at next entry)
        qahs = take(QL)[:Q]

        paused = int(oscal[3])
        if paused >= 0 or self._fetch_f32:
            # Only pause/resume (the pod-affinity hybrid) and the parity
            # tests need the float state on the host; one more round-trip.
            fcat = np.asarray(fcat_d)
            fpos = [0]

            def ftake(n):
                s = fcat[fpos[0] : fpos[0] + n]
                fpos[0] += n
                return s

            idle = _unfold2(ftake(R8 * NL).reshape(R8, Nr, LANES), N, R)
            rel = _unfold2(ftake(R8 * NL).reshape(R8, Nr, LANES), N, R)
            used = _unfold2(ftake(R8 * NL).reshape(R8, Nr, LANES), N, R)
            jalloc = _unfold2(ftake(R8 * JL).reshape(R8, Jr, LANES), J, R)
            qalloc = _unfold2(ftake(R8 * QL).reshape(R8, Qr, LANES), Q, R)
        else:
            # Unused by the replay path on a completed solve; carry the
            # entry state forward so the tuple stays well-formed.
            idle, rel, used = state.idle, state.rel, state.used
            jalloc, qalloc = state.job_alloc, state.q_alloc

        P = np.asarray(self.a["task_ports"]).shape[1]
        nports_bool = (nport_bits[:, None] & (1 << np.arange(P, dtype=np.int64))) != 0
        return SolveState(
            it=np.int32(oscal[0]),
            step=np.int32(oscal[1]),
            cur=np.int32(oscal[2]),
            ptr=jptr,
            assigned_node=tnode,
            assigned_kind=tkind,
            assign_pos=tpos,
            idle=idle,
            rel=rel,
            used=used,
            ntasks=ntasks,
            nports=nports_bool,
            ready_cnt=jready,
            job_active=jactive.astype(bool),
            q_dropped=qdropped.astype(bool),
            job_alloc=jalloc,
            q_alloc=qalloc,
            q_alloc_has_sc=qahs.astype(bool),
            paused_at=np.int32(paused),
        )
