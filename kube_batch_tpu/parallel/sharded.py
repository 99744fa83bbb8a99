"""Node-axis sharding of the allocate solve over a device mesh.

The encoded snapshot's node-axis arrays are partitioned across the mesh's
``nodes`` axis (task/job/queue state is small and replicated); the jitted
while-loop kernel then runs SPMD: each device evaluates feasibility and
scores for its node block, GSPMD reduces the argmax across blocks and
broadcasts the winning assignment's capacity update. Static shapes are
guaranteed by encode.py's bucketing — the node axis pads to multiples of
128 (one lane row), so any power-of-two mesh size up to 128 divides the
bucket and shards cleanly (the action clamps larger meshes).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kube_batch_tpu.ops.kernels import (
    SolveResult,
    SolveState,
    result_of,
    solve_allocate_step,
)

# Arrays carrying the node dimension first (see ops/encode.py).
NODE_AXIS_ARRAYS = frozenset(
    {
        "node_idle",
        "node_rel",
        "node_used",
        "node_alloc",
        "node_ok",
        "node_valid",
        "node_max_tasks",
        "node_ntasks",
        "node_idle_has_sc",
        "node_rel_has_sc",
        "node_gid",
        "node_ports",
    }
)

AXIS_NAME = "nodes"


def make_mesh(
    n_devices: Optional[int] = None,
    axis_name: str = AXIS_NAME,
    devices: Optional[list] = None,
) -> Mesh:
    """1-D device mesh over the node axis. Defaults to every visible
    device (ICI within a slice; DCN across slices is the same mesh with
    more devices — XLA picks the transport)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def node_shardings(arrays: dict, mesh: Mesh, axis_name: str = AXIS_NAME) -> dict:
    """PartitionSpec per array: node-axis arrays sharded, rest replicated.
    pod_sc is [task-groups, nodes] — node axis second."""
    out = {}
    for k in arrays:
        if k in NODE_AXIS_ARRAYS:
            spec = P(axis_name)
        elif k == "pod_sc":
            spec = P(None, axis_name)
        else:
            spec = P()
        out[k] = NamedSharding(mesh, spec)
    return out


def state_shardings(mesh: Mesh, axis_name: str = AXIS_NAME) -> SolveState:
    """Sharding per SolveState field: the node-axis state (idle, rel,
    used, ntasks, nports) follows the array sharding; everything else —
    scalars, task-axis assignment log, job/queue vectors — is replicated
    (tiny next to [N,R])."""
    rep = NamedSharding(mesh, P())
    sh = NamedSharding(mesh, P(axis_name))
    return SolveState(
        it=rep, step=rep, cur=rep, ptr=rep,
        assigned_node=rep, assigned_kind=rep, assign_pos=rep,
        idle=sh, rel=sh, used=sh, ntasks=sh, nports=sh,
        ready_cnt=rep, job_active=rep, q_dropped=rep,
        job_alloc=rep, q_alloc=rep, q_alloc_has_sc=rep,
        paused_at=rep,
    )


class ShardedSolver:
    """The multi-chip production solver behind xla_allocate (conf
    ``actionArguments: {xla_allocate: {mesh: auto}}``): the XLA
    while-loop kernel with its node axis GSPMD-sharded over the mesh.

    Speaks the same SolveState protocol as the single-chip solvers —
    `solve(None)` starts fresh, `solve(state)` resumes after the action's
    host-side pod-affinity step — so the segmented hybrid works
    unchanged: state comes back with node-axis fields sharded, the action
    gathers it to host (`np.array`), patches it, and re-enters; the jit's
    in_shardings scatter it again.

    ``arrays`` may be host numpy or arena handles
    (ops/encode_cache.TensorArena device arrays placed with this mesh's
    shardings): pre-placed arrays already match ``in_shardings``, so
    warm cycles skip the full host->mesh scatter and upload only the
    rows the arena found changed.

    Each loop iteration evaluates feasibility + scores on the local node
    block and GSPMD inserts the cross-device argmax/select for the
    winning node (psum-style reduction over the lone sharded axis riding
    ICI); the capacity update touches one node row, which XLA turns into
    a masked local update. See `sharded_solve_allocate` for the one-shot
    form; the single-chip `xla` twin runs the same program, which is
    the per-chip price floor of this path.
    """

    def __init__(
        self,
        arrays: dict,
        mesh: Mesh,
        enable_drf: bool = False,
        enable_proportion: bool = False,
        axis_name: str = AXIS_NAME,
    ) -> None:
        n = mesh.devices.size
        n_nodes = arrays["node_idle"].shape[0]
        if n_nodes % n != 0:
            raise ValueError(
                f"node bucket {n_nodes} not divisible by mesh size {n}; "
                "encode with pad=True (node buckets are multiples of 128; meshes up to 128 divide them)"
            )
        self.arrays = arrays
        self.mesh = mesh
        # jitted programs are cached per (devices, axis, key set, flags) —
        # a fresh ShardedSolver every cycle must NOT discard the trace/
        # compile cache (the jit wrappers re-trace per shape bucket
        # internally, so stable buckets hit the compiled program).
        self._fresh, self._resume = _sharded_programs(
            tuple(mesh.devices.flat),
            axis_name,
            frozenset(arrays),
            enable_drf,
            enable_proportion,
        )

    def solve(self, state: Optional[SolveState]) -> SolveState:
        if state is None:
            return self._fresh(self.arrays)
        return self._resume(self.arrays, state)


@lru_cache(maxsize=16)
def _sharded_programs(
    devices: tuple,
    axis_name: str,
    array_keys: frozenset,
    enable_drf: bool,
    enable_proportion: bool,
):
    """(fresh, resume) jitted SPMD programs for a mesh + snapshot layout.
    Keyed on the device tuple (jax Device objects are process singletons),
    the array key set (determines the in_shardings pytree), and the
    static kernel flags; shapes are left to jit's own per-signature
    cache."""
    mesh = Mesh(np.asarray(devices), (axis_name,))
    in_sh = node_shardings(dict.fromkeys(array_keys), mesh, axis_name)
    st_sh = state_shardings(mesh, axis_name)
    step = partial(
        solve_allocate_step,
        enable_drf=enable_drf,
        enable_proportion=enable_proportion,
    )
    fresh = jax.jit(
        lambda a: step(a, None), in_shardings=(in_sh,), out_shardings=st_sh
    )
    resume = jax.jit(step, in_shardings=(in_sh, st_sh), out_shardings=st_sh)
    return fresh, resume


def sharded_solve_allocate(
    arrays: dict,
    mesh: Mesh,
    axis_name: str = AXIS_NAME,
    enable_drf: bool = False,
    enable_proportion: bool = False,
) -> SolveResult:
    """Run the allocate solve with the node axis sharded over ``mesh``.

    The result arrays (task-axis) come back replicated. jit caches per
    (mesh, shapes), so repeated cycles at stable bucket sizes reuse the
    compiled SPMD program.
    """
    n = mesh.devices.size
    n_nodes = arrays["node_idle"].shape[0]
    if n_nodes % n != 0:
        raise ValueError(
            f"node bucket {n_nodes} not divisible by mesh size {n}; "
            "encode with pad=True (node buckets are multiples of 128; meshes up to 128 divide them)"
        )
    shardings = node_shardings(arrays, mesh, axis_name)
    fn = jax.jit(
        partial(
            solve_allocate_step,
            enable_drf=enable_drf,
            enable_proportion=enable_proportion,
        ),
        in_shardings=(shardings,),
    )
    return result_of(fn(arrays))
