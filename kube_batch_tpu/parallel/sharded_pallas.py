"""Blocked sharded-Pallas solver: the fused solve, one node block per chip.

The single-chip fused Pallas kernel (ops/pallas_solve) wins by holding
the whole snapshot in VMEM; its envelope is therefore one chip's VMEM
budget. The GSPMD-sharded XLA twin (parallel/sharded) scales capacity
but pays ~70us of per-HLO dispatch per gang iteration. This module is
the missing rung between them: each device runs the **fused block-local
kernel** — feasibility + score + block argmax over its own 128-lane
node blocks, every node array resident in VMEM — inside one
`jax.shard_map` SPMD program, and the only cross-device traffic is a
**per-gang-iteration argmax exchange**: one small all-gather of each
shard's (best score, global node index, fits-idle bit) triple over the
mesh axis, after which every shard deterministically agrees on the
winner and only the owning shard applies the capacity update to its
block. Queue/job selection and the task/job/queue bookkeeping are tiny
and run replicated (identical inputs -> identical results on every
shard), sharing `ops.kernels.select_queue_job` with the XLA twin so the
paths cannot drift on selection numerics.

Capacity therefore scales with mesh size: the per-shard VMEM claim is
the node block only (`ops.pallas_solve.block_vmem_bytes`), so a
snapshot that overflows `vmem_budget()` on one chip stays on the Pallas
rung when `node_block_bytes / mesh_size` fits — instead of falling to
the XLA twin (the cliff between the rungs is not measured on current
code).

Block backends (``KBT_MESH_PALLAS`` or the ``block_impl`` argument):

- ``mosaic`` — the real TPU kernel (auto-selected on TPU meshes);
- ``interpret`` — the same kernel through the Pallas interpreter
  (traceable, so it compiles inside the SPMD program; how the CPU
  parity tests execute the kernel code bit-for-bit);
- ``jnp`` — a plain-XLA twin of the block step (the fast path on
  virtual-CPU meshes and the oracle the kernel is pinned against).

Speaks the same `SolveState` resume protocol as `ShardedSolver`, so the
action's segmented pod-affinity pause/resume hybrid works unchanged,
including the live InterPodAffinity re-fold between segments.

K-deep batched exchange (``KBT_EXCHANGE_BATCH``, pipelined mode only):
at mesh 8 the per-iteration all-gather dispatch is the floor — the
block kernel itself runs exchange-free at ~1/3 of the measured
per-iteration cost. With ``exchange_batch = K > 1`` each shard first
**speculates** K gang iterations against a throwaway copy of the state,
assuming its own candidate wins every round (losers' blocks are
untouched by a loss, so a shard's speculative slab stays exact for as
long as its recorded candidates keep being used), recording per depth
the (score, global node index, fits-idle) triple plus the task fields
that fully determine the block step (gid, has-sc, ports mask, req8,
res8). One all-gather then ships the whole [K, record] buffer, and a
collective-free **replay** loop re-runs the true replicated
bookkeeping, taking each shard's candidate from its record at a
per-shard depth pointer that advances only when that shard wins (or on
a global abandon, which every speculative world agreed on because all
recorded scores are -inf). A record is used only if its task fields
equal the true current task's — the first mismatch ends the replay and
the next outer iteration re-speculates from the authoritative state, so
the batched program is bind-for-bind identical to the per-iteration
exchange; gang members are near-identical pods, so in the common case
all K iterations commit off a single exchange.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from kube_batch_tpu.ops import pallas_solve as ps
from kube_batch_tpu.ops.kernels import (
    KIND_ALLOCATED,
    KIND_PIPELINED,
    SolveState,
    init_state,
    select_queue_job,
)
from kube_batch_tpu.parallel.sharded import AXIS_NAME, NODE_AXIS_ARRAYS

LANES = ps.LANES
R8 = ps.R8

# Arrays the replicated loop body never reads (node-axis arrays travel
# folded+sharded; affinity/compat are pre-folded into cnode/affw).
_DROP = frozenset(NODE_AXIS_ARRAYS) | {"pod_sc", "aff_sc", "compat"}


def _default_exchange_batch() -> int:
    """K for the K-deep batched argmax exchange (``KBT_EXCHANGE_BATCH``).

    Batching only pays when the dispatch it amortizes is overlapped
    work, so K > 1 requires the pipelined-cycles gate (``KBT_PIPELINE``)
    — without it the env knob is inert and the per-iteration exchange
    runs unchanged. Tests pass ``exchange_batch`` to the
    solver explicitly to exercise the batched program in isolation.
    """
    from kube_batch_tpu import pipeline

    if not pipeline.env_on():
        return 1
    raw = os.environ.get("KBT_EXCHANGE_BATCH", "").strip()
    try:
        k = int(raw) if raw else 4
    except ValueError:
        from kube_batch_tpu import log

        log.errorf("bad KBT_EXCHANGE_BATCH=%r; using 4", raw)
        k = 4
    return max(1, min(k, 64))


def _resolve_block_impl(spec: Optional[str], mesh: Mesh) -> str:
    if spec is None:
        spec = os.environ.get("KBT_MESH_PALLAS", "auto")
    spec = (spec or "auto").strip().lower()
    if spec not in ("auto", "mosaic", "interpret", "jnp"):
        raise ValueError(f"unknown block impl {spec!r}")
    if spec == "auto":
        plat = next(iter(mesh.devices.flat)).platform
        return "mosaic" if plat == "tpu" else "jnp"
    return spec


class ShardedPallasSolver:
    """Per-execute driver for the blocked sharded solve: fold the node
    statics once, then solve / resume through the cached SPMD program."""

    def __init__(
        self,
        arrays: dict,
        mesh: Mesh,
        enable_drf: bool = False,
        enable_proportion: bool = False,
        axis_name: str = AXIS_NAME,
        block_impl: Optional[str] = None,
        exchange_batch: Optional[int] = None,
    ) -> None:
        # Arena handles (ops/encode_cache.TensorArena device arrays) are
        # accepted: the block path folds its statics host-side, so any
        # device-resident inputs are gathered to host numpy once here
        # instead of syncing per fold.
        if any(
            not isinstance(v, (np.ndarray, np.generic, float, int, bool))
            for v in arrays.values()
        ):
            arrays = {k: np.asarray(v) for k, v in arrays.items()}
        if np.dtype(np.asarray(arrays["task_req"]).dtype) != np.float32:
            raise ValueError(
                "blocked sharded-Pallas solve is float32-only (like the "
                "single-chip fused kernel); encode with dtype=float32"
            )
        self.a = arrays
        self.mesh = mesh
        self.axis_name = axis_name
        m = mesh.devices.size
        n_nodes = arrays["node_idle"].shape[0]
        nr = ps._rows(n_nodes)
        # The folded row axis pads up to a multiple of the mesh size so
        # shard_map divides it evenly; pad rows carry cnode=0/nmax=0 and
        # can never be candidates.
        self.nr_pad = -(-nr // m) * m
        self.block_impl = _resolve_block_impl(block_impl, mesh)
        self._statics = self._fold_statics(arrays)
        self._tports = ps._ports_mask(np.asarray(arrays["task_ports"]))
        self._pod_sc = arrays.get("pod_sc")  # identity marker for refresh
        self.exchange_batch = (
            _default_exchange_batch()
            if exchange_batch is None
            else max(1, int(exchange_batch))
        )
        # Gang iterations committed straight from a K-deep batched
        # exchange (accumulated across solve/resume calls; the action
        # meters the delta into exchange_batched_iters_total).
        self.batched_iters = 0
        self._fresh, self._resume = _blocked_programs(
            tuple(mesh.devices.flat),
            axis_name,
            enable_drf,
            enable_proportion,
            self.block_impl,
            self.exchange_batch,
        )

    def _fold_statics(self, a: dict) -> dict:
        f32, i32 = np.float32, np.int32
        node_gid = np.asarray(a["node_gid"], np.int64)
        okv = np.asarray(a["node_ok"] & a["node_valid"])
        cnode_full = np.asarray(a["compat"])[:, node_gid] & okv[None, :]
        gt, n = cnode_full.shape
        cnode = np.zeros((gt, self.nr_pad, LANES), i32)
        cnode[:, : (n + LANES - 1) // LANES, :].reshape(gt, -1)[:, :n] = cnode_full
        return {
            "cnode": cnode,
            "affw": ps.fold_affinity_scores(a, self.nr_pad),
            "nalloc": ps._fold2(np.asarray(a["node_alloc"], f32), self.nr_pad, f32),
            "nmax": ps._fold1(np.asarray(a["node_max_tasks"], i32), self.nr_pad, i32),
            "nihs": ps._fold1(np.asarray(a["node_idle_has_sc"], i32), self.nr_pad, i32),
            "nrhs": ps._fold1(np.asarray(a["node_rel_has_sc"], i32), self.nr_pad, i32),
        }

    def solve(self, state: Optional[SolveState]) -> SolveState:
        if self.a.get("pod_sc") is not self._pod_sc:
            # The action recomputed live InterPodAffinity scores after a
            # host-stepped pod landed: re-fold just the affinity static
            # and resume with fresh scores (same contract as the
            # single-chip PallasSolver).
            self._pod_sc = self.a.get("pod_sc")
            self._statics["affw"] = ps.fold_affinity_scores(self.a, self.nr_pad)
        a_call = dict(self.a)
        a_call["_tports"] = self._tports
        if state is None:
            out = self._fresh(a_call, self._statics)
        else:
            out = self._resume(a_call, self._statics, state)
        if self.exchange_batch > 1:
            out, n_batched = out
            self.batched_iters += int(n_batched)
        return out


@lru_cache(maxsize=16)
def _blocked_programs(
    devices: tuple,
    axis_name: str,
    enable_drf: bool,
    enable_proportion: bool,
    block_impl: str,
    exchange_batch: int = 1,
):
    """(fresh, resume) jitted SPMD programs for a mesh + block backend.
    Keyed on the device tuple and static flags; shapes (and the derived
    Nr_pad/Nr_loc/GT block geometry) are left to jit's per-signature
    cache, so stable encode buckets hit the compiled program across
    cycles. With ``exchange_batch > 1`` the programs return
    ``(SolveState, n_batched_iters)`` — the gang loop speculates K
    iterations per shard, ships one [K, record] all-gather, and replays
    validated records collective-free (module docstring has the full
    scheme); the SolveState itself keeps the exact per-iteration
    signature so the cross-tier resume protocol cannot drift."""
    import jax.numpy as jnp
    from jax import lax

    mesh = Mesh(np.asarray(devices), (axis_name,))
    m = len(devices)
    spec3 = P(None, axis_name, None)
    spec2 = P(axis_name, None)
    sh_specs = {
        "cnode": spec3, "affw": spec3, "nalloc": spec3,
        "nmax": spec2, "nihs": spec2, "nrhs": spec2,
        "idle": spec3, "rel": spec3, "used": spec3,
        "ntasks": spec2, "nports": spec2,
    }
    out_sh_specs = {
        "idle": spec3, "rel": spec3, "used": spec3,
        "ntasks": spec2, "nports": spec2,
    }
    INT_MAX = ps.INT_MAX
    NINF = float("-inf")

    def local(rep, a, sh):
        """One shard's SPMD body: the full gang loop over the local node
        block, replicated selection/bookkeeping, one argmax exchange per
        gang iteration — or per K-iteration speculate/replay batch when
        ``exchange_batch > 1``."""
        i32, f32 = jnp.int32, jnp.float32
        T, R = a["task_req"].shape
        J = a["job_min"].shape[0]
        Q = a["queue_rank"].shape[0]
        gt = sh["cnode"].shape[0]
        nr_loc = sh["cnode"].shape[1]
        sent = nr_loc * m * LANES  # global padded N: "no candidate"
        axis_idx = lax.axis_index(axis_name).astype(i32)
        off = axis_idx * (nr_loc * LANES)

        if block_impl == "jnp":
            block = ps.block_step_jnp
        else:
            block = ps._build_block_step(nr_loc, gt, block_impl == "interpret")

        eps8 = jnp.concatenate(
            [jnp.asarray(a["eps"], f32), jnp.ones(R8 - R, f32)]
        )
        wvec = jnp.stack(
            [jnp.asarray(a["w_least"], f32), jnp.asarray(a["w_balanced"], f32)]
        )
        fpad = jnp.zeros(ps.FVEC_LEN - 3 * R8 - 2, f32)
        host_only = a["task_host_only"]
        max_iter = jnp.int32(T + J + Q + 1) + jnp.sum(host_only).astype(i32)
        lane1 = lax.broadcasted_iota(i32, (1, LANES), 1)

        # The loop body is factored into prefix (replicated selection +
        # task pop), taskvec (the fields that fully determine a task's
        # block step — also the speculative-record validity key), the
        # block call, and commit (everything after the winner is known),
        # so the per-iteration exchange and the K-deep batched program
        # share every line of bookkeeping and cannot drift.

        def prefix(s: SolveState):
            # -- replicated queue + job selection (shared with the XLA twin)
            need_sel = s.cur < 0
            qsel, q_any, overused, jsel, j_any = select_queue_job(
                a, s, enable_drf, enable_proportion
            )
            drop_q = need_sel & q_any & overused
            sel_ok = q_any & ~overused & j_any
            cur = jnp.where(need_sel, jnp.where(sel_ok, jsel, -1), s.cur)
            job_active = jnp.where(
                drop_q, s.job_active & (a["job_queue"] != qsel), s.job_active
            )
            q_dropped = s.q_dropped.at[qsel].set(drop_q | s.q_dropped[qsel])

            # -- pop the current job's next pending task (O(1) pointer) ----
            cur_c = jnp.maximum(cur, 0)
            t = s.ptr[cur_c]
            t_any = (cur >= 0) & (t < a["job_end"][cur_c])
            t = jnp.minimum(t, T - 1)
            drop = (cur >= 0) & ~t_any
            pause = t_any & host_only[t]
            proc = t_any & ~pause
            return cur, cur_c, t, drop, pause, proc, job_active, q_dropped

        def taskvec(t):
            req8 = jnp.concatenate(
                [jnp.asarray(a["task_req"][t], f32), jnp.zeros(R8 - R, f32)]
            )
            res8 = jnp.concatenate(
                [jnp.asarray(a["task_res"][t], f32), jnp.zeros(R8 - R, f32)]
            )
            gid = jnp.clip(a["task_gid"][t], 0, gt - 1).astype(i32)
            tports = a["_tports"][t].astype(i32)
            has_sc = a["task_has_sc"][t].astype(i32)
            return req8, res8, gid, tports, has_sc

        def run_block(s, req8, res8, gid, tports, has_sc):
            # -- fused block-local feasibility + score + argmax ------------
            fvec = jnp.concatenate([req8, res8, eps8, wvec, fpad])
            ivec = jnp.stack(
                [
                    gid,
                    has_sc,
                    tports,
                    off,
                    jnp.int32(sent),
                    jnp.int32(0), jnp.int32(0), jnp.int32(0),
                ]
            )
            return block(
                ivec, fvec,
                sh["cnode"], sh["affw"], sh["nalloc"],
                sh["nmax"], sh["nihs"], sh["nrhs"],
                s.idle, s.rel, s.used, s.ntasks, s.nports,
            )

        def winner(scores, idxs, fits):
            # Every shard derives the same winner (max score, min global
            # node index on ties — identical to the single-chip
            # tie-break); the winner's fits-idle bit comes from the
            # shard that owns it.
            big = jnp.max(scores)
            any_cand = big > NINF
            nb = jnp.min(jnp.where(scores == big, idxs, INT_MAX))
            nb = jnp.minimum(nb, sent - 1)
            fits_idle_nb = (
                jnp.sum(jnp.where((scores == big) & (idxs == nb), fits, 0)) > 0
            )
            return any_cand, nb, fits_idle_nb

        def commit(
            s, cur, cur_c, t, drop, pause, proc, job_active, q_dropped,
            req8, res8, tports, any_cand, nb, fits_idle_nb,
        ) -> SolveState:
            abandon = proc & ~any_cand
            assign = proc & any_cand
            do_alloc = assign & fits_idle_nb

            # -- capacity update: owning shard only, one 128-lane slab ----
            rloc = nb // LANES - axis_idx * nr_loc
            mine = (rloc >= 0) & (rloc < nr_loc)
            rc = jnp.clip(rloc, 0, nr_loc - 1)
            l = nb % LANES
            upd = assign & mine
            lmask = upd & (lane1 == l)  # [1, 128]
            lmask3 = lmask[None]  # [1, 1, 128]
            col_alloc = jnp.where(do_alloc, res8, 0.0)[:, None, None]
            col_pipe = jnp.where(do_alloc, 0.0, res8)[:, None, None]
            res3 = res8[:, None, None]

            z = jnp.int32(0)  # index literals pinned to rc's dtype (x64)

            def slab_update(arr, delta3):
                slab = lax.dynamic_slice(arr, (z, rc, z), (R8, 1, LANES))
                slab = slab + jnp.where(lmask3, delta3, 0.0)
                return lax.dynamic_update_slice(arr, slab, (z, rc, z))

            idle = slab_update(s.idle, -col_alloc)
            rel = slab_update(s.rel, -col_pipe)
            used = slab_update(s.used, res3)
            nt_row = lax.dynamic_slice(s.ntasks, (rc, z), (1, LANES))
            nt_row = nt_row + jnp.where(lmask, 1, 0)
            ntasks = lax.dynamic_update_slice(s.ntasks, nt_row, (rc, z))
            np_row = lax.dynamic_slice(s.nports, (rc, z), (1, LANES))
            np_row = np_row | jnp.where(lmask, tports, 0)
            nports = lax.dynamic_update_slice(s.nports, np_row, (rc, z))

            # -- replicated bookkeeping (identical on every shard) ---------
            ready_cnt = s.ready_cnt.at[cur_c].add(jnp.where(do_alloc, 1, 0))
            ptr = s.ptr.at[cur_c].add(jnp.where(proc, 1, 0))
            assigned_node = s.assigned_node.at[t].set(
                jnp.where(assign, nb, s.assigned_node[t])
            )
            kind = jnp.where(
                do_alloc, KIND_ALLOCATED, jnp.where(assign, KIND_PIPELINED, 0)
            )
            assigned_kind = s.assigned_kind.at[t].set(
                jnp.where(assign, kind, s.assigned_kind[t])
            )
            assign_pos = s.assign_pos.at[t].set(
                jnp.where(assign, s.step, s.assign_pos[t])
            )
            add_row = jnp.where(assign, a["task_res"][t], jnp.zeros(R, f32))
            job_alloc = (
                s.job_alloc.at[cur_c].add(add_row) if enable_drf else s.job_alloc
            )
            if enable_proportion:
                qcur = a["job_queue"][cur_c]
                q_alloc = s.q_alloc.at[qcur].add(add_row)
                q_alloc_has_sc = s.q_alloc_has_sc.at[qcur].set(
                    s.q_alloc_has_sc[qcur] | (assign & a["task_res_has_sc"][t])
                )
            else:
                q_alloc = s.q_alloc
                q_alloc_has_sc = s.q_alloc_has_sc

            job_active = job_active.at[cur_c].set(
                jnp.where(drop | abandon, False, job_active[cur_c])
            )
            ready_now = ready_cnt[cur_c] >= a["job_min"][cur_c]
            cur_next = jnp.where(drop | abandon | (proc & ready_now), -1, cur)

            return SolveState(
                it=s.it + 1,
                step=s.step + assign.astype(i32),
                cur=cur_next,
                ptr=ptr,
                assigned_node=assigned_node,
                assigned_kind=assigned_kind,
                assign_pos=assign_pos,
                idle=idle,
                rel=rel,
                used=used,
                ntasks=ntasks,
                nports=nports,
                ready_cnt=ready_cnt,
                job_active=job_active,
                q_dropped=q_dropped,
                job_alloc=job_alloc,
                q_alloc=q_alloc,
                q_alloc_has_sc=q_alloc_has_sc,
                paused_at=jnp.where(pause, t, jnp.int32(-1)),
            )

        def body(s: SolveState) -> SolveState:
            cur, cur_c, t, drop, pause, proc, job_active, q_dropped = prefix(s)
            req8, res8, gid, tports, has_sc = taskvec(t)
            bscore, bidx, bfits = run_block(s, req8, res8, gid, tports, has_sc)

            # -- the cross-chip argmax exchange: one packed all-gather per
            # gang iteration.
            packed = jnp.stack(
                [bscore, bidx.astype(f32), bfits.astype(f32)]
            )
            allp = lax.all_gather(packed, axis_name)  # [mesh, 3]
            any_cand, nb, fits_idle_nb = winner(
                allp[:, 0], allp[:, 1].astype(i32), allp[:, 2].astype(i32)
            )
            return commit(
                s, cur, cur_c, t, drop, pause, proc, job_active, q_dropped,
                req8, res8, tports, any_cand, nb, fits_idle_nb,
            )

        def cond(s: SolveState):
            return (
                ((s.cur >= 0) | jnp.any(s.job_active))
                & (s.it < max_iter)
                & (s.paused_at < 0)
            )

        # -- K-deep batched exchange: speculate, one gather, replay --------
        K = exchange_batch
        REC_F = 1 + 2 * R8  # score, req8, res8

        def spec_body(c):
            # One speculative gang iteration on a throwaway state: this
            # shard's own candidate is assumed to win, so its block stays
            # exact for its own chain; proc iterations append a record.
            s, w, rf, ri = c
            cur, cur_c, t, drop, pause, proc, job_active, q_dropped = prefix(s)
            req8, res8, gid, tports, has_sc = taskvec(t)
            bscore, bidx, bfits = run_block(s, req8, res8, gid, tports, has_sc)
            any_cand = bscore > NINF
            nb = jnp.minimum(bidx.astype(i32), sent - 1)
            fits_idle_nb = bfits.astype(i32) > 0
            s2 = commit(
                s, cur, cur_c, t, drop, pause, proc, job_active, q_dropped,
                req8, res8, tports, any_cand, nb, fits_idle_nb,
            )
            slot = jnp.where(proc, w, jnp.int32(K))  # K = out of bounds: drop
            rf = rf.at[slot].set(
                jnp.concatenate([bscore[None].astype(f32), req8, res8]),
                mode="drop",
            )
            ri = ri.at[slot].set(
                jnp.stack(
                    [bidx.astype(i32), bfits.astype(i32), gid, has_sc, tports]
                ),
                mode="drop",
            )
            return s2, w + proc.astype(i32), rf, ri

        def spec_cond(c):
            s, w, _, _ = c
            return (w < K) & cond(s)

        def replay_cond(c):
            s, _, live, _ = c
            return live & cond(s)

        def make_replay_body(allf, alli, nrec):
            shard_ids = jnp.arange(m, dtype=i32)

            def replay_body(c):
                # One true gang iteration, collective-free: candidates
                # come from the gathered records at each shard's depth
                # pointer. A record is usable only while its task fields
                # equal the true current task's; the first mismatch (or
                # an exhausted shard) ends the replay un-committed and
                # the outer loop re-speculates from the true state.
                s, d, live, nc = c
                cur, cur_c, t, drop, pause, proc, job_active, q_dropped = (
                    prefix(s)
                )
                req8, res8, gid, tports, has_sc = taskvec(t)
                dcl = jnp.minimum(d, K - 1)
                rowf = jnp.take_along_axis(
                    allf, dcl[:, None, None], axis=1
                )[:, 0]  # [mesh, REC_F]
                rowi = jnp.take_along_axis(
                    alli, dcl[:, None, None], axis=1
                )[:, 0]  # [mesh, 5]
                scores = rowf[:, 0]
                idxs = rowi[:, 0]
                fits = rowi[:, 1]
                valid = jnp.all(
                    (d < nrec)
                    & (rowi[:, 2] == gid)
                    & (rowi[:, 3] == has_sc)
                    & (rowi[:, 4] == tports)
                    & jnp.all(rowf[:, 1 : 1 + R8] == req8[None, :], axis=1)
                    & jnp.all(rowf[:, 1 + R8 :] == res8[None, :], axis=1)
                )
                any_cand, nb, fits_idle_nb = winner(scores, idxs, fits)
                s2 = commit(
                    s, cur, cur_c, t, drop, pause, proc, job_active,
                    q_dropped, req8, res8, tports, any_cand, nb, fits_idle_nb,
                )
                # Depth pointers: the winning shard consumed its record;
                # a global abandon consumed everyone's (all recorded
                # scores were -inf, so every speculative world abandoned
                # this task too, with no block change on either side).
                win_shard = (nb // (nr_loc * LANES)).astype(i32)
                d2 = jnp.where(
                    any_cand,
                    jnp.where(shard_ids == win_shard, d + 1, d),
                    d + 1,
                )
                d2 = jnp.where(proc, d2, d)
                ok = (~proc) | valid
                s3 = jax.tree_util.tree_map(
                    lambda nv, ov: jnp.where(ok, nv, ov), s2, s
                )
                return (
                    s3,
                    jnp.where(ok, d2, d),
                    live & ok,
                    nc + (proc & ok).astype(i32),
                )

            return replay_body

        def outer_cond(c):
            s, _ = c
            return cond(s)

        def outer_body(c):
            s, nb_tot = c
            rf0 = jnp.zeros((K, REC_F), f32)
            ri0 = jnp.zeros((K, 5), i32)
            _, w, rf, ri = lax.while_loop(
                spec_cond, spec_body, (s, jnp.int32(0), rf0, ri0)
            )
            allf = lax.all_gather(rf, axis_name)  # [mesh, K, REC_F]
            alli = lax.all_gather(ri, axis_name)  # [mesh, K, 5]
            nrec = lax.all_gather(w, axis_name)  # [mesh]
            # Replay iteration 0 is always committable: speculation and
            # replay both start from the true state, and the native
            # selection/drop steps before the first proc iteration are
            # replicated-deterministic — so depth-0 records are exact
            # and every outer iteration advances s.it by at least one.
            s2, _, _, nc = lax.while_loop(
                replay_cond,
                make_replay_body(allf, alli, nrec),
                (s, jnp.zeros(m, i32), jnp.bool_(True), jnp.int32(0)),
            )
            return s2, nb_tot + nc

        (
            it, step, cur, ptr, an, ak, ap,
            ready_cnt, job_active, q_dropped, job_alloc, q_alloc, qahs, paused,
        ) = rep
        state = SolveState(
            it=it, step=step, cur=cur, ptr=ptr,
            assigned_node=an, assigned_kind=ak, assign_pos=ap,
            idle=sh["idle"], rel=sh["rel"], used=sh["used"],
            ntasks=sh["ntasks"], nports=sh["nports"],
            ready_cnt=ready_cnt, job_active=job_active, q_dropped=q_dropped,
            job_alloc=job_alloc, q_alloc=q_alloc, q_alloc_has_sc=qahs,
            paused_at=paused,
        )
        if exchange_batch > 1:
            out, n_batched = lax.while_loop(
                outer_cond, outer_body, (state, jnp.int32(0))
            )
        else:
            out = lax.while_loop(cond, body, state)
            n_batched = None
        rep_out = (
            out.it, out.step, out.cur, out.ptr,
            out.assigned_node, out.assigned_kind, out.assign_pos,
            out.ready_cnt, out.job_active, out.q_dropped,
            out.job_alloc, out.q_alloc, out.q_alloc_has_sc, out.paused_at,
        )
        if n_batched is not None:
            rep_out = rep_out + (n_batched,)
        sh_out = {
            "idle": out.idle, "rel": out.rel, "used": out.used,
            "ntasks": out.ntasks, "nports": out.nports,
        }
        return rep_out, sh_out

    smapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), sh_specs),
        out_specs=(P(), out_sh_specs),
        check_vma=False,
    )

    def run(a: dict, statics: dict, state: Optional[SolveState]) -> SolveState:
        i32, f32 = jnp.int32, jnp.float32
        n = a["node_idle"].shape[0]
        R = a["task_req"].shape[1]
        p = a["task_ports"].shape[1]
        nr_pad = statics["cnode"].shape[1]
        nf = nr_pad * LANES

        if state is None:
            state = init_state(
                a, enable_drf=enable_drf, enable_proportion=enable_proportion
            )
        state = state._replace(paused_at=jnp.int32(-1))

        def fold2(x):
            xp = jnp.pad(
                jnp.asarray(x, f32), ((0, nf - n), (0, R8 - R))
            )
            return xp.reshape(nr_pad, LANES, R8).transpose(2, 0, 1)

        def fold1(x, dt):
            return jnp.pad(jnp.asarray(x, dt), (0, nf - n)).reshape(nr_pad, LANES)

        if p:
            bits = jnp.sum(
                jnp.asarray(state.nports, i32)
                * (jnp.int32(1) << jnp.arange(p, dtype=i32))[None, :],
                axis=1,
                dtype=i32,
            )
        else:
            bits = jnp.zeros(n, i32)

        sh_in = dict(statics)
        sh_in.update(
            idle=fold2(state.idle),
            rel=fold2(state.rel),
            used=fold2(state.used),
            ntasks=fold1(state.ntasks, i32),
            nports=fold1(bits, i32),
        )
        rep_in = (
            jnp.asarray(state.it, i32), jnp.asarray(state.step, i32),
            jnp.asarray(state.cur, i32), jnp.asarray(state.ptr, i32),
            jnp.asarray(state.assigned_node, i32),
            jnp.asarray(state.assigned_kind, i32),
            jnp.asarray(state.assign_pos, i32),
            jnp.asarray(state.ready_cnt, i32),
            jnp.asarray(state.job_active, bool),
            jnp.asarray(state.q_dropped, bool),
            jnp.asarray(state.job_alloc, f32),
            jnp.asarray(state.q_alloc, f32),
            jnp.asarray(state.q_alloc_has_sc, bool),
            state.paused_at,
        )
        a_rep = {k: v for k, v in a.items() if k not in _DROP}
        rep_out, sh_out = smapped(rep_in, a_rep, sh_in)
        n_batched = None
        if exchange_batch > 1:
            *rep_flat, n_batched = rep_out
            rep_out = tuple(rep_flat)

        def unfold2(x):
            return x.transpose(1, 2, 0).reshape(nf, R8)[:n, :R]

        def unfold1(x):
            return x.reshape(nf)[:n]

        obits = unfold1(sh_out["nports"])
        if p:
            nports_bool = (
                (obits[:, None] >> jnp.arange(p, dtype=i32)[None, :]) & 1
            ) != 0
        else:
            nports_bool = jnp.zeros((n, 0), bool)
        (
            it, step, cur, ptr, an, ak, ap,
            ready_cnt, job_active, q_dropped, job_alloc, q_alloc, qahs, paused,
        ) = rep_out
        final = SolveState(
            it=it, step=step, cur=cur, ptr=ptr,
            assigned_node=an, assigned_kind=ak, assign_pos=ap,
            idle=unfold2(sh_out["idle"]),
            rel=unfold2(sh_out["rel"]),
            used=unfold2(sh_out["used"]),
            ntasks=unfold1(sh_out["ntasks"]),
            nports=nports_bool,
            ready_cnt=ready_cnt, job_active=job_active, q_dropped=q_dropped,
            job_alloc=job_alloc, q_alloc=q_alloc, q_alloc_has_sc=qahs,
            paused_at=paused,
        )
        if n_batched is not None:
            return final, n_batched
        return final

    fresh = jax.jit(partial(run, state=None))
    resume = jax.jit(run)
    return fresh, resume
