"""Node-class compressed solve (``KBT_CLASS_COMPRESS``, default off).

Production fleets have dozens-to-hundreds of distinct node *shapes* —
capacity signature, labels/taints, idle vector — so at 400k x 40k the
node axis the solver tiers scan every gang iteration is overwhelmingly
redundant. This module folds interchangeable nodes into equivalence
classes and runs feasibility + score + argmax at **class granularity**:

- The per-node class key is byte-exact over every array the kernel's
  fit/score block reads (idle, releasing, used, capacity, pod count,
  port mask, static-feasibility bits, label/affinity group id, live
  InterPodAffinity column), reusing the encode slabs directly — no
  re-derivation, so two nodes share a class iff the uncompressed kernel
  could not tell them apart.
- Dedup runs through the native ``class_dedup`` hash pass (multi-slab
  form, satellite of this PR) with a widened ``np.unique`` fallback and
  the pre-existing ``native.class_dedup`` fault point.
- The compressed kernel mirrors ``ops.kernels.solve_allocate_step``
  operation-for-operation over the class axis (shared
  ``select_queue_job``, shared ``ieee_div``/``_le_eps`` numerics), with
  a multiplicity counter per class. Selection uses
  ``_lex_argmin(cand, -score, tiebreak)`` where ``tiebreak`` is each
  class's lowest member node row — exactly the uncompressed kernel's
  ``argmax`` first-row tie-break, so placement is **bind-for-bind
  identical** by construction.
- **Dynamic splitting**: a bind changes only the chosen node, so that
  member splits off into a fresh singleton slot (statics copied, task
  deltas applied) while the parent class decrements its multiplicity
  and advances its member cursor — no per-iteration re-dedup. The slot
  axis is padded to a sticky power-of-two bucket (grow-only per action
  lifetime) so warm cycles stay at zero recompiles under churn; slot
  exhaustion pauses the kernel, the host re-buckets to the next power
  of two (bounded by the node bucket — slots can never exceed live
  nodes) and resumes mid-iteration.
- At segment boundaries (pod-affinity pause/resume, streaming
  micro-cycles absorbing peer-bind occupancy patches, the next cycle's
  encode) the table regroups from the current node-space state: split
  members whose rows re-converged **re-merge** into shared classes, and
  a node whose *static* key changed (encode-cache dirty node) is
  dropped from its class and re-keyed — both metered on
  ``class_table_splits_total`` / the solver stats.

The solver wraps whichever tier ``_make_solver`` picked and speaks
node-space ``SolveState`` at every boundary (pause/resume, result,
explain), expanding class state through the member table — per shard
when a mesh is configured (replicated class table, per-shard
membership), matching the GSPMD rung's layout. Any failure, or the
``solve.class_table`` fault point, drops the cycle to the uncompressed
tier loudly (``degraded_cycles`` + error log).

``python -m kube_batch_tpu.ops.class_solve --json`` runs the seeded
self-check: a heterogeneous node-pool world solved serial, uncompressed
and compressed (bind parity asserted), across two cycles so in-solve
splits AND cross-cycle re-merges are both exercised.
"""

from __future__ import annotations

import logging
import os
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kube_batch_tpu.ops.kernels import (
    KIND_ALLOCATED,
    KIND_PIPELINED,
    MAX_PRIORITY,
    SolveState,
    _le_eps,
    _lex_argmin,
    ieee_div,
    select_queue_job,
)

ENV = "KBT_CLASS_COMPRESS"
_ON_WORDS = ("1", "true", "on", "yes")

log = logging.getLogger("kube_batch_tpu.ops.class_solve")


def enabled() -> bool:
    return os.environ.get(ENV, "").strip().lower() in _ON_WORDS


def _pow2(n: int) -> int:
    return max(8, 1 << (max(int(n), 1) - 1).bit_length())


# -- multi-slab row dedup -----------------------------------------------------


def _as_rows(slab) -> np.ndarray:
    """Byte-exact [N, k] uint8 view of one key slab (1-D slabs become one
    column). Views require contiguity; the copy is taken at most once."""
    s = np.ascontiguousarray(slab)
    if s.ndim == 1:
        s = s.reshape(-1, 1)
    return s.view(np.uint8).reshape(s.shape[0], -1)


def dedup_rows(slabs) -> tuple[np.ndarray, np.ndarray]:
    """Dedup rows over the concatenated byte spans of ``slabs`` (each
    [N] or [N, k], equal row counts): returns (first int64 per class,
    inverse int32 per row). Native ``class_dedup`` multi-buffer hash
    pass when available (one O(N) pass, no Python-level concat), else
    the widened np.unique void-sort. Class *order* differs between the
    two paths (first-occurrence vs sorted) and carries no meaning —
    callers derive representatives from member lists, never from ids."""
    mats = [_as_rows(s) for s in slabs]
    n = mats[0].shape[0]
    if any(m.shape[0] != n for m in mats):
        raise ValueError("dedup_rows slabs disagree on row count")
    from kube_batch_tpu import faults as _faults
    from kube_batch_tpu.native import lib as _native

    if (
        _native is not None
        and hasattr(_native, "class_dedup")
        and not _faults.should_fire("native.class_dedup")
    ):
        try:
            arg = mats[0] if len(mats) == 1 else tuple(mats)
            first_b, inv_b = _native.class_dedup(arg)
            return (
                np.frombuffer(first_b, np.int64),
                np.frombuffer(inv_b, np.int32),
            )
        except TypeError:
            # older single-buffer extension: fall through to the
            # widened host path rather than failing the cycle
            log.debug("native class_dedup lacks multi-buffer keys; using np.unique")
    key = np.ascontiguousarray(np.concatenate(mats, axis=1))
    void = key.view(np.dtype((np.void, key.shape[1])))
    _, first, inv = np.unique(void.ravel(), return_index=True, return_inverse=True)
    return first.astype(np.int64), inv.astype(np.int32)


# -- cross-cycle static class table -------------------------------------------

_STATIC_KEYS = (
    "node_alloc",
    "node_ok",
    "node_valid",
    "node_max_tasks",
    "node_idle_has_sc",
    "node_rel_has_sc",
    "node_gid",
)


class ClassTable:
    """The persistent half of the compression: static per-node keys
    (capacity, feasibility bits, label/affinity group) deduped once,
    then delta-refreshed — a churned node's changed row is dropped from
    its class and re-keyed through the key dict (re-merging with any
    class already holding that key), without re-hashing the fleet.
    Class ids are stable across cycles so the dynamic regroup (which
    folds them into its key) stays incremental-friendly; the sticky
    power-of-two slot bucket lives here so warm cycles never change the
    compiled class-kernel shapes."""

    def __init__(self) -> None:
        self.key_bytes: np.ndarray | None = None  # [N, K] uint8
        self.class0_of: np.ndarray | None = None  # [N] int32 stable static ids
        self.key_to_id: dict[bytes, int] = {}
        self.sticky_cpad = 8
        self.rekeys_total = 0  # static-key churn (encode-cache dirty nodes)
        self.splits_total = 0  # in-solve bind splits
        self.remerges_total = 0
        self.rebuilds = 0
        self._prev_singleton: np.ndarray | None = None  # [N] bool at last solve end

    def _next_id(self, key: bytes) -> int:
        cid = self.key_to_id.get(key)
        if cid is None:
            cid = len(self.key_to_id)
            self.key_to_id[key] = cid
        return cid

    def refresh_static(self, arrays: dict) -> tuple[np.ndarray, int]:
        """Return ([N] stable static class ids, re-keyed row count)."""
        mats = [_as_rows(np.asarray(arrays[k])) for k in _STATIC_KEYS]
        key = np.ascontiguousarray(np.concatenate(mats, axis=1))
        if (
            self.key_bytes is None
            or self.key_bytes.shape != key.shape
            or self.class0_of is None
        ):
            # cold (or re-bucketed fleet): one dedup pass, ids minted in
            # class order so a later warm refresh maps changed rows only
            first, inv = dedup_rows([key])
            ids = np.fromiter(
                (self._next_id(key[r].tobytes()) for r in first),
                np.int32,
                count=len(first),
            )
            self.class0_of = ids[inv]
            self.key_bytes = key
            self.rebuilds += 1
            self._prev_singleton = None
            return self.class0_of, 0
        changed = np.nonzero(np.any(self.key_bytes != key, axis=1))[0]
        if changed.size:
            out = self.class0_of.copy()
            for r in changed:
                out[r] = self._next_id(key[r].tobytes())
            self.class0_of = out
            self.key_bytes = key
            self.rekeys_total += int(changed.size)
        return self.class0_of, int(changed.size)

    def note_end(self, slot_of_end: np.ndarray) -> None:
        counts = np.bincount(slot_of_end, minlength=int(slot_of_end.max()) + 1)
        self._prev_singleton = counts[slot_of_end] == 1

    def note_regroup(self, slot_of: np.ndarray, counts: np.ndarray) -> int:
        """Re-merge accounting: nodes that sat in singleton slots at the
        last solve end and now share a multi-member class again."""
        if self._prev_singleton is None or self._prev_singleton.shape != slot_of.shape:
            return 0
        merged = int(np.count_nonzero(self._prev_singleton & (counts[slot_of] > 1)))
        self.remerges_total += merged
        return merged


# -- the class-granularity kernel ---------------------------------------------


class ClassSolveState(NamedTuple):
    """``SolveState`` with the node axis folded to slot granularity plus
    the split machinery (multiplicity, member cursor, free slot pointer).
    Job/queue/task fields keep their ``SolveState`` names so
    ``select_queue_job`` reads this state unchanged."""

    it: "np.ndarray"
    step: "np.ndarray"
    cur: "np.ndarray"
    ptr: "np.ndarray"
    assigned_node: "np.ndarray"
    assigned_kind: "np.ndarray"
    assign_pos: "np.ndarray"
    # slot-granular node state (mutable within a segment)
    cidle: "np.ndarray"  # [C, R]
    crel: "np.ndarray"
    cused: "np.ndarray"
    cntasks: "np.ndarray"  # [C]
    cnports: "np.ndarray"  # [C, P]
    # slot-granular statics (copied to the child on split)
    calloc: "np.ndarray"  # [C, R]
    cok: "np.ndarray"  # [C] bool (node_ok & node_valid)
    cmax_tasks: "np.ndarray"
    cidle_has_sc: "np.ndarray"
    crel_has_sc: "np.ndarray"
    cgid: "np.ndarray"
    cpod_sc: "np.ndarray"  # [GT, C] live InterPodAffinity columns
    # split machinery
    cmult: "np.ndarray"  # [C] members remaining (0 = dead slot)
    ctie: "np.ndarray"  # [C] lowest member node row (the tie-break key)
    cpos: "np.ndarray"  # [C] absolute cursor into members_sorted
    free_ptr: "np.ndarray"  # first free slot
    overflow: "np.ndarray"  # bool: slot bucket exhausted, host must re-bucket
    seg_it: "np.ndarray"  # iterations burned in this segment (re-pack cap)
    # job/queue state, verbatim SolveState layout
    ready_cnt: "np.ndarray"
    job_active: "np.ndarray"
    q_dropped: "np.ndarray"
    job_alloc: "np.ndarray"
    q_alloc: "np.ndarray"
    q_alloc_has_sc: "np.ndarray"
    paused_at: "np.ndarray"


def _fit_score_block(
    cidle, crel, cused, cntasks, cnports, calloc, cok, cmax_tasks,
    cidle_has_sc, crel_has_sc, cgid, cpod_col,
    req, res, tports, t_has_sc, eps, compat_t, aff_t,
    w_least, w_balanced, w_aff, w_podaff, fdtype,
):
    """The per-iteration fit+score block over (a block of) the slot
    axis — the exact ops of the uncompressed kernel's HOT LOOP #1/#2
    (``ops.kernels.solve_allocate_step``), shared by the flat XLA twin
    and the blocked mesh rung so the two cannot drift numerically."""
    fits_idle = _le_eps(req, cidle, eps) & ~(t_has_sc & ~cidle_has_sc)
    fits_rel = _le_eps(req, crel, eps) & ~(t_has_sc & ~crel_has_sc)
    static_ok = cok & compat_t[cgid]
    room = cntasks < cmax_tasks
    port_ok = ~jnp.any(tports[None, :] & cnports, axis=1)

    req_cpu = cused[:, 0] + res[0]
    req_mem = cused[:, 1] + res[1]
    cap_cpu = calloc[:, 0]
    cap_mem = calloc[:, 1]

    def least_dim(rq, cp):
        safe = jnp.where(cp == 0, 1.0, cp)
        sc = jnp.floor(ieee_div((cp - rq) * MAX_PRIORITY, safe)).astype(jnp.int32)
        return jnp.where((cp == 0) | (rq > cp), 0, sc)

    least = (least_dim(req_cpu, cap_cpu) + least_dim(req_mem, cap_mem)) // 2
    cpu_f = jnp.where(
        cap_cpu != 0, ieee_div(req_cpu, jnp.where(cap_cpu == 0, 1.0, cap_cpu)), 1.0
    )
    mem_f = jnp.where(
        cap_mem != 0, ieee_div(req_mem, jnp.where(cap_mem == 0, 1.0, cap_mem)), 1.0
    )
    balanced = jnp.where(
        (cpu_f >= 1.0) | (mem_f >= 1.0),
        0,
        (MAX_PRIORITY - jnp.abs(cpu_f - mem_f) * MAX_PRIORITY).astype(jnp.int32),
    )
    score = (
        least.astype(fdtype) * w_least
        + balanced.astype(fdtype) * w_balanced
        + aff_t[cgid] * w_aff
        + cpod_col * w_podaff
    )
    return fits_idle, fits_rel, static_ok & room & port_ok, score


@partial(
    jax.jit,
    static_argnames=("enable_drf", "enable_proportion", "blocks", "seg_budget"),
)
def _class_step(
    ca: dict,
    state: ClassSolveState,
    enable_drf: bool,
    enable_proportion: bool,
    blocks: int,
    seg_budget: int,
) -> ClassSolveState:
    """One kernel segment at class granularity: runs until every job is
    retired, a host-only task pauses it, the slot bucket overflows, or
    ``seg_budget`` iterations elapse. The budget bounds split-driven
    fragmentation: each bind to a fresh node splits a singleton, so a
    long segment degenerates toward node granularity — capping the
    segment forces a host re-pack that re-merges equivalent occupied
    nodes and keeps the slot axis small for the whole solve. The budget
    is ``cpad // 2 <= cpad - C`` free slots, so in-segment overflow
    cannot fire (the re-bucket path stays as a backstop). Mirrors
    ``solve_allocate_step`` body-for-body; the only structural
    additions are the multiplicity/tie-break selection and the
    split-on-assign scatter."""
    T = ca["task_req"].shape[0]
    J = ca["job_min"].shape[0]
    Q = ca["queue_rank"].shape[0]
    C = state.cmult.shape[0]
    N = ca["members_sorted"].shape[0]

    task_req = ca["task_req"]
    task_res = ca["task_res"]
    task_gid = ca["task_gid"]
    task_has_sc = ca["task_has_sc"]
    task_res_has_sc = ca["task_res_has_sc"]
    task_ports = ca["task_ports"]
    task_host_only = ca["task_host_only"]
    compat = ca["compat"]
    aff_sc = ca["aff_sc"]
    members_sorted = ca["members_sorted"]
    job_end = ca["job_end"]
    job_min = ca["job_min"]
    job_queue = ca["job_queue"]
    eps = ca["eps"]
    fdtype = task_req.dtype
    w_least = jnp.asarray(ca["w_least"], fdtype)
    w_balanced = jnp.asarray(ca["w_balanced"], fdtype)
    w_aff = jnp.asarray(ca["w_aff"], fdtype)
    w_podaff = jnp.asarray(ca["w_podaff"], fdtype)

    max_iter = jnp.int32(T + J + Q + 1) + jnp.sum(task_host_only).astype(jnp.int32)

    state = state._replace(
        paused_at=jnp.int32(-1),
        overflow=jnp.asarray(False),
        seg_it=jnp.int32(0),
    )

    def cond(s: ClassSolveState):
        return (
            ((s.cur >= 0) | jnp.any(s.job_active))
            & (s.it < max_iter)
            & (s.seg_it < seg_budget)
            & (s.paused_at < 0)
            & ~s.overflow
        )

    def body(s: ClassSolveState) -> ClassSolveState:
        need_sel = s.cur < 0
        qsel, q_any, overused, jsel, j_any = select_queue_job(
            ca, s, enable_drf, enable_proportion
        )
        drop_q = need_sel & q_any & overused
        sel_ok = q_any & ~overused & j_any
        cur = jnp.where(need_sel, jnp.where(sel_ok, jsel, -1), s.cur)

        job_active = jnp.where(
            drop_q, s.job_active & (job_queue != qsel), s.job_active
        )
        q_dropped = s.q_dropped.at[qsel].set(drop_q | s.q_dropped[qsel])

        cur_c = jnp.maximum(cur, 0)
        t = s.ptr[cur_c]
        t_any = (cur >= 0) & (t < job_end[cur_c])
        t = jnp.minimum(t, T - 1)
        drop = (cur >= 0) & ~t_any
        pause = t_any & task_host_only[t]
        proc = t_any & ~pause

        # -- fit + score over the slot axis (flat, or blocked for the
        # mesh-Pallas rung: identical elementwise ops per block) ------------
        req = task_req[t]
        res = task_res[t]
        tports = task_ports[t]
        t_has = task_has_sc[t]
        compat_t = compat[task_gid[t]]
        aff_t = aff_sc[task_gid[t]]
        cpod_col = s.cpod_sc[task_gid[t]]
        if blocks > 1:
            cb_n = C // blocks

            def blk(ci, cr, cu, cn, cp, al, ok, mx, ih, rh, gd, pc):
                return _fit_score_block(
                    ci, cr, cu, cn, cp, al, ok, mx, ih, rh, gd, pc,
                    req, res, tports, t_has, eps, compat_t, aff_t,
                    w_least, w_balanced, w_aff, w_podaff, fdtype,
                )

            fi, fr, so, sc = jax.vmap(blk)(
                s.cidle.reshape(blocks, cb_n, -1),
                s.crel.reshape(blocks, cb_n, -1),
                s.cused.reshape(blocks, cb_n, -1),
                s.cntasks.reshape(blocks, cb_n),
                s.cnports.reshape(blocks, cb_n, -1),
                s.calloc.reshape(blocks, cb_n, -1),
                s.cok.reshape(blocks, cb_n),
                s.cmax_tasks.reshape(blocks, cb_n),
                s.cidle_has_sc.reshape(blocks, cb_n),
                s.crel_has_sc.reshape(blocks, cb_n),
                s.cgid.reshape(blocks, cb_n),
                cpod_col.reshape(blocks, cb_n),
            )
            fits_idle = fi.reshape(C)
            fits_rel = fr.reshape(C)
            hard_ok = so.reshape(C)
            score = sc.reshape(C)
        else:
            fits_idle, fits_rel, hard_ok, score = _fit_score_block(
                s.cidle, s.crel, s.cused, s.cntasks, s.cnports,
                s.calloc, s.cok, s.cmax_tasks, s.cidle_has_sc,
                s.crel_has_sc, s.cgid, cpod_col,
                req, res, tports, t_has, eps, compat_t, aff_t,
                w_least, w_balanced, w_aff, w_podaff, fdtype,
            )
        cand = (s.cmult > 0) & hard_ok & (fits_idle | fits_rel)
        any_cand = jnp.any(cand)
        abandon = proc & ~any_cand

        # -- selection: max score, then lowest member node row — exactly the
        # uncompressed argmax's first-row tie-break, because every member of
        # a slot shares the score and ctie is the slot's lowest row ---------
        cb, _ = _lex_argmin(cand, -score, s.ctie)
        cb = cb.astype(jnp.int32)

        # -- split-on-assign ------------------------------------------------
        ns_raw = proc & any_cand & (s.cmult[cb] > 1)
        ovf = ns_raw & (s.free_ptr >= C)
        proc = proc & ~ovf
        assign = proc & any_cand
        ns = assign & (s.cmult[cb] > 1)

        do_alloc = assign & fits_idle[cb]
        do_pipe = assign & ~fits_idle[cb]
        nb_node = s.ctie[cb]  # the concrete node this assignment consumes

        f = jnp.minimum(s.free_ptr, C - 1)
        zero_row = jnp.zeros_like(res)
        new_idle = s.cidle[cb] + jnp.where(do_alloc, -res, zero_row)
        new_rel = s.crel[cb] + jnp.where(do_pipe, -res, zero_row)
        new_used = s.cused[cb] + jnp.where(assign, res, zero_row)
        new_ntasks = s.cntasks[cb] + jnp.where(assign, 1, 0)
        new_ports = s.cnports[cb] | (tports & assign)

        inplace = assign & ~ns  # mult==1: the slot IS the node

        def upd(arr, new_row):
            arr = arr.at[cb].set(jnp.where(inplace, new_row, arr[cb]))
            return arr.at[f].set(jnp.where(ns, new_row, arr[f]))

        cidle = upd(s.cidle, new_idle)
        crel = upd(s.crel, new_rel)
        cused = upd(s.cused, new_used)
        cntasks = upd(s.cntasks, new_ntasks)
        cnports = upd(s.cnports, new_ports)
        # child inherits the parent's statics
        calloc = s.calloc.at[f].set(jnp.where(ns, s.calloc[cb], s.calloc[f]))
        cok = s.cok.at[f].set(jnp.where(ns, s.cok[cb], s.cok[f]))
        cmax_tasks = s.cmax_tasks.at[f].set(
            jnp.where(ns, s.cmax_tasks[cb], s.cmax_tasks[f])
        )
        cidle_has_sc = s.cidle_has_sc.at[f].set(
            jnp.where(ns, s.cidle_has_sc[cb], s.cidle_has_sc[f])
        )
        crel_has_sc = s.crel_has_sc.at[f].set(
            jnp.where(ns, s.crel_has_sc[cb], s.crel_has_sc[f])
        )
        cgid = s.cgid.at[f].set(jnp.where(ns, s.cgid[cb], s.cgid[f]))
        cpod_sc = s.cpod_sc.at[:, f].set(
            jnp.where(ns, s.cpod_sc[:, cb], s.cpod_sc[:, f])
        )
        # the consumed member becomes the child's sole member; the parent
        # advances its cursor to the next-lowest remaining member
        cmult = s.cmult.at[cb].add(jnp.where(ns, -1, 0))
        cmult = cmult.at[f].set(jnp.where(ns, 1, cmult[f]))
        next_tie = members_sorted[jnp.minimum(s.cpos[cb] + 1, N - 1)]
        ctie = s.ctie.at[cb].set(jnp.where(ns, next_tie, s.ctie[cb]))
        ctie = ctie.at[f].set(jnp.where(ns, nb_node, ctie[f]))
        cpos = s.cpos.at[cb].add(jnp.where(ns, 1, 0))
        free_ptr = s.free_ptr + ns.astype(jnp.int32)

        # -- bookkeeping, verbatim from the uncompressed kernel -------------
        ready_cnt = s.ready_cnt.at[cur_c].add(jnp.where(do_alloc, 1, 0))
        ptr = s.ptr.at[cur_c].add(jnp.where(proc, 1, 0))
        assigned_node = s.assigned_node.at[t].set(
            jnp.where(assign, nb_node, s.assigned_node[t])
        )
        kind = jnp.where(
            do_alloc, KIND_ALLOCATED, jnp.where(do_pipe, KIND_PIPELINED, 0)
        )
        assigned_kind = s.assigned_kind.at[t].set(
            jnp.where(assign, kind, s.assigned_kind[t])
        )
        assign_pos = s.assign_pos.at[t].set(
            jnp.where(assign, s.step, s.assign_pos[t])
        )

        add_row = jnp.where(assign, task_res[t], zero_row)
        job_alloc = s.job_alloc.at[cur_c].add(add_row) if enable_drf else s.job_alloc
        if enable_proportion:
            qcur = job_queue[cur_c]
            q_alloc = s.q_alloc.at[qcur].add(add_row)
            q_alloc_has_sc = s.q_alloc_has_sc.at[qcur].set(
                s.q_alloc_has_sc[qcur] | (assign & task_res_has_sc[t])
            )
        else:
            q_alloc = s.q_alloc
            q_alloc_has_sc = s.q_alloc_has_sc

        job_active = job_active.at[cur_c].set(
            jnp.where(drop | abandon, False, job_active[cur_c])
        )
        ready_now = ready_cnt[cur_c] >= job_min[cur_c]
        cur_next = jnp.where(drop | abandon | (proc & ready_now), -1, cur)

        return ClassSolveState(
            it=s.it + jnp.where(ovf, 0, 1),
            step=s.step + assign.astype(jnp.int32),
            cur=jnp.where(ovf, s.cur, cur_next),
            ptr=ptr,
            assigned_node=assigned_node,
            assigned_kind=assigned_kind,
            assign_pos=assign_pos,
            cidle=cidle,
            crel=crel,
            cused=cused,
            cntasks=cntasks,
            cnports=cnports,
            calloc=calloc,
            cok=cok,
            cmax_tasks=cmax_tasks,
            cidle_has_sc=cidle_has_sc,
            crel_has_sc=crel_has_sc,
            cgid=cgid,
            cpod_sc=cpod_sc,
            cmult=cmult,
            ctie=ctie,
            cpos=cpos,
            free_ptr=free_ptr,
            overflow=ovf,
            seg_it=s.seg_it + jnp.where(ovf, 0, 1),
            ready_cnt=ready_cnt,
            job_active=jnp.where(ovf, s.job_active, job_active),
            q_dropped=jnp.where(ovf, s.q_dropped, q_dropped),
            job_alloc=job_alloc,
            q_alloc=q_alloc,
            q_alloc_has_sc=q_alloc_has_sc,
            paused_at=jnp.where(pause, t, jnp.int32(-1)),
        )

    return lax.while_loop(cond, body, state)


# -- the wrapping solver ------------------------------------------------------

_DYNAMIC_SLABS = ("idle", "rel", "used", "ntasks", "nports")

_CA_KEYS = (
    "task_req", "task_res", "task_gid", "task_has_sc", "task_res_has_sc",
    "task_ports", "task_host_only", "job_end", "job_min", "job_queue",
    "job_prio", "job_rank", "queue_rank", "q_deserved", "q_dims",
    "drf_total", "drf_dims", "compat", "aff_sc", "eps",
    "w_least", "w_balanced", "w_aff", "w_podaff",
)


class ClassCompressedSolver:
    """Drop-in ``solve_fn`` layer: takes and returns node-space
    ``SolveState`` (numpy leaves), compressing on entry and expanding on
    exit, so the action's pause loop, ``_host_step``, ``result_of`` and
    explain all run unchanged. Regrouping happens only at segment
    boundaries; within a segment the kernel splits incrementally."""

    def __init__(
        self, table: ClassTable, arrays: dict, enable_drf: bool,
        enable_proportion: bool, dtype, mesh=None, arena=None,
    ) -> None:
        self.table = table
        self.arrays = arrays
        self.enable_drf = bool(enable_drf)
        self.enable_proportion = bool(enable_proportion)
        self.dtype = dtype
        self.mesh = mesh
        self.arena = arena
        self.blocks = 1
        self.rung = "xla"
        if mesh is not None:
            mmode = os.environ.get("KBT_MESH_PALLAS", "auto").strip().lower() or "auto"
            if mmode not in ("0", "off"):
                # the blocked rung: the fit/score block runs per class
                # block (the jnp twin of the mesh-Pallas formulation)
                self.blocks = int(mesh.devices.size)
                self.rung = "mesh_pallas"
            else:
                self.rung = "sharded_xla"
        self._sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # replicated class table over the mesh: the slot axis is
            # tiny, so every device carries the full table and the
            # node-sharded structures stay host-side (per-shard member
            # lists drive the expansion below)
            self._sharding = NamedSharding(mesh, PartitionSpec())
        # per-solve stats (the metrics surface)
        self.class_count = 0
        self.classes_valid = 0
        self.compression_ratio = 0.0
        self.splits = 0
        self.remerges = 0
        self.rekeys = 0
        self.segments = 0
        self.group_s = 0.0
        self.kernel_s = 0.0
        self.c_pad = 0
        self.shard_members: list[np.ndarray] | None = None
        self._slot_of: np.ndarray | None = None
        self._entry_C = 0

    # -- node-space <-> class-space ---------------------------------------

    def _init_node_state(self):
        """Numpy twin of ``kernels.init_state`` (the fresh-solve entry)."""
        a = self.arrays
        T = a["task_req"].shape[0]
        J = np.asarray(a["job_min"]).shape[0]
        Q = np.asarray(a["queue_rank"]).shape[0]
        R = a["task_req"].shape[1]
        fdtype = np.asarray(a["task_req"]).dtype
        return SolveState(
            it=np.int32(0),
            step=np.int32(0),
            cur=np.int32(-1),
            ptr=np.asarray(a["job_start"], np.int32).copy(),
            assigned_node=np.full(T, -1, np.int32),
            assigned_kind=np.zeros(T, np.int32),
            assign_pos=np.full(T, -1, np.int32),
            idle=np.asarray(a["node_idle"]).copy(),
            rel=np.asarray(a["node_rel"]).copy(),
            used=np.asarray(a["node_used"]).copy(),
            ntasks=np.asarray(a["node_ntasks"]).copy(),
            nports=np.asarray(a["node_ports"]).copy(),
            ready_cnt=np.asarray(a["job_ready0"], np.int32).copy(),
            job_active=np.asarray(a["job_valid"], bool).copy(),
            q_dropped=np.zeros(Q, bool),
            job_alloc=(
                np.asarray(a["job_alloc0"]).copy()
                if self.enable_drf
                else np.zeros((J, R), fdtype)
            ),
            q_alloc=(
                np.asarray(a["q_alloc0"]).copy()
                if self.enable_proportion
                else np.zeros((Q, R), fdtype)
            ),
            q_alloc_has_sc=(
                np.asarray(a["q_alloc_has_sc0"], bool).copy()
                if self.enable_proportion
                else np.zeros(Q, bool)
            ),
            paused_at=np.int32(-1),
        )

    def _pack(self, st) -> ClassSolveState:
        """Regroup the current node-space state into slots and build the
        kernel state. Runs at segment boundaries only."""
        a = self.arrays
        t0 = time.perf_counter()
        class0_of, rekeys = self.table.refresh_static(a)
        self.rekeys += rekeys
        idle = np.asarray(st.idle)
        rel = np.asarray(st.rel)
        used = np.asarray(st.used)
        ntasks = np.asarray(st.ntasks)
        nports = np.asarray(st.nports)
        pod_sc = np.asarray(a["pod_sc"])
        N = idle.shape[0]
        first, inv = dedup_rows(
            [
                class0_of.astype(np.int32),
                idle, rel, used,
                ntasks.astype(np.int32),
                nports,
                np.ascontiguousarray(pod_sc.T),
            ]
        )
        C = int(len(first))
        slot_of = inv.astype(np.int32)
        counts = np.bincount(slot_of, minlength=C)
        self.remerges += self.table.note_regroup(slot_of, counts)
        order = np.argsort(slot_of, kind="stable").astype(np.int32)
        off = np.zeros(C, counts.dtype)
        np.cumsum(counts[:-1], out=off[1:])
        rep = order[off]  # lowest member row per slot (stable sort)

        cpad = min(
            _pow2(max(2 * C, C + 64, self.table.sticky_cpad)), _pow2(N)
        )
        # N-scaled floor: the segment budget is cpad // 2, so a large
        # fleet gets long-enough segments that the host re-pack between
        # them stays a rounding error. Capped at 1024: past that the
        # slot-axis cost per iteration outweighs the amortized re-pack
        # (measured on the 1-core CPU host — the re-pack is ~9 ms at
        # 40k nodes, the kernel pays ~0.03 us per slot row per step)
        cpad = max(cpad, _pow2(C), min(_pow2(N) // 16, 1024))
        self.table.sticky_cpad = max(self.table.sticky_cpad, cpad)
        cpad = self.table.sticky_cpad
        if self.segments == 0:
            self.class_count = C
            valid = np.asarray(a["node_valid"], bool)
            self.classes_valid = int(valid[rep].sum())
            n_valid = int(valid.sum())
            self.compression_ratio = (
                float(n_valid) / float(self.classes_valid)
                if self.classes_valid
                else 1.0
            )
        self.c_pad = int(cpad)
        self._slot_of = slot_of
        self._entry_C = C
        if self.mesh is not None:
            # per-shard membership: contiguous node-axis chunks, the same
            # layout the GSPMD rung shards its node arrays by
            shards = int(self.mesh.devices.size)
            bounds = np.linspace(0, N, shards + 1).astype(np.int64)
            self.shard_members = [
                np.arange(bounds[i], bounds[i + 1], dtype=np.int64)
                for i in range(shards)
            ]

        def pad1(x, fill=0):
            out = np.full((cpad,) + x.shape[1:], fill, x.dtype)
            out[:C] = x
            return out

        fdtype = idle.dtype
        node_ok = np.asarray(a["node_ok"], bool) & np.asarray(a["node_valid"], bool)
        imax = np.iinfo(np.int32).max
        cs = ClassSolveState(
            it=np.int32(st.it),
            step=np.int32(st.step),
            cur=np.int32(st.cur),
            ptr=np.asarray(st.ptr, np.int32),
            assigned_node=np.asarray(st.assigned_node, np.int32),
            assigned_kind=np.asarray(st.assigned_kind, np.int32),
            assign_pos=np.asarray(st.assign_pos, np.int32),
            cidle=pad1(idle[rep]),
            crel=pad1(rel[rep]),
            cused=pad1(used[rep]),
            cntasks=pad1(ntasks[rep]),
            cnports=pad1(nports[rep]),
            calloc=pad1(np.asarray(a["node_alloc"])[rep]),
            cok=pad1(node_ok[rep]),
            cmax_tasks=pad1(np.asarray(a["node_max_tasks"])[rep]),
            cidle_has_sc=pad1(np.asarray(a["node_idle_has_sc"], bool)[rep]),
            crel_has_sc=pad1(np.asarray(a["node_rel_has_sc"], bool)[rep]),
            cgid=pad1(np.asarray(a["node_gid"], np.int32)[rep]),
            cpod_sc=np.ascontiguousarray(
                np.pad(pod_sc[:, rep], ((0, 0), (0, cpad - C))).astype(fdtype)
            ),
            cmult=pad1(counts.astype(np.int32)),
            ctie=pad1(rep.astype(np.int32), fill=imax),
            cpos=pad1(off.astype(np.int32)),
            free_ptr=np.int32(C),
            overflow=np.asarray(False),
            seg_it=np.int32(0),
            ready_cnt=np.asarray(st.ready_cnt, np.int32),
            job_active=np.asarray(st.job_active, bool),
            q_dropped=np.asarray(st.q_dropped, bool),
            job_alloc=np.asarray(st.job_alloc),
            q_alloc=np.asarray(st.q_alloc),
            q_alloc_has_sc=np.asarray(st.q_alloc_has_sc, bool),
            paused_at=np.int32(st.paused_at),
        )
        self._members_sorted = order
        self.group_s += time.perf_counter() - t0
        return cs

    def _ca(self) -> dict:
        a = self.arrays
        ca = {k: a[k] for k in _CA_KEYS}
        members = self._members_sorted
        if self.arena is not None:
            try:
                members = self.arena.upload("class_members", members, mesh=self.mesh)
            except Exception:  # noqa: BLE001 - arena loss must not fail the solve
                log.exception("class member slab upload failed; passing host array")
        ca["members_sorted"] = members
        return ca

    def _rebucket(self, cs: ClassSolveState) -> ClassSolveState:
        """Slot bucket exhausted mid-solve: grow to the next power of two
        (bounded by the node bucket — slots can never outnumber nodes)
        and resume. A recompile at the new shape is expected and cold;
        the sticky bucket keeps later cycles at the grown size."""
        old = int(cs.cmult.shape[0])
        N = int(self._slot_of.shape[0])
        new = min(_pow2(old * 2), _pow2(N))
        if new <= old:
            raise RuntimeError(
                f"class slot bucket cannot grow past {old} (nodes={N})"
            )
        self.table.sticky_cpad = max(self.table.sticky_cpad, new)
        log.warning(
            "class slot bucket overflow: re-bucketing %d -> %d slots", old, new
        )

        def grow(x, fill=0):
            x = np.asarray(x)
            out = np.full((new,) + x.shape[1:], fill, x.dtype)
            out[:old] = x
            return out

        imax = np.iinfo(np.int32).max
        return cs._replace(
            cidle=grow(cs.cidle),
            crel=grow(cs.crel),
            cused=grow(cs.cused),
            cntasks=grow(cs.cntasks),
            cnports=grow(cs.cnports),
            calloc=grow(cs.calloc),
            cok=grow(cs.cok),
            cmax_tasks=grow(cs.cmax_tasks),
            cidle_has_sc=grow(cs.cidle_has_sc),
            crel_has_sc=grow(cs.crel_has_sc),
            cgid=grow(cs.cgid),
            cpod_sc=np.ascontiguousarray(
                np.pad(np.asarray(cs.cpod_sc), ((0, 0), (0, new - old)))
            ),
            cmult=grow(cs.cmult),
            ctie=grow(cs.ctie, fill=imax),
            cpos=grow(cs.cpos),
            overflow=np.asarray(False),
        )

    def _expand(self, cs: ClassSolveState):
        """Class state back to a node-space ``SolveState`` view: every
        node reads its slot's row (children first override their split
        origin). With a mesh the gather runs per member shard — the
        node-space view is assembled shard by shard, the class table
        itself staying replicated."""
        slot_of = self._slot_of.copy()
        fp = int(cs.free_ptr)
        if fp > self._entry_C:
            child = np.arange(self._entry_C, fp)
            slot_of[np.asarray(cs.ctie)[child]] = child
        self.splits += fp - self._entry_C
        self.table.splits_total += fp - self._entry_C
        self.table.note_end(slot_of)

        def gather(arr):
            arr = np.asarray(arr)
            if self.shard_members is None:
                return arr[slot_of].copy()
            return np.concatenate(
                [arr[slot_of[m]] for m in self.shard_members], axis=0
            )

        return SolveState(
            it=np.int32(cs.it),
            step=np.int32(cs.step),
            cur=np.int32(cs.cur),
            ptr=np.asarray(cs.ptr, np.int32).copy(),
            assigned_node=np.asarray(cs.assigned_node, np.int32).copy(),
            assigned_kind=np.asarray(cs.assigned_kind, np.int32).copy(),
            assign_pos=np.asarray(cs.assign_pos, np.int32).copy(),
            idle=gather(cs.cidle),
            rel=gather(cs.crel),
            used=gather(cs.cused),
            ntasks=gather(cs.cntasks),
            nports=gather(cs.cnports),
            ready_cnt=np.asarray(cs.ready_cnt, np.int32).copy(),
            job_active=np.asarray(cs.job_active, bool).copy(),
            q_dropped=np.asarray(cs.q_dropped, bool).copy(),
            job_alloc=np.asarray(cs.job_alloc).copy(),
            q_alloc=np.asarray(cs.q_alloc).copy(),
            q_alloc_has_sc=np.asarray(cs.q_alloc_has_sc, bool).copy(),
            paused_at=np.int32(cs.paused_at),
        )

    # -- the solve_fn surface ----------------------------------------------

    def solve(self, st):
        if st is None:
            st = self._init_node_state()
        a = self.arrays
        max_iter = (
            int(a["task_req"].shape[0])
            + int(a["job_min"].shape[0])
            + int(a["queue_rank"].shape[0])
            + 1
            + int(np.asarray(a["task_host_only"]).sum())
        )
        while True:
            cs = self._pack(st)
            ca = self._ca()
            seg_budget = max(int(cs.cmult.shape[0]) // 2, 1)
            if self._sharding is not None:
                # replicated class table + replicated (task/job) inputs:
                # the slot axis is small, so every device carries the
                # full table
                ca = jax.device_put(ca, self._sharding)
                cs = jax.device_put(cs, self._sharding)
            self.segments += 1
            t0 = time.perf_counter()
            while True:
                out = _class_step(
                    ca, cs, self.enable_drf, self.enable_proportion,
                    self.blocks, seg_budget,
                )
                out = jax.tree_util.tree_map(np.asarray, out)
                if bool(out.overflow):
                    self.kernel_s += time.perf_counter() - t0
                    cs = self._rebucket(out)
                    seg_budget = max(int(cs.cmult.shape[0]) // 2, 1)
                    if self._sharding is not None:
                        cs = jax.device_put(cs, self._sharding)
                    t0 = time.perf_counter()
                    continue
                break
            self.kernel_s += time.perf_counter() - t0
            st = self._expand(out)
            if (
                int(out.paused_at) >= 0
                or int(out.it) >= max_iter
                or (int(out.cur) < 0 and not bool(np.any(out.job_active)))
            ):
                return st
            # segment budget exhausted mid-solve: loop back through
            # ``_pack`` so equivalent occupied nodes re-merge — the
            # split machinery fragments within a segment, the re-pack
            # collapses the fragments, and the slot axis stays small
            # for the whole solve instead of degenerating toward node
            # granularity

    def stats(self) -> dict:
        return {
            "class_count": int(self.class_count),
            "classes_valid": int(self.classes_valid),
            "compression_ratio": round(float(self.compression_ratio), 4),
            "splits": int(self.splits),
            "remerges": int(self.remerges),
            "rekeys": int(self.rekeys),
            "segments": int(self.segments),
            "c_pad": int(self.c_pad),
            "group_s": round(self.group_s, 6),
            "kernel_s": round(self.kernel_s, 6),
            "rung": self.rung,
        }


def wrap_solver(
    action, inner, arrays: dict, enable_drf: bool, enable_proportion: bool,
    dtype, mesh=None,
):
    """Wrap a tier's ``solve_fn`` with the class-compressed layer. Any
    failure — including the ``solve.class_table`` fault point standing
    in for a poisoned/stale table — degrades the call to the wrapped
    uncompressed tier loudly: the cycle completes, parity holds (the
    solver is functional on its input state), and the degrade is
    metered."""
    from kube_batch_tpu import faults, metrics

    table = getattr(action, "_class_table", None)
    if table is None:
        table = ClassTable()
        action._class_table = table
    solver = ClassCompressedSolver(
        table, arrays, enable_drf, enable_proportion, dtype, mesh=mesh,
        arena=getattr(action, "_arena", None),
    )

    def solve_fn(st):
        try:
            if faults.should_fire("solve.class_table"):
                raise faults.FaultInjected("solve.class_table")
            out = solver.solve(st)
        except Exception:
            log.exception(
                "class-compressed solve failed; degrading to the "
                "uncompressed %s tier for this segment",
                "mesh" if mesh is not None else "single-chip",
            )
            metrics.register_degraded_cycle("class_solve", "class_table")
            action.last_class_stats = None
            return inner(st)
        action.last_solver_tier = "class_" + solver.rung
        stats = solver.stats()
        action.last_class_stats = stats
        metrics.set_class_solve_classes(stats["class_count"])
        metrics.set_class_solve_compression_ratio(stats["compression_ratio"])
        delta = (stats["splits"] + stats["rekeys"]) - getattr(
            solver, "_metered_splits", 0
        )
        if delta > 0:
            metrics.register_class_table_splits(delta)
        solver._metered_splits = stats["splits"] + stats["rekeys"]
        return out

    return solve_fn


# -- seeded self-check --------------------------------------------------------

_SMOKE_TIERS = """
actions: "allocate"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: predicates
  - name: nodeorder
"""


def _smoke_world(bound=None, arrivals=0, seed=7):
    """Heterogeneous node pools with heavy intra-pool duplication: four
    pool shapes x 18 identical nodes, a few pre-occupied residents (so
    classes are plural from the start), selector-confined and free gang
    jobs. ``bound`` (pod name -> node name) materializes a previous
    cycle's placements as running residents; ``arrivals`` appends fresh
    gangs so the next cycle has work — together they exercise
    split-then-re-merge across cycles."""
    import random

    from kube_batch_tpu.apis.types import PodPhase
    from kube_batch_tpu.testing import (
        build_cluster,
        build_node,
        build_pod,
        build_pod_group,
        build_queue,
        build_resource_list,
    )

    rng = random.Random(seed)
    pools = {
        "small": build_resource_list(cpu=8, memory="16Gi", pods=32),
        "medium": build_resource_list(cpu=16, memory="32Gi", pods=64),
        "large": build_resource_list(cpu=32, memory="65536Mi", pods=110),
        "tainted": build_resource_list(cpu=16, memory="32Gi", pods=64),
    }
    nodes = []
    for pool, alloc in pools.items():
        for i in range(18):
            nodes.append(
                build_node(
                    f"{pool}-{i:03d}", dict(alloc), labels={"pool": pool}
                )
            )
    bound = dict(bound or {})
    pods, pgs = [], []
    for j in range(12):
        name = f"gang-{j:03d}"
        members = rng.choice([3, 4, 6])
        pgs.append(build_pod_group(name, min_member=members))
        pool = rng.choice([None, "small", "medium", "large"])
        cpu = rng.choice(["500m", "1", "2"])
        for m in range(members):
            pod = build_pod(
                name=f"{name}-t{m}",
                group_name=name,
                req=build_resource_list(cpu=cpu, memory="1Gi"),
                node_selector={"pool": pool} if pool else None,
            )
            host = bound.pop(f"default/{name}-t{m}", None)
            if host is not None:
                pod.node_name = host
                pod.phase = PodPhase.RUNNING
            pods.append(pod)
    for j in range(arrivals):
        name = f"arrival-{j:03d}"
        pgs.append(build_pod_group(name, min_member=2))
        for m in range(2):
            pods.append(
                build_pod(
                    name=f"{name}-t{m}",
                    group_name=name,
                    req=build_resource_list(cpu="1", memory="2Gi"),
                )
            )
    # residents diversify the initial classes inside one pool
    for i in range(4):
        pods.append(
            build_pod(
                name=f"resident-{i}",
                node_name=f"medium-{i:03d}",
                phase=PodPhase.RUNNING,
                req=build_resource_list(cpu=2, memory="4Gi"),
            )
        )
    return build_cluster(pods, nodes, pgs, [build_queue("default")])


def _smoke_run(action, cluster):
    from kube_batch_tpu.conf import parse_scheduler_conf
    from kube_batch_tpu.framework import close_session, open_session
    from kube_batch_tpu.testing import FakeCache

    tiers = parse_scheduler_conf(_SMOKE_TIERS).tiers
    cache = FakeCache(cluster)
    ssn = open_session(cache, tiers)
    try:
        action.execute(ssn)
    finally:
        close_session(ssn)
    return dict(cache.binder.binds)


def smoke() -> dict:
    """Seeded self-check (verify gate ``class_solve_smoke`` + image
    build): heterogeneous-pool world solved serial / uncompressed /
    compressed with bind parity, across two cycles so in-solve splits
    and re-merges (at the segment re-packs and across cycles) both
    demonstrably fire."""
    from kube_batch_tpu.actions.allocate import AllocateAction
    from kube_batch_tpu.actions.xla_allocate import XlaAllocateAction

    saved = {}
    for env, value in (("KBT_MIN_DEVICE_PAIRS", "0"), (ENV, "0")):
        saved[env] = os.environ.get(env)
        os.environ[env] = value
    try:
        serial_binds = _smoke_run(AllocateAction(), _smoke_world())
        plain = XlaAllocateAction()
        plain_binds = _smoke_run(plain, _smoke_world())
        os.environ[ENV] = "1"
        comp = XlaAllocateAction()
        comp_binds = _smoke_run(comp, _smoke_world())
        stats1 = dict(comp.last_class_stats or {})
        tier1 = comp.last_solver_tier

        # cycle 2: cycle-1 placements become running residents, fresh
        # gangs arrive; identical nodes that split in cycle 1 re-merge
        world2 = lambda: _smoke_world(bound=comp_binds, arrivals=6)  # noqa: E731
        comp_binds2 = _smoke_run(comp, world2())
        stats2 = dict(comp.last_class_stats or {})
        os.environ[ENV] = "0"
        plain_binds2 = _smoke_run(plain, world2())
    finally:
        for env, value in saved.items():
            if value is None:
                os.environ.pop(env, None)
            else:
                os.environ[env] = value

    n_nodes = 4 * 18
    parity1 = serial_binds == plain_binds == comp_binds
    parity2 = plain_binds2 == comp_binds2
    # re-merges fire at the segment re-packs inside a solve (bound-alike
    # nodes collapse back together) and/or across cycles — either is the
    # mechanism working
    remerges = stats1.get("remerges", 0) + stats2.get("remerges", 0)
    ok = bool(
        parity1
        and parity2
        and tier1.startswith("class_")
        and stats1.get("class_count", n_nodes) < n_nodes
        and stats1.get("splits", 0) > 0
        and remerges > 0
    )
    return {
        "ok": ok,
        "binds": len(comp_binds),
        "binds_cycle2": len(comp_binds2),
        "parity_cycle1": parity1,
        "parity_cycle2": parity2,
        "tier": tier1,
        "class_count": stats1.get("class_count"),
        "compression_ratio": stats1.get("compression_ratio"),
        "splits": stats1.get("splits"),
        "remerges": remerges,
        "remerges_cycle2": stats2.get("remerges"),
        "cycle2": stats2,
    }


def main(argv=None) -> int:
    import argparse
    import json

    parser = argparse.ArgumentParser(
        description="class-compressed solve smoke: heterogeneous pools, "
        "serial/uncompressed/compressed bind parity, split + re-merge"
    )
    parser.add_argument("--json", action="store_true", help="print the result as JSON")
    args = parser.parse_args(argv)
    result = smoke()
    if args.json:
        print(json.dumps(result, sort_keys=True, default=str))
    else:
        status = "ok" if result["ok"] else "FAILED"
        print(
            f"class_solve smoke: {status} ({result['binds']} binds, "
            f"classes={result['class_count']}, "
            f"ratio={result['compression_ratio']}, "
            f"splits={result['splits']}, "
            f"remerges={result['remerges']})"
        )
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    # re-enter through the canonical module: `python -m` executes this
    # file as __main__, whose jitted kernel and table singletons would
    # otherwise be different objects than the ones the action imports
    from kube_batch_tpu.ops.class_solve import main as _canonical_main

    raise SystemExit(_canonical_main())
