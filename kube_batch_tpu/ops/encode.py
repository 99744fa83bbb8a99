"""Snapshot -> struct-of-arrays tensor encoder (the TPU path's front end).

Encodes the session view (jobs/nodes/queues, reference
pkg/scheduler/api/cluster_info.go:22-26) into dense, padded, fixed-width
arrays that `kernels.solve_allocate` consumes in one jitted program:

- resource rows follow the `Resource.to_vector` contract
  ``[milli_cpu, memory, *scalar_slots]`` with the per-slot epsilon vector
  (api/resource_info.py);
- tasks are laid out **contiguously per job** in serial pop order
  (priority desc -> creation -> uid within the job;
  session_plugins.go:329-341), jobs in serial fallback order
  (creation -> uid), so the kernel pops a job's next task with one
  pointer increment instead of an O(T) masked argmin (`job_start` /
  `job_end` delimit each job's rows);
- the label-world predicates (node selector, required node affinity,
  taints/tolerations, cordon) and the preferred-node-affinity score are
  **deduplicated into (task-group x node-group) matrices**: tasks sharing
  a pod spec signature and nodes sharing a label/taint signature hit the
  same pure check functions (plugins/predicates.py, plugins/nodeorder.py)
  exactly once per group pair, then broadcast by integer gather on device.
  A 10k-task job is one group, so encoding is O(T + N + GT*GN), not
  O(T*N). Node signatures keep only the label keys actually referenced by
  pending tasks' selectors/affinity terms — a cluster whose nodes all
  carry unique labels (kubernetes.io/hostname) still collapses to a
  handful of groups (round-2 advisor finding);
- host ports become a small boolean incidence over the distinct ports
  pending tasks actually use, so conflicts with both residents and
  newly-assigned tasks are dynamic bitmask tests in the kernel;
- drf / proportion session state is lifted straight from the plugin
  instances (per-job allocated vectors + cluster totals, per-queue
  allocated / water-filled deserved + the Go nil-scalar-map parity bits)
  so the kernel's in-loop share updates start bit-identical to the serial
  plugins' event-handler state (drf.go:60-83, proportion.go:58-144);
- everything is padded to stable buckets — power-of-two for tasks/jobs/
  queues, multiples of 128 for the node axis (static shapes for XLA,
  SURVEY.md section 7 hard part (e)) with validity masks.

Tasks using required pod (anti-)affinity are flagged ``host_only``: that
predicate is pairwise-dynamic over resident pods (reference
predicates.go:187-199). The kernel pauses when such a task reaches the
head of its job and the action serial-steps it (segmented hybrid,
actions/xla_allocate).

Dtype: float64 arrays make the XLA path bit-identical to the serial
float64 Python path (the equivalence property tests run this way on CPU);
the TPU path uses float32, which is exact for milli-CPU-granular
cpu and MiB-granular memory (values stay on a 2^20-multiple grid well
inside the 24-bit mantissa).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from kube_batch_tpu.native import lib as _native

from kube_batch_tpu.api.job_info import JobInfo, TaskInfo
from kube_batch_tpu.api.node_info import NodeInfo
from kube_batch_tpu.api.queue_info import QueueInfo
from kube_batch_tpu.api.resource_info import Resource
from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.apis.types import PodGroupPhase
from kube_batch_tpu.plugins.predicates import (
    check_node_condition,
    check_node_selector,
    check_node_unschedulable,
    check_pressure,
    check_taints,
)


_warned_native_fallback: set[str] = set()


def _log_native_fallback(fn: str) -> None:
    """A native extractor failing is a defect signal (the slow path is
    correct, so it must not be silent) — log it once per function."""
    if fn not in _warned_native_fallback:
        _warned_native_fallback.add(fn)
        import logging

        logging.getLogger("kube_batch_tpu.ops.encode").warning(
            "native %s failed; using the numpy encode path", fn, exc_info=True
        )


def _bucket(n: int, minimum: int = 8) -> int:
    """Next power-of-two bucket >= max(n, 1) so XLA recompiles only on
    bucket crossings, not on every pod/node churn."""
    size = max(n, 1, minimum)
    return 1 << (size - 1).bit_length()


def _job_bucket(n: int, tasks: int) -> int:
    """Job-axis bucket: a power of two, and at least an eighth of the
    task bucket ``tasks`` (up to 256). Gangs carry several tasks each, so
    the two axes move together; a cycle whose gang count dips just under
    a power of two while its task count stays in its bucket keeps the
    kernel shape it already compiled instead of compiling a new pair."""
    return _bucket(n, max(4, min(tasks // 8, 256)))


def _node_bucket(n: int) -> int:
    """Node-axis bucket: next multiple of 128 (one TPU lane row).

    The node axis is the kernel's per-iteration payload — every loop
    step evaluates feasibility + scores over all N_pad lanes — so
    power-of-two padding is real wasted VPU work (5k nodes -> 8192 pad
    = +64%). Nodes churn rarely (tasks churn every cycle; they keep the
    coarse pow2 buckets), so 128-granular buckets recompile only when
    the fleet itself crosses a lane row, and any power-of-two mesh size
    up to 128 still divides the bucket for the GSPMD path."""
    return max((n + 127) // 128 * 128, 128)


_PLAIN_SIG = ((), "None", (), ())


def group_by_signature(items, sig_fn):
    """Dedup `items` by signature: returns (gids int32[len(items)],
    reps) with group ids in first-occurrence order — the (task-group ×
    node-group) machinery shared by the encoder and the vectorized
    backfill scan."""
    groups: dict = {}
    gids = np.zeros(len(items), np.int32)
    reps: list = []
    for i, item in enumerate(items):
        sig = sig_fn(item)
        gid = groups.get(sig)
        if gid is None:
            gid = groups[sig] = len(reps)
            reps.append(item)
        gids[i] = gid
    return gids, reps


def build_static_compat(t_reps, n_reps, aff_sc=None):
    """[GT, GN] static predicate verdicts per (task-group, node-group)
    pair — `static_pod_node_compat` over the reps; a node group without
    a Node object rejects everything (predicates.py). When `aff_sc` is
    given, the preferred-node-affinity score is filled in the same
    sweep (the encoder's fused form)."""
    from kube_batch_tpu.plugins.nodeorder import node_affinity_score

    compat = np.zeros((max(len(t_reps), 1), max(len(n_reps), 1)), bool)
    for gi, trep in enumerate(t_reps):
        for gj, nrep in enumerate(n_reps):
            if nrep.node is None:
                continue
            compat[gi, gj] = static_pod_node_compat(trep.pod, nrep.node)
            if aff_sc is not None:
                aff_sc[gi, gj] = node_affinity_score(trep, nrep)
    return compat


def static_pod_node_compat(pod, node) -> bool:
    """The task-static × node-static predicate subset — cordon, node
    selector/required node affinity, taints (predicates.py) — shared by
    the encoder's (task-group × node-group) compat matrix and the
    vectorized backfill scan, so a predicate-chain change lands in one
    place. The node-dynamic checks (condition/pressure/pod count/ports)
    and the pairwise pod-affinity check stay with their callers."""
    return (
        check_node_unschedulable(pod, node)
        and check_node_selector(pod, node)
        and check_taints(pod, node)
    )


def _task_signature(task: TaskInfo, with_labels: bool = False) -> tuple:
    """Dedup key for the (task-group x node-group) predicate matrices.
    ``with_labels`` extends the key with the pod's own labels — needed
    when any pod in the snapshot carries pod-affinity terms, because the
    symmetric InterPodAffinity score reads the *incoming* pod's labels
    (plugins/nodeorder.py interpod_affinity_scores)."""
    pod = task.pod
    if (
        not pod.node_selector
        and pod.affinity is None
        and not pod.tolerations
        and not (with_labels and pod.metadata.labels)
    ):
        return _PLAIN_SIG  # fast path: the overwhelmingly common pod shape
    return (
        tuple(sorted(pod.node_selector.items())),
        repr(pod.affinity),
        tuple(sorted(repr(t) for t in pod.tolerations)),
        tuple(sorted(pod.metadata.labels.items())) if with_labels else (),
    )


def _node_signature(node: NodeInfo, label_keys: frozenset[str]) -> tuple:
    n = node.node
    if n is None:
        return (None,)
    return (
        tuple(sorted((k, v) for k, v in n.labels.items() if k in label_keys)),
        tuple(sorted(repr(t) for t in n.taints)),
        bool(n.unschedulable),
    )


_EMPTY_PORTS: frozenset[int] = frozenset()


def _task_ports(task: TaskInfo) -> frozenset[int]:
    cs = task.pod.containers
    if len(cs) == 1 and not cs[0].ports:
        return _EMPTY_PORTS  # fast path: single portless container
    return frozenset(p for c in cs for p in c.ports)


@dataclass
class EncodedSnapshot:
    """The dense snapshot + the host-side metadata needed to decode the
    kernel's assignment back into session mutations."""

    scalar_names: tuple[str, ...]
    tasks: list[TaskInfo]  # row order (contiguous per job)
    jobs: list[JobInfo]  # row order
    queues: list[QueueInfo]  # row order
    node_names: list[str]  # row order (sorted, = utils.get_node_list order)
    n_tasks: int
    n_nodes: int
    n_jobs: int
    n_queues: int
    host_only: list[TaskInfo] = field(default_factory=list)
    arrays: dict = field(default_factory=dict)
    # pod-affinity terms present somewhere in the snapshot: interpod
    # scores are live (arrays["pod_sc"] nonzero-able, refreshed by the
    # action after each host-stepped placement)
    interpod_active: bool = False
    task_reps: list[TaskInfo] = field(default_factory=list)  # group reps

    @property
    def has_host_only(self) -> bool:
        return bool(self.host_only)


def compute_pod_sc(
    task_reps: Sequence[TaskInfo],
    nodes: dict[str, NodeInfo],
    node_names: Sequence[str],
    n_pad: int,
    dtype,
) -> np.ndarray:
    """[GT, N] InterPodAffinity score matrix — one normalized 0..10 row
    per task group against the *current* residents. Exact for every task
    whose group rep shares its labels + affinity spec (the group
    signature guarantees that when interpod is active)."""
    from kube_batch_tpu.plugins.nodeorder import interpod_affinity_scores

    out = np.zeros((max(len(task_reps), 1), n_pad), dtype)
    for gi, rep in enumerate(task_reps):
        scores = interpod_affinity_scores(rep, nodes)
        out[gi, : len(node_names)] = [scores[name] for name in node_names]
    return out


def _collect_task_scalar_names(tasks: Sequence[TaskInfo]) -> frozenset[str]:
    names: set[str] = set()
    for t in tasks:
        # guard: the overwhelmingly common scalar-less resource avoids
        # a set.update call per task (2 x 50k calls on the 50k path)
        if t.resreq.scalars:
            names.update(t.resreq.scalars)
        if t.init_resreq.scalars:
            names.update(t.init_resreq.scalars)
    return frozenset(names)


def _collect_node_scalar_names(nodes: Sequence[NodeInfo]) -> set[str]:
    names: set[str] = set()
    for n in nodes:
        if n.idle.scalars:
            names.update(n.idle.scalars)
        if n.releasing.scalars:
            names.update(n.releasing.scalars)
        if n.allocatable.scalars:
            names.update(n.allocatable.scalars)
        if n.used.scalars:
            names.update(n.used.scalars)
    return names


def _collect_scalar_names(
    tasks: Sequence[TaskInfo], nodes: Sequence[NodeInfo]
) -> tuple[str, ...]:
    return tuple(
        sorted(_collect_task_scalar_names(tasks) | _collect_node_scalar_names(nodes))
    )


def _node_static_values(n: NodeInfo) -> tuple[bool, int]:
    """(schedulable-verdict, max_task_num) — the per-node fields that are
    pure in the Node object (condition/pressure read node.conditions,
    max_task_num the allocatable pod count), i.e. identity-cacheable."""
    return (
        n.node is not None
        and check_node_condition(n.node)
        and check_pressure(n.node),
        n.allocatable.max_task_num,
    )


def _pair_values(trep: TaskInfo, nrep: NodeInfo) -> tuple[bool, float]:
    """One (task-group, node-group) cell of the static products — the
    pair-memo compute twin of `build_static_compat`'s fused sweep. Pure
    in the two group signatures (the same property the group dedup
    itself relies on), which is what makes cross-cycle reuse sound."""
    if nrep.node is None:
        return False, 0.0
    from kube_batch_tpu.plugins.nodeorder import node_affinity_score

    return (
        static_pod_node_compat(trep.pod, nrep.node),
        node_affinity_score(trep, nrep),
    )


def _dims_mask(res: Resource, scalar_names: Sequence[str]) -> list[bool]:
    """Which vector slots `res.resource_names()` would iterate: cpu and
    memory always, scalar slots only when the key is present in the
    scalar map (share()/LessEqual walk map keys — Go nil/absent-key
    semantics, resource_info.go:255-278, helpers.go:43-60)."""
    return [True, True, *(n in res.scalars for n in scalar_names)]


def _build_task_side(shortlist):
    """The encode's task side: per-job pending extraction + pop-order
    sort + plain-task classification (one native pass when available —
    "plain" = no selector/affinity/tolerations/volumes/ports, so every
    later per-task pass can skip the row), row layout, host-only
    routing, and referenced-label-key collection. Split out so the
    encode cache can reuse the whole product for an unmutated session."""
    collected = None
    if _native is not None:
        from kube_batch_tpu.api.resource_info import (
            MIN_MEMORY,
            MIN_MILLI_CPU,
            MIN_MILLI_SCALAR,
        )

        try:
            collected = _native.collect_pending(
                shortlist,
                TaskStatus.PENDING,
                float(MIN_MILLI_CPU),
                float(MIN_MEMORY),
                float(MIN_MILLI_SCALAR),
            )
        except Exception:  # noqa: BLE001 -- fall back to the Python pass
            _log_native_fallback("collect_pending")

    job_list: list[JobInfo] = []
    job_pending: dict[str, tuple[list[TaskInfo], Optional[bytes]]] = {}
    if collected is not None:
        for job, (pending, flags) in zip(shortlist, collected):
            if pending:
                job_list.append(job)
                job_pending[job.uid] = (pending, flags)
    else:
        for job in shortlist:
            pending = [
                t
                for t in job.task_status_index.get(TaskStatus.PENDING, {}).values()
                if not t.resreq.is_empty()
            ]
            if pending:
                # Within-job pop order = priority desc, creation, uid
                # (priority plugin task_order_fn + session fallback,
                # session_plugins.go:329-341). The native pass pre-sorts.
                pending.sort(
                    key=lambda t: (
                        -t.priority,
                        t.pod.metadata.creation_timestamp,
                        t.uid,
                    )
                )
                job_list.append(job)
                job_pending[job.uid] = (pending, None)
    # Stable row order = the serial job heap's fallback order (creation,
    # uid). Dynamic ordering (priority/ready/drf share) is decided by the
    # kernel's selection keys, with this row order as the final key.
    job_list.sort(key=lambda j: (j.creation_timestamp, j.uid))
    job_idx = {j.uid: i for i, j in enumerate(job_list)}

    task_list: list[TaskInfo] = []
    task_plain = bytearray()  # parallel row flags (native-classified)
    host_only: list[TaskInfo] = []
    job_ranges: list[tuple[int, int]] = []
    host_only_rows: list[int] = []
    # Label keys the pending tasks' selectors / node-affinity terms can
    # actually read, collected inline (one pass instead of a separate
    # _referenced_label_keys sweep). Node signatures project labels onto
    # this set so per-node unique labels (hostname et al) do not defeat
    # node-group deduplication (ADVICE r2: encode.py finding).
    ref_label_keys: set[str] = set()
    for job in job_list:
        pending, flags = job_pending[job.uid]
        start = len(task_list)
        if flags is not None and flags.count(0) == 0:
            # whole job plain: no selector/affinity/volume/port rows
            task_list.extend(pending)
            task_plain.extend(flags)
            job_ranges.append((start, len(task_list)))
            continue
        for off, t in enumerate(pending):
            if flags is not None and flags[off]:
                task_plain.append(1)
                task_list.append(t)
                continue
            task_plain.append(0)
            pod = t.pod
            if pod.node_selector:
                ref_label_keys.update(pod.node_selector)
            aff = pod.affinity
            if aff is not None:
                for term in aff.node_affinity_required:
                    ref_label_keys.add(term.key)
                for _, term in aff.node_affinity_preferred:
                    ref_label_keys.add(term.key)
            if aff is not None and aff.has_pod_affinity_terms():
                # required terms gate feasibility pairwise; preferred terms
                # change *other* tasks' scores once this pod lands (the
                # symmetric InterPodAffinity half) — both must be stepped
                # host-side against the live session
                host_only.append(t)
                host_only_rows.append(len(task_list))
            elif pod.volumes:
                # claims need the volume binder's assume step (PV
                # topology, capacity, class matching) against live PVC/PV
                # state — serial-stepped host-side like the reference's
                # AssumePodVolumes inside ssn.Allocate (session.go:241-260)
                host_only.append(t)
                host_only_rows.append(len(task_list))
            task_list.append(t)
        job_ranges.append((start, len(task_list)))
    return (
        job_list, job_idx, task_list, task_plain, host_only,
        job_ranges, host_only_rows, ref_label_keys,
    )


def encode_session(
    jobs: dict[str, JobInfo],
    nodes: dict[str, NodeInfo],
    queues: dict[str, QueueInfo],
    dtype=np.float64,
    pad: bool = True,
    drf=None,
    proportion=None,
    session=None,
    resident_interpod=None,
) -> EncodedSnapshot:
    """Build the SoA snapshot for one allocate solve.

    Job/task eligibility mirrors the serial allocate action exactly
    (reference allocate.go:48-70,120-125): Pending-phase PodGroups wait
    for enqueue, jobs of unknown queues are skipped, BestEffort
    (empty-resreq) tasks are backfill's business.

    ``drf`` / ``proportion`` are the session's live plugin instances (or
    None when the conf does not enable them); their open-session state is
    copied verbatim so kernel share arithmetic starts from the exact
    serial floats.

    ``session`` (optional) scopes the cross-cycle encode cache's
    whole-block reuse (ops/encode_cache.py, ``KBT_ENCODE_CACHE``): with
    it, an encode of an unmutated session (``state_seq`` unchanged)
    reuses the previous encode's task-side products wholesale, and any
    encode reuses per-object signatures / group-pair products validated
    by API-object identity. Warm output is byte-identical to cold by
    construction — every reused value is the value this function would
    recompute.

    ``resident_interpod`` (optional) short-circuits the O(resident-pods)
    affinity sweep over every node's task map: a streaming micro-cycle
    (kube_batch_tpu.streaming) passes the last full cycle's
    ``interpod_active`` verdict for the resident side, and only the
    micro-session's own pending/host-only tasks are swept. Passing True
    when no resident pod has affinity terms costs score work but never
    correctness; the reverse is prevented by the caller (external
    bound-pod churn invalidates the resident base entirely).
    """
    from kube_batch_tpu.ops import encode_cache as _encode_cache

    ec = _encode_cache.active()
    if ec is not None:
        ec.begin_encode()

    node_list = [nodes[name] for name in sorted(nodes)]
    queue_list = sorted(
        queues.values(), key=lambda q: (q.queue.metadata.creation_timestamp, q.uid)
    )
    queue_idx = {q.name: i for i, q in enumerate(queue_list)}

    shortlist: list[JobInfo] = []
    for job in jobs.values():
        if job.pod_group is not None and job.pod_group.status.phase == PodGroupPhase.PENDING:
            continue
        if job.queue not in queues:
            continue
        shortlist.append(job)

    # Cross-cycle task-block reuse: the whole task side of the previous
    # encode is valid while the session is unmutated (state_seq) and the
    # job/queue objects are identical — the steady-state warm cycle.
    tb = (
        ec.lookup_task_block(session, shortlist, queues, dtype, pad)
        if ec is not None
        else None
    )
    if tb is not None:
        job_list = tb.job_list
        job_idx = tb.job_idx
        task_list = tb.task_list
        task_plain = tb.task_plain
        host_only = tb.host_only
        job_ranges = tb.job_ranges
        host_only_rows = tb.host_only_rows
        ref_label_keys = tb.ref_label_keys
    else:
        (
            job_list, job_idx, task_list, task_plain, host_only,
            job_ranges, host_only_rows, ref_label_keys,
        ) = _build_task_side(shortlist)
        if ec is not None:
            tb = ec.store_task_block(
                session, shortlist, queues, dtype, pad,
                job_list=job_list, job_idx=job_idx, task_list=task_list,
                task_plain=task_plain, host_only=host_only,
                job_ranges=job_ranges, host_only_rows=host_only_rows,
                ref_label_keys=ref_label_keys,
            )

    # InterPodAffinity activation: any pod-affinity terms anywhere (pending
    # or resident) make nodeorder's interpod score nonzero-able; the score
    # is per *node* (it reads each node's residents), so it rides its own
    # [GT, N] matrix rather than the node-group-level aff_sc. Volume-only
    # host_only tasks do NOT activate it — claims change no scores.
    interpod_active = any(
        t.pod.affinity is not None and t.pod.affinity.has_pod_affinity_terms()
        for t in host_only
    ) or (
        bool(resident_interpod)
        if resident_interpod is not None
        else any(
            rt.pod.affinity is not None and rt.pod.affinity.has_pod_affinity_terms()
            for n in node_list
            for rt in n.tasks.values()
        )
    )

    if tb is not None and tb.scalar_task_names is not None:
        t_scalars = tb.scalar_task_names
    else:
        t_scalars = _collect_task_scalar_names(task_list)
        if tb is not None:
            tb.scalar_task_names = t_scalars
    scalar_names = tuple(sorted(t_scalars | _collect_node_scalar_names(node_list)))
    R = 2 + len(scalar_names)
    t_n, n_n, j_n, q_n = len(task_list), len(node_list), len(job_list), len(queue_list)
    T = _bucket(t_n) if pad else max(t_n, 1)
    N = _node_bucket(n_n) if pad else max(n_n, 1)
    J = _job_bucket(j_n, T) if pad else max(j_n, 1)
    Q = _bucket(q_n, 2) if pad else max(q_n, 1)

    # -- ports ---------------------------------------------------------------
    # plain rows have no ports by classification, so only the non-plain
    # rows can contribute (flag shortcuts apply whenever the native
    # collect pass classified; otherwise every row is scanned)
    if tb is not None and tb.interesting_ports is not None:
        interesting_ports = tb.interesting_ports
    else:
        interesting_ports = sorted(
            {
                p
                for i, t in enumerate(task_list)
                if not task_plain[i]
                for p in _task_ports(t)
            }
        )
        if tb is not None:
            tb.interesting_ports = interesting_ports
    port_idx = {p: i for i, p in enumerate(interesting_ports)}
    P = max(len(interesting_ports), 1)

    # -- predicate / affinity groups ----------------------------------------
    label_keys = frozenset(ref_label_keys)
    grouping = tb.groupings.get(interpod_active) if tb is not None else None
    if grouping is not None:
        task_gid, t_reps, t_rep_sigs = grouping
    else:
        t_groups: dict[tuple, int] = {}
        task_gid = np.zeros(T, np.int32)
        t_reps: list[TaskInfo] = []
        t_rep_sigs: list[tuple] = []
        if interpod_active:
            # signatures read pod labels: no plain-row shortcut (a plain pod
            # with labels is a distinct group under InterPodAffinity)
            for i, t in enumerate(task_list):
                sig = (
                    ec.task_sig(t, True, _task_signature)
                    if ec is not None
                    else _task_signature(t, with_labels=True)
                )
                if sig not in t_groups:
                    t_groups[sig] = len(t_reps)
                    t_reps.append(t)
                    t_rep_sigs.append(sig)
                task_gid[i] = t_groups[sig]
        else:
            for i, t in enumerate(task_list):
                if task_plain[i]:
                    sig = _PLAIN_SIG
                elif ec is not None:
                    sig = ec.task_sig(t, False, _task_signature)
                else:
                    sig = _task_signature(t)
                if sig not in t_groups:
                    t_groups[sig] = len(t_reps)
                    t_reps.append(t)
                    t_rep_sigs.append(sig)
                task_gid[i] = t_groups[sig]
        if tb is not None:
            tb.groupings[interpod_active] = (task_gid, t_reps, t_rep_sigs)
    ec_node_entries = None
    if ec is not None:
        # per-node memo (identity-validated: signature + static
        # verdicts in one touch) + first-occurrence regroup — the
        # regroup is O(N) dict ops; only churned nodes recompute
        ec_node_entries = [
            ec.node_row(n, label_keys, _node_signature, _node_static_values)
            for n in node_list
        ]
        n_groups: dict[tuple, int] = {}
        node_gids = np.zeros(len(node_list), np.int32)
        n_reps = []
        n_rep_sigs = []
        for i, e in enumerate(ec_node_entries):
            sig = e.sig
            gid = n_groups.get(sig)
            if gid is None:
                gid = n_groups[sig] = len(n_reps)
                n_reps.append(node_list[i])
                n_rep_sigs.append(sig)
            node_gids[i] = gid
    else:
        node_gids, n_reps = group_by_signature(
            node_list, lambda n: _node_signature(n, label_keys)
        )
    node_gid = np.zeros(N, np.int32)
    node_gid[: len(node_gids)] = node_gids
    GT, GN = max(len(t_reps), 1), max(len(n_reps), 1)
    aff_sc = np.zeros((GT, GN), dtype)
    if ec is not None:
        # (task-group x node-group) products via the cross-cycle pair
        # memo: unchanged pairs are reused verbatim, new pairs compute
        # exactly what build_static_compat would
        compat = np.zeros((GT, GN), bool)
        for gi, trep in enumerate(t_reps):
            tsig = t_rep_sigs[gi]
            for gj, nrep in enumerate(n_reps):
                c, s = ec.pair(
                    tsig,
                    n_rep_sigs[gj],
                    lambda trep=trep, nrep=nrep: _pair_values(trep, nrep),
                )
                compat[gi, gj] = c
                aff_sc[gi, gj] = s
    else:
        compat = build_static_compat(t_reps, n_reps, aff_sc=aff_sc)

    # -- task arrays (bulk-filled: one ndarray conversion, not 50k row
    #    assignments — encode_s is on the session critical path; on a
    #    warm task-block the whole dense bundle is reused verbatim —
    #    its inputs are exactly the block's identity-validated tasks) --
    arrays_key = (scalar_names, tuple(interesting_ports))
    cached = (
        tb.arrays
        if tb is not None and tb.arrays is not None and tb.arrays_key == arrays_key
        else None
    )
    if cached is not None:
        (
            task_req, task_res, task_job, task_has_sc, task_res_has_sc,
            task_host_only, task_ports, task_created,
            job_start, job_end, job_min, job_ready0, job_prio, job_rank,
            job_queue, job_valid,
        ) = cached
    else:
        task_req = np.zeros((T, R), dtype)
        task_res = np.zeros((T, R), dtype)
        task_job = np.zeros(T, np.int32)
        task_has_sc = np.zeros(T, bool)
        task_res_has_sc = np.zeros(T, bool)
        task_host_only = np.zeros(T, bool)
        task_ports = np.zeros((T, P), bool)
        filled = False
        if t_n and not scalar_names and _native is not None:
            # native single pass: req/res cpu+mem columns, job row index,
            # scalar-presence flags (kube_batch_tpu/native extract_task_columns)
            try:
                _native.extract_task_columns(
                    task_list, job_idx, task_req, task_res,
                    task_job, task_has_sc, task_res_has_sc,
                )
                filled = True
            except Exception:  # noqa: BLE001 -- fall back to the numpy passes
                _log_native_fallback("extract_task_columns")
        if t_n and not filled:
            if scalar_names:
                task_req[:t_n] = np.asarray(
                    [t.init_resreq.to_vector(scalar_names) for t in task_list], dtype
                )
                task_res[:t_n] = np.asarray(
                    [t.resreq.to_vector(scalar_names) for t in task_list], dtype
                )
            else:
                # column-wise fromiter: one C loop per column, no 50k tuple
                # objects + list->ndarray conversion on the critical path
                task_req[:t_n, 0] = np.fromiter(
                    (t.init_resreq.milli_cpu for t in task_list), dtype, count=t_n
                )
                task_req[:t_n, 1] = np.fromiter(
                    (t.init_resreq.memory for t in task_list), dtype, count=t_n
                )
                task_res[:t_n, 0] = np.fromiter(
                    (t.resreq.milli_cpu for t in task_list), dtype, count=t_n
                )
                task_res[:t_n, 1] = np.fromiter(
                    (t.resreq.memory for t in task_list), dtype, count=t_n
                )
            task_job[:t_n] = np.fromiter(
                (job_idx[t.job] for t in task_list), np.int32, count=t_n
            )
            task_has_sc[:t_n] = np.fromiter(
                (bool(t.init_resreq.scalars) for t in task_list), bool, count=t_n
            )
            task_res_has_sc[:t_n] = np.fromiter(
                (bool(t.resreq.scalars) for t in task_list), bool, count=t_n
            )
        if t_n:
            if interesting_ports:
                for i, t in enumerate(task_list):
                    if task_plain[i]:
                        continue
                    for p in _task_ports(t):
                        task_ports[i, port_idx[p]] = True
        task_host_only[host_only_rows] = True
        # per-row pod creation timestamp: the replay's dispatch-latency
        # metric gathers from this instead of a per-task Python pass
        task_created = np.zeros(T)
        if t_n:
            task_created[:t_n] = np.fromiter(
                (t.pod.metadata.creation_timestamp for t in task_list),
                np.float64, count=t_n,
            )

        # -- job arrays (cached with the task bundle: inputs are the
        #    block's job_list/job_ranges + queue order + state_seq) ----
        job_start = np.zeros(J, np.int32)
        job_end = np.zeros(J, np.int32)
        job_min = np.zeros(J, np.int32)
        job_ready0 = np.zeros(J, np.int32)
        job_prio = np.zeros(J, np.int32)
        job_rank = np.zeros(J, np.int32)
        job_queue = np.zeros(J, np.int32)
        job_valid = np.zeros(J, bool)
        for i, j in enumerate(job_list):
            job_start[i], job_end[i] = job_ranges[i]
            job_min[i] = j.min_available
            job_ready0[i] = j.ready_task_num()
            job_prio[i] = j.priority
            job_rank[i] = i  # job_list pre-sorted by (creation, uid)
            job_queue[i] = queue_idx[j.queue]
            job_valid[i] = True
        if tb is not None:
            tb.arrays_key = arrays_key
            tb.arrays = (
                task_req, task_res, task_job, task_has_sc, task_res_has_sc,
                task_host_only, task_ports, task_created,
                job_start, job_end, job_min, job_ready0, job_prio, job_rank,
                job_queue, job_valid,
            )

    # -- node arrays ---------------------------------------------------------
    node_idle = np.zeros((N, R), dtype)
    node_rel = np.zeros((N, R), dtype)
    node_used = np.zeros((N, R), dtype)
    node_alloc = np.zeros((N, R), dtype)
    node_ok = np.zeros(N, bool)
    node_valid = np.zeros(N, bool)
    node_max_tasks = np.zeros(N, np.int32)
    node_ntasks = np.zeros(N, np.int32)
    node_idle_has_sc = np.zeros(N, bool)
    node_rel_has_sc = np.zeros(N, bool)
    node_ports = np.zeros((N, P), bool)
    node_vecs_filled = False
    if n_n and not scalar_names and _native is not None:
        # native pass over the 4 per-node resource vectors (cpu+mem)
        stacked = np.zeros((4, N, R), dtype)
        try:
            _native.extract_node_columns(
                node_list, ("idle", "releasing", "used", "allocatable"), stacked
            )
            node_idle, node_rel, node_used, node_alloc = (
                np.ascontiguousarray(stacked[0]),
                np.ascontiguousarray(stacked[1]),
                np.ascontiguousarray(stacked[2]),
                np.ascontiguousarray(stacked[3]),
            )
            node_vecs_filled = True
        except Exception:  # noqa: BLE001 -- fall back to to_vector rows
            _log_native_fallback("extract_node_columns")
    if not node_vecs_filled:
        for i, n in enumerate(node_list):
            node_idle[i] = n.idle.to_vector(scalar_names)
            node_rel[i] = n.releasing.to_vector(scalar_names)
            node_used[i] = n.used.to_vector(scalar_names)
            node_alloc[i] = n.allocatable.to_vector(scalar_names)
    # node statics (condition/pressure verdict, max_task_num) reuse per
    # Node-object identity; the dynamic residency columns (ntasks,
    # has_sc, ports) re-gather every cycle because binds move them
    if ec_node_entries is not None and n_n:
        for i, e in enumerate(ec_node_entries):
            node_ok[i] = e.ok
            node_max_tasks[i] = e.max_tasks
    elif n_n:
        node_ok[:n_n] = np.fromiter(
            (_node_static_values(n)[0] for n in node_list), bool, count=n_n
        )
        node_max_tasks[:n_n] = np.fromiter(
            (n.allocatable.max_task_num for n in node_list), np.int32, count=n_n
        )
    if n_n:
        node_valid[:n_n] = True
        node_ntasks[:n_n] = np.fromiter(
            (len(n.tasks) for n in node_list), np.int32, count=n_n
        )
        node_idle_has_sc[:n_n] = np.fromiter(
            (bool(n.idle.scalars) for n in node_list), bool, count=n_n
        )
        node_rel_has_sc[:n_n] = np.fromiter(
            (bool(n.releasing.scalars) for n in node_list), bool, count=n_n
        )
    if interesting_ports:
        # only pending tasks' ports matter; with none in play the whole
        # resident sweep is skippable (port_idx gates every write anyway)
        for i, n in enumerate(node_list):
            for task in n.tasks.values():
                for p in _task_ports(task):
                    if p in port_idx:
                        node_ports[i, port_idx[p]] = True

    queue_rank = np.arange(Q, dtype=np.int32)  # queue_list pre-sorted

    # -- drf / proportion session state (plugin-exact floats) ---------------
    job_alloc0 = np.zeros((J, R), dtype)
    drf_total = np.zeros(R, dtype)
    drf_dims = np.zeros(R, bool)
    if drf is not None:
        drf_total[:] = drf.total_resource.to_vector(scalar_names)
        drf_dims[:] = _dims_mask(drf.total_resource, scalar_names)
        for i, j in enumerate(job_list):
            attr = drf.job_attrs.get(j.uid)
            if attr is not None:
                job_alloc0[i] = attr.allocated.to_vector(scalar_names)

    q_alloc0 = np.zeros((Q, R), dtype)
    q_deserved = np.zeros((Q, R), dtype)
    q_dims = np.zeros((Q, R), bool)
    q_alloc_has_sc0 = np.zeros(Q, bool)
    if proportion is not None:
        for i, q in enumerate(queue_list):
            attr = proportion.queue_attrs.get(q.name)
            if attr is None:
                continue  # queue with no jobs: never selected by the kernel
            q_alloc0[i] = attr.allocated.to_vector(scalar_names)
            q_deserved[i] = attr.deserved.to_vector(scalar_names)
            q_dims[i] = _dims_mask(attr.deserved, scalar_names)
            q_alloc_has_sc0[i] = bool(attr.allocated.scalars)

    eps = np.asarray(Resource.vector_epsilons(scalar_names), dtype)

    if interpod_active:
        pod_sc = compute_pod_sc(t_reps, nodes, [n.name for n in node_list], N, dtype)
    else:
        pod_sc = np.zeros((GT, N), dtype)

    if ec is not None:
        ec.end_encode()

    return EncodedSnapshot(
        scalar_names=scalar_names,
        tasks=task_list,
        jobs=job_list,
        queues=queue_list,
        node_names=[n.name for n in node_list],
        n_tasks=t_n,
        n_nodes=n_n,
        n_jobs=j_n,
        n_queues=q_n,
        host_only=host_only,
        interpod_active=interpod_active,
        task_reps=t_reps,
        arrays=dict(
            task_req=task_req,
            task_res=task_res,
            task_created=task_created,
            task_job=task_job,
            task_gid=task_gid,
            task_has_sc=task_has_sc,
            task_res_has_sc=task_res_has_sc,
            task_host_only=task_host_only,
            task_ports=task_ports,
            node_idle=node_idle,
            node_rel=node_rel,
            node_used=node_used,
            node_alloc=node_alloc,
            node_ok=node_ok,
            node_valid=node_valid,
            node_max_tasks=node_max_tasks,
            node_ntasks=node_ntasks,
            node_idle_has_sc=node_idle_has_sc,
            node_rel_has_sc=node_rel_has_sc,
            node_gid=node_gid,
            node_ports=node_ports,
            compat=compat,
            aff_sc=aff_sc,
            pod_sc=pod_sc,
            job_start=job_start,
            job_end=job_end,
            job_min=job_min,
            job_ready0=job_ready0,
            job_prio=job_prio,
            job_rank=job_rank,
            job_queue=job_queue,
            job_valid=job_valid,
            queue_rank=queue_rank,
            job_alloc0=job_alloc0,
            drf_total=drf_total,
            drf_dims=drf_dims,
            q_alloc0=q_alloc0,
            q_deserved=q_deserved,
            q_dims=q_dims,
            q_alloc_has_sc0=q_alloc_has_sc0,
            eps=eps,
        ),
    )
