"""xla_allocate action: the allocate loop as one device program.

Drop-in replacement for the serial allocate action (conf
``actions: "enqueue, xla_allocate, backfill"``): encodes the session
snapshot to SoA tensors (ops.encode), runs the gang-aware device solve —
the fused Pallas kernel (ops.pallas_solve) on TPU, the jitted XLA
`lax.while_loop` twin (ops.kernels.solve_allocate) elsewhere and as the
runtime fallback — which vectorizes the reference's per-task node scans
(scheduler_helper.go:34-109) over the whole node axis, then
**bulk-replays** the resulting assignments into the session — the same
state mutations `ssn.allocate`/`ssn.pipeline` would make (status index
moves, node accounting, drf/proportion event bookkeeping, the gang
dispatch barrier with cache binds), applied in kernel assignment order
but without 50k Python call frames of per-task session machinery.

Policy envelope: the kernel hardwires the reference's *default* conf
semantics (util.go:31-42) — priority/gang ordering + barrier, drf job
shares, proportion queue shares + overused gate, predicates masks,
nodeorder scores. Anything else (extra plugins, disabled enable flags,
a chain order the kernel's selection keys do not model) falls back to
the serial action for the cycle — correctness first.

Pod (anti-)affinity is pairwise-dynamic over resident pods
(predicates.go:187-199) and stays host-side, but no longer forces a
wholesale fallback: the kernel pauses when a flagged task reaches the
head of its job (ops/kernels.py `paused_at`), the action replays the
segment, serial-steps that one task against the live session (identical
to the serial inner loop, allocate.go:139-180), patches the solver state
and resumes — a snapshot with one affinity task costs one extra device
round-trip, not a serial cycle.

NodesFitDelta diagnostics (allocate.go:139-145,162-168) are reproduced
only on the host-stepped tasks — they are human-readable FitError text,
not policy.

Float dtype (round-2 advisor finding): float64 by default — bit-identical
to the serial float64 path. When x64 is unavailable (default TPU config)
the action runs float32 — exact for milli-CPU/MiB-granular quantities but
able to flip least-requested/balanced floor/tie boundaries on off-grid
values — and logs that it did so.
"""

from __future__ import annotations

import contextvars
import dataclasses
import logging
import os
from typing import Optional

import jax  # noqa: F401  -- fail registration, not mid-cycle, when absent
import numpy as np

from kube_batch_tpu import faults, metrics, obs
from kube_batch_tpu import log as _glog
from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.framework.interface import Action
from kube_batch_tpu.framework.session import Session

from kube_batch_tpu.actions.envelope import kernel_supported as _kernel_supported
from kube_batch_tpu.native import lib as _native

log = logging.getLogger("kube_batch_tpu.actions.xla_allocate")


class _DeviceSolveError(RuntimeError):
    """Every device tier failed (or the XLA twin's breaker rejected the
    cycle mid-solve): the caller degrades to serial for this cycle."""


def _nonfinite_inputs(arrays: dict) -> list[str]:
    """Names of float solver inputs carrying NaN/Inf. One reduction per
    array (any non-finite value propagates through sum; a finite array
    overflowing the sum is an overflow worth flagging too) — cheap next
    to the solve, and the guard that turns a poisoned score tensor into
    a logged serial cycle instead of silently wrong placements."""
    bad = []
    for name, v in arrays.items():
        a = np.asarray(v)
        if a.dtype.kind == "f" and not np.isfinite(a.sum()):
            bad.append(name)
    return bad


def _nodeorder_weights(ssn: Session) -> tuple[float, float, float, float]:
    """(w_least, w_balanced, w_aff, w_podaff) from the tiers, matching the
    serial plugin's defaults (nodeorder.go:139-153)."""
    from kube_batch_tpu.framework.arguments import Arguments
    from kube_batch_tpu.plugins.nodeorder import (
        BALANCED_RESOURCE_WEIGHT,
        LEAST_REQUESTED_WEIGHT,
        NODE_AFFINITY_WEIGHT,
        POD_AFFINITY_WEIGHT,
    )

    for tier in ssn.tiers:
        for option in tier.plugins:
            if option.name in ("nodeorder", "tensorscore") and option.enabled_node_order:
                args = Arguments(option.arguments)
                return (
                    args.get_int(LEAST_REQUESTED_WEIGHT, 1),
                    args.get_int(BALANCED_RESOURCE_WEIGHT, 1),
                    args.get_int(NODE_AFFINITY_WEIGHT, 1),
                    args.get_int(POD_AFFINITY_WEIGHT, 1),
                )
    return 0.0, 0.0, 0.0, 0.0


class XlaAllocateAction(Action):
    """The TPU-native allocate. Falls back to serial when out of envelope."""

    def __init__(self, dtype=None) -> None:
        self._dtype = dtype
        self._warned_f32 = False
        # Device-resident tensor arena (ops/encode_cache.TensorArena):
        # persists across cycles on the registered action instance, so
        # warm cycles upload only changed rows of the node slabs / group
        # matrices instead of re-transferring the full tensor set.
        from kube_batch_tpu.ops.encode_cache import TensorArena

        self._arena = TensorArena()
        # Wall-clock split of the last execute() (benchmark/run.py and
        # chip_smoke.py read this).
        self.last_timings: dict[str, float] = {}
        # Devices in the mesh the last execute() resolved (1 = single-chip);
        # the driver dryrun asserts on this to prove the sharded path ran.
        self.last_mesh_size = 1
        # Which rung actually solved the last execute() ("mesh_pallas",
        # "sharded_xla", "pallas", "xla", "serial"); Scheduler and the
        # benchmark read this so a silent downgrade cannot masquerade as
        # evidence.
        self.last_solver_tier = "none"
        # Block backend of the last mesh_pallas solve ("mosaic",
        # "interpret", "jnp"; "" off that rung): chip_smoke asserts the
        # real kernel ran.
        self.last_block_impl = ""
        # Gang iterations the last execute() committed from K-deep
        # batched mesh exchanges (KBT_EXCHANGE_BATCH; 0 off the batched
        # program). Tests read this as amortization evidence.
        self.last_batched_iters = 0
        # Stats dict from the last class-compressed solve (ops/class_solve,
        # KBT_CLASS_COMPRESS): class_count, compression_ratio, splits,
        # remerges, group_s/kernel_s solve-cost split. None when the
        # compression was off or degraded for the cycle; tests read this
        # as the compression-honesty evidence.
        self.last_class_stats = None
        # Whether the last FULL-cycle encode saw any pod-affinity terms
        # (pending or resident). Streaming micro-cycles pass this as the
        # resident_interpod hint so the encode skips the O(resident-pods)
        # sweep over every node's task map (see encode_session).
        self.last_interpod_active = False

    @property
    def name(self) -> str:
        return "xla_allocate"

    # -- main ----------------------------------------------------------------

    def execute(self, ssn: Session) -> None:
        from kube_batch_tpu.ops.encode import encode_session
        from kube_batch_tpu.ops.kernels import result_of, solve_allocate_state

        self.last_timings = {}  # never report a previous cycle's path
        self.last_solver_tier = "none"
        self.last_block_impl = ""
        self.last_batched_iters = 0
        self.last_class_stats = None
        if not _kernel_supported(ssn):
            log.info("conf outside kernel envelope; running serial allocate")
            self._fallback(ssn)
            return

        mesh = self._resolve_mesh(ssn)

        # Size floor: one device solve costs a fixed dispatch round trip
        # regardless of payload (not measured on current code), while the
        # serial loop clears tiny snapshots in microseconds-per-pair —
        # route (tasks x nodes) below the floor to the serial action
        # (bit-exact float64, no device). A mesh *request* — even one
        # that failed to resolve — is a statement of device intent and
        # skips the floor (the multichip dryrun relies on this).
        if mesh is None and not self._mesh_requested(ssn):
            pend = sum(
                len(j.task_status_index.get(TaskStatus.PENDING, {}))
                for j in ssn.jobs.values()
            )
            if pend * max(len(ssn.nodes), 1) < self._min_device_pairs(ssn):
                log.debug(
                    "snapshot below the device size floor (%d pending x %d "
                    "nodes); running serial allocate",
                    pend,
                    len(ssn.nodes),
                )
                import time as _time

                t0 = _time.perf_counter()
                self._fallback(ssn)
                self.last_timings = {
                    "serial_routed_s": _time.perf_counter() - t0
                }
                return

        import jax.numpy as jnp

        dtype = self._dtype
        if dtype is None:
            if jnp.zeros(0).dtype == np.float64:
                dtype = np.float64
            else:
                dtype = np.float32
                if not self._warned_f32:
                    log.warning(
                        "jax x64 disabled: solving in float32 — exact on "
                        "milli-CPU/MiB-granular requests, but off-grid values "
                        "can flip score floor/tie boundaries vs the serial "
                        "float64 path (enable jax_enable_x64 for bit parity)"
                    )
                    self._warned_f32 = True

        import time as _time

        # Degradation ladder (kube_batch_tpu.faults): the XLA twin is the
        # device floor — every other device tier falls back onto it — so
        # with its breaker open the whole device path sits the cycle out
        # and serial (the bottom rung, the correctness oracle) runs. The
        # breaker recovers through half-open probes, unlike the previous
        # one-way exception fallback.
        ladder = faults.solver_ladder
        if not ladder.allow("xla"):
            log.warning(
                "device-solve breaker open; running serial allocate for this cycle"
            )
            metrics.register_degraded_cycle("serial", "breaker_open")
            t0 = _time.perf_counter()
            self._fallback(ssn)
            self.last_timings = {"serial_degraded_s": _time.perf_counter() - t0}
            return

        order = [o.name for t in ssn.tiers for o in t.plugins]
        enable_drf = "drf" in order
        enable_proportion = "proportion" in order

        micro = bool(getattr(ssn, "micro_cycle", False))
        t0 = _time.perf_counter()
        with obs.span("encode", micro=micro) as espan:
            enc = encode_session(
                ssn.jobs,
                ssn.nodes,
                ssn.queues,
                dtype=dtype,
                drf=ssn.plugins.get("drf") if enable_drf else None,
                proportion=ssn.plugins.get("proportion") if enable_proportion else None,
                session=ssn,
                resident_interpod=self.last_interpod_active if micro else None,
            )
            if not micro:
                self.last_interpod_active = bool(enc.interpod_active)
            espan.set_attr("tasks", len(enc.tasks))
            espan.set_attr("nodes", len(ssn.nodes))
            # cross-cycle encode-cache temperature of THIS encode
            espan.set_attr("warm_fraction", metrics.encode_warm_fraction.value())
        if not enc.tasks:
            return
        t_encode = _time.perf_counter() - t0

        w_least, w_balanced, w_aff, w_podaff = _nodeorder_weights(ssn)
        arrays = dict(enc.arrays)
        # host-only metadata: the replay's latency stamps read it from
        # enc.arrays — keep it out of the kernel input dict (it would
        # ride every solve's transfer and change the jit pytree)
        arrays.pop("task_created", None)
        arrays["w_least"] = dtype(w_least)
        arrays["w_balanced"] = dtype(w_balanced)
        arrays["w_aff"] = dtype(w_aff)
        arrays["w_podaff"] = dtype(w_podaff)

        # Fault point solve.nan: a poisoned score tensor, the failure the
        # finite guard below exists to catch.
        if faults.should_fire("solve.nan"):
            arrays["w_least"] = dtype(float("nan"))
        bad = _nonfinite_inputs(arrays)
        if bad:
            log.error(
                "non-finite solver inputs (%s); running serial allocate for "
                "this cycle", ", ".join(bad),
            )
            metrics.register_degraded_cycle("serial", "nonfinite")
            t0 = _time.perf_counter()
            self._fallback(ssn)
            self.last_timings = {"serial_degraded_s": _time.perf_counter() - t0}
            return

        replay = _Replayer(ssn, enc, arrays, enable_drf, enable_proportion)

        # Device-resident arena: the XLA rungs (single-chip twin and the
        # GSPMD sharded solver) take persistent device handles — warm
        # cycles upload only changed rows of the node slabs / group
        # matrices. The Pallas rungs pack host-side and keep numpy. Any
        # arena failure degrades to plain host arrays (jit's own
        # transfer), never the cycle.
        from kube_batch_tpu.ops import encode_cache as _encode_cache

        dev_arrays = None
        if _encode_cache.enabled():
            try:
                dev_arrays = self._arena.device_view(arrays, mesh=mesh)
            except Exception:  # noqa: BLE001 -- residency is an optimization
                log.exception("tensor arena upload failed; solving from host arrays")
                self._arena.clear()
                dev_arrays = None

        # Cycle deadline budget (recovery/budget.py), threaded from
        # run_once via the session: the solver entry receives the
        # remaining budget and every pre-dispatch boundary checks it.
        budget = getattr(ssn, "cycle_budget", None)
        solve_fn = self._make_solver(
            arrays, enable_drf, enable_proportion, dtype, mesh, budget=budget,
            dev_arrays=dev_arrays,
        )

        t0 = _time.perf_counter()
        sspan = obs.span("solve", mesh=self.last_mesh_size)
        compile0 = 0
        if sspan is not obs.NOOP_SPAN:
            from kube_batch_tpu.analysis.trace.sentinel import compile_count

            compile0 = compile_count()
        try:
            with sspan:
                state = solve_fn(None)
                while int(state.paused_at) >= 0:
                    if budget is not None:
                        budget.check("between solve segments")
                    # Segmented hybrid: sync the session up to the pause point,
                    # serial-step the host-only task, resume the kernel.
                    sspan.event("host_step", step=int(state.step))
                    s = jax.tree_util.tree_map(np.array, state)  # writable host copy
                    replay.apply_upto(s.assign_pos, s.assigned_node, s.assigned_kind, int(s.step))
                    s = self._host_step(ssn, enc, arrays, replay, s)
                    if enc.interpod_active:
                        # the host-stepped pod carries pod-affinity terms; once
                        # resident it shifts every group's InterPodAffinity score
                        from kube_batch_tpu.ops.encode import compute_pod_sc

                        arrays["pod_sc"] = compute_pod_sc(
                            enc.task_reps,
                            ssn.nodes,
                            enc.node_names,
                            np.asarray(arrays["pod_sc"]).shape[1],
                            dtype,
                        )
                        if dev_arrays is not None:
                            # mirror the refresh into the device view the
                            # XLA rungs solve from
                            dev_arrays["pod_sc"] = self._arena.upload(
                                "pod_sc", arrays["pod_sc"], mesh=mesh
                            )
                    state = solve_fn(s)

                result = result_of(state)
                # Device fencepost (device-phase telemetry): block until
                # the solver's outputs have materialized ON DEVICE before
                # the host transfers below — solve_device_s is then a
                # device-event-measured phase boundary, not a wall-clock
                # figure with transfer time folded in.
                jax.block_until_ready(result.assign_pos)
                t_solve_device = _time.perf_counter() - t0
                # all three result vectors come off-device here: the transfer is
                # part of the solve's device round-trip, not of the replay
                assign_pos = np.asarray(result.assign_pos)
                assigned_node = np.asarray(result.assigned_node)
                assigned_kind = np.asarray(result.assigned_kind)
                sspan.set_attr("tier", self.last_solver_tier)
                if sspan is not obs.NOOP_SPAN:
                    from kube_batch_tpu.analysis.trace.sentinel import compile_count

                    compiled = compile_count() - compile0
                    if compiled:
                        # a warm cycle that compiles is THE regression the
                        # CompileSentinel exists for — make it visible on
                        # the trace, not just in the budget assert
                        sspan.event("compile", count=compiled)
        except _DeviceSolveError as e:
            # Bottom of the ladder: serial finishes the cycle. Any
            # already-replayed host-step segments stand — serial allocate
            # simply continues over the remaining pending tasks, the same
            # session semantics as a mixed actions string.
            log.error("device solve failed (%s); degrading to serial allocate", e)
            metrics.register_degraded_cycle("serial", "solve_failed")
            t0 = _time.perf_counter()
            self._fallback(ssn)
            self.last_timings = {"serial_degraded_s": _time.perf_counter() - t0}
            return
        t_solve = _time.perf_counter() - t0

        # Pipelined cycles (kube_batch_tpu.pipeline, KBT_PIPELINE): the
        # post-solve phase — statement replay, forensics, dispatch — is
        # pure host/cache work that needs nothing further from the
        # device, so it can ride the cache's kb-write pool while the
        # next cycle encodes and solves. The dispatch fence keeps the
        # ordering the synchronous path gets for free (dispatch N <
        # snapshot N+1), close_session joins before the commit
        # write-back, and micro-cycles never defer (their outcome
        # accounting reads the session synchronously).
        from kube_batch_tpu import pipeline as _pipeline

        defer = _pipeline.enabled() and not micro
        if defer and budget is not None:
            # The last pre-dispatch gate must stay on the scheduling
            # thread so a deadline abort (and the cycle.overrun drill's
            # inject=True) still unwinds through run_once's discard path
            # with zero cache mutation.
            budget.check("dispatch barrier", inject=True)

        timings: dict[str, float] = {
            "encode_s": t_encode,
            "solve_s": t_solve,
            "solve_device_s": t_solve_device,
        }
        self.last_timings = timings

        def _post_solve() -> float:
            # replay, explain and dispatch are siblings under the action
            # span: replay is the session's own work (apply + the gang
            # barrier), dispatch the store's
            t0 = _time.perf_counter()
            t_explain = 0.0
            with obs.span("replay") as rspan:
                replay.apply_upto(assign_pos, assigned_node, assigned_kind, int(result.n_assigned))
                if not defer and budget is not None:
                    # The last pre-dispatch gate: past this point binds reach
                    # the cache and the cycle can no longer abort cleanly. The
                    # cycle.overrun drill injects here (inject=True) — maximal
                    # discardable work, zero cache mutation.
                    budget.check("dispatch barrier", inject=True)
                plan = replay.finish(np.asarray(result.ready_cnt))
                if rspan is not obs.NOOP_SPAN:
                    rspan.set_attr("gangs", len({t.job for t in plan.tasks}))
                    rspan.set_attr("tasks", len(plan.tasks))
            # Post-solve forensics (obs/explain): batched plane/score
            # reductions against the FINAL solver state, published before
            # the dispatch so the journal intents it writes can attach
            # per-gang reason payloads — and after the budget gate, so an
            # aborted cycle leaves no half-cycle records behind.
            from kube_batch_tpu.obs import explain as _explain

            if _explain.enabled():
                te = _time.perf_counter()
                with obs.span("explain", micro=micro) as xsp:
                    recs = _explain.explain_post_solve(ssn, enc, arrays, state, result)
                    _explain.publish(ssn, recs)
                    for k, v in _explain.summary(recs).items():
                        xsp.set_attr(k, v)
                t_explain = _time.perf_counter() - te
            replay.dispatch(plan)
            dur = _time.perf_counter() - t0
            timings["replay_s"] = dur - t_explain
            if t_explain:
                timings["explain_s"] = t_explain
            return dur

        if defer:
            # pool threads don't inherit the contextvar: the post-solve
            # spans run under this action's captured context
            ctx = contextvars.copy_context()

            def _deferred() -> None:
                # stamp the dispatch window for the measured overlap
                # fraction: [d0, d1] intersected with the consumer's
                # join window is the serialized share
                d0 = _time.perf_counter()
                ctx.run(_post_solve)
                _pipeline.fence.record_dispatch_window(d0, _time.perf_counter())

            fut = _pipeline.submit(ssn.cache, _deferred)
            ssn.deferred_dispatch = fut
            _pipeline.fence.arm(fut)
        else:
            _post_solve()

    def _mesh_requested(self, ssn: Session) -> bool:
        """True when the conf/env names a mesh at all — resolution may
        still fail (bad backend, one device), but the operator asked for
        the device path, so the size floor must not reroute to serial."""
        spec = ssn.action_arguments.get(self.name, {}).get(
            "mesh", os.environ.get("KBT_MESH", "")
        )
        return (spec or "").strip().lower() not in ("", "off", "none", "0", "1")

    def _min_device_pairs(self, ssn: Session) -> int:
        """(pending tasks x nodes) below which the serial action is the
        faster allocator. Default 32768: at ~6 us/pair the serial loop
        finishes in ~0.2 s, the break-even with the device round trip.
        Conf `actionArguments: {xla_allocate: {min_device_pairs: N}}`
        or env KBT_MIN_DEVICE_PAIRS overrides; 0 forces the device path
        (how the parity suites pin the kernel under test)."""
        spec = ssn.action_arguments.get(self.name, {}).get(
            "min_device_pairs", os.environ.get("KBT_MIN_DEVICE_PAIRS", "")
        )
        try:
            return int(spec)
        except (TypeError, ValueError):
            if str(spec).strip():
                log.warning(
                    "min_device_pairs=%r is not an integer; using default", spec
                )
            return 32768

    def _resolve_mesh(self, ssn: Session):
        """Conf-selected device mesh for the solve, or None (single-chip).

        `actionArguments: {xla_allocate: {mesh: ...}}` (env KBT_MESH as
        the conf-less override): ``off``/``0``/``1`` -> single chip;
        ``auto`` -> every visible device; an integer -> that many; an
        explicit ``backend:count`` (e.g. ``cpu:8``) pins the JAX backend
        — how the driver/tests exercise the multi-chip path on a virtual
        CPU mesh when the ambient default backend is a single TPU. The
        mesh size is clamped to the largest power of two available so it
        always divides the encoder's power-of-two node buckets. The
        resolved size lands in `self.last_mesh_size` so callers can
        verify the sharded path actually engaged."""
        self.last_mesh_size = 1
        spec = ssn.action_arguments.get(self.name, {}).get(
            "mesh", os.environ.get("KBT_MESH", "")
        )
        spec = (spec or "").strip().lower()
        if spec in ("", "off", "none", "0", "1"):
            return None
        import jax as _jax

        backend = None
        if ":" in spec:
            backend, spec = spec.split(":", 1)
        try:
            devices = _jax.devices(backend)
        except RuntimeError:
            log.warning(
                "mesh backend %r unavailable; running single-chip", backend
            )
            return None
        if spec == "auto":
            want = len(devices)
        else:
            try:
                want = int(spec)
            except ValueError:
                # A bad conf value must not kill the scheduling loop
                # (scheduler.py's rule for parse errors applies to
                # values too) — degrade to single-chip and say so.
                log.warning(
                    "unrecognized mesh spec %r; running single-chip", spec
                )
                return None
        if want < 1:
            log.warning("mesh=%s is not a device count; running single-chip", spec)
            return None
        n = min(want, len(devices))
        n = 1 << (n.bit_length() - 1)  # largest pow2 <= n
        # The encoder buckets the node axis to multiples of 128, which
        # every pow2 mesh up to 128 divides; a larger mesh would break
        # the GSPMD divisibility invariant.
        if n > 128:
            log.warning(
                "mesh clamped from %d to 128 devices (node-bucket divisibility)", n
            )
            n = 128
        if n <= 1:
            if spec != "auto" and want > 1:
                log.warning(
                    "mesh=%s requested but only %d device(s) visible; "
                    "running single-chip",
                    spec,
                    len(devices),
                )
            return None
        if n != want and spec != "auto":
            log.warning("mesh=%s clamped to %d devices (pow2, available)", spec, n)
        from kube_batch_tpu.parallel import make_mesh

        self.last_mesh_size = n
        return make_mesh(n, devices=devices[:n])

    def _make_solver(
        self,
        arrays,
        enable_drf: bool,
        enable_proportion: bool,
        dtype,
        mesh=None,
        budget=None,
        dev_arrays=None,
    ):
        """Pick the device solve: with a conf-selected multi-chip mesh,
        the GSPMD node-axis-sharded XLA kernel (parallel.ShardedSolver);
        single-chip, the fused Pallas kernel on TPU-class backends
        (float32, in-envelope snapshots), else the XLA `lax.while_loop`
        kernel. `KBT_PALLAS=0` forces the XLA kernel; `KBT_PALLAS=interpret`
        runs the Pallas kernel in interpreter mode (CPU parity tests).
        Live InterPodAffinity scores no longer force the XLA kernel: the
        Pallas solver re-folds its affinity static whenever the action
        refreshes arrays["pod_sc"] between pause/resume segments
        (pallas_solve.fold_affinity_scores).

        Tier health flows through the faults.solver_ladder breakers: a
        pallas failure (init or solve) both falls back within the cycle
        AND records against the pallas breaker, so a persistently broken
        tier sits out its backoff instead of being retried blindly every
        cycle (and, unlike the old `solver = None`, is probed again once
        the backoff elapses). An XLA-twin failure raises
        _DeviceSolveError so execute() degrades the cycle to serial."""
        from kube_batch_tpu.ops.kernels import solve_allocate_state

        ladder = faults.solver_ladder
        # The single-chip XLA twin solves from the arena's device
        # handles when available; with a mesh the arena view is sharded
        # for the GSPMD rung, so the single-chip fallback keeps host
        # arrays (resharding a committed mesh array into a single-chip
        # program is a cross-device copy jit would have to insert).
        xla_arrays = dev_arrays if (dev_arrays is not None and mesh is None) else arrays

        def _wrap(fn):
            """Node-class compressed layer (ops/class_solve,
            KBT_CLASS_COMPRESS): runs feasibility+score+argmax at class
            granularity over whichever rung was picked, expanding back
            to node-space SolveState at every segment boundary. Inside
            the budget gate — a compressed segment is still a solver
            entry — and any class-table failure degrades to ``fn``
            within the call, so the rung ladder below is unchanged."""
            from kube_batch_tpu.ops import class_solve

            if not class_solve.enabled():
                return fn
            return class_solve.wrap_solver(
                self, fn, arrays, enable_drf, enable_proportion, dtype,
                mesh=mesh,
            )

        def _with_budget(fn):
            """Solver-entry budget gate: a device solve is the cycle's
            dominant cost, so a hard budget already gone must abort
            BEFORE another segment dispatches — outside the tier
            try/except blocks, so the abort cannot be mistaken for a
            tier failure and feed a breaker."""
            if budget is None:
                return fn

            def checked(st):
                budget.check("solver entry")
                return fn(st)

            return checked

        def _xla_solve(st):
            # The device floor. Failures (organic or the solve.xla fault
            # point) feed the xla breaker and surface as _DeviceSolveError
            # — execute() runs serial for the cycle; the breaker's
            # half-open probe re-tries the device path later.
            try:
                if faults.should_fire("solve.xla"):
                    raise faults.FaultInjected("solve.xla")
                out = solve_allocate_state(
                    xla_arrays, st, enable_drf=enable_drf,
                    enable_proportion=enable_proportion,
                )
            except Exception as e:
                log.exception("XLA solve failed")
                ladder.record_failure("xla")
                raise _DeviceSolveError(str(e)) from e
            ladder.record_success("xla")
            self.last_solver_tier = "xla"
            return out

        if mesh is not None:
            from kube_batch_tpu.ops import pallas_solve
            from kube_batch_tpu.parallel import ShardedSolver

            xla_sharded = None
            try:
                # arena handles (sharded placement) when available —
                # the solver's in_shardings match, so warm cycles skip
                # the full host->mesh scatter
                xla_sharded = ShardedSolver(
                    dev_arrays if dev_arrays is not None else arrays,
                    mesh, enable_drf=enable_drf,
                    enable_proportion=enable_proportion,
                )
            except Exception:
                log.exception(
                    "sharded solver init failed; using single-chip path"
                )

            def solve_sharded(st):
                # The mesh's XLA rung. First solve still traces/compiles
                # lazily; fall back to the single-chip XLA kernel on
                # failure rather than losing the cycle.
                nonlocal xla_sharded
                if xla_sharded is not None:
                    try:
                        out = xla_sharded.solve(st)
                        self.last_solver_tier = "sharded_xla"
                        return out
                    except Exception:
                        log.exception(
                            "sharded solve failed; falling back to "
                            "single-chip XLA kernel"
                        )
                        xla_sharded = None
                return _xla_solve(st)

            # Top rung of the mesh path: the blocked sharded-Pallas
            # solver (parallel.sharded_pallas) — the fused block kernel
            # per shard, one argmax exchange per gang iteration. The
            # VMEM gate is PER SHARD (pallas_solve.mesh_supported): a
            # snapshot that overflows one chip's vmem_budget() stays on
            # the Pallas rung when its node block divided over the mesh
            # fits, instead of falling to the ~9x-slower XLA twin.
            # KBT_MESH_PALLAS=0/off disables the rung; mosaic/interpret/
            # jnp pin the block backend (default auto: mosaic on TPU
            # meshes, the jnp twin elsewhere).
            mesh_pallas = None
            mmode = (
                os.environ.get("KBT_MESH_PALLAS", "auto").strip().lower()
                or "auto"
            )
            if (
                mmode not in ("0", "off")
                and dtype == np.float32
                and ladder.allow("mesh_pallas")
                and pallas_solve.mesh_supported(arrays, mesh.devices.size)
            ):
                from kube_batch_tpu.parallel.sharded_pallas import (
                    ShardedPallasSolver,
                )

                try:
                    mesh_pallas = ShardedPallasSolver(
                        arrays, mesh, enable_drf=enable_drf,
                        enable_proportion=enable_proportion,
                        block_impl=mmode,
                    )
                    log.info(
                        "solving with blocked sharded-Pallas kernel "
                        "(%s block) over a %d-device mesh",
                        mesh_pallas.block_impl, mesh.devices.size,
                    )
                except Exception:
                    log.exception(
                        "sharded-Pallas solver init failed; using the "
                        "mesh XLA rung"
                    )
                    ladder.record_failure("mesh_pallas")

            if mesh_pallas is not None:
                mp = mesh_pallas

                def solve_mesh_pallas(st):
                    # Tracing/compile is lazy here too; a failed solve
                    # feeds the mesh_pallas breaker and degrades to the
                    # mesh XLA rung within the cycle.
                    nonlocal mp
                    if mp is not None:
                        try:
                            if faults.should_fire("solve.mesh_pallas"):
                                raise faults.FaultInjected("solve.mesh_pallas")
                            before = mp.batched_iters
                            out = mp.solve(st)
                            gained = mp.batched_iters - before
                            if gained:
                                self.last_batched_iters += gained
                                metrics.register_exchange_batched_iters(
                                    gained
                                )
                            ladder.record_success("mesh_pallas")
                            self.last_solver_tier = "mesh_pallas"
                            self.last_block_impl = mp.block_impl
                            return out
                        except Exception:
                            log.exception(
                                "sharded-Pallas solve failed; falling "
                                "back to the mesh XLA rung"
                            )
                            ladder.record_failure("mesh_pallas")
                            mp = None
                    return solve_sharded(st)

                return _with_budget(_wrap(solve_mesh_pallas))
            if xla_sharded is not None:
                log.info(
                    "solving with node-axis-sharded XLA kernel over a "
                    "%d-device mesh", mesh.devices.size,
                )
                return _with_budget(_wrap(solve_sharded))

        mode = os.environ.get("KBT_PALLAS", "1")
        solver = None
        if mode != "0" and dtype == np.float32 and ladder.allow("pallas"):
            import jax as _jax

            from kube_batch_tpu.ops import pallas_solve

            interpret = mode == "interpret"
            on_tpu = _jax.default_backend() == "tpu"  # Mosaic kernels are TPU-only
            if (on_tpu or interpret) and pallas_solve.supported(arrays):
                try:
                    solver = pallas_solve.PallasSolver(
                        arrays, enable_drf, enable_proportion, interpret=interpret
                    )
                    log.debug("solving with fused pallas kernel")
                except Exception:
                    log.exception("pallas solver init failed; using XLA kernel")
                    ladder.record_failure("pallas")
                    solver = None

        def solve_fn(st):
            # Tracing/Mosaic lowering is lazy — the first solve call can
            # still fail, so the fallback has to live here, not only at
            # solver construction. Both solvers speak SolveState, so the
            # XLA kernel resumes exactly from wherever pallas left off.
            nonlocal solver
            if solver is not None:
                try:
                    if faults.should_fire("solve.pallas"):
                        raise faults.FaultInjected("solve.pallas")
                    out = solver.solve(st)
                    ladder.record_success("pallas")
                    self.last_solver_tier = "pallas"
                    return out
                except Exception:
                    log.exception("pallas solve failed; falling back to XLA kernel")
                    ladder.record_failure("pallas")
                    solver = None
            return _xla_solve(st)

        return _with_budget(_wrap(solve_fn))

    # -- host-side serial step for one pod-affinity task ---------------------

    def _host_step(self, ssn: Session, enc, arrays, replay: "_Replayer", s):
        """Exactly the serial inner-loop body (allocate.py:90-119 /
        reference allocate.go:139-185) for the paused task, then patch the
        solver state: pointer, node vectors, job lifecycle."""
        from kube_batch_tpu.ops.kernels import KIND_ALLOCATED, KIND_PIPELINED
        from kube_batch_tpu.plugins.predicates import PredicateError
        from kube_batch_tpu.utils import (
            get_node_list,
            predicate_nodes,
            prioritize_nodes,
            select_best_node,
        )

        row = int(s.paused_at)
        task = enc.tasks[row]
        job = ssn.jobs[task.job]
        job.rev += 1  # its nodes_fit_delta is rewritten below
        jrow = int(s.cur)
        all_nodes = get_node_list(ssn.nodes)

        def predicate_fn(t, node):
            if not t.init_resreq.less_equal(node.idle) and not t.init_resreq.less_equal(
                node.releasing
            ):
                raise PredicateError(
                    f"task <{t.namespace}/{t.name}> ResourceFit failed "
                    f"on node <{node.name}>"
                )
            ssn.predicate_fn(t, node)

        if job.nodes_fit_delta:
            job.nodes_fit_delta = {}

        s.ptr[jrow] += 1
        candidates = predicate_nodes(task, all_nodes, predicate_fn)
        if not candidates:
            # serial `break`: the job leaves the heap unassigned.
            log.debug("host step: no candidates for %s; abandoning job", task.uid)
            s.job_active[jrow] = False
            return s._replace(cur=np.int32(-1), it=s.it + 1)

        node_scores = prioritize_nodes(
            task, candidates, ssn.node_order_map_fn, ssn.node_order_reduce_fn
        )
        node = select_best_node(node_scores)
        nrow = replay.node_idx[node.name]

        if task.init_resreq.less_equal(node.idle):
            kind = KIND_ALLOCATED
        else:
            delta = node.idle.clone()
            delta.fit_delta(task.init_resreq)
            job.nodes_fit_delta[node.name] = delta
            kind = KIND_PIPELINED if task.init_resreq.less_equal(node.releasing) else 0

        cur = jrow
        if kind:
            try:
                replay.apply_immediate(row, nrow, kind, int(s.step))
            except Exception as e:  # noqa: BLE001
                # Volume assume failed (the first mutation apply_one makes,
                # so session state is untouched): serial semantics — the
                # task is consumed unassigned and the loop moves on
                # (allocate.go:158-161 logs and continues).
                log.error(
                    "host step: failed to allocate task %s on %s: %s",
                    task.uid, node.name, e,
                )
                return s._replace(cur=np.int32(cur), it=s.it + np.int32(1))
            res = np.asarray(arrays["task_res"][row], s.idle.dtype)
            s.used[nrow] += res
            if kind == KIND_ALLOCATED:
                s.idle[nrow] -= res
                s.ready_cnt[jrow] += 1
            else:
                s.rel[nrow] -= res
            s.ntasks[nrow] += 1
            s.nports[nrow] |= arrays["task_ports"][row]
            s.assigned_node[row] = nrow
            s.assigned_kind[row] = kind
            s.assign_pos[row] = int(s.step)
            if replay.drf is not None:
                s.job_alloc[jrow] += res
            qrow = int(arrays["job_queue"][jrow])
            if replay.prop is not None:
                s.q_alloc[qrow] += res
                s.q_alloc_has_sc[qrow] |= bool(arrays["task_res_has_sc"][row])
            s = s._replace(step=s.step + np.int32(1))
            if int(s.ready_cnt[jrow]) >= int(arrays["job_min"][jrow]):
                cur = -1
        return s._replace(cur=np.int32(cur), it=s.it + np.int32(1))

    def _fallback(self, ssn: Session) -> None:
        from kube_batch_tpu.actions.allocate import AllocateAction

        self.last_solver_tier = "serial"
        AllocateAction().execute(ssn)


class _Replayer:
    """Applies kernel assignments to the session in bulk — the exact net
    state mutations of `ssn.allocate`/`ssn.pipeline` (session.go:198-296)
    without per-task Python session machinery:

    - task status index surgery + `job.allocated` growth (job_info.go:233-259);
    - node task map + idle/releasing/used accounting aggregated per node
      (node_info.go:108-136) — exact because milli-CPU/byte quantities are
      integers, so float addition order cannot change the sums; scalar-map
      key presence follows the same add/sub rules as the sequential path;
    - drf/proportion allocated vectors advanced per event in kernel order
      with one final share recompute (the intermediate shares the serial
      event handlers maintain are never read between events);
    - the gang dispatch barrier at `finish`: jobs whose final ready count
      clears min_available get every Allocated task dispatched —
      BindVolumes + cache.Bind + Binding status, exactly the set the
      serial flip-time dispatches produce (session.go:285-322).
    """

    def __init__(self, ssn: Session, enc, arrays, enable_drf: bool, enable_prop: bool) -> None:
        self.ssn = ssn
        self.enc = enc
        self.arrays = arrays
        # Native extension boundary: the 'native.load' fault point
        # simulates the extension failing to load for this cycle — every
        # native fast path below degrades to its Python twin at once.
        self._native = None if faults.should_fire("native.load") else _native
        self.task_res64 = np.asarray(arrays["task_res"], np.float64)
        self.task_job = np.asarray(arrays["task_job"])
        self.task_res_has_sc = np.asarray(arrays["task_res_has_sc"])
        self.job_queue = np.asarray(arrays["job_queue"])
        self.drf = ssn.plugins.get("drf") if enable_drf else None
        self.prop = ssn.plugins.get("proportion") if enable_prop else None
        self.node_idx = {name: i for i, name in enumerate(enc.node_names)}
        # Row-indexed hot lookups for the bulk loop. row_of is lazy: the
        # numeric dispatch-column path never needs it, so the 200k-entry
        # dict build is paid only on the fallback paths.
        self.task_keys = [f"{t.namespace}/{t.name}" for t in enc.tasks]
        self._row_of: "Optional[dict]" = None
        self.node_by_row = [ssn.nodes[name] for name in enc.node_names]
        self.node_tasks_by_row = [n.tasks for n in self.node_by_row]
        self.replayed = 0  # assignment events already applied
        self.alloc_jobs: set[str] = set()  # jobs with >=1 Allocated event
        # vectorized twin of alloc_jobs (job-row indexed) + the bulk
        # replay's per-segment Allocated event log — what the dispatch
        # barrier's numpy mask and numeric bind columns are built from
        self._alloc_flags = np.zeros(len(enc.jobs), bool)
        self._bulk_alloc_log: list[tuple] = []  # (rows, nrows, jrows) per segment
        # jobs that took a host-stepped (apply_immediate) event: their
        # allocated tasks may carry volume claims / binder-managed
        # volume_ready, so finish() keeps the per-task checks for them
        self.stepped_jobs: set[str] = set()
        # per-node aggregation buffers (flushed once per segment)
        self._node_buf: dict[int, _NodeDelta] = {}
        self._touched_drf: set[str] = set()
        self._touched_prop: set[str] = set()
        # wall time each task's assignment came OFF the device (its solve
        # segment's completion) — the honest per-task schedule timestamp
        # for the bulk path (reference metrics.go:66-72 stamps at
        # dispatch; one batch timestamp would smear the whole action's
        # replay time into every task's latency)
        self.decided_at = np.zeros(len(enc.tasks))

    @property
    def row_of(self) -> dict:
        if self._row_of is None:
            self._row_of = {t.uid: r for r, t in enumerate(self.enc.tasks)}
        return self._row_of

    # -- one event -----------------------------------------------------------

    def apply_one(self, row: int, nrow: int, kind: int) -> None:
        from kube_batch_tpu.ops.kernels import KIND_ALLOCATED

        ssn = self.ssn
        task = self.enc.tasks[row]
        job = ssn.jobs[task.job]
        hostname = self.enc.node_names[nrow]
        node = ssn.nodes[hostname]
        status = TaskStatus.ALLOCATED if kind == KIND_ALLOCATED else TaskStatus.PIPELINED
        # both change below, past their mutator methods
        job.rev += 1
        node.rev += 1

        if kind == KIND_ALLOCATED:
            ssn.cache.allocate_volumes(task, hostname)
            self.alloc_jobs.add(job.uid)
            self._alloc_flags[self.task_job[row]] = True
        self.stepped_jobs.add(job.uid)

        # status index surgery == update_task_status's net effect
        pend = job.task_status_index.get(TaskStatus.PENDING)
        if pend is not None:
            pend.pop(task.uid, None)
            if not pend:
                del job.task_status_index[TaskStatus.PENDING]
        task.status = status
        task.node_name = hostname
        job.task_status_index.setdefault(status, {})[task.uid] = task
        if kind == KIND_ALLOCATED:
            job.allocated.add(task.resreq)

        # node: task map entry (a clone, node_info.go:117) + deferred sums
        node.tasks[self.task_keys[row]] = task.clone_for_residency()
        buf = self._node_buf.get(nrow)
        if buf is None:
            buf = self._node_buf[nrow] = _NodeDelta()
        res64 = self.task_res64[row]
        if kind == KIND_ALLOCATED:
            buf.alloc += res64
        else:
            buf.pipe += res64
        if task.resreq.scalars:
            buf.scalar_keys.update(task.resreq.scalars)

        # drf / proportion event handlers (drf.go:135-154, proportion.go:202-223)
        if self.drf is not None:
            self.drf.job_attrs[job.uid].allocated.add(task.resreq)
            self._touched_drf.add(job.uid)
        if self.prop is not None:
            self.prop.queue_attrs[job.queue].allocated.add(task.resreq)
            self._touched_prop.add(job.queue)

    # -- a segment -----------------------------------------------------------

    def apply_immediate(self, row: int, nrow: int, kind: int, pos: int) -> None:
        """One host-stepped event, applied and flushed right away (the next
        host step's predicates need the node state current)."""
        self.apply_one(row, nrow, kind)
        import time as _time

        self.decided_at[row] = _time.time()
        self.replayed = pos + 1
        self._flush_nodes()
        # Invalidate state_seq-keyed score memos (nodeorder/tensorscore):
        # the replay mutates node accounting without going through
        # ssn.allocate/pipeline, which are what normally bump the seq.
        self.ssn.bump_state()

    def apply_upto(self, assign_pos, assigned_node, assigned_kind, step: int) -> None:
        """Apply all events with replayed <= pos < step — the same net
        state mutations as per-event `apply_one`, but with every
        order-independent aggregate (node idle/releasing/used, job
        allocated, drf/proportion vectors) computed as a vectorized
        segment sum. Exact: all quantities are integer-grid float64, so
        addition order cannot change the sums, and scalar-map key
        creation follows the same per-event add/sub rules via the
        tracked key sets."""
        from kube_batch_tpu.ops.kernels import KIND_ALLOCATED

        if step <= self.replayed:
            return
        sel = (assign_pos >= self.replayed) & (assign_pos < step)
        rows = np.nonzero(sel)[0]
        self.replayed = step
        if rows.size == 0:
            return
        import time as _time

        self.decided_at[rows] = _time.time()  # this segment's solve completion
        # Same memo invalidation as apply_immediate: bulk replay mutates
        # node.used/tasks behind the session's back.
        self.ssn.bump_state()
        rows = rows[np.argsort(assign_pos[rows], kind="stable")]
        nrows = assigned_node[rows]
        kinds = assigned_kind[rows]
        alloc = kinds == KIND_ALLOCATED
        res = self.task_res64[rows]
        tjob = self.task_job[rows]
        scalar_names = self.enc.scalar_names
        R = res.shape[1]
        empty: frozenset = frozenset()

        # -- scalar-key bookkeeping (only rows whose resreq has scalars) --
        nkeys_alloc: dict[int, set] = {}
        nkeys_pipe: dict[int, set] = {}
        jkeys_alloc: dict[int, set] = {}
        jkeys_all: dict[int, set] = {}
        qkeys: dict[int, set] = {}
        for i in np.nonzero(self.task_res_has_sc[rows])[0].tolist():
            keys = self.enc.tasks[int(rows[i])].resreq.scalars.keys()
            n_i, j_i = int(nrows[i]), int(tjob[i])
            (nkeys_alloc if alloc[i] else nkeys_pipe).setdefault(n_i, set()).update(keys)
            if alloc[i]:
                jkeys_alloc.setdefault(j_i, set()).update(keys)
            jkeys_all.setdefault(j_i, set()).update(keys)
            qkeys.setdefault(int(self.job_queue[j_i]), set()).update(keys)

        # -- node accounting (node_info.go:108-136 net effect) ------------
        touched_n = np.unique(nrows)
        compn = np.searchsorted(touched_n, nrows)
        n_alloc_vec = _segment_sum(compn[alloc], res[alloc], touched_n.size, R)
        n_pipe_vec = _segment_sum(compn[~alloc], res[~alloc], touched_n.size, R)
        # The dense cpu/mem columns update natively in one pass per pool
        # (identical f64 adds, just without 60k interpreter round trips);
        # scalar dimensions keep the Go nil-map semantics on the Python
        # side and only run for the (rare) pools whose key sets are
        # non-empty.
        axpy_native = (
            getattr(self._native, "bulk_res_axpy", None) if self._native else None
        )

        def axpy(objs, mat, sign) -> None:
            # Per-POOL fallback: the native prepass guarantees failures
            # are pre-mutation, so a variant Resource pool degrades to
            # the Python loop without double-applying sibling pools.
            if axpy_native is not None:
                try:
                    axpy_native(objs, mat, sign)
                    return
                except (TypeError, AttributeError):
                    pass
            for k, res in enumerate(objs):
                res.milli_cpu += sign * float(mat[k, 0])
                res.memory += sign * float(mat[k, 1])

        touched_n_l = touched_n.tolist()
        nodes_t = [self.node_by_row[nrow] for nrow in touched_n_l]
        # every node and job this segment touches changes past its
        # mutator methods: the sums here, the task maps and status
        # indexes below
        for node in nodes_t:
            node.rev += 1
        axpy([n.idle for n in nodes_t], n_alloc_vec, -1)
        axpy([n.releasing for n in nodes_t], n_pipe_vec, -1)
        axpy([n.used for n in nodes_t], n_alloc_vec + n_pipe_vec, 1)
        for nrow in set(nkeys_alloc) | set(nkeys_pipe):
            k = int(np.searchsorted(touched_n, nrow))
            node = self.node_by_row[nrow]
            ka = nkeys_alloc.get(nrow, empty)
            kp = nkeys_pipe.get(nrow, empty)
            _res_scalars(node.idle, n_alloc_vec[k], scalar_names, ka, -1, nil_map=True)
            _res_scalars(node.releasing, n_pipe_vec[k], scalar_names, kp, -1, nil_map=True)
            _res_scalars(
                node.used, n_alloc_vec[k] + n_pipe_vec[k], scalar_names, ka | kp, 1
            )

        # -- job.allocated + drf/proportion event bookkeeping -------------
        touched_j = np.unique(tjob)
        compj = np.searchsorted(touched_j, tjob)
        j_tot = _segment_sum(compj, res, touched_j.size, R)
        j_alloc = _segment_sum(compj[alloc], res[alloc], touched_j.size, R)
        wa = np.unique(tjob[alloc])
        self._alloc_flags[wa] = True
        drf = self.drf
        touched_j_l = touched_j.tolist()
        jobs_t = [self.enc.jobs[jrow] for jrow in touched_j_l]
        for job in jobs_t:
            job.rev += 1
        wa_pos = np.searchsorted(touched_j, wa)
        jobs_wa = [jobs_t[p] for p in wa_pos.tolist()]
        axpy([j.allocated for j in jobs_wa], j_alloc[wa_pos], 1)
        self.alloc_jobs.update(j.uid for j in jobs_wa)
        if drf is not None:
            axpy([drf.job_attrs[j.uid].allocated for j in jobs_t], j_tot, 1)
            self._touched_drf.update(j.uid for j in jobs_t)
        for jrow in jkeys_alloc:
            k = int(np.searchsorted(touched_j, jrow))
            _res_scalars(
                jobs_t[k].allocated, j_alloc[k], scalar_names,
                jkeys_alloc[jrow], 1,
            )
        if drf is not None:
            for jrow in jkeys_all:
                k = int(np.searchsorted(touched_j, jrow))
                _res_scalars(
                    drf.job_attrs[jobs_t[k].uid].allocated, j_tot[k],
                    scalar_names, jkeys_all[jrow], 1,
                )
        prop = self.prop
        if prop is not None:
            qrow_arr = self.job_queue[tjob]
            touched_q = np.unique(qrow_arr)
            compq = np.searchsorted(touched_q, qrow_arr)
            q_tot = _segment_sum(compq, res, touched_q.size, R)
            attrs_q = [
                prop.queue_attrs[self.enc.queues[qrow].name]
                for qrow in touched_q.tolist()
            ]
            axpy([a.allocated for a in attrs_q], q_tot, 1)
            self._touched_prop.update(a.name for a in attrs_q)
            for qrow in qkeys:
                k = int(np.searchsorted(touched_q, qrow))
                _res_scalars(
                    attrs_q[k].allocated, q_tot[k], scalar_names, qkeys[qrow], 1
                )

        # -- per-task surgery (status index, node task map, volumes) ------
        # Rows grouped per job (stable sort preserves assign order within
        # a job, which is what fixes sidx insertion order and therefore
        # dispatch/bind order); the status-index moves then land as one
        # C-level dict.update per (job, status) instead of per-task
        # get/setdefault (VERDICT r3 item 8, the replay diet). The
        # per-event body itself — status flip, node_name set, residency
        # clone, node task-map insert — runs in the native module when
        # built (kube_batch_tpu/native, round-4 replay diet), with the
        # Python loop as fallback and for volume-carrying rows.
        jobs_l = self.enc.jobs
        ALLOCATED, PIPELINED = TaskStatus.ALLOCATED, TaskStatus.PIPELINED
        order = np.argsort(compj, kind="stable")
        counts = np.bincount(compj, minlength=touched_j.size).tolist()
        rows_a = np.ascontiguousarray(rows[order], np.int64)
        nrows_a = np.ascontiguousarray(nrows[order], np.int64)
        alloc_a = alloc[order]
        # log this segment's Allocated events (job-major, assign order
        # within job — exactly the status-index insertion order) for the
        # dispatch barrier's numeric bind-column reconstruction
        self._bulk_alloc_log.append(
            (rows_a[alloc_a], nrows_a[alloc_a], tjob[order][alloc_a])
        )
        segments = None
        if self._native is not None:
            try:
                if faults.should_fire("native.prepass"):
                    raise ValueError("fault injected: native.prepass")
                # index vectors go down as int64 buffers — no 2x200k
                # PyLong boxing/unboxing round trip
                # trusted=True: encode_session routes volume-carrying
                # tasks host_only, so bulk rows are volume-free by
                # construction and the prepass skips its per-event
                # pod.volumes attribute read (~half of bulk_assign's
                # cost at 400k). "task_created" marks our encoder; a
                # custom EncodedSnapshot keeps the defensive prepass.
                segments = self._native.bulk_assign(
                    self.enc.tasks,
                    self.task_keys,
                    self.node_tasks_by_row,
                    self.enc.node_names,
                    rows_a,
                    nrows_a,
                    alloc_a.astype(np.uint8).tobytes(),
                    counts,
                    ALLOCATED,
                    PIPELINED,
                    "task_created" in self.enc.arrays,
                )
            except (ValueError, TypeError, AttributeError):
                # ValueError: a bulk row carries volume claims (custom
                # encoder/binder). TypeError/AttributeError: a TaskInfo
                # variant without the expected plain member slots. Either
                # way the prepass mutated nothing — take the Python path,
                # which routes volumes through cache.allocate_volumes and
                # handles any attribute layout.
                segments = None
        if segments is None:
            segments = self._assign_segments_py(
                rows_a.tolist(), nrows_a.tolist(), alloc_a.tolist(), counts
            )
        for k, jrow in enumerate(touched_j.tolist()):
            alloc_d, pipe_d = segments[k]
            sidx = jobs_l[jrow].task_status_index
            pend = sidx.get(TaskStatus.PENDING)
            if pend is not None:
                if len(alloc_d) + len(pipe_d) == len(pend):
                    # this segment consumed the job's every remaining
                    # pending task (uids are distinct and all drawn from
                    # pend) — drop the bucket whole instead of 200k
                    # one-at-a-time pops across the batch
                    del sidx[TaskStatus.PENDING]
                else:
                    for uid in alloc_d:
                        pend.pop(uid, None)
                    for uid in pipe_d:
                        pend.pop(uid, None)
                    if not pend:
                        del sidx[TaskStatus.PENDING]
            if alloc_d:
                d = sidx.get(ALLOCATED)
                if d is None:
                    sidx[ALLOCATED] = alloc_d
                else:
                    d.update(alloc_d)
            if pipe_d:
                d = sidx.get(PIPELINED)
                if d is None:
                    sidx[PIPELINED] = pipe_d
                else:
                    d.update(pipe_d)

    def _assign_segments_py(self, rows_o, nrows_o, alloc_o, counts):
        """Pure-Python twin of native.bulk_assign: per-event status flip,
        node_name set, residency clone, node task-map insert; returns one
        (alloc_d, pipe_d) pair per job segment."""
        tasks = self.enc.tasks
        tkeys = self.task_keys
        node_by_row = self.node_by_row
        alloc_volumes = self.ssn.cache.allocate_volumes
        ALLOCATED, PIPELINED = TaskStatus.ALLOCATED, TaskStatus.PIPELINED
        segments = []
        pos = 0
        for cnt in counts:
            end = pos + cnt
            alloc_d: dict = {}
            pipe_d: dict = {}
            for row, nrow_i, is_alloc in zip(
                rows_o[pos:end], nrows_o[pos:end], alloc_o[pos:end]
            ):
                task = tasks[row]
                node = node_by_row[nrow_i]
                if is_alloc:
                    if task.pod.volumes:
                        # bulk rows cannot carry claims (encode routes
                        # volume pods host_only) — guard kept for custom
                        # encoders/binders; the job keeps finish()'s
                        # per-task volume checks
                        alloc_volumes(task, node.name)
                        self.stepped_jobs.add(task.job)
                    else:
                        task.volume_ready = True
                    task.status = ALLOCATED
                    alloc_d[task.uid] = task
                else:
                    task.status = PIPELINED
                    pipe_d[task.uid] = task
                task.node_name = node.name
                node.tasks[tkeys[row]] = task.clone_for_residency()
            pos = end
            segments.append((alloc_d, pipe_d))
        return segments

    def _numeric_columns(self, mask_arr, to_bind):
        """(rows, keys, hostnames, created) for the pure-bulk dispatch
        list, reconstructed from the replay's Allocated event log by
        array gathers alone. Valid only when the log covers the ENTIRE
        dispatch list (a prior action in the actions string can leave
        Allocated tasks this encode never saw — the count check detects
        that and the caller falls back to the per-task column pass).
        Order matches bulk_dispatch's list: both are job-major with
        status-index insertion order within a job."""
        if not self._bulk_alloc_log or "task_created" not in self.enc.arrays:
            return None
        n_to_bind = len(to_bind)
        if len(self._bulk_alloc_log) == 1:
            rows_all, nrows_all, jrows_all = self._bulk_alloc_log[0]
        else:
            rows_all = np.concatenate([s[0] for s in self._bulk_alloc_log])
            nrows_all = np.concatenate([s[1] for s in self._bulk_alloc_log])
            jrows_all = np.concatenate([s[2] for s in self._bulk_alloc_log])
        sel = mask_arr[jrows_all]
        if int(sel.sum()) != n_to_bind:
            return None
        rows_b = rows_all[sel]
        nrows_b = nrows_all[sel]
        if len(self._bulk_alloc_log) > 1:
            # job-major across segments, preserving per-segment (=
            # bucket insertion) order within a job
            order = np.argsort(jrows_all[sel], kind="stable")
            rows_b = rows_b[order]
            nrows_b = nrows_b[order]
        tasks = self.enc.tasks
        if n_to_bind and (
            to_bind[0] is not tasks[int(rows_b[0])]
            or to_bind[-1] is not tasks[int(rows_b[-1])]
        ):
            # order drift (should not happen) — take the per-task pass
            return None
        keys = np.asarray(self.task_keys, dtype=object)[rows_b].tolist()
        hostnames = np.asarray(self.enc.node_names, dtype=object)[nrows_b].tolist()
        created = np.asarray(self.enc.arrays["task_created"], np.float64)[rows_b]
        return rows_b, keys, hostnames, created

    def _flush_nodes(self) -> None:
        """Fold the per-node resource deltas into NodeInfo, following
        Resource.add/sub scalar-map key rules (resource_info.go:146-166)."""
        scalar_names = self.enc.scalar_names
        for nrow, buf in self._node_buf.items():
            node = self.ssn.nodes[self.enc.node_names[nrow]]
            total = buf.alloc + buf.pipe
            _res_sub(node.idle, buf.alloc, scalar_names, buf.scalar_keys)
            _res_sub(node.releasing, buf.pipe, scalar_names, buf.scalar_keys)
            _res_add(node.used, total, scalar_names, buf.scalar_keys)
        self._node_buf = {}

    # -- end of action -------------------------------------------------------

    def _finish_dispatch_py(self, ready_cnt_l, job_min_l, to_bind, pure_bulk,
                            BINDING, bind_volumes, debug_on) -> None:
        """The per-job dispatch barrier loop (Python twin of the native
        bulk_dispatch fast path; also the only path handling host-stepped
        jobs, whose tasks may carry volumes)."""
        ssn = self.ssn
        for i, job in enumerate(self.enc.jobs):
            if job.uid not in self.alloc_jobs:
                continue
            if ready_cnt_l[i] < job_min_l[i]:
                continue
            allocated = job.task_status_index.get(TaskStatus.ALLOCATED)
            if not allocated:
                continue
            job.rev += 1
            if job.uid not in self.stepped_jobs:
                # Pure-bulk gang: every task came through bulk_assign, so
                # it is volume-less with volume_ready=True — no per-task
                # checks, one bulk index move; the status flip for ALL
                # pure-bulk gangs is a single native call after the loop
                # (nothing observes status between here and there).
                dispatched = list(allocated.values())
                pure_bulk.extend(dispatched)
                to_bind.extend(dispatched)
                binding = job.task_status_index.setdefault(BINDING, {})
                binding.update(allocated)
                job.task_status_index.pop(TaskStatus.ALLOCATED, None)
                if debug_on:
                    log.debug(
                        "dispatched gang job %s (%d tasks)", job.uid, ready_cnt_l[i]
                    )
                continue
            dispatched = []
            failed = False
            for task in allocated.values():
                if task.pod.volumes or not task.volume_ready:
                    try:
                        bind_volumes(task)
                    except Exception as e:  # noqa: BLE001
                        # Same routing as session._dispatch: errTasks
                        # resync + stop dispatching this gang (the serial
                        # path's early return, session.go:285-295).
                        log.error("failed to bind volumes of %s: %s", task.uid, e)
                        resync = getattr(ssn.cache, "resync_task", None)
                        if resync is not None:
                            resync(task)
                        failed = True
                        break
                task.status = BINDING
                dispatched.append(task)
                to_bind.append(task)
            # status-index move as one bulk update instead of per-task
            # pop/insert; on a volume failure only the dispatched prefix
            # moves (the rest stay Allocated, exactly like the serial
            # early return).
            binding = job.task_status_index.setdefault(BINDING, {})
            if not failed:
                binding.update(allocated)
                job.task_status_index.pop(TaskStatus.ALLOCATED, None)
            else:
                for task in dispatched:
                    allocated.pop(task.uid, None)
                    binding[task.uid] = task
            if debug_on:
                log.debug("dispatched gang job %s (%d tasks)", job.uid, ready_cnt_l[i])

    def finish(self, ready_cnt) -> "_Dispatch":
        """Final share sync + the gang dispatch barrier: the gangs that
        reached minMember flip to Binding and come back, with their bind
        columns, for :meth:`dispatch`."""
        ssn = self.ssn
        if self.drf is not None:
            drf = self.drf
            tot = drf.total_resource
            attrs = [drf.job_attrs[uid] for uid in self._touched_drf]
            if attrs and not tot.scalars:
                # vectorized final share sync: same comparison-dtype
                # division as helpers.share, one array op instead of
                # 2 boxed divisions x 18k touched jobs
                from kube_batch_tpu.api.numerics import comparison_dtype

                dt = comparison_dtype()
                a = np.array(
                    [(at.allocated.milli_cpu, at.allocated.memory) for at in attrs],
                    dtype=dt,
                )
                t = np.array([tot.milli_cpu, tot.memory], dtype=dt)
                s = np.where(
                    t == 0,
                    np.where(a == 0, dt(0.0), dt(1.0)),
                    a / np.where(t == 0, dt(1.0), t),
                )
                shares = np.maximum(np.maximum(s[:, 0], s[:, 1]), 0.0)
                for at, sv in zip(attrs, shares.tolist()):
                    at.share = sv
            else:
                for attr in attrs:
                    drf._update_share(attr)
        if self.prop is not None:
            for qname in self._touched_prop:
                attr = self.prop.queue_attrs[qname]
                self.prop._update_share(attr)

        job_min = self.arrays["job_min"]
        bind_volumes = ssn.cache.bind_volumes
        BINDING = TaskStatus.BINDING
        to_bind: list = []  # dispatched tasks, in dispatch order
        pure_bulk: list = []  # pure-bulk gangs' tasks: ONE status flip below
        ready_cnt_l = ready_cnt.tolist()  # one C pass, not 2 np getitems/job
        job_min_l = np.asarray(job_min).tolist()
        # Gate per-gang debug narration on the PACKAGE verbosity, not on
        # isEnabledFor: kube_batch_tpu.log._ensure_handler sets the parent
        # logger to DEBUG the first time ANY glog line is emitted (leader
        # election chatter, any errorf), which this module logger inherits
        # — isEnabledFor would then disable the native bulk_dispatch fast
        # path for the process lifetime at -v 0 (ADVICE r5, medium).
        debug_on = _glog.get_verbosity() >= 4
        mask_arr = None
        if (
            not self.stepped_jobs
            and not debug_on
            and self._native is not None
            and hasattr(self._native, "bulk_dispatch")
        ):
            # Every gang is pure-bulk (no volumes, no host steps): the
            # whole dispatch barrier is one native pass — per GANG the
            # ALLOCATED bucket moves wholesale under BINDING (dict move
            # when no bucket exists), tasks returned in dispatch order.
            # The gang-ready mask is one vector compare instead of a
            # per-job Python genexpr (the replay diet, round 6).
            jn = len(self.enc.jobs)
            mask_arr = self._alloc_flags[:jn] & (
                np.asarray(ready_cnt)[:jn] >= np.asarray(job_min)[:jn]
            )
            mask = mask_arr.astype(np.uint8).tobytes()
            # every job in the mask took an Allocated event in this
            # replay, whose apply_one/apply_upto bumped its rev
            try:
                if faults.should_fire("native.dispatch"):
                    raise TypeError("fault injected: native.dispatch")
                to_bind = self._native.bulk_dispatch(
                    self.enc.jobs, mask, TaskStatus.ALLOCATED, BINDING
                )
                pure_bulk = to_bind
            except (TypeError, AttributeError):
                to_bind, pure_bulk = [], []
                self._finish_dispatch_py(
                    ready_cnt_l, job_min_l, to_bind, pure_bulk, BINDING,
                    bind_volumes, debug_on,
                )
        else:
            self._finish_dispatch_py(
                ready_cnt_l, job_min_l, to_bind, pure_bulk, BINDING,
                bind_volumes, debug_on,
            )
        # Status flip + bind columns (rows / created / keys / hostnames).
        # Preferred: NUMERIC reconstruction from the bulk replay's own
        # Allocated event log — pure array gathers, no per-task dict
        # lookups or attribute reads (replaces native finish_columns on
        # the pure-bulk path); the flip is one native bulk_set_slot.
        # Fallbacks: the native finish_columns single pass, then the
        # Python per-task loop. The flip covers every dispatched task —
        # stepped-path tasks are already BINDING, re-setting the
        # identical value is a no-op.
        rows_b = created = keys = hostnames = None
        if to_bind and pure_bulk is to_bind and mask_arr is not None:
            cols = self._numeric_columns(mask_arr, to_bind)
            if cols is not None:
                rows_b, keys, hostnames, created = cols
                flipped = False
                if self._native is not None:
                    try:
                        self._native.bulk_set_slot(to_bind, "status", BINDING)
                        flipped = True
                    except (TypeError, AttributeError):
                        pass
                if not flipped:
                    for task in to_bind:
                        task.status = BINDING
        if to_bind and rows_b is None:
            if self._native is not None and hasattr(self._native, "finish_columns"):
                try:
                    rb, cb, keys, hostnames = self._native.finish_columns(
                        to_bind, self.row_of, self.task_keys, BINDING
                    )
                    rows_b = np.frombuffer(rb, np.int64)
                    created = np.frombuffer(cb, np.float64)
                except (TypeError, AttributeError):
                    rows_b = created = keys = hostnames = None
            if rows_b is None:
                # flip the pure-bulk gangs (a partial native prefix flip
                # is harmless: same value re-set)
                flipped = False
                if pure_bulk and self._native is not None:
                    try:
                        self._native.bulk_set_slot(pure_bulk, "status", BINDING)
                        flipped = True
                    except (TypeError, AttributeError):
                        pass
                if pure_bulk and not flipped:
                    for task in pure_bulk:
                        task.status = BINDING
                row_of = self.row_of
                tk = self.task_keys
                rows_b = np.fromiter(
                    (row_of.get(t.uid, -1) for t in to_bind),
                    np.int64,
                    count=len(to_bind),
                )
                created = np.fromiter(
                    (t.pod.metadata.creation_timestamp for t in to_bind),
                    np.float64,
                    count=len(to_bind),
                )
                keys = [
                    tk[r] if r >= 0 else f"{t.namespace}/{t.name}"
                    for t, r in zip(to_bind, rows_b.tolist())
                ]
                hostnames = [t.node_name for t in to_bind]
        return _Dispatch(to_bind, hostnames, keys, rows_b, created)

    def dispatch(self, plan: "_Dispatch") -> None:
        """The store side of :meth:`finish`'s barrier: bulk-bind the
        dispatched tasks, then record their scheduling latencies."""
        to_bind, hostnames, keys = plan.tasks, plan.hostnames, plan.keys
        if not to_bind:
            return
        cache = self.ssn.cache
        # Bulk bind: one cache mutex acquisition + one async write batch
        # for the whole action's dispatches (the replay-diet half of
        # VERDICT r3 item 8 — per-task cache.bind was the replay's
        # single largest cost at 50k).
        keyed_bind = getattr(cache, "bind_many_keyed", None)
        bind_many = getattr(cache, "bind_many", None)
        if keyed_bind is not None:
            # parallel-list form: no 200k (task, host) tuple builds
            keyed_bind(to_bind, hostnames, keys)
        elif bind_many is not None:
            pairs = list(zip(to_bind, hostnames))
            if _accepts_keys(bind_many):
                bind_many(pairs, keys=keys)
            else:
                bind_many(pairs)
        else:
            for t, h in zip(to_bind, hostnames):
                cache.bind(t, h)
        # e2e scheduling latency per dispatched pod, as one vector op
        # instead of a 50k-iteration max() loop. Each task's latency
        # ends at ITS solve segment's completion (decided_at), not at
        # one post-replay batch timestamp (reference metrics.go:66-72
        # stamps per task at dispatch). A gang can also carry tasks a
        # PRIOR action allocated (e.g. serial allocate earlier in the
        # actions string) that this encode never saw — those stamp at
        # dispatch time, exactly as the serial path would have.
        import time as _time

        rows_b = plan.rows
        decided = np.where(
            rows_b >= 0, self.decided_at[np.maximum(rows_b, 0)], _time.time()
        )
        metrics.update_task_schedule_durations(
            np.maximum(0.0, decided - plan.created)
        )


@dataclasses.dataclass
class _Dispatch:
    """What :meth:`_Replayer.finish` hands to :meth:`_Replayer.dispatch`:
    the dispatched tasks in dispatch order with their bind columns (host
    names, store keys, encode rows, creation stamps; None when nothing
    was dispatched)."""

    tasks: list
    hostnames: Optional[list]
    keys: Optional[list]
    rows: Optional[np.ndarray]
    created: Optional[np.ndarray]


def _accepts_keys(bind_many) -> bool:
    """Signature-probe for the keys= extension — catching TypeError
    around the CALL would misread an internal TypeError raised after
    partial submission as 'no keys support' and double-submit the
    batch."""
    import inspect

    try:
        params = inspect.signature(bind_many).parameters
    except (TypeError, ValueError):
        return False
    return "keys" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


def _segment_sum(seg_ids, vecs, n_segments: int, R: int) -> np.ndarray:
    """[n_segments, R] column-wise weighted bincount — the net effect of
    `np.add.at(out, seg_ids, vecs)` but ~10x faster (ufunc.at is a
    scalar scatter loop; bincount is one C pass per column). Exact:
    integer-grid float64 sums are order-independent."""
    out = np.zeros((n_segments, R))
    if seg_ids.size == 0 or n_segments == 0:
        return out
    for r in range(R):
        out[:, r] = np.bincount(seg_ids, weights=vecs[:, r], minlength=n_segments)
    return out


class _NodeDelta:
    __slots__ = ("alloc", "pipe", "scalar_keys")

    def __init__(self) -> None:
        self.alloc = 0.0  # np broadcasts to [R] on first +=
        self.pipe = 0.0
        self.scalar_keys: set[str] = set()


def _res_sub(res, vec, scalar_names, keys) -> None:
    """Resource -= vec with the Go nil-map branch: scalar entries change
    only when the receiver already tracks scalars (resource_info.go:151-153)."""
    if np.ndim(vec) == 0:  # this pool saw no assignments
        return
    res.milli_cpu -= float(vec[0])
    res.memory -= float(vec[1])
    if res.scalars and keys:
        for k in keys:
            res.scalars[k] = res.scalars.get(k, 0.0) - float(vec[2 + scalar_names.index(k)])


def _res_add(res, vec, scalar_names, keys) -> None:
    if np.ndim(vec) == 0:
        return
    res.milli_cpu += float(vec[0])
    res.memory += float(vec[1])
    for k in keys:
        res.scalars[k] = res.scalars.get(k, 0.0) + float(vec[2 + scalar_names.index(k)])


def _res_scalars(res, vec, scalar_names, keys, sign, nil_map: bool = False) -> None:
    """Scalar-dimension half of _res_add/_res_sub, for when the dense
    cpu/mem columns already went through native bulk_res_axpy. With
    ``nil_map`` the receiver's empty scalar map stays empty
    (resource_info.go:151-153 sub semantics); adds create entries."""
    if not keys or np.ndim(vec) == 0:
        return
    if nil_map and not res.scalars:
        return
    for k in keys:
        res.scalars[k] = res.scalars.get(k, 0.0) + sign * float(
            vec[2 + scalar_names.index(k)]
        )


def new() -> Action:
    return XlaAllocateAction()
