"""L6: the scheduler loop (reference pkg/scheduler/scheduler.go:35-102).

``Scheduler`` owns a cache and drives the session pipeline on a fixed
period: every cycle it (re-)loads the scheduler configuration, opens a
session over a fresh ``cache.snapshot()``, runs the configured actions
in order, and records per-action and end-to-end latency — the metric
families the reference emits from the same spot
(scheduler.go:88-102).

Divergences from the reference, by design:

- the conf file is re-read **every cycle** (the reference loads it once
  at startup, scheduler.go:63-85); a conf push takes effect on the next
  cycle without a restart, and a broken conf falls back to the previous
  good one rather than killing the loop;
- the default action pipeline is ``enqueue, allocate, backfill``: the
  reference's ``allocate, backfill`` default (util.go:31-42) relies on
  Go's zero-value PodGroup phase ("") passing allocate's Pending gate
  (allocate.go:52); our object model defaults the phase to Pending, so
  the enqueue action (enqueue.go:66-119) owns that gate explicitly.
"""

from __future__ import annotations

import contextvars
import threading
import time
from typing import Optional

import kube_batch_tpu.actions  # noqa: F401  (registers the action pipeline)
import kube_batch_tpu.plugins  # noqa: F401  (registers the plugin builders)
from kube_batch_tpu import faults, log, metrics, obs, pipeline
from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.obs import explain as _obs_explain
from kube_batch_tpu.obs import fleet as _obs_fleet
from kube_batch_tpu.conf import (
    load_scheduler_conf,
    parse_scheduler_conf,
    read_scheduler_conf,
)
from kube_batch_tpu.faults import mutation_detector
from kube_batch_tpu.framework import close_session, open_session
from kube_batch_tpu.recovery.budget import CycleBudget, CycleDeadlineExceeded
from kube_batch_tpu.utils import collector


def _env_float(name: str, default: float) -> float:
    import os

    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        log.errorf(
            "%s=%r is not a number; using %g", name, os.environ.get(name), default
        )
        return default

DEFAULT_SCHEDULER_CONF = """
actions: "enqueue, allocate, backfill"
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


class Scheduler:
    """reference scheduler.go:35-61."""

    def __init__(
        self,
        cache,
        scheduler_conf: Optional[str] = None,
        schedule_period: float = 1.0,
    ) -> None:
        # A scheduler process wants the persistent XLA compile cache
        # (restart/failover skips the bucket compiles); the call is lazy
        # so embedders who configure jax themselves are never overridden.
        from kube_batch_tpu.ops import enable_compilation_cache

        enable_compilation_cache()
        self.cache = cache
        self.scheduler_conf = scheduler_conf  # path; None -> default conf
        self.schedule_period = schedule_period
        self.actions = []
        self.plugins = []
        self.action_arguments: dict[str, dict[str, str]] = {}
        self._conf_cache: Optional[str] = None
        # Cycle deadline budget (recovery/budget.py): soft overruns arm
        # a solver-tier downgrade through the ladder breakers; a hard
        # overrun aborts the cycle pre-dispatch. 0/unset = no deadline.
        self._soft_deadline = _env_float("KBT_CYCLE_SOFT_DEADLINE_S", 0.0) or None
        self._hard_deadline = _env_float("KBT_CYCLE_HARD_DEADLINE_S", 0.0) or None
        # Bounded-staleness guard: refuse to schedule over a snapshot
        # older than this (watch-fed caches report real age; the
        # in-process store reports 0). 0 = guard off.
        self._max_snapshot_age = _env_float("KBT_MAX_SNAPSHOT_AGE_S", 0.0)
        # Consecutive soft overruns — tracked here, NOT via breaker
        # record_failure: a slow-but-successful solve records a breaker
        # success every cycle, which would reset per-call failures and
        # make the downgrade unreachable.
        self._soft_overruns = 0
        # Streaming mode (streaming.py): event-driven micro-cycles
        # between periodic full cycles. Armed by the conf `streaming:`
        # key or KBT_STREAMING; _stream_state is non-None only while
        # _run_streaming is live, and run_once harvests its resident
        # node table through it.
        self._conf_streaming = False
        self._conf_trace = ""
        self._conf_explain = ""
        self._conf_fleet = ""
        self._stream_state = None
        self._stream_trigger = None
        self.micro_cycles_run = 0
        self._load_conf()

    def _load_conf(self) -> None:
        """Load (or re-load) the conf; on failure keep the last good one
        (reference scheduler.go:69-85 falls back to the default)."""
        conf_str = DEFAULT_SCHEDULER_CONF
        if self.scheduler_conf:
            try:
                conf_str = read_scheduler_conf(self.scheduler_conf)
            except OSError as e:
                log.errorf(
                    "Failed to read scheduler configuration %r, using %s: %s",
                    self.scheduler_conf,
                    "previous" if self._conf_cache else "default",
                    e,
                )
                conf_str = self._conf_cache or DEFAULT_SCHEDULER_CONF
        if conf_str == self._conf_cache:
            # env flips (KBT_TRACE/KBT_EXPLAIN/KBT_FLEET) still apply
            # between conf pushes; the conf value, when set, wins
            obs.configure(self._conf_trace)
            _obs_explain.configure(self._conf_explain)
            _obs_fleet.configure(self._conf_fleet)
            return
        try:
            self.actions, self.plugins, self.action_arguments = load_scheduler_conf(
                conf_str
            )
            self._conf_cache = conf_str
            parsed = parse_scheduler_conf(conf_str)
            self._conf_streaming = parsed.streaming
            self._conf_trace = parsed.trace
            obs.configure(parsed.trace)
            self._conf_explain = parsed.explain
            _obs_explain.configure(parsed.explain)
            self._conf_fleet = parsed.fleet
            _obs_fleet.configure(parsed.fleet)
            # Conf-driven fault drills (the `faults:` key, same grammar as
            # KBT_FAULTS): armed only when the conf actually changed, so a
            # drill's fire counts are not re-armed every cycle.
            if parsed.faults:
                faults.registry.configure(parsed.faults)
        except Exception as e:  # noqa: BLE001 - bad conf must not kill the loop
            if self._conf_cache is None:
                raise
            log.errorf("Failed to load scheduler configuration, keeping previous: %s", e)

    def run(self, stop: threading.Event) -> None:
        """Start the cache and loop run_once until stopped
        (reference scheduler.go:63-86). When streaming mode is armed
        (conf `streaming:` key or KBT_STREAMING), the fixed-period sleep
        is replaced by the event-driven micro-cycle loop; flipping the
        conf key off returns here on the next iteration."""
        self.cache.run()
        self.cache.wait_for_cache_sync()
        while not stop.is_set():
            if self._streaming_on():
                self._run_streaming(stop)
                continue
            start = time.perf_counter()
            try:
                self.run_once()
            except Exception as e:  # noqa: BLE001 - a bad cycle must not kill the loop
                log.errorf("scheduling cycle failed: %s", e)
            elapsed = time.perf_counter() - start
            stop.wait(max(0.0, self.schedule_period - elapsed))

    def _streaming_on(self) -> bool:
        from kube_batch_tpu import streaming

        return streaming.enabled() or self._conf_streaming

    def _run_streaming(self, stop: threading.Event) -> None:
        """The streaming loop (streaming.py): full cycles keep running
        every schedule_period as the fairness/preemption backstop; in
        between, the trigger wakes on store churn and micro-cycles
        drain the dirty-gang backlog against the resident node table.
        Any micro-cycle that cannot complete degrades to an immediate
        full cycle — arrivals are never dropped, only served slower."""
        from kube_batch_tpu import streaming

        # Federated cache (duck-typed by its slot-ownership surface):
        # peer shards' binds cross the pod filter as bound-pod
        # adds/deletes — absorb them as occupancy patches instead of
        # degrading to a full cycle per peer bind. Safe because a
        # federated cache forces conditional binds: if the absorbed view
        # ever lags, the store rejects and the retry ladder resyncs.
        absorb = hasattr(self.cache, "set_owned_slots")
        trigger = streaming.StreamTrigger(absorb_external=absorb)
        state = streaming.StreamState()
        self._stream_trigger = trigger
        self._stream_state = state
        log.infof(
            "streaming mode on: micro-cycles between full cycles every %.2fs",
            self.schedule_period,
        )
        # attach immediately before the try: anything between the
        # registration and the protecting finally is one exception away
        # from a leaked listener firing into a dead loop (KBT-C005)
        trigger.attach()
        try:
            next_full = time.monotonic()  # first full cycle immediately
            while not stop.is_set() and self._streaming_on():
                now = time.monotonic()
                if now >= next_full:
                    try:
                        self.run_once()  # harvests the resident table
                    except Exception as e:  # noqa: BLE001
                        log.errorf("scheduling cycle failed: %s", e)
                        state.invalidate("full cycle failed")
                    next_full = time.monotonic() + self.schedule_period
                    continue
                if not trigger.wait(min(next_full - now, 0.5)):
                    continue
                work = trigger.drain()
                handled = False
                try:
                    handled = self.run_micro(work)
                except Exception as e:  # noqa: BLE001
                    log.errorf(
                        "micro-cycle failed: %s; degrading to a full cycle", e
                    )
                    state.invalidate("micro-cycle failed")
                    metrics.register_micro_cycle("degraded")
                if not handled:
                    next_full = time.monotonic()  # backstop now, not in period
        finally:
            trigger.detach()
            self._stream_trigger = None
            self._stream_state = None
            log.infof("streaming mode off: back to the fixed-period loop")

    def on_owned_slots_changed(self, adopted_keys, removed_keys=()) -> None:
        """Shard-slot ownership changed mid-run (federation.py
        ShardSlotManager adoption/handoff). In streaming mode, seed the
        adopted gang keys into the trigger and prune the handed-off
        ones — the resident node table stays valid (node state did not
        change), so only the adopted keys' gangs need solving and the
        next micro-cycle serves exactly them. In periodic mode the next
        full cycle re-snapshots the widened mirror; nothing to do."""
        trigger = self._stream_trigger
        if trigger is None:
            return
        if removed_keys:
            trigger.prune(set(removed_keys))
        if adopted_keys:
            trigger.seed(set(adopted_keys))

    def run_micro(self, work) -> bool:
        """One micro-cycle over the drained churn. Returns True when the
        backlog was served (or there was nothing to solve); False means
        the caller must run a full cycle now — the resident table was
        stale/invalid, a fault fired, or the cycle aborted on deadline.
        Either way no arrival is lost: the trigger keeps every gang
        until ``prune`` sees it bound or gone."""
        from kube_batch_tpu import streaming  # noqa: F401  (docs pair this file)

        st = self._stream_state
        trigger = self._stream_trigger
        if st is None or trigger is None:
            return False
        # A previous full cycle's deferred dispatch must land before the
        # micro-cycle clones jobs (micro-cycles themselves never defer —
        # their outcome accounting reads the session synchronously).
        if not pipeline.fence.wait():
            metrics.register_micro_cycle("fence")
            log.errorf(
                "dispatch fence did not clear before micro-cycle; degrading "
                "to a full cycle (pipeline degraded: %s)",
                pipeline.fence.degraded_reason,
            )
            return False
        if not st.valid:
            metrics.register_micro_cycle("stale")
            log.V(4).infof("micro-cycle skipped: resident table invalid (%s)", st.reason)
            return False
        if work.stale:
            st.invalidate(work.stale_reason)
            metrics.register_micro_cycle("stale")
            log.infof(
                "resident table stale (%s); degrading to a full cycle",
                work.stale_reason,
            )
            return False
        with obs.span("micro_cycle", gangs=len(work.gangs)) as mspan, collector.cycle():
            if faults.should_fire("stream.micro_cycle"):
                # injected micro-solve failure: invalidate and degrade to the
                # backstop full cycle — the backlog is untouched, no pod drops
                st.invalidate("stream.micro_cycle fault")
                metrics.register_micro_cycle("fault")
                return False
            # no _load_conf() here: conf reload (a file read + parse) stays a
            # full-cycle affair — the backstop cycle picks up pushes within
            # one schedule_period, and the micro hot path stays disk-free
            detector = None
            if mutation_detector.enabled():
                store = getattr(self.cache, "store", None)
                if store is not None:
                    detector = mutation_detector.MutationDetector(store)
                    detector.snapshot()
            if hasattr(self.cache, "cycle"):
                self.cache.cycle += 1
                mspan.set_attr("cycle", self.cache.cycle)
            st.apply_node_patches(work.node_patches)
            if work.bound_patches and not st.apply_bound_patches(work.bound_patches):
                # peer-shard occupancy churn the resident table could not
                # absorb: degrade to the backstop full cycle, backlog kept
                metrics.register_micro_cycle("stale")
                log.infof(
                    "resident table could not absorb bound-pod churn (%s); "
                    "degrading to a full cycle", st.reason,
                )
                return False
            cloned, missing = self.cache.clone_jobs_for_stream(work.gangs)
            # A gang is solvable only once enough of it exists: the podgroup
            # add event lands before its member pods, and a mid-burst drain
            # sees a partial gang — opening a session for either wastes a
            # full micro-cycle (the gang gate would discard it anyway). A
            # deferred gang stays in the backlog; its remaining pod arrivals
            # re-wake the trigger, and the backstop full cycle catches any
            # gang that never completes.
            jobs = {}
            settled = set(missing)
            for uid, job in cloned.items():
                pending = job.task_status_index.get(TaskStatus.PENDING)
                if not pending:
                    settled.add(uid)  # fully placed (or empty): nothing to solve
                elif len(job.tasks) >= job.min_available:
                    jobs[uid] = job
            if settled:
                trigger.prune(settled)
            if not jobs:
                metrics.register_micro_cycle("empty")
                return True
            from kube_batch_tpu.streaming import open_micro_session

            budget = CycleBudget(self._soft_deadline, self._hard_deadline)
            ssn = open_micro_session(
                self.cache, self.plugins, self.action_arguments,
                jobs, st.nodes, self.cache.clone_queues_for_stream(),
            )
            ssn.cycle_budget = budget
            ssn.micro_cycle = True  # xla_allocate reads this for the
            # resident-interpod hint; tests read it to prove the micro path ran
            aborted: Optional[CycleDeadlineExceeded] = None
            failed = True
            try:
                for action in self.actions:
                    try:
                        action_start = time.perf_counter()
                        with obs.span("action." + action.name):
                            action.execute(ssn)
                        metrics.update_action_duration(
                            action.name, time.perf_counter() - action_start
                        )
                        budget.check(f"after action {action.name}")
                    except CycleDeadlineExceeded as e:
                        aborted = e
                        break
                failed = False
            finally:
                if failed or aborted is not None:
                    # the session may have mutated the resident table before
                    # dying — rebuild it from the next full snapshot
                    st.invalidate("micro-cycle aborted" if aborted else "micro-cycle failed")
                else:
                    done = {
                        uid
                        for uid, job in ssn.jobs.items()
                        if not job.task_status_index.get(TaskStatus.PENDING)
                    }
                    trigger.prune(done)
                close_session(ssn, discard=failed or aborted is not None)
                self.micro_cycles_run += 1
            if aborted is not None:
                metrics.register_micro_cycle("aborted")
                metrics.register_cycle_overrun("hard")
                mspan.set_attr("aborted", str(aborted))
                obs.recorder.dump(reason="hard_deadline", min_interval_s=1.0)
                log.errorf(
                    "micro-cycle aborted: %s (session discarded; degrading to a "
                    "full cycle)", aborted,
                )
                return False
            if detector is not None:
                detector.verify()  # raises CacheMutationError on violation
            metrics.register_micro_cycle("ok")
            return True

    def run_once(self) -> None:
        """One scheduling cycle (reference scheduler.go:88-102)."""
        log.V(4).infof("Start scheduling ...")
        cycle_start = time.perf_counter()
        self._load_conf()  # before the span: a conf push may flip tracing

        # collector.cycle(): no automatic cyclic collection inside the
        # cycle; its boundary (utils/collector.py) collects and freezes
        # the survivors on the way out, still inside the cycle span
        with obs.span("cycle") as cspan, collector.cycle():
            # Dispatch fence (pipeline.py, KBT_PIPELINE): the previous
            # cycle's deferred dispatch must land before this cycle
            # snapshots — same ordering the synchronous path gets for
            # free. A timeout degrades the pipeline to synchronous
            # cycles (sticky, loud) and skips this cycle; the wedged
            # dispatch stays armed so the next cycle re-joins it.
            if not pipeline.fence.wait():
                cspan.set_attr("skipped", "pipeline_fence")
                log.errorf(
                    "dispatch fence did not clear; skipping this cycle "
                    "(pipeline degraded: %s)", pipeline.fence.degraded_reason,
                )
                return

            # Bounded-staleness guard: scheduling over a stale mirror binds
            # pods onto nodes that may no longer exist — refuse the cycle
            # and let the watch client catch up (the k8s contract is the
            # same: a scheduler partitioned from the apiserver stops).
            if self._max_snapshot_age > 0:
                age_fn = getattr(self.cache, "snapshot_age", None)
                age = age_fn() if age_fn is not None else 0.0
                if age > self._max_snapshot_age:
                    metrics.register_stale_cycle_skip()
                    cspan.set_attr("skipped", "stale_snapshot")
                    log.errorf(
                        "snapshot is %.1fs stale (threshold %.1fs); refusing to "
                        "schedule this cycle", age, self._max_snapshot_age,
                    )
                    return

            # Cycle id for the write-intent journal (recovery/journal.py):
            # every bind/evict this cycle dispatches carries it, so a
            # takeover can group in-flight intents by statement.
            if hasattr(self.cache, "cycle"):
                self.cache.cycle += 1
                cspan.set_attr("cycle", self.cache.cycle)

            # Cache-mutation detector (VERDICT row 58): when enabled (tier-1
            # runs set KBT_CACHE_MUTATION_DETECTOR), digest the store's
            # objects before plugin+action execution and verify after — any
            # plugin/action mutating shared cluster state in place fires.
            detector = None
            if mutation_detector.enabled():
                store = getattr(self.cache, "store", None)
                if store is not None:
                    detector = mutation_detector.MutationDetector(store)
                    detector.snapshot()

            budget = CycleBudget(self._soft_deadline, self._hard_deadline)
            ssn = open_session(self.cache, self.plugins, self.action_arguments)
            # Actions read the budget off the session (xla_allocate threads
            # the remaining budget into its solver entry and checks it at
            # every pre-dispatch boundary).
            ssn.cycle_budget = budget
            aborted: Optional[CycleDeadlineExceeded] = None
            deferred_finish = False
            try:
                for action in self.actions:
                    try:
                        # a previous action's deferred dispatch must land
                        # before the next action reads the session
                        if ssn.deferred_dispatch is not None:
                            pipeline.join_session(ssn)
                        action_start = time.perf_counter()
                        with obs.span("action." + action.name):
                            action.execute(ssn)
                        metrics.update_action_duration(
                            action.name, time.perf_counter() - action_start
                        )
                        # post-action gate: a cycle already past its hard
                        # budget must not start the next action
                        budget.check(f"after action {action.name}")
                    except CycleDeadlineExceeded as e:
                        aborted = e
                        break
            finally:
                if ssn.deferred_dispatch is not None and aborted is None:
                    # Pipelined cycle: the last action's dispatch is in
                    # flight on the kb-write pool. Chain the cycle's tail
                    # (streaming harvest, close, e2e metrics, detector
                    # verify) behind its Future so run_once returns and
                    # the next cycle's encode/solve overlaps the
                    # dispatch; the fence keeps the cycles ordered.
                    deferred_finish = True
                    self._finish_deferred(ssn, cycle_start, detector)
                else:
                    # streaming harvest: grab the session's node table BEFORE
                    # close_session rebinds it — micro-cycles solve against this
                    # resident state until the next full cycle replaces it
                    if self._stream_state is not None:
                        self._stream_state.adopt_full_cycle(ssn, aborted=aborted is not None)
                    # discard on abort: skip the status write-back so the
                    # store stays byte-identical to the cycle's start (every
                    # abort point is pre-dispatch)
                    close_session(ssn, discard=aborted is not None)
                    metrics.update_e2e_duration(time.perf_counter() - cycle_start)
                    metrics.schedule_attempts.inc()
                    log.V(4).infof("End scheduling ...")
            if aborted is not None:
                metrics.register_cycle_overrun("hard")
                cspan.set_attr("aborted", str(aborted))
                # the interrupted cycle's spans are exactly what a
                # post-mortem needs — dump the ring (throttled)
                obs.recorder.dump(reason="hard_deadline", min_interval_s=1.0)
                log.errorf(
                    "scheduling cycle aborted: %s (session discarded; pending "
                    "gangs reschedule next cycle)", aborted,
                )
            elif budget.soft_exceeded():
                self._arm_tier_downgrade(budget)
            else:
                self._soft_overruns = 0  # a within-budget cycle clears the streak
            if detector is not None and not deferred_finish:
                detector.verify()  # raises CacheMutationError on violation

    def _finish_deferred(self, ssn, cycle_start: float, detector) -> None:
        """Chain a pipelined cycle's tail behind its deferred dispatch.
        Runs on the kb-write pool thread when the dispatch lands; any
        failure is logged and degrades the pipeline (the synchronous
        path would have surfaced it through run()'s catch-log)."""
        stream_state = self._stream_state

        # the pool thread has no ambient span: close the session under
        # the cycle's context so session.close stays in the cycle's trace
        ctx = contextvars.copy_context()

        def _finish(_fut) -> None:
            try:
                if stream_state is not None:
                    stream_state.adopt_full_cycle(ssn, aborted=False)
                # joins the (now done) deferred future
                ctx.run(close_session, ssn)
                metrics.update_e2e_duration(time.perf_counter() - cycle_start)
                metrics.schedule_attempts.inc()
                if detector is not None:
                    detector.verify()  # raises CacheMutationError on violation
                log.V(4).infof("End scheduling ...")
            except Exception as e:  # noqa: BLE001 - must not kill the pool thread
                log.errorf("pipelined cycle tail failed: %s", e)
                pipeline.fence.degrade(f"cycle tail raised {type(e).__name__}: {e}")

        ssn.deferred_dispatch.add_done_callback(_finish)

    def _arm_tier_downgrade(self, budget: CycleBudget) -> None:
        """Soft overrun: consecutive slow cycles trip the breaker of the
        tier that ran them (faults/ladder.py), routing the next cycles
        one rung down — instead of the cycle stalling until the lease
        watchdog calls a healthy leader dead. The streak is counted
        here (see __init__) and the trip reuses the breaker automaton:
        open -> backoff -> half-open probe -> close."""
        metrics.register_cycle_overrun("soft")
        self._soft_overruns += 1
        tier = next(
            (
                getattr(a, "last_solver_tier", None)
                for a in self.actions
                if getattr(a, "last_solver_tier", None) not in (None, "none")
            ),
            None,
        )
        if tier == "sharded_xla":
            tier = "xla"  # the sharded rung shares the xla breaker
        ladder = faults.solver_ladder
        breaker = ladder.breakers.get(tier)
        if breaker is None:
            log.warningf(
                "cycle exceeded soft deadline (%.2fs > %.2fs) on tier %s "
                "(no breaker to arm)", budget.elapsed(), budget.soft_s, tier,
            )
            return
        if self._soft_overruns >= breaker.failure_threshold:
            ladder.trip(tier)
            self._soft_overruns = 0
            log.warningf(
                "cycle exceeded soft deadline (%.2fs > %.2fs) repeatedly; "
                "tripped solver tier %s (ladder downgrades until the "
                "recovery probe closes it)", budget.elapsed(), budget.soft_s, tier,
            )
        else:
            log.warningf(
                "cycle exceeded soft deadline (%.2fs > %.2fs) on tier %s "
                "(downgrade trips after %d consecutive overruns)",
                budget.elapsed(), budget.soft_s, tier, breaker.failure_threshold,
            )
