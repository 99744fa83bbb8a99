"""Cycle-level tracing, flight recorder, SLO accounting (ISSUE 11).

Covers the tentpole end to end: the span tree of a full cycle and a
streaming micro-cycle, cross-process trace propagation over a live
LoopbackBackend (the federated smoke), the flight-recorder dump landing
during a chaos kill-mid-dispatch drill and staying readable across the
takeover, SLO sliding-window math, Prometheus label escaping against a
golden file, the /debug endpoints, and the zero-cost-off guarantee.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.request

import pytest

from kube_batch_tpu import faults, metrics, obs
from kube_batch_tpu.cache import ClusterStore, SchedulerCache
from kube_batch_tpu.cache.cache import StoreBinder
from kube_batch_tpu.cache.store import PODS
from kube_batch_tpu.recovery import WriteIntentJournal, reconcile_journal
from kube_batch_tpu.scheduler import Scheduler
from kube_batch_tpu.testing import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource_list,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.registry.reset()
    faults.solver_ladder.reset()
    yield
    faults.registry.reset()
    faults.solver_ladder.reset()


@pytest.fixture
def tracing(monkeypatch, tmp_path):
    """Tracing on, flight recorder pointed at tmp, clean slates; the
    switch is armed through the env var because every scheduler cycle
    re-resolves it from conf/env (hot reload)."""
    monkeypatch.setenv(obs.ENV, "1")
    monkeypatch.setenv(obs.RECORDER_ENV, str(tmp_path / "flight"))
    obs.configure()
    obs.recorder.clear()
    obs.recorder._last_dump_mono = 0.0  # undo earlier tests' dump throttle
    obs.slo.reset()
    yield
    obs.configure("off")
    obs.recorder.clear()
    obs.slo.reset()


def wait_until(pred, timeout=15.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {what}")


XLA_CONF = """
actions: "enqueue, xla_allocate"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: predicates
  - name: nodeorder
"""

STREAM_CONF = """
actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: predicates
  - name: nodeorder
streaming: true
"""


def seed_store(store: ClusterStore, gangs: int = 2, members: int = 4,
               nodes: int = 4) -> None:
    store.create_queue(build_queue("default"))
    for i in range(nodes):
        store.create_node(
            build_node(f"n{i}", build_resource_list(cpu=16, memory="16Gi", pods=32))
        )
    for g in range(gangs):
        store.create_pod_group(build_pod_group(f"g{g}", min_member=members))
        for m in range(members):
            store.create_pod(
                build_pod(
                    name=f"g{g}-p{m}", group_name=f"g{g}",
                    req=build_resource_list(cpu=1, memory="512Mi"),
                )
            )


def make_scheduler(store, tmp_path, conf=XLA_CONF, journal=None, binder=None,
                   period=0.05):
    path = tmp_path / "conf.yaml"
    path.write_text(conf)
    cache = SchedulerCache(store, journal=journal, binder=binder)
    return cache, Scheduler(cache, scheduler_conf=str(path), schedule_period=period)


def spans_by_name(spans):
    out: dict[str, list] = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


# -- zero-cost off -----------------------------------------------------------


def test_off_every_entry_point_is_the_noop_singleton():
    assert not obs.enabled()
    assert obs.span("cycle") is obs.NOOP_SPAN
    assert obs.span("cycle", parent=("abc", "def"), attr=1) is obs.NOOP_SPAN
    # the solver's span is its own profiler annotation: off, it is the no-op
    assert obs.span("solve") is obs.NOOP_SPAN
    assert obs.current() is None
    assert obs.current_headers() == {}
    assert obs.from_headers({obs.HDR_TRACE: "t", obs.HDR_SPAN: "s"}) is None
    obs.event("ignored")  # no current span, no error
    obs.emit("time_to_bind", 0.0, 1.0, queue="q")
    assert obs.recorder.spans() == []


def test_off_cycle_records_nothing(tmp_path):
    assert not obs.enabled()
    store = ClusterStore()
    seed_store(store)
    _, sched = make_scheduler(store, tmp_path)
    sched.run_once()
    assert obs.recorder.spans() == []
    assert all(p.node_name for p in store.list(PODS))


def test_off_overhead_is_one_branch(tmp_path):
    """The hot-path guard: with tracing off, a span open is a module
    bool check returning a singleton. Guard the shape (identity, no
    recorder traffic) and a generous relative timing bound so a future
    allocation on the off path fails loudly."""
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs.span("cycle")
    off_cost = (time.perf_counter() - t0) / n
    # microseconds per call, not milliseconds: 50us/call would still
    # pass, an accidental Span() allocation + ring append would not
    assert off_cost < 5e-5


def test_off_cycle_never_touches_the_profiler(tmp_path, monkeypatch):
    """With tracing off a whole cycle — every new span site included —
    takes the no-op path: the profiler annotation is never resolved."""
    assert not obs.enabled()

    def resolved():
        raise AssertionError("the off path reached the profiler")

    monkeypatch.setattr(obs, "_annotation_cls", resolved)
    store = ClusterStore()
    seed_store(store)
    _, sched = make_scheduler(store, tmp_path)
    sched.run_once()
    assert obs.recorder.spans() == []
    assert all(p.node_name for p in store.list(PODS))


def test_off_ingest_counting_is_one_increment():
    """The per-event ingest path with tracing off: one tally increment
    and one branch around the handler — a generous per-event bound so a
    timer pair or a metrics lock on the off path fails loudly."""
    cache = SchedulerCache(ClusterStore())
    calls = []
    counted = cache._counted("pod", "add", calls.append)
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        counted(i)
    per_event = (time.perf_counter() - t0) / n
    assert len(calls) == n and cache._ingest[("pod", "add")][:2] == [n, 0.0]
    assert per_event < 2e-5


# -- span trees --------------------------------------------------------------


def _ancestors(span, by_id):
    out = []
    while span["parent_id"] in by_id:
        span = by_id[span["parent_id"]]
        out.append(span["name"])
    return out


def test_full_cycle_span_tree(tmp_path, tracing):
    journal = WriteIntentJournal(str(tmp_path / "j.wal"))
    store = ClusterStore()
    seed_store(store)
    _, sched = make_scheduler(store, tmp_path, journal=journal)
    sched.run_once()
    wait_until(lambda: all(p.node_name for p in store.list(PODS)),
               what="all pods bound")
    sched.cache.stop()

    spans = obs.recorder.spans()
    assert obs.check_tree(spans) == []
    by = spans_by_name(spans)
    for name in ("cycle", "session.open", "snapshot", "snapshot.nodes",
                 "snapshot.jobs", "action.enqueue", "action.xla_allocate",
                 "encode", "solve", "replay", "dispatch", "journal.append",
                 "session.close", "commit"):
        assert name in by, f"missing {name} span; got {sorted(by)}"
    assert "gang.assign" not in by
    cycles = [s for s in by["cycle"] if s["attrs"].get("cycle") == 1]
    assert len(cycles) == 1
    root = cycles[0]
    assert root["parent_id"] == ""
    # every span of the scheduling cycle hangs off the one root trace
    cycle_spans = [s for s in spans if s["trace_id"] == root["trace_id"]]
    for name in ("snapshot", "encode", "solve", "dispatch", "journal.append"):
        assert any(s["name"] == name for s in cycle_spans), name
    solve = next(s for s in cycle_spans if s["name"] == "solve")
    assert "tier" in solve["attrs"]
    # the gang.bind spans crossed the kb-write pool but kept the trace
    assert any(s["name"] == "gang.bind" and s["trace_id"] == root["trace_id"]
               for s in spans) or "gang.bind" not in by

    # the nesting: the cycle is session open + one span per action +
    # session close; dispatch is replay's sibling, not its child
    by_id = {s["span_id"]: s for s in cycle_spans}
    one = {s["name"]: s for s in cycle_spans}
    parent = {name: by_id[s["parent_id"]]["name"] for name, s in one.items()
              if s["parent_id"] in by_id}
    assert parent["session.open"] == parent["session.close"] == "cycle"
    assert parent["action.enqueue"] == parent["action.xla_allocate"] == "cycle"
    assert parent["snapshot"] == "session.open"
    assert parent["snapshot.nodes"] == parent["snapshot.jobs"] == "snapshot"
    assert parent["commit"] == "session.close"
    for name in ("encode", "solve", "replay", "dispatch"):
        assert parent[name] == "action.xla_allocate", name
    assert "replay" not in _ancestors(one["dispatch"], by_id)
    # the cache's first snapshot: nothing to reuse yet
    assert one["snapshot.nodes"]["attrs"] == {"objects": 4, "tasks": 0,
                                              "reused": 0, "cloned": 4}
    assert one["snapshot.jobs"]["attrs"] == {"objects": 2, "tasks": 8,
                                             "reused": 0, "cloned": 2}
    assert one["replay"]["attrs"] == {"gangs": 2, "tasks": 8}
    covered = sum(s["dur_us"] for s in cycle_spans
                  if s["parent_id"] == root["span_id"])
    assert covered <= root["dur_us"] + len(cycle_spans)


def test_spans_nest_on_the_profiler_clock(tmp_path, tracing):
    """Every span is a ``kbt.<name>`` profiler annotation: one cycle
    under the JAX profiler nests on the host plane as the span tree
    does."""
    import glob

    import jax
    from jax.profiler import ProfileData

    store = ClusterStore()
    seed_store(store)
    _, sched = make_scheduler(store, tmp_path)
    trace_dir = tmp_path / "profile"
    jax.profiler.start_trace(str(trace_dir))
    try:
        sched.run_once()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    found: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("kbt."):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))

    def inside(inner, outer):
        (i0, i1), (o0, o1) = found[inner][0], found[outer][0]
        return o0 <= i0 and i1 <= o1

    assert inside("kbt.session.open", "kbt.cycle")
    assert inside("kbt.snapshot", "kbt.session.open")
    assert inside("kbt.snapshot.jobs", "kbt.snapshot")
    assert inside("kbt.replay", "kbt.action.xla_allocate")
    assert inside("kbt.solve", "kbt.action.xla_allocate")
    assert "kbt.time_to_bind" not in found  # emitted spans carry no annotation


def test_cache_ingest_counts_events_and_times_them_only_when_tracing(
        monkeypatch):
    obs.configure("off")
    store = ClusterStore()
    cache = SchedulerCache(store)
    events, seconds = metrics.cache_events, metrics.cache_event_seconds

    def read(kind, verb):
        labels = {"kind": kind, "verb": verb}
        return events.value(labels), seconds.value(labels)

    before = {k: read(*k) for k in (("pod", "add"), ("node", "add"),
                                    ("podgroup", "add"), ("queue", "add"))}
    seed_store(store, gangs=1, members=3, nodes=2)
    # tallied per cache; exported only when a snapshot folds them in
    assert read("pod", "add") == before[("pod", "add")]
    cache.snapshot()
    for (kind, verb), n in {("pod", "add"): 3, ("node", "add"): 2,
                            ("podgroup", "add"): 1, ("queue", "add"): 1}.items():
        assert read(kind, verb) == (before[(kind, verb)][0] + n,
                                    before[(kind, verb)][1]), kind
    monkeypatch.setenv(obs.ENV, "1")
    obs.configure()
    try:
        store.create_pod(build_pod(name="g0-p3", group_name="g0",
                                   req=build_resource_list(cpu=1, memory="512Mi")))
        cache.snapshot()
    finally:
        obs.configure("off")
        obs.recorder.clear()
    n, secs = read("pod", "add")
    assert n == before[("pod", "add")][0] + 4
    assert secs > before[("pod", "add")][1]
    cache.snapshot()  # nothing new: a second fold adds nothing
    assert read("pod", "add") == (n, secs)


def test_journal_records_carry_the_cycle_trace(tmp_path, tracing):
    journal = WriteIntentJournal(str(tmp_path / "j.wal"))
    store = ClusterStore()
    seed_store(store)
    _, sched = make_scheduler(store, tmp_path, journal=journal)
    sched.run_once()
    sched.cache.stop()
    root = next(s for s in obs.recorder.spans() if s["name"] == "cycle")
    with open(journal.path, encoding="utf-8") as fh:
        intents = [json.loads(line) for line in fh
                   if '"rec":"intent"' in line]
    assert intents
    assert all(rec.get("trace") == root["trace_id"] for rec in intents)
    # unknown keys must not break replay
    replay = WriteIntentJournal.replay(journal.path)
    assert replay.corrupt == 0 and len(replay.intents) == len(intents)


def test_micro_cycle_emits_time_to_bind_spans(tmp_path, tracing):
    store = ClusterStore()
    store.create_queue(build_queue("default"))
    for i in range(4):
        store.create_node(
            build_node(f"n{i}", build_resource_list(cpu=16, memory="16Gi", pods=32))
        )
    # full-cycle period far longer than the test: every bind after the
    # initial cycle must come from a micro-cycle
    _, sched = make_scheduler(store, tmp_path, conf=STREAM_CONF, period=30.0)
    stop = threading.Event()
    t = threading.Thread(target=sched.run, args=(stop,), daemon=True)
    t.start()
    try:
        # arrive AFTER the initial full cycle harvested the resident
        # node table — the gang must bind through a micro-cycle, with
        # its arrival timestamp on record for time_to_bind
        wait_until(lambda: sched._stream_state is not None,
                   what="resident stream state")
        store.create_pod_group(build_pod_group("g0", min_member=3))
        for m in range(3):
            store.create_pod(
                build_pod(
                    name=f"g0-p{m}", group_name="g0",
                    req=build_resource_list(cpu=1, memory="512Mi"),
                )
            )
        wait_until(lambda: all(p.node_name for p in store.list(PODS))
                   and any(s["name"] == "time_to_bind"
                           for s in obs.recorder.spans()),
                   what="binds + time_to_bind spans")
    finally:
        stop.set()
        t.join(timeout=10.0)
    spans = obs.recorder.spans()
    assert obs.check_tree(spans) == []
    ttb = [s for s in spans if s["name"] == "time_to_bind"]
    assert ttb and all(s["attrs"]["queue"] == "default" for s in ttb)
    assert all(s["dur_us"] >= 1 for s in ttb)
    if sched.micro_cycles_run:
        assert any(s["name"] == "micro_cycle" for s in spans)
    # the per-queue SLO window saw the same binds
    snap = obs.slo.snapshot()
    assert snap["time_to_bind"]["default"]["n"] >= len(ttb)


# -- cross-process propagation ----------------------------------------------


def test_header_roundtrip_joins_the_trace(tracing):
    with obs.span("gang.bind") as parent:
        headers = obs.current_headers()
        assert headers[obs.HDR_TRACE] == parent.trace_id
        assert headers[obs.HDR_SPAN] == parent.span_id
    ctx = obs.from_headers(headers)
    child = obs.span("store.bind", parent=ctx)
    with child:
        pass
    assert child.trace_id == parent.trace_id
    assert child.parent_id == parent.span_id


def test_federated_smoke_joins_conflicted_bind_across_processes(tmp_path):
    """The acceptance drill: a seeded two-shard federated run over live
    LoopbackBackends with a forced stale dispatch — one connected trace
    per conflicted gang bind, Chrome trace exported, tree complete."""
    result = obs.smoke(shards=2, gangs=4, members=3, nodes=6,
                       out_dir=str(tmp_path / "smoke"))
    assert result["ok"], result
    assert result["tree_violations"] == []
    assert result["conflicted_gang_binds"] >= 1
    assert result["remote_spans_joined"] >= 1
    with open(result["chrome_trace"], encoding="utf-8") as fh:
        trace = json.load(fh)
    events = trace["traceEvents"]
    assert any(ev["ph"] == "X" and ev["name"] == "store.bind" for ev in events)
    assert any(ev["ph"] == "s" for ev in events), "missing flow start arrows"
    assert any(ev["ph"] == "f" for ev in events), "missing flow finish arrows"
    with open(result["jsonl"], encoding="utf-8") as fh:
        lines = [json.loads(line) for line in fh]
    assert len(lines) == result["spans"]


# -- flight recorder ---------------------------------------------------------


class _LeaderKilled(BaseException):
    """SIGKILL stand-in (BaseException defeats the retry ladder), same
    contract as the recovery chaos drill."""


class DyingBinder(StoreBinder):
    def __init__(self, store, die_after: int) -> None:
        super().__init__(store)
        self.left = die_after

    def bind(self, pod, hostname: str) -> None:
        if self.left <= 0:
            raise _LeaderKilled()
        self.left -= 1
        super().bind(pod, hostname)


def test_flight_recorder_dump_survives_kill_mid_dispatch(tmp_path, tracing):
    """Chaos: the leader dies mid-dispatch (after journal append, after
    some store writes). The ``bind.slow`` fault firing just before the
    kill snapshots the flight recorder, so the dump on disk holds the
    interrupted cycle's spans — and both the dump and the journal stay
    readable for the standby's takeover."""
    faults.registry.arm("bind.slow", count=1)
    journal = WriteIntentJournal(str(tmp_path / "leader.wal"))
    store = ClusterStore()
    seed_store(store, gangs=2, members=6)
    _, sched = make_scheduler(
        store, tmp_path, journal=journal,
        binder=DyingBinder(store, die_after=4),
    )
    with pytest.raises(_LeaderKilled):
        sched.run_once()
    landed = sum(1 for p in store.list(PODS) if p.node_name)
    assert 0 < landed < 12, "kill must land mid-batch"

    dump_dir = obs.recorder.dump_dir()
    dumps = [f for f in os.listdir(dump_dir) if f.endswith(".jsonl")]
    assert dumps, "fault fire must have dumped the ring pre-kill"
    assert any("fault_bind.slow" in f for f in dumps)
    with open(os.path.join(dump_dir, dumps[0]), encoding="utf-8") as fh:
        dumped = [json.loads(line) for line in fh]
    names = {s["name"] for s in dumped}
    # children of the interrupted cycle, finished before the kill
    assert {"snapshot", "encode", "solve", "journal.append"} <= names
    trace_ids = {s["trace_id"] for s in dumped if s["name"] == "solve"}
    assert len(trace_ids) == 1, "one interrupted cycle, one trace"
    # the sibling Chrome trace parses too
    chrome = [f for f in os.listdir(dump_dir) if f.endswith(".trace.json")]
    assert chrome
    with open(os.path.join(dump_dir, chrome[0]), encoding="utf-8") as fh:
        assert json.load(fh)["traceEvents"]

    # standby takeover: journal (with trace links) replays clean
    standby = WriteIntentJournal(str(tmp_path / "leader.wal"))
    report = reconcile_journal(standby, store)
    assert report.redispatched == 12 - landed
    assert all(p.node_name for p in store.list(PODS))


def test_flight_recorder_ring_is_bounded(tracing):
    obs.recorder.resize(4)
    try:
        for i in range(10):
            with obs.span("cycle", i=i):
                pass
        assert obs.recorder.trace_count() == 4
        kept = {s["attrs"]["i"] for s in obs.recorder.spans()}
        assert kept == {6, 7, 8, 9}, "ring must evict oldest traces first"
    finally:
        obs.recorder.resize(256)


def test_dump_throttle_and_disable(tmp_path, tracing, monkeypatch):
    with obs.span("cycle"):
        pass
    assert obs.recorder.dump(reason="first") is not None
    assert obs.recorder.dump(reason="second", min_interval_s=60.0) is None
    monkeypatch.setenv(obs.RECORDER_ENV, "0")
    assert obs.recorder.dump(reason="disabled") is None


# -- SLO accountant ----------------------------------------------------------


def test_slo_window_quantile_math():
    """The sketch-backed window tracks the exact nearest-rank quantiles
    within the sketch's declared relative error (DDSketch alpha = 1%);
    counts stay exact."""
    acc = obs.SLOAccountant(window_s=300.0)
    rel = obs.QuantileSketch.DEFAULT_ALPHA * 1.05
    for v in range(1, 101):
        acc.observe("time_to_bind", "tenant-a", float(v))
    acc.observe("queue_wait", "", 2.5)  # empty queue falls to "default"
    snap = acc.snapshot()
    a = snap["time_to_bind"]["tenant-a"]
    assert a["n"] == 100
    assert a["p50"] == pytest.approx(50.0, rel=rel)
    assert a["p90"] == pytest.approx(90.0, rel=rel)
    assert a["p99"] == pytest.approx(99.0, rel=rel)
    assert snap["queue_wait"]["default"]["n"] == 1
    assert acc.snapshot()["time_to_bind"]["tenant-a"]["window_s"] == 300.0


def test_slo_window_expires_old_observations():
    acc = obs.SLOAccountant(window_s=0.05)
    acc.observe("time_to_bind", "q", 1.0)
    time.sleep(0.08)
    acc.observe("time_to_bind", "q", 9.0)
    snap = acc.snapshot()
    assert snap["time_to_bind"]["q"]["n"] == 1
    assert snap["time_to_bind"]["q"]["p99"] == pytest.approx(
        9.0, rel=obs.QuantileSketch.DEFAULT_ALPHA * 1.05
    )


def test_slo_publish_lands_on_metrics_gauges():
    obs.slo.reset()
    try:
        obs.slo.observe("queue_wait", "gold", 0.25)
        obs.slo.publish()
        got = metrics.slo_queue_wait.value({"queue": "gold", "quantile": "p99"})
        assert got == pytest.approx(0.25, rel=obs.QuantileSketch.DEFAULT_ALPHA * 1.05)
        text = metrics.render_prometheus_text()
        assert 'kube_batch_tpu_slo_queue_wait_seconds{quantile="p50",queue="gold"}' in text
    finally:
        obs.slo.reset()


def test_slo_always_on_even_with_tracing_off():
    assert not obs.enabled()
    obs.slo.reset()
    try:
        obs.slo.observe("time_to_bind", "q", 0.1)
        assert obs.slo.snapshot()["time_to_bind"]["q"]["n"] == 1
    finally:
        obs.slo.reset()


# -- Prometheus text format (satellite: escaping + golden file) ---------------

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "metrics_golden.txt")


def _golden_families():
    h = metrics.Histogram("t_hist_seconds", "a histogram", (0.1, 1.0))
    h.observe(0.05, {"queue": 'say "hi"\nback\\slash'})
    h.observe(5.0, {"queue": 'say "hi"\nback\\slash'})
    h.observe(0.5)
    c = metrics.Counter("t_total", "a counter")
    c.inc({"op": "bind"}, by=3)
    g = metrics.Gauge("t_gauge", "a gauge")
    g.set(1.5, {"queue": "a\\b", "quantile": "p50"})
    return [h, c, g]


def test_metrics_exposition_matches_golden_file():
    """Pin the exact exposition text: label escaping (backslash, quote,
    newline), the +Inf bucket equal to _count, and _sum/_count emitted
    for every label set. Regenerate by running this test with
    KBT_REGEN_GOLDEN=1 after an intentional format change."""
    lines: list[str] = []
    for fam in _golden_families():
        lines.extend(metrics._render_family(fam))
    text = "\n".join(lines) + "\n"
    if os.environ.get("KBT_REGEN_GOLDEN") == "1":  # pragma: no cover
        with open(GOLDEN, "w", encoding="utf-8") as fh:
            fh.write(text)
    with open(GOLDEN, encoding="utf-8") as fh:
        assert text == fh.read()


def test_every_registered_family_exposes_help_and_type():
    """The real exposition (not the synthetic golden families) must
    carry a # HELP and # TYPE pair for every family — including the
    forensics counters — so scrapers never see an undocumented series.
    The KBT-R011 analyzer enforces the declaration side statically;
    this pins the rendered text."""
    metrics.register_unschedulable("ports")
    metrics.register_would_fit_if("ports")
    text = metrics.render_prometheus_text()
    helps = {
        line.split()[2] for line in text.splitlines()
        if line.startswith("# HELP ")
    }
    types = {
        line.split()[2] for line in text.splitlines()
        if line.startswith("# TYPE ")
    }
    assert helps == types and helps
    for name in ("kube_batch_tpu_unschedulable_total",
                 "kube_batch_tpu_would_fit_if_total"):
        assert name in helps, f"{name} missing from exposition"
    # every sample line belongs to a family that announced itself
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        bare = line.split("{")[0].split()[0]
        # histogram samples ride _bucket/_sum/_count suffixes; a family
        # may itself END in one of those (unschedule_task_count), so
        # accept the bare name first and the stripped root second
        candidates = {bare} | {
            bare[: -len(s)]
            for s in ("_bucket", "_sum", "_count")
            if bare.endswith(s)
        }
        assert candidates & helps, f"sample {bare} has no # HELP"


def test_histogram_inf_bucket_equals_count_per_label_set():
    h, _, _ = _golden_families()
    rendered = "\n".join(metrics._render_family(h))
    for labels in ({"queue": 'say "hi"\nback\\slash'}, {}):
        snap = h.snapshot(labels)
        assert snap["count"] == (2 if labels else 1)
    assert rendered.count('le="+Inf"') == 2
    assert rendered.count("t_hist_seconds_sum") == 2
    assert rendered.count("t_hist_seconds_count") == 2
    # escaped, not raw: the newline never appears verbatim in the text
    assert "\nback" not in rendered.replace("\\nback", "")


# -- /debug endpoints + hot reload -------------------------------------------


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.status, r.read().decode()


def test_debug_endpoints_serve_recorder_and_slo(tmp_path, tracing):
    from kube_batch_tpu.server import SchedulerServer

    server = SchedulerServer(
        scheduler_name="obs-test", listen_address="127.0.0.1:0",
        schedule_period=60.0,
    )
    server.start()
    try:
        with obs.span("cycle"):
            pass
        obs.slo.observe("queue_wait", "default", 0.2)
        status, body = _get(server.listen_port, "/debug/trace")
        assert status == 200
        payload = json.loads(body)
        assert payload["enabled"] is True
        assert payload["traces"] >= 1
        assert any(s["name"] == "cycle" for s in payload["spans"])
        status, body = _get(server.listen_port, "/debug/slo")
        assert status == 200
        assert json.loads(body)["queue_wait"]["default"]["n"] == 1
        status, body = _get(server.listen_port, "/metrics")
        assert status == 200
        assert "kube_batch_tpu_slo_queue_wait_seconds" in body
    finally:
        server.stop()


def test_conf_trace_key_hot_reloads_the_switch(tmp_path):
    store = ClusterStore()
    seed_store(store, gangs=0)
    conf = tmp_path / "conf.yaml"
    conf.write_text(XLA_CONF + 'trace: "on"\n')
    cache = SchedulerCache(store)
    sched = Scheduler(cache, scheduler_conf=str(conf), schedule_period=0.05)
    try:
        sched._load_conf()
        assert obs.enabled()
        conf.write_text(XLA_CONF + 'trace: "off"\n')
        sched._load_conf()
        assert not obs.enabled()
    finally:
        obs.configure("off")


def test_span_names_registry_matches_reality():
    """Every name the tree checker accepts is declared, and the five
    debug endpoints are exactly the declared surface (the KBT-R analyzer
    enforces the call-site side; this pins the registry's shape)."""
    assert len(obs.SPAN_NAMES) == len(set(obs.SPAN_NAMES))
    assert obs.DEBUG_ENDPOINTS == (
        "/debug/trace", "/debug/slo", "/debug/explain", "/debug/fleet",
        "/debug/admission",
    )
    bad = obs.check_tree([{
        "name": "not-a-span", "trace_id": "t", "span_id": "s",
        "parent_id": "missing",
    }])
    assert len(bad) == 2  # undeclared name + dangling parent
