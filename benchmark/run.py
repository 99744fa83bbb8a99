"""The benchmark: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It seeds a `ClusterStore` from the cell's configuration,
builds the scheduler the way `server.py` does (`Scheduler` over a
`SchedulerCache` of that store, the configuration's scheduler conf),
warms up every task bucket the window will use, then drives
`Scheduler.run_once` for ``--seconds`` of whole cycles while the traffic
mix writes into the store at each cycle boundary. What the mix does at a
boundary, in warm-up, in the window and in the drain after it is its
loop's (benchmark/loops/<loop>.py, named by the mix's "loop"). After the
drain it reads the device's peak memory, frees the scheduler, and
compares a sample of the measured cycles, drawn from the seed, with the
plain reference (benchmark/reference.py).

Earlier lines on stdout: set-up, per-cycle phases, compile counts and
generator lateness. The last line is the result object; the last lines
on stderr are each compared number beside its limit. No TPU, fewer
chips than the cell asks for, x64 on, the native host loops missing, a
cycle that left the `pallas` rung, a compile inside the window, or a
program hook the harness needs gone: exit code 2 and no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TIER = "pallas"
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class Refused(RuntimeError):
    """A condition under which a run's numbers would not be the chip's."""


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


# -- the cell, from BENCHMARK.json and the files it names -------------------


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise Refused(f"no BENCHMARK.json at {ROOT}")
    with open(path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return spec, cell, config, traffic


def metrics_for(spec: dict, cell: str, trace: bool) -> list:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# -- the device ---------------------------------------------------------------


def preflight(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise Refused(f"{chips} chips asked for, JAX found {len(devices)}")
    if jax.config.jax_enable_x64:
        raise Refused("jax_enable_x64 is on; the device path solves in float32")
    from kube_batch_tpu import native

    if native.lib is None:
        raise Refused("the native host loops are missing; the scheduler fell back to Python")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or
    where JAX_COMPILATION_CACHE_DIR says); every program is kept, so
    only a checkout's first run compiles."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def peak_memory() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# -- one run ----------------------------------------------------------------


class Runner:
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool) -> None:
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.cycles: list[dict] = []
        self.aborted = 0

    def build(self) -> None:
        from benchmark.cluster import Cluster
        from kube_batch_tpu.cache import SchedulerCache
        from kube_batch_tpu.scheduler import Scheduler

        self.cluster = Cluster(self.config, self.traffic, self.seed)
        self.loop = load_module("loops", self.traffic["loop"]).Loop(self)
        self.cluster.seed()
        self.cache = SchedulerCache(self.cluster.store)
        resync = hook(self.cache, "resync_task")

        def counted_resync(task):  # a bind or evict write that failed
            self.cluster.write_errors += 1
            resync(task)

        self.cache.resync_task = counted_resync
        conf = os.path.join(ROOT, self.config["scheduler_conf"])
        self.sched = Scheduler(self.cache, scheduler_conf=conf)
        self.cluster.watch()
        self.allocate = next(a for a in self.sched.actions if a.name == "xla_allocate")
        if self.trace:
            self._annotate_actions()

    def _annotate_actions(self) -> None:
        """Host annotations around each action and around the session's
        open and close, so the trace reduction can name the device's idle
        gaps. Traced runs only; the program's code is not changed."""
        from jax.profiler import TraceAnnotation

        from kube_batch_tpu import scheduler

        for name in ("open_session", "close_session"):
            fn = getattr(scheduler, name)

            def wrapped(*a, fn=fn, label="session." + name.split("_")[0], **kw):
                with TraceAnnotation(label):
                    return fn(*a, **kw)

            setattr(scheduler, name, wrapped)
        for action in self.sched.actions:
            run = action.execute

            def execute(ssn, run=run, label=f"action.{action.name}"):
                with TraceAnnotation(label):
                    return run(ssn)

            action.execute = execute

    def one_cycle(self, arrivals: list, idle_ok: bool = False) -> dict:
        """Boundary writes, then one `run_once`; refuses a cycle that left
        the device path (``idle_ok``: a drain cycle may find nothing to
        solve)."""
        from kube_batch_tpu import faults, metrics
        from kube_batch_tpu.analysis.trace.sentinel import compile_count
        from kube_batch_tpu.faults.ladder import CLOSED

        cl = self.cluster
        t0 = time.perf_counter()
        with self._annotation("bench.ingest"):
            writes = self.loop.boundary(arrivals)
        t1 = time.perf_counter()
        pending, pending_jobs = cl.pending_pods(), cl.pending_jobs()
        cl.start_cycle()
        overruns0 = sum(metrics.cycle_overruns.samples().values())
        c0 = compile_count()
        with self._annotation("bench.cycle"):
            self.sched.run_once()
        t2 = time.perf_counter()
        tier = self.allocate.last_solver_tier
        breaker = faults.solver_ladder.breakers.get(TIER)
        if tier != TIER and not (idle_ok and tier == "none"):
            raise Refused(f"cycle {cl.cycle} solved on {tier!r}, not {TIER!r}")
        if breaker is not None and (breaker.state != CLOSED or breaker.failures):
            raise Refused(f"cycle {cl.cycle}: the {TIER} breaker is {breaker.state}")
        overruns = sum(metrics.cycle_overruns.samples().values()) - overruns0
        self.aborted += int(overruns > 0)
        timings = self.allocate.last_timings or {}
        return {
            "cycle": cl.cycle, "start": t0, "end": t2, "ingest_s": t1 - t0,
            "wall_s": t2 - t0, "run_once_s": t2 - t1, "writes": writes,
            "arrivals": sum(len(j.pods) for j in arrivals),
            "binds": cl.binds_per_cycle.get(cl.cycle, 0),
            "pending_before": pending, "pending_jobs": pending_jobs, "compiles": compile_count() - c0,
            "replay_s": timings.get("replay_s"), "tier": tier,
        }

    def _annotation(self, label: str):
        if not self.trace:
            import contextlib

            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(label)

    # -- phases ---------------------------------------------------------

    def warm_up(self) -> None:
        """The loop's warm-up cycles (every task and job bucket the window
        will use), then the arena's row buckets."""
        for arrivals in self.loop.warmup():
            row = self.one_cycle(arrivals)
            emit(phase="warmup", **{k: v for k, v in row.items() if k not in ("start", "end")})
        self._warm_row_buckets()

    def _warm_row_buckets(self) -> None:
        """`xla_allocate`'s arena uploads a node slab's changed rows as one
        scatter padded to a power-of-two row count, compiled per count;
        which counts a window hits depends on how many nodes its cycles
        touch. Compile every count the arena takes, on buffers of the
        slabs' own shape and type, so that none compiles in the window.
        The program has no public warm-up for them: a hook gone refuses
        the run, so that a renamed arena cannot move compiles into the
        window unseen."""
        import jax
        import numpy as np

        from kube_batch_tpu.ops import encode_cache

        arena = hook(self.allocate, "_arena")
        scatter = hook(encode_cache, "_scatter_jit")
        slots, row_delta = hook(arena, "_slots"), hook(arena, "ROW_DELTA")
        fraction = hook(arena, "ROW_DELTA_MAX_FRACTION")
        sigs = {(s.host.shape, s.host.dtype.str) for (name, _), s in slots.items()
                if name in row_delta}
        if not sigs:
            raise Refused("the warm-up left no row-delta slab in xla_allocate's arena")
        for shape, dtype in sorted(sigs):
            cap = int(fraction * shape[0])
            b = 1
            while True:
                buf = jax.device_put(np.zeros(shape, dtype))
                scatter()(buf, np.zeros(b, np.int64), np.zeros((b, *shape[1:]), dtype))
                if b >= cap:
                    break
                b *= 2

    def window(self) -> None:
        """Whole cycles for ``seconds``. The schedule period holds between
        cycle starts; a cycle longer than it is followed at once. A compile
        inside the window refuses the run."""
        period = float(self.traffic.get("period_s", 1.0))
        if self.trace:
            self._start_profiler()
        from kube_batch_tpu import metrics, obs

        obs.recorder.clear()
        actions0 = self._action_totals(metrics)
        self.w0 = time.perf_counter()
        self.loop.start(self.w0)
        start = None
        window_label = self._annotation("bench.window")
        window_label.__enter__()
        try:
            while True:
                now = time.perf_counter()
                if self.cycles and now - self.w0 >= self.seconds:
                    break
                if start is not None and now < start + period:
                    time.sleep(start + period - now)
                    now = time.perf_counter()
                start = now
                row = self.one_cycle(self.loop.arrivals(now))
                self.cycles.append(row)
                emit(phase="window", **{k: v for k, v in row.items() if k not in ("start", "end")})
        finally:
            window_label.__exit__(None, None, None)
        self.w1 = self.cycles[-1]["end"]
        self.spans = self._spans(obs)
        self.actions = {a: t - actions0.get(a, 0.0)
                        for a, t in self._action_totals(metrics).items()}
        if self.trace:
            self._stop_profiler()
        self.refuse_compiles()

    def refuse_compiles(self) -> None:
        compiled = [c["cycle"] for c in self.cycles if c["compiles"]]
        if compiled:
            if self.trace:
                import shutil

                shutil.rmtree(self.trace_dir, ignore_errors=True)
            raise Refused(f"compiles inside the window, in cycles {compiled}: "
                          "the warm-up missed a shape the window uses")

    def drain(self) -> int:
        """The loop's drain, then the last cycle closed for the reference."""
        n = self.loop.drain()
        self.cluster.start_cycle()
        return n

    @staticmethod
    def _action_totals(metrics) -> dict:
        """Seconds per action so far, from the action-latency histogram."""
        h = metrics.action_scheduling_latency
        return {dict(key).get("action", ""): h.snapshot(dict(key))["sum"]
                for key in h.label_sets()}

    def _spans(self, obs) -> dict:
        out: dict[str, list] = {}
        for s in obs.recorder.spans():
            out.setdefault(s["name"], []).append(s["dur_us"] / 1e6)
        return out

    def _start_profiler(self) -> None:
        import tempfile

        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def _stop_profiler(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def read_trace(self) -> dict | None:
        import glob
        import shutil

        from benchmark.trace import events_from_xplane, reduce

        paths = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"), recursive=True)
        try:
            if not paths:
                return None
            return reduce(*events_from_xplane(paths[0]))
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    # -- end-to-end numbers -----------------------------------------------

    def results(self) -> dict:
        """Binds over the window's wall time and the wall time per cycle,
        then the loop's own numbers (with ``attempted`` and ``unbound``)."""
        wall = self.w1 - self.w0
        binds = sum(c["binds"] for c in self.cycles)
        out = {"pods_bound_per_s": binds / wall, "session_s": wall / len(self.cycles)}
        out.update(self.loop.results())
        return out


def hook(obj, name: str):
    """A part of the program the harness reaches into; gone, it refuses
    the run rather than measure without it."""
    if not hasattr(obj, name):
        raise Refused(f"the program has no {type(obj).__name__}.{name}, which the harness needs")
    return getattr(obj, name)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by the name in BENCHMARK.json or
    in a traffic mix."""
    path = os.path.join(ROOT, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, fault=None, loaded=None,
        control=None, runner_cls=None) -> dict:
    """One whole run; returns the result object. ``require_tpu=False``,
    ``fault`` and ``loaded`` (a small (spec, cell, config, traffic)) are
    for the tests under benchmark/tests; ``control`` (a lower precision)
    adds the control's readings under "control" (benchmark/control.py);
    ``runner_cls`` is benchmark/sweep.py's."""
    spec, cell, config, traffic = loaded or load_cell(cell_name)
    if trace:
        os.environ["KBT_TRACE"] = "1"
    os.environ.setdefault("KBT_FLIGHT_RECORDER", "0")
    if require_tpu:
        device = preflight(int(cell["chips"]))
    else:
        import jax

        device = {"platform": jax.devices()[0].platform,
                  "kind": jax.devices()[0].device_kind, "count": 1}
    emit(phase="setup", compile_cache=compile_cache(), device=device,
         since_start_s=time.perf_counter() - T_START)
    runner = (runner_cls or Runner)(cell, config, traffic, seed, seconds, trace)
    runner.build()
    emit(phase="built", since_start_s=time.perf_counter() - T_START)
    if fault is not None:
        fault(runner)
    runner.warm_up()
    setup_s = time.perf_counter() - T_START
    emit(phase="setup_done", setup_s=setup_s)
    runner.window()
    drained = runner.drain()
    res = runner.results()
    compiles = sum(c["compiles"] for c in runner.cycles)
    late = runner.loop.lateness
    emit(phase="window_done", cycles=len(runner.cycles), window_s=runner.w1 - runner.w0,
         compiles_in_window=compiles, drain_cycles=drained,
         generator_lateness_mean_s=sum(late) / len(late) if late else None,
         generator_lateness_max_s=max(late) if late else None,
         evictions=runner.cluster.evictions, write_errors=runner.cluster.write_errors,
         aborted_cycles=runner.aborted, **res)
    device["memory_peak_bytes"] = peak_memory() if require_tpu else None
    trace_result = runner.read_trace() if trace else None
    ctx = {"cycles": runner.cycles, "spans": runner.spans, "actions": runner.actions,
           "trace": trace_result}
    # the program's state goes before the reference runs
    ledger, cycles = runner.cluster.ledger, [c["cycle"] for c in runner.cycles]
    del runner.sched, runner.cache, runner.allocate
    runner.cluster.store = None
    gc.collect()
    from benchmark.check import check

    k = int(traffic.get("check_cycles", 3))
    verdict = check(ledger, cycles, seed, k=k)
    emit(phase="checked", cycles_compared=verdict["cycles_compared"],
         decisions_compared=verdict["decisions_compared"])
    metrics = {}
    for m in metrics_for(spec, cell_name, trace):
        if trace:
            value = load_module("layers", m["name"]).read(ctx)
        elif m["name"] == "setup_s":
            value = setup_s
        else:
            value = res.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = res["unbound"] + runner.aborted + runner.cluster.write_errors
    out = {
        "correct": verdict["correct"],
        "attempted": res["attempted"],
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace_result is not None:
        device["busy_s"] = trace_result["busy_s"]
        device["window_s"] = trace_result["window_s"]
        out["breakdown"] = trace_result["breakdown"]
    if control is not None:
        out["control"] = check(ledger, cycles, seed, k=k, against_reference_dtype=control)
    out["compared"] = verdict["compared"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr, flush=True)
        return 2
    for name, entry in out["compared"].items():
        print(f"{name}: {entry['value']} (limit {entry['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
