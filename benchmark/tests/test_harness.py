"""The harness off the chip: generators, window arithmetic, the trace
reduction, the result line, BENCHMARK.json, and the refusals."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from collections import Counter

import pytest

from benchmark.tests.helpers import ROOT, cpu_env, tiny  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]


def _streams(cell, seed, n):
    from benchmark.workload import Generator, Ledger

    _, _, config, traffic = tiny(cell)
    gen, led = Generator(config, traffic, seed), Ledger(config)
    jobs = [gen.next_job(led) for _ in range(n)]
    return [(j.queue, j.min_member, tuple((led.pods[k].cpu, led.pods[k].mem, led.pods[k].gpu)
                                           for k in j.pods)) for j in jobs]


@pytest.mark.parametrize("cell", CELLS)
def test_generator_is_a_function_of_the_seed(cell):
    assert _streams(cell, 2**33 + 5, 300) == _streams(cell, 2**33 + 5, 300)
    assert _streams(cell, 2**33 + 5, 300) != _streams(cell, 7, 300)


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_gets_the_same_sizes(cell):
    from benchmark.run import load_cell

    _, _, config, _ = load_cell(cell)
    block = sum(k["share"] for k in config["jobs"])
    combos = max(len(k["worker"]["cpu_milli"]) * len(k["worker"]["memory_mi"]) for k in config["jobs"])
    n = block * combos

    def sizes(seed):
        return Counter(p for _, _, pods in _streams(cell, seed, n) for p in pods)

    assert sizes(1) == sizes(2**31 + 12345)


def test_open_loop_gaps_are_permuted_quantiles():
    from benchmark.workload import Generator

    _, _, config, traffic = tiny("k8s5k-open")
    a = Generator(config, traffic, 1).gaps(50.0)
    b = Generator(config, traffic, 2).gaps(50.0)
    ga = [next(a) for _ in range(64)]
    gb = [next(b) for _ in range(64)]
    assert ga != gb and sorted(ga) == pytest.approx(sorted(gb))
    assert sum(ga) == pytest.approx(64 / 50.0, rel=0.05)


def _open_loop():
    from benchmark.run import load_module

    return load_module("loops", "open")


def test_percentile_is_nearest_rank():
    percentile = _open_loop().percentile

    vals = sorted(range(1, 101))
    assert percentile(vals, 0.50) == 50
    assert percentile(vals, 0.95) == 95
    assert percentile([3.0], 0.95) == 3.0


class _Stub:
    pass


def _runner_with(due, bind_time, cycles, traffic):
    from benchmark.run import Runner
    from benchmark.workload import JobRec

    r = Runner({}, {}, traffic, 1, 10.0, False)
    cl = _Stub()
    cl.due = due
    cl.bind_time = bind_time
    cl.ledger = _Stub()
    cl.ledger.all_jobs = {n: JobRec(n, "q", 1, 0.0, [f"default/{n}-w0"]) for n in due}
    r.cluster = cl
    r.loop = _open_loop().Loop(r)
    r.cycles = cycles
    r.w0, r.w1 = cycles[0]["start"], cycles[-1]["end"]
    return r


def test_window_is_whole_cycles_from_due_times():
    cycles = [{"start": 100.0, "end": 102.0, "binds": 3}, {"start": 102.0, "end": 106.0, "binds": 5}]
    due = {"a": 99.0, "b": 100.5, "c": 103.0, "d": 106.5}  # a before, d after the window
    bt = {"default/a-w0": 101.0, "default/b-w0": 101.5, "default/c-w0": 105.0, "default/d-w0": 107.0}
    res = _runner_with(due, bt, cycles, {"loop": "open"}).results()
    assert res["attempted"] == 2 and res["unbound"] == 0
    assert res["pods_bound_per_s"] == pytest.approx(8 / 6.0)
    assert res["session_s"] == pytest.approx(3.0)
    assert res["time_to_bind_p50_s"] == pytest.approx(1.0)   # b: 100.5 -> 101.5
    assert res["time_to_bind_p95_s"] == pytest.approx(2.0)   # c: 103 -> 105


def test_unbound_pods_fail_and_sit_beyond_every_percentile():
    cycles = [{"start": 0.0, "end": 10.0, "binds": 1}]
    due = {"a": 1.0, "b": 2.0}
    r = _runner_with(due, {"default/a-w0": 1.5}, cycles, {"loop": "open"})
    res = r.results()
    assert res["unbound"] == 1 and res["attempted"] == 2
    assert res["time_to_bind_p50_s"] == pytest.approx(0.5)
    assert res["time_to_bind_p95_s"] > 0.5


def test_an_unbound_pod_sorts_after_every_bound_one():
    cycles = [{"start": 0.0, "end": 10.0, "binds": 1}]
    due = {"a": 1.0, "b": 2.0}
    r = _runner_with(due, {"default/a-w0": 1.5}, cycles, {"loop": "open"})
    r.cluster.bind_time["default/a-w0"] = 1.0 + 1e9  # bound after any clock reading
    res = r.results()
    assert res["unbound"] == 1
    assert res["time_to_bind_p95_s"] >= res["time_to_bind_p50_s"] == pytest.approx(1e9)


def _excerpt():
    with open(os.path.join(ROOT, "benchmark", "tests", "trace_excerpt.json")) as f:
        ex = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in ex["devices"].items()}
    return devices, [tuple(h) for h in ex["host"]], ex["expect"]


def test_trace_reduction_on_a_recorded_trace():
    from benchmark.trace import reduce

    devices, host, expect = _excerpt()
    out = reduce(devices, host)
    assert out["window_s"] == pytest.approx(expect["window_s"])
    assert out["busy_s"] == pytest.approx(expect["busy_s"])
    assert 0.0 < out["busy_s"] <= out["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    names = {h[2] for h in host} | {"host"}
    assert all(g[0] in names for g in out["breakdown"]["idle_gaps"])
    assert "tpu_custom_call" in [op for op, _ in out["breakdown"]["device_ops"]]
    gaps = sum(t for _, t in out["breakdown"]["idle_gaps"])
    assert gaps <= out["window_s"] - out["busy_s"] + 1e-9


def test_trace_reduction_by_hand():
    from benchmark.trace import reduce

    dev = {"/device:TPU:0": [(10, 20, "a"), (15, 30, "b"), (50, 60, "a"), (95, 120, "c")]}
    host = [(0, 100, "bench.window"), (0, 100, "bench.cycle"), (30, 50, "action.enqueue")]
    out = reduce(dev, host)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(35e-9)  # 10-30, 50-60, 95-100
    # idle: 0-10 and 60-95 under bench.cycle, 30-50 under action.enqueue
    assert out["breakdown"]["idle_gaps"] == [["bench.cycle", pytest.approx(45e-9)],
                                             ["action.enqueue", pytest.approx(20e-9)]]
    assert out["breakdown"]["device_ops"][:2] == [["a", pytest.approx(20e-9)],
                                                  ["b", pytest.approx(15e-9)]]
    assert reduce({}, host) is None and reduce(dev, []) is None


def test_benchmark_json_holds_to_its_contract():
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.25 for m in e2e.values())
    for c in SPEC["configs"]:
        assert name.match(c["name"]) and os.path.exists(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    for w in SPEC["workloads"]:
        assert name.match(w["name"]) and w["chips"] == 1 and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
        reported = [m for m in SPEC["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    for m in SPEC["per_layer"]:
        assert name.match(m["name"]) and m["moves"] in e2e
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", m["name"] + ".py"))
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", CELLS)


def test_last_line_keys_and_the_compared_numbers(monkeypatch):
    from benchmark import run

    cpu_env(monkeypatch)
    out = run.run("k8s5k-open", 2**32 + 9, 3.0, False, require_tpu=False, loaded=tiny("k8s5k-open"))
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in run.metrics_for(SPEC, "k8s5k-open", False)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(e["limit"] == 0 and e["value"] == 0 for e in out["compared"].values())


def test_traced_run_reports_the_cells_layers(monkeypatch):
    from benchmark import run

    cpu_env(monkeypatch)
    out = run.run("k8s5k-open", 31, 3.0, True, require_tpu=False, loaded=tiny("k8s5k-open"))
    want = {m["name"] for m in run.metrics_for(SPEC, "k8s5k-open", True)}
    # the CPU has no TPU plane: the device reader finds nothing and is left out
    assert set(out["metrics"]) == want - {"device_idle_share.open"}
    assert out["correct"] is True


def _compiles_each_cycle(runner):
    import jax

    window, run_once = runner.window, runner.sched.run_once

    def compiling():
        run_once()
        jax.jit(lambda x: x + 1.0)(1.0)  # a new function: one compile

    def patched():
        runner.sched.run_once = compiling
        return window()

    runner.window = patched


def test_a_compile_inside_the_window_refuses_the_run(monkeypatch):
    from benchmark import run

    cpu_env(monkeypatch)
    with pytest.raises(run.Refused, match="compiles inside the window"):
        run.run("k8s5k-open", 2**31 + 5, 2.0, False, require_tpu=False,
                loaded=tiny("k8s5k-open"), fault=_compiles_each_cycle)


def test_a_program_hook_gone_refuses_the_run(monkeypatch):
    from benchmark import run
    from kube_batch_tpu.ops import encode_cache

    cpu_env(monkeypatch)
    monkeypatch.delattr(encode_cache, "_scatter_jit")
    with pytest.raises(run.Refused, match="_scatter_jit"):
        run.run("k8s5k-open", 2**31 + 6, 2.0, False, require_tpu=False,
                loaded=tiny("k8s5k-open"))


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "k8s5k-open", "--seed",
         str(2**31 + 77), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_without_a_tpu():
    out = _run_cli(ROOT)
    assert out.returncode == 2
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
