"""A run with the timed path broken underneath reads ``correct`` false,
once for each fault a cycle of the cell can have: a cycle that leaves the
cluster as it found it, half of a cycle's binds left out, and a bind
altered where it is produced (the cell's one queue evicts nothing). The
harness's look for a chip is skipped; everything else is a whole run at
a small size."""

from __future__ import annotations

import pytest

from benchmark.tests.helpers import cpu_env, tiny


def _window_only(runner, install):
    """Install a fault when the window starts (warm-up stays sound)."""
    window = runner.window

    def patched():
        install(runner)
        return window()

    runner.window = patched


def unchanged(runner):
    runner.sched.run_once = lambda: None


def half_left_out(runner):
    binder = runner.cache.binder
    bind = binder.bind
    seen = [0]

    def bind_half(pod, hostname):
        seen[0] += 1
        if seen[0] % 2:
            bind(pod, hostname)

    binder.bind = bind_half


def altered(runner):
    """A pod that asks for no GPU (every k8s5k pod) lands on another node
    where it fits by the benchmark's ledger, so no node goes over capacity
    and only the answer is wrong."""
    binder = runner.cache.binder
    bind = binder.bind
    led = runner.cluster.ledger

    def elsewhere(key, besides):
        a = led.node_alloc
        free = {n: [a["cpu"], a["mem"], a["gpu"], a["pods"]] for n in led.nodes}
        for rec in led.pods.values():
            if rec.node:
                f = free[rec.node]
                f[0] -= rec.cpu
                f[1] -= rec.mem
                f[2] -= rec.gpu
                f[3] -= 1
        rec = led.pods[key]
        for n in led.nodes:
            f = free[n]
            if n != besides and f[0] >= rec.cpu and f[1] >= rec.mem and f[2] >= rec.gpu and f[3] >= 1:
                return n
        return besides

    def bind_elsewhere(pod, hostname):
        key = f"{pod.namespace}/{pod.name}"
        gpu = any("nvidia.com/gpu" in c.requests for c in pod.containers)
        bind(pod, hostname if gpu else elsewhere(key, hostname))

    binder.bind = bind_elsewhere


CASES = [
    ("k8s5k-open", unchanged),
    ("k8s5k-open", half_left_out),
    ("k8s5k-open", altered),
]


@pytest.mark.parametrize("cell,fault", CASES, ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_a_broken_path_reads_not_correct(monkeypatch, cell, fault):
    from benchmark import run

    cpu_env(monkeypatch)
    out = run.run(cell, 2**32 + 21, 4.0, False, require_tpu=False, loaded=tiny(cell),
                  fault=lambda r: _window_only(r, fault))
    assert out["correct"] is False
    assert any(e["value"] > e["limit"] for e in out["compared"].values())
