"""Chip smoke: the scheduler's device path on the TPU, once, end to end.

Drives `Scheduler.run_once` -- the loop `server.py` runs -- over a
`ClusterStore` seeded through its create calls with the reference's
density shape (`models.synthetic(50_000, 5_000)`: 50k pending pods in
gangs of 10 on 5k nodes) under `examples/scheduler-conf-tpu.yaml`:

- a cold cycle, then a warm one after a wave of 5k new pods; every
  cycle must solve on the `pallas` rung with its breaker closed and
  bind something;
- the cold snapshot solved by the XLA twin rung (`KBT_PALLAS=0`) must
  bind identically, bind for bind;
- at `synthetic(10_000, 1_000)` the serial actions of
  `examples/scheduler-conf.yaml` (the plain reference) must bind
  identically to the device path.

``--chips 4`` runs only the mesh phase: the 50k x 5k snapshot through
`examples/scheduler-conf-tpu-multichip.yaml` (``mesh: auto``) on a
4-device mesh -- tier `mesh_pallas`, block backend `mosaic` -- against
the mesh-off single-chip solve of the same snapshot.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``. Any failed check, no TPU, x64 on, or
the native host loops fallen back to Python raises: the exit code is
non-zero and the ``ok`` line is never printed. One process; nothing it
starts touches JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager

ROOT = os.path.dirname(os.path.abspath(__file__))
CONF_TPU = os.path.join(ROOT, "examples", "scheduler-conf-tpu.yaml")
CONF_MESH = os.path.join(ROOT, "examples", "scheduler-conf-tpu-multichip.yaml")
CONF_SERIAL = os.path.join(ROOT, "examples", "scheduler-conf.yaml")

FULL = (50_000, 5_000)  # (pods, nodes): BASELINE config 2 at 50k density
WAVE_PODS = 5_000
REFERENCE = (10_000, 1_000)  # where the serial reference is affordable
MESH_CHIPS = 4
MESH_BLOCK = "mosaic"


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


@contextmanager
def env(name: str, value: str):
    old = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            del os.environ[name]
        else:
            os.environ[name] = old


def preflight(chips: int) -> list:
    """The device, dtype and host-loop conditions under which a pass
    means the chip ran the path; raises otherwise."""
    import jax

    devices = jax.devices()
    require(
        devices[0].platform == "tpu",
        f"no TPU: JAX found {devices[0].platform!r} devices "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})",
    )
    require(
        len(devices) >= chips,
        f"{chips} chips asked for, JAX found {len(devices)}",
    )
    require(
        not jax.config.jax_enable_x64,
        "jax_enable_x64 is on: the Pallas rung solves in float32 only",
    )
    from kube_batch_tpu import native

    require(
        native.lib is not None,
        "native host loops unavailable: the scheduler fell back to Python",
    )
    return devices


def seeded_store(pods: int, nodes: int):
    from kube_batch_tpu.cache import ClusterStore
    from kube_batch_tpu.models import synthetic_objects

    store = ClusterStore()
    pod_objs, node_objs, groups, queues = synthetic_objects(pods, nodes)
    for q in queues:
        store.create_queue(q)
    for n in node_objs:
        store.create_node(n)
    for pg in groups:
        store.create_pod_group(pg)
    for p in pod_objs:
        store.create_pod(p)
    return store


def add_wave(store, pods: int) -> None:
    from kube_batch_tpu.models import synthetic_objects

    pod_objs, _, groups, _ = synthetic_objects(pods, 0, seed=1, prefix="wave")
    for pg in groups:
        store.create_pod_group(pg)
    for p in pod_objs:
        store.create_pod(p)


def placements(store) -> dict:
    from kube_batch_tpu.cache.store import PODS

    return {
        f"{p.namespace}/{p.name}": p.node_name
        for p in store.list(PODS)
        if p.node_name
    }


def scheduler(store, conf: str):
    from kube_batch_tpu.cache import SchedulerCache
    from kube_batch_tpu.scheduler import Scheduler

    return Scheduler(SchedulerCache(store), scheduler_conf=conf)


def cycle(phase: str, sched, store, size: tuple, tier: str | None) -> dict:
    """One `run_once`; returns this cycle's binds {pod: node}. With
    ``tier`` set, the xla_allocate action must have solved on that rung
    with its breaker closed."""
    import jax

    from kube_batch_tpu import faults, native
    from kube_batch_tpu.analysis.trace.sentinel import compile_count
    from kube_batch_tpu.faults.ladder import CLOSED
    from kube_batch_tpu.ops.pallas_solve import vmem_budget

    before = placements(store)
    compiles0 = compile_count()
    t0 = time.perf_counter()
    sched.run_once()
    wall = time.perf_counter() - t0
    compiles = compile_count() - compiles0
    binds = {k: v for k, v in placements(store).items() if k not in before}
    action = next((a for a in sched.actions if a.name == "xla_allocate"), None)
    row = {
        "phase": phase,
        "pods": size[0],
        "nodes": size[1],
        "wall_s": wall,
        "binds": len(binds),
        "tier": action.last_solver_tier if action else "serial actions",
        "compiles": compiles,
        "device_kind": jax.devices()[0].device_kind,
        "vmem_budget": vmem_budget(),
        "native": native.lib is not None,
    }
    if action is not None:
        row["xla_allocate_split_s"] = action.last_timings
    if action is not None and action.last_mesh_size > 1:
        row["mesh_size"] = action.last_mesh_size
        row["block_impl"] = action.last_block_impl
    emit(**row)
    require(len(binds) > 0, f"{phase}: the cycle bound nothing")
    if tier is not None:
        require(
            row["tier"] == tier,
            f"{phase}: solved on tier {row['tier']!r}, expected {tier!r}",
        )
        breaker = faults.solver_ladder.breakers[tier]
        require(
            breaker.state == CLOSED and breaker.failures == 0,
            f"{phase}: {tier} breaker is {breaker.state} with "
            f"{breaker.failures} failures",
        )
    return binds


def same_binds(what: str, got: dict, want: dict) -> None:
    diff = set(got.items()) ^ set(want.items())
    emit(check=what, binds=len(want), identical=not diff)
    require(not diff, f"{what}: {len(diff)} binds differ")


def main_path() -> None:
    from kube_batch_tpu.ops import enable_compilation_cache

    emit(compile_cache=enable_compilation_cache())

    store = seeded_store(*FULL)
    sched = scheduler(store, CONF_TPU)
    cold = cycle("cold", sched, store, FULL, "pallas")
    add_wave(store, WAVE_PODS)
    cycle("warm", sched, store, (WAVE_PODS, FULL[1]), "pallas")

    twin = seeded_store(*FULL)
    with env("KBT_PALLAS", "0"):
        twin_binds = cycle("xla_twin", scheduler(twin, CONF_TPU), twin, FULL, "xla")
    same_binds("pallas_vs_xla_twin", cold, twin_binds)

    dev = seeded_store(*REFERENCE)
    dev_binds = cycle("reference_device", scheduler(dev, CONF_TPU), dev, REFERENCE, "pallas")
    ref = seeded_store(*REFERENCE)
    ref_binds = cycle("reference_serial", scheduler(ref, CONF_SERIAL), ref, REFERENCE, None)
    same_binds("device_vs_serial", dev_binds, ref_binds)


def mesh_path() -> None:
    mesh = seeded_store(*FULL)
    sched = scheduler(mesh, CONF_MESH)
    mesh_binds = cycle("mesh", sched, mesh, FULL, "mesh_pallas")
    action = next(a for a in sched.actions if a.name == "xla_allocate")
    require(
        action.last_mesh_size == MESH_CHIPS,
        f"mesh size {action.last_mesh_size}, expected {MESH_CHIPS}",
    )
    require(
        action.last_block_impl == MESH_BLOCK,
        f"block backend {action.last_block_impl!r}, expected {MESH_BLOCK!r}",
    )
    single = seeded_store(*FULL)
    single_binds = cycle("single_chip", scheduler(single, CONF_TPU), single, FULL, "pallas")
    same_binds("mesh_vs_single_chip", mesh_binds, single_binds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, MESH_CHIPS), default=1,
        help=f"{MESH_CHIPS}: run only the mesh phase",
    )
    args = ap.parse_args(argv)
    devices = preflight(args.chips)
    if args.chips == MESH_CHIPS:
        require(
            len(devices) == MESH_CHIPS,
            f"mesh phase needs exactly {MESH_CHIPS} chips, found {len(devices)}",
        )
        mesh_path()
    else:
        main_path()
    emit(
        ok=True,
        device={
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
