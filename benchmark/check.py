"""The comparison that decides ``correct``.

Replays the ledger cycle by cycle. For every measured cycle it checks the
configuration's guarantees on what the cycle landed in the store (no
node over its allocatable, no gang bound below its minMember). For a
sample of the measured cycles, drawn from the seed, it runs the plain
reference (benchmark/reference.py) on the state the cycle started from
and compares, decision for decision, the binds and the evictions the
program landed. Every number compared is exact: its limit is 0.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import GPU, MIN_CPU, MIN_MEM, MIN_SC, Session, World

LIMITS = {
    "bind_mismatches": 0,
    "eviction_mismatches": 0,
    "capacity_violations": 0,
    "gang_violations": 0,
}
CHECK_CYCLES = 3


def sample(cycles: list, seed: int, k: int) -> list:
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 11])
    k = min(k, len(cycles))
    return sorted(int(c) for c in rng.choice(cycles, size=k, replace=False))


def _over(world: World, node: str) -> bool:
    a = world.ledger.node_alloc
    cpu = mem = gpu = 0.0
    keys = world.on_node[node]
    for key in keys:
        _, req, _, _, _ = world.pods[key]
        cpu += req.c
        mem += req.m
        gpu += req.s.get(GPU, 0.0)
    return (cpu - a["cpu"] * 1000.0 >= MIN_CPU or mem - a["mem"] >= MIN_MEM
            or gpu - a["gpu"] * 1000.0 >= MIN_SC or len(keys) > a["pods"])


def _short_gangs(world: World, binds: dict, evicted: dict) -> int:
    """Jobs that took binds in the cycle with fewer than minMember pods
    holding resources: bound at the cycle's end, or bound and evicted in
    it (a later action of the same cycle may preempt a gang's own pod)."""
    jobs = {world.pods[k][0] for k in binds if k in world.pods}
    held: dict[str, int] = {}
    for key, p in world.pods.items():
        if p[0] in jobs and p[3]:
            held[p[0]] = held.get(p[0], 0) + 1
    for job in evicted.values():
        if job in jobs:
            held[job] = held.get(job, 0) + 1
    return sum(1 for j in jobs if held.get(j, 0) < world.jobs[j][1])


def check(ledger, cycles: list, seed: int, *, dtype=np.float32, k: int = CHECK_CYCLES,
          against_reference_dtype=None) -> dict:
    """``dtype`` is the precision the reference computes in. With
    ``against_reference_dtype`` set, the decisions compared against are
    the reference's own in that precision instead of the program's: the
    control (a lower-precision reference put in the program's place)."""
    picked = set(sample(cycles, seed, k))
    world = World(ledger)
    counts = dict.fromkeys(LIMITS, 0)
    decisions = 0
    for c in sorted(cycles):
        world.advance(c)
        ref = twin = None
        if c in picked:
            ref = Session(world, dtype).run()
            if against_reference_dtype is not None:
                twin = Session(world, against_reference_dtype).run()
        binds, evicted = world.advance(c + 1)
        evicts = set(evicted)
        if twin is not None:
            binds, evicts = twin
        else:
            counts["capacity_violations"] += sum(1 for n in set(binds.values()) if _over(world, n))
            counts["gang_violations"] += _short_gangs(world, binds, evicted)
        if ref is not None:
            rb, re = ref
            counts["bind_mismatches"] += len(set(rb.items()) ^ set(binds.items()))
            counts["eviction_mismatches"] += len(re ^ evicts)
            decisions += len(rb) + len(re)
    compared = {name: {"value": counts[name], "limit": LIMITS[name]} for name in LIMITS}
    return {
        "correct": all(counts[n] <= LIMITS[n] for n in LIMITS),
        "compared": compared,
        "cycles_compared": sorted(picked),
        "decisions_compared": decisions,
    }
