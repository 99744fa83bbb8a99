"""The open loop: gangs arrive on a Poisson schedule at the mix's
``gang_rate_per_s``, whatever the scheduler does, and completions delete
the oldest fully bound gangs before each cycle to hold
``hold_resident_pods`` bound pods.

Arrivals due during a cycle are created at the next cycle boundary (a
scheduler that snapshots at cycle start cannot tell the difference); a
pod's time to bind runs from its due time. The schedule's clock starts
``lead_s`` before the window, so the first measured cycle carries a
steady cycle's arrivals. After the window the jobs due during the last
measured cycle arrive, and cycles without arrivals run until every pod
due in the window is bound, or ``drain_max_cycles``.

A loop module is found by the mix's ``"loop"`` key and holds everything
the mix does at a cycle boundary, in warm-up, in the window and after
it; the runner (benchmark/run.py) knows none of it.
"""

from __future__ import annotations

import math
import time
from collections import deque


class Loop:
    def __init__(self, runner) -> None:
        self.runner = runner
        self.traffic = runner.traffic
        self.cluster = runner.cluster
        self.lateness: list[float] = []

    # -- the mix at a cycle boundary ------------------------------------

    def boundary(self, arrivals: list) -> int:
        """Completions, then ``arrivals``; returns the store writes."""
        cl = self.cluster
        writes = self._complete()
        for job in arrivals:
            cl.create_job(job)
            writes += 1 + len(job.pods)
        return writes

    def _complete(self) -> int:
        """Delete the oldest fully bound jobs until the hold is met."""
        cl = self.cluster
        hold = int(self.traffic["hold_resident_pods"])
        writes = 0
        keep: deque = deque()
        while cl.order and cl.bound > hold:
            name = cl.order.popleft()
            if name not in cl.ledger.jobs:
                continue
            if not cl.fully_bound(name):
                keep.append(name)
                continue
            writes += 1 + len(cl.ledger.jobs[name].pods)
            cl.delete_job(name)
        while keep:
            cl.order.appendleft(keep.pop())
        return writes

    # -- phases ---------------------------------------------------------

    def warmup(self):
        """One warm-up cycle per entry of ``warmup_s``, each sent the gangs
        the rate offers in that many seconds, so that every task and job
        bucket the window reaches has been compiled."""
        rate = float(self.traffic["gang_rate_per_s"])
        for seconds in self.traffic["warmup_s"]:
            yield [self.cluster.gen.next_job(self.cluster.ledger)
                   for _ in range(round(rate * seconds))]

    def start(self, w0: float) -> None:
        self._gaps = self.cluster.gen.gaps(float(self.traffic["gang_rate_per_s"]))
        lead = float(self.traffic.get("lead_s", self.traffic.get("period_s", 1.0)))
        self._due = w0 - lead + next(self._gaps)

    def arrivals(self, now: float, until: float | None = None) -> list:
        """The jobs due up to ``until`` (default ``now``), created at ``now``."""
        cl = self.cluster
        until = now if until is None else until
        out = []
        while self._due < until:
            job = cl.gen.next_job(cl.ledger)
            cl.due[job.name] = self._due
            self.lateness.append(now - self._due)
            out.append(job)
            self._due += next(self._gaps)
        return out

    def drain(self) -> int:
        cap = int(self.traffic.get("drain_max_cycles", 0))
        r = self.runner
        n = 0
        if cap:
            r.one_cycle(self.arrivals(time.perf_counter(), r.w1), idle_ok=True)
            n += 1
        while n < cap and self._unbound():
            r.one_cycle([], idle_ok=True)
            n += 1
        return n

    # -- end-to-end numbers -----------------------------------------------

    def window_pods(self) -> list:
        """(pod key, due time) of every pod due in the window."""
        cl, r = self.cluster, self.runner
        out = []
        for name, due in cl.due.items():
            if r.w0 <= due < r.w1:
                out += [(k, due) for k in cl.ledger.all_jobs[name].pods]
        return out

    def _unbound(self) -> bool:
        bt = self.cluster.bind_time
        return any(k not in bt for k, _ in self.window_pods())

    def results(self) -> dict:
        """Time to bind over every pod due in the window: nearest-rank
        percentiles, a pod still unbound after the drain failed and sorted
        after every bound one."""
        bt = self.cluster.bind_time
        pods = self.window_pods()
        end = time.perf_counter()
        bound = sorted(bt[k] - due for k, due in pods if k in bt)
        top = bound[-1] if bound else 0.0
        late = sorted(max(end - due, top) for k, due in pods if k not in bt)
        waits = bound + late
        return {
            "time_to_bind_p50_s": percentile(waits, 0.50),
            "time_to_bind_p95_s": percentile(waits, 0.95),
            "attempted": len(pods),
            "unbound": len(late),
        }


def percentile(sorted_values: list, q: float) -> float:
    """Nearest rank: the smallest value with at least q of the set at or
    below it."""
    n = len(sorted_values)
    return sorted_values[max(0, math.ceil(q * n) - 1)]
