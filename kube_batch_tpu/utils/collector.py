"""When CPython's cyclic collector runs: at the scheduling cycle's
boundary, over a frozen resident heap.

A cycle clones the whole resident cluster into its session and drops it
again at session close. Left to itself, the interpreter's generational
collector fires every few hundred allocations inside the cycle, promotes
the session's objects (they live for the whole cycle), and once promoted
objects pass a quarter of the long-lived heap walks every tracked object
of the process, resident cache included, inside whichever span happens
to be running. Refcounting already frees the session's acyclic garbage;
only unreachable reference cycles need the collector.

So, for one process:

- inside a cycle (``cycle()``), automatic collection is off. Entries
  nest and overlap by a depth count: only the outermost exit ends it,
  and a collector that was already off on entry is left off and left
  alone (an embedding that disabled it keeps control of it);
- at the outermost exit, the boundary runs ``gc.collect()`` and then
  ``gc.freeze()``: cyclic garbage the cycle left is freed, and the
  survivors join the permanent generation, which automatic collections
  never walk. With the resident heap frozen, that collection walks only
  what was allocated since the previous boundary;
- frozen objects freed by refcount leave the permanent generation as
  usual, but frozen cyclic garbage is found only by a full pass. The
  boundary keeps the interpreter's own rule for full passes
  (``long_lived_pending > long_lived_total / 4``): once the objects
  frozen since the last full pass exceed :data:`FULL_PASS_SHARE` of the
  frozen count that pass left, it runs ``gc.unfreeze(); gc.collect();
  gc.freeze()`` instead. The first boundary of a process is always a
  full pass, and freezes the heap the process built before it.

Between cycles automatic collection is on again and walks only unfrozen
objects. Every collection the interpreter (or anything else) starts on
its own is recorded by a ``gc.callbacks`` hook and reported at the next
boundary: ``kube_batch_tpu_gc_collections_total{when}`` and
``kube_batch_tpu_gc_pause_seconds_total{when}``, with ``when`` one of
``cycle`` (started inside a cycle), ``between`` (outside one),
``boundary`` (the boundary's young pass) or ``full`` (its full pass);
while tracing is on, a ``gc.pause`` span per recorded pause and the
entered span ``gc`` around the boundary.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time

from kube_batch_tpu import metrics, obs

# The interpreter runs a full collection once the objects promoted into
# its oldest generation since the last one pass a quarter of those that
# one left; the boundary keeps the same share over the frozen heap.
FULL_PASS_SHARE = 0.25

# Pauses recorded between two boundaries beyond this many are counted
# but not kept one by one (a process that stops running cycles must not
# grow the record without bound).
MAX_RECORDED_PAUSES = 10_000


class BoundaryCollector:
    """The collector policy of one process (the interpreter's collector
    is process-wide, so :data:`policy` is the one the scheduler uses).
    The ``gc.callbacks`` hook takes no lock: a collection can start while
    its thread holds any lock, the metrics' and the flight recorder's
    included, so the hook only appends to plain lists."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # read without the lock by the gc hook (one int load); written
        # only under it
        self._depth = 0
        self._restore = False  #: guarded_by _lock
        self._frozen_base: int | None = None  #: guarded_by _lock
        self._frozen_since = 0  #: guarded_by _lock
        self._installed = False  #: guarded_by _lock
        # the hook's state: the boundary's own collections are not
        # recorded; a collection's start stamp waits for its stop
        self._own = False
        self._started: tuple[float, bool] | None = None
        self._pauses: list[tuple[float, float, int, str]] = []
        self._overflow = {"cycle": [0, 0.0], "between": [0, 0.0]}

    def uninstall(self) -> None:
        with self._lock:
            if self._installed:
                gc.callbacks.remove(self._on_gc)
                self._installed = False

    def _on_gc(self, phase: str, info: dict) -> None:
        if self._own:
            return
        if phase == "start":
            self._started = (time.perf_counter(), self._depth > 0)
            return
        if self._started is None:
            return  # installed mid-collection
        start, in_cycle = self._started
        self._started = None
        end = time.perf_counter()
        when = "cycle" if in_cycle else "between"
        if len(self._pauses) < MAX_RECORDED_PAUSES:
            self._pauses.append((start, end, info["generation"], when))
        else:
            tally = self._overflow[when]
            tally[0] += 1
            tally[1] += end - start

    @contextlib.contextmanager
    def cycle(self):
        """The extent of one scheduling cycle: no automatic collection
        inside it, the boundary at the outermost exit."""
        with self._lock:
            if not self._installed:
                gc.callbacks.append(self._on_gc)
                self._installed = True
            if self._depth == 0:
                self._restore = gc.isenabled()
                gc.disable()
            self._depth += 1
        try:
            yield
        finally:
            with self._lock:
                self._depth -= 1
                if self._depth == 0:
                    try:
                        if self._restore:
                            self._boundary_locked()
                        else:
                            self._flush_locked()
                    finally:
                        if self._restore:
                            gc.enable()

    def _boundary_locked(self) -> None:
        full = (
            self._frozen_base is None
            or self._frozen_since > self._frozen_base * FULL_PASS_SHARE
        )
        with obs.span("gc", full=full) as sp:
            self._flush_locked()
            self._own = True
            try:
                t0 = time.perf_counter()
                if full:
                    gc.unfreeze()
                collected = gc.collect()
                # what the freeze moves: every unfrozen survivor. Counted
                # by listing them, which walks only the unfrozen heap
                # (gc.get_freeze_count() walks the whole frozen one)
                frozen = len(gc.get_objects())
                gc.freeze()
                seconds = time.perf_counter() - t0
            finally:
                self._own = False
            if full:
                self._frozen_base = frozen
                self._frozen_since = 0
            else:
                self._frozen_since += frozen
            sp.set_attr("collected", collected)
            sp.set_attr("frozen", frozen)
        metrics.register_gc_pause("full" if full else "boundary", seconds)

    def _flush_locked(self) -> None:
        """Report the automatic collections recorded since the last
        boundary. Only the records taken here are removed: the hook may
        append behind them at any time."""
        n = len(self._pauses)
        pauses = self._pauses[:n]
        del self._pauses[:n]
        overflow, self._overflow = self._overflow, {
            "cycle": [0, 0.0], "between": [0, 0.0],
        }
        for start, end, generation, when in pauses:
            metrics.register_gc_pause(when, end - start)
            obs.emit("gc.pause", start, end, generation=generation, when=when)
        for when, (count, seconds) in overflow.items():
            if count:
                metrics.register_gc_pause(when, seconds, n=count)

    def state(self) -> dict:
        """The boundary's counts: the frozen count the last full pass
        left (None before the first) and the objects frozen since."""
        with self._lock:
            return {
                "depth": self._depth,
                "frozen_base": self._frozen_base,
                "frozen_since": self._frozen_since,
            }


policy = BoundaryCollector()


def cycle():
    """:meth:`BoundaryCollector.cycle` of the process's policy."""
    return policy.cycle()
