"""Compile-cache sentinel: count XLA compiles inside a region.

A silent recompile in the warm scheduling loop is the single most
expensive latent regression this repo can grow — one retrace of the
50k-task program costs more wall-clock than a thousand warm cycles —
and it is invisible to every source-level lint: the code that triggers
it (a host int that should have been a device scalar, a dict key added
per cycle, a shape bucket that stopped being stable) looks identical to
the code that doesn't. jax publishes a monitoring event per backend
compile; :class:`CompileSentinel` turns that into an assertable budget:

    with CompileSentinel("warm cycles", budget=0) as cs:
        for _ in range(3):
            solver.solve(arrays)
    # raises CompileBudgetExceeded if anything recompiled

Used three ways: tier-1 pins zero recompiles across 3 warm cycles of
the XLA twin and the mesh rungs; ``benchmark/run.py`` reads
:func:`compile_count` to report compiles inside its measured window;
and the seeded recompile-storm fixture in the tests proves the counter
actually sees shape-keyed jit churn.

The listener is global and lazily registered (jax keeps listeners for
the process lifetime; there is no unregister API), so sentinels can
nest and interleave — each one reads deltas of one shared counter.
Counts are process-wide: don't run device work on side threads inside
a sentinel region you want to be exact.
"""

from __future__ import annotations

import threading

__all__ = ["CompileBudgetExceeded", "CompileSentinel", "compile_count"]

# The monitoring key jax records once per backend_compile (cache misses
# only — warm cache hits never reach the backend).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_mu = threading.Lock()
_count = 0  # compiles seen since the listener registered; guarded by _mu
_registered = False  # guarded by _mu


def _on_event(event: str, duration: float, **kw) -> None:
    global _count
    if event == _COMPILE_EVENT:
        with _mu:
            _count += 1


def _ensure_listener() -> None:
    global _registered
    with _mu:
        if _registered:
            return
        _registered = True
    # Import inside: the analysis package proper stays stdlib-only; only
    # the trace half may pull jax in.
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_event)


def compile_count() -> int:
    """Process-wide compile count since the first sentinel was armed."""
    _ensure_listener()
    with _mu:
        return _count


class CompileBudgetExceeded(AssertionError):
    """A sentinel region compiled more programs than its budget allows."""


class CompileSentinel:
    """Context manager counting jit cache misses in its region.

    ``budget=None`` observes only (read ``.compiles`` after exit);
    ``budget=N`` raises :class:`CompileBudgetExceeded` on exit when the
    region compiled more than N programs. An exception already in
    flight wins — the sentinel never masks it.
    """

    def __init__(self, label: str = "", budget: int | None = None) -> None:
        self.label = label
        self.budget = budget
        self.compiles = 0
        self._start = 0

    def __enter__(self) -> "CompileSentinel":
        _ensure_listener()
        self._start = compile_count()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.compiles = compile_count() - self._start
        if exc_type is None and self.budget is not None and self.compiles > self.budget:
            what = f" [{self.label}]" if self.label else ""
            raise CompileBudgetExceeded(
                f"compile sentinel{what}: {self.compiles} compiles in a "
                f"region budgeted for {self.budget} — a warm path is "
                "retracing (new shape bucket, dict key churn, or a "
                "python value that should be a device scalar)"
            )
        return False
