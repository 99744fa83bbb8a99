"""The `gc_s.open` reader: the program's collector spans per measured
cycle, and nothing where the program records none."""

from __future__ import annotations

import pytest

from benchmark.tests.helpers import ROOT  # noqa: F401  (puts the checkout on the path)


def _read(spans, cycles=4):
    from benchmark import run

    ctx = {"cycles": [{}] * cycles, "spans": spans, "actions": {}, "trace": None}
    return run.load_module("layers", "gc_s.open").read(ctx)


def test_without_the_collector_spans_it_reads_nothing():
    assert _read({}) is None
    assert _read({"cycle": [2.0] * 4, "session.open": [1.0] * 4}) is None


def test_it_reads_boundary_and_pause_seconds_per_cycle():
    got = _read({"cycle": [2.0] * 4, "gc": [0.01, 0.02, 0.5, 0.03],
                 "gc.pause": [0.1, 0.02]})
    assert got == pytest.approx((0.56 + 0.12) / 4)
    assert _read({"gc": [0.01, 0.03]}) == pytest.approx(0.01)
    assert _read({"gc.pause": [0.2]}, cycles=2) == pytest.approx(0.1)
