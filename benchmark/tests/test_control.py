"""The control at a size a test run holds: the reference computed in
bfloat16, put in the program's place, has to read not correct where the
program reads correct. benchmark/control.py runs the same at a cell's
own size on the chip."""

from __future__ import annotations

import pytest

from benchmark.tests.helpers import cpu_env, tiny


@pytest.mark.parametrize("seed", [2**31 + 3, 2**33 + 17])
def test_lower_precision_reference_fails_where_the_program_passes(monkeypatch, seed):
    from benchmark import run
    from benchmark.control import control_dtype

    cpu_env(monkeypatch)
    out = run.run("k8s5k-open", seed, 4.0, False, require_tpu=False,
                  loaded=tiny("k8s5k-open"), control=control_dtype())
    assert out["correct"] is True
    assert out["control"]["correct"] is False
    assert out["control"]["compared"]["bind_mismatches"]["value"] > 0
