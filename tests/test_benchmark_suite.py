"""The chip benchmark's own tests (``benchmark/tests``) run in tier-1.

The harness reaches into program hooks (``SchedulerCache.resync_task``,
``xla_allocate._arena``, ``_slots``, ``ROW_DELTA``,
``ROW_DELTA_MAX_FRACTION``, ``encode_cache._scatter_jit``); a rename of
one of them fails here on the CPU instead of on the chip.

Each file runs in its own pytest process from the repo root, with the
environment it has on its own: the knobs this suite's conftest sets for
the parity suites are taken out again.
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FILES = ("test_control.py", "test_faults.py", "test_gc_reader.py", "test_harness.py")
# set by tests/conftest.py for this suite, not by the benchmark's tests
_SUITE_ENV = ("XLA_FLAGS", "KBT_MIN_DEVICE_PAIRS", "KBT_CACHE_MUTATION_DETECTOR")


@pytest.mark.parametrize("name", _FILES)
def test_benchmark_tests_pass(name):
    env = {k: v for k, v in os.environ.items() if k not in _SUITE_ENV}
    env["JAX_PLATFORMS"] = "cpu"
    path = os.path.join("benchmark", "tests", name)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", path, "-q", "-p", "no:cacheprovider"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
