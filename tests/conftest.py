"""Test configuration: force a deterministic 8-device virtual CPU mesh so
multi-chip sharding tests run anywhere (the driver separately dry-runs the
multichip path).

JAX is pinned through jax.config.update, whatever JAX_PLATFORMS the
shell sets (the chip is reached through chip_smoke.py, never through the
tests):

- platform cpu: the serial ≡ XLA equivalence tests need deterministic
  IEEE arithmetic; TPU f32 division is approximate and can flip floor/tie
  boundaries against the serial python path;
- x64: float64 arrays make the XLA path bit-identical to the serial
  float64 path. The TPU bench path runs float32, which is exact for
  milli/MiB-granular quantities (see ops/encode.py).
"""

import os
import sys

# Must precede the first CPU-backend initialization.
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The parity suites exist to exercise the device kernels: disable the
# size floor that would route their (deliberately small) snapshots to
# the serial action in production.
os.environ.setdefault("KBT_MIN_DEVICE_PAIRS", "0")

# Cache-mutation detector on for every tier-1 run (VERDICT row 58): the
# reference gates its whole unit suite on KUBE_CACHE_MUTATION_DETECTOR=true
# (hack/make-rules/test.sh:27-28); any test driving Scheduler.run_once
# gets the digest-before/verify-after guard over shared store objects.
os.environ.setdefault("KBT_CACHE_MUTATION_DETECTOR", "1")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import threading

import pytest


@pytest.fixture(autouse=True)
def _no_leaked_threads():
    """Thread-lifecycle discipline at test granularity (the dynamic twin
    of the KBT-T001 static check): a test that starts a non-daemon
    thread must stop/join it before returning, or interpreter shutdown
    hangs on the whole suite's behalf.

    Zero-cost on the common path: the grace join only runs when a NEW
    non-daemon thread is still alive at teardown. Daemon leaks (pumps
    whose stop() the test deliberately skipped) are tolerated here —
    the analyzer's witness drive and the chaos suite police those.
    """
    from kube_batch_tpu.utils.race import leaked_threads, thread_snapshot

    before = thread_snapshot()
    yield
    fresh_nondaemon = [
        t for t in threading.enumerate()
        if t.ident not in before
        and not t.daemon
        and t is not threading.current_thread()
        and t.is_alive()
    ]
    if not fresh_nondaemon:
        return
    leaked = leaked_threads(before, grace_s=2.0, include_daemon=False)
    if leaked:
        pytest.fail(
            "leaked non-daemon thread(s) past teardown: "
            + ", ".join(t.name for t in leaked)
            + " — every start() needs a reachable bounded join/stop path",
            pytrace=False,
        )
