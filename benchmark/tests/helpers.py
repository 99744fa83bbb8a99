"""Small versions of the cells, for the CPU: the same code, a few dozen
nodes, the Pallas kernel in interpret mode."""

from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def tiny(cell: str) -> tuple:
    from benchmark import run

    spec, entry, config, traffic = run.load_cell(cell)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["nodes"]["count"] = 48
    config["residents"]["pods"] = 400
    traffic["hold_resident_pods"] = 400
    traffic["gang_rate_per_s"] = 10.0
    # every task and job bucket that 0.3 s to 4.4 s of arrivals reach here
    traffic["warmup_s"] = [0.3, 0.4, 0.6, 0.8, 1.2, 1.6, 2.4, 4.0]
    return spec, entry, config, traffic


def cpu_env(monkeypatch) -> None:
    """What a CPU run of the harness needs: the kernel interpreted, the
    device path forced at tiny sizes, tracing off unless asked."""
    monkeypatch.setenv("KBT_PALLAS", "interpret")
    monkeypatch.setenv("KBT_MIN_DEVICE_PAIRS", "0")
    monkeypatch.setenv("KBT_TRACE", "0")
    from kube_batch_tpu import faults

    faults.solver_ladder.reset()
