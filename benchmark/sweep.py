"""The knee of an open-loop cell, by one sweep on the chip: the cell run
at each offered rate in turn, in one process, with the rest of its
traffic as committed. For each rate it prints the cycles in the window,
their mean length, and whether the pending count at cycle start grew
across the window (its least-squares slope per cycle, over the second
half of the window's cycles).

    python3 benchmark/sweep.py --workload k8s5k-open --rates 50,100,200 --seconds 30
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench  # noqa: E402


class SweepRunner(bench.Runner):
    """Ends a rate's window after a cycle longer than ``stop_cycle_s``:
    the rate is past the knee, and its cycles would only grow."""

    stop_cycle_s = float("inf")

    def one_cycle(self, arrivals: list, idle_ok: bool = False) -> dict:
        row = super().one_cycle(arrivals, idle_ok)
        if row["wall_s"] > self.stop_cycle_s:
            self.seconds = 0.0
        return row

    def refuse_compiles(self) -> None:
        """A sweep reports its windows' compiles instead (the first rate
        carries the process's)."""


def slope(ys: list) -> float:
    n = len(ys)
    if n < 2:
        return 0.0
    mx, my = (n - 1) / 2, sum(ys) / n
    return sum((i - mx) * (y - my) for i, y in enumerate(ys)) / sum((i - mx) ** 2 for i in range(n))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=20260101)
    ap.add_argument("--stop-cycle-s", type=float, default=15.0,
                    help="end a rate's window after a cycle this long: past the knee")
    ap.add_argument("--check-cycles", type=int, default=1)
    ap.add_argument("--drain", type=int, default=2)
    args = ap.parse_args(argv)
    spec, cell, config, traffic = bench.load_cell(args.workload)
    rows: list = []
    emit = bench.emit
    bench.emit = lambda **row: rows.append(row) or emit(**row)
    for rate in (float(r) for r in args.rates.split(",")):
        rows.clear()
        tr = copy.deepcopy(traffic)
        tr["gang_rate_per_s"] = rate
        tr["check_cycles"] = args.check_cycles
        tr["drain_max_cycles"] = args.drain
        SweepRunner.stop_cycle_s = args.stop_cycle_s
        out = bench.run(args.workload, args.seed, args.seconds, False,
                        loaded=(spec, cell, config, tr), runner_cls=SweepRunner)
        window = [r for r in rows if r.get("phase") == "window"]
        pend = [r["pending_before"] for r in window]
        half = pend[len(pend) // 2:]
        print(json.dumps({
            "sweep_rate_gangs_per_s": rate,
            "cycles": len(window),
            "mean_cycle_s": sum(r["wall_s"] for r in window) / max(len(window), 1),
            "pending_first_last": [pend[0], pend[-1]] if pend else None,
            "pending_slope_per_cycle": slope(half),
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "correct": out["correct"], "failed": out["failed"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
