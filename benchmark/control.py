"""The control of the comparison that decides ``correct``, on the chip at
a cell's own size: for each seed, one process runs the cell's window as
the benchmark does, then compares the sampled cycles twice:

- the program against the float32 reference (the lower reading: sound
  runs read 0 on every number);
- the reference computed in bfloat16, put in the program's place,
  against the float32 reference (the upper reading: it has to read
  above every limit on some number).

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 --seconds 20

Prints one JSON line per seed and exits non-zero unless every seed's
program reads correct and every seed's control reads not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench  # noqa: E402


def control_dtype():
    import ml_dtypes

    return ml_dtypes.bfloat16


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = bench.run(args.workload, seed, args.seconds, False, control=control_dtype())
        ctl = out["control"]
        row = {
            "seed": seed,
            "program": {k: v["value"] for k, v in out["compared"].items()},
            "program_correct": out["correct"],
            "control": {k: v["value"] for k, v in ctl["compared"].items()},
            "control_correct": ctl["correct"],
            "decisions_compared": ctl["decisions_compared"],
        }
        print(json.dumps(row), flush=True)
        ok = ok and out["correct"] and not ctl["correct"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
