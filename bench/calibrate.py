#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process on the chip.

    python3 bench/calibrate.py --workload rcv1.glmnet50 --seeds 1 2 3 \\
        --control-seeds 4 5 6

Each seed is one run of ``run.py`` with a window of one path: the cell's own
entry for ``--seeds``, the lower-precision control (``entries/control_high.py``)
for ``--control-seeds``, both judged by the same comparison. One JSON line
per run on standard output, with the run's verdict and every reading; the
lower reading of a number is the largest over the program's seeds, the upper
the smallest over the control's. The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    runs = [(s, []) for s in args.seeds]
    runs += [(s, ["--entry", "control_high"]) for s in args.control_seeds]
    for seed, extra in runs:
        try:
            out = run.run(run.parse(["--workload", args.workload, "--seed",
                                     str(seed), "--seconds", "0", *extra]))
        except run.Refusal as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 1
        print(json.dumps(dict(seed=seed, entry=extra[-1] if extra else "cell",
                              correct=out["correct"], failed=out["failed"],
                              readings=out["readings"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
