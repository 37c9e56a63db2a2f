"""Readings that set a cell's ``logit_gap`` limit: the program's number on
many seeds (the lower reading) and the control's on the same requests (the
upper reading), at the cell's own size and load, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        --seconds 15

Each seed makes its own weights, front door and window, as a benchmark run
does.  After the window the control, the reference at float8, is put in the
program's place on the sampled engine answers: it picks the token at every
position of the same prompts and served tokens, and the harness's own
comparison reads the float32 reference's gap of each pick and decides
``correct`` (false is what the control must give).  The program's own
reading on the same answers is printed beside it.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           t_start=t, control=True)
        print(json.dumps({"seed": seed,
                          "program_logit_gap": out["program"]["logit_gap"],
                          "control_logit_gap":
                              out["checks"]["logit_gap"]["value"],
                          "limit": out["checks"]["logit_gap"]["limit"],
                          "control_correct": out["correct"],
                          "checks": {k: v["value"] for k, v in
                                     out["checks"].items()},
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
