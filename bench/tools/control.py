#!/usr/bin/env python3
"""Readings for a cell's limit: on each seed, the program's widest
logit gap and the fp8 control's, over the same sampled requests.

    python3 bench/tools/control.py --workload <cell> --seconds 12 \
        --seeds 1 2 3 [--out out/control.jsonl]

Each seed is one whole run of the cell (set-up, a window at the cell's
own load, the check) in this one process; the control is the plain
reference with every product with a weight in float8 e4m3, the step
below the served bfloat16, and its reading is the gap under the
float32 reference of the token it puts first at each position.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "bench")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.argv = sys.argv[:1]
    from run import require_chips, use_cache

    from bench import harness, spec

    use_cache()
    cell = spec.load_cell(args.workload, ROOT)
    devices = require_chips(cell.chips)
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False, devices,
                               time.monotonic(), control=True)
        row = {"cell": args.workload, "seed": seed,
               "program_gap": out["check"]["logit_gap"]["value"],
               "control_gap": out["control_gap"],
               "correct": out["correct"], "attempted": out["attempted"],
               "failed": out["failed"]}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
