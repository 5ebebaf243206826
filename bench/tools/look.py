#!/usr/bin/env python3
"""Run a cell once with the profiler on, keep its trace, and describe
it by hand: every plane and line, event counts and the heaviest event
names.  For reading a trace before writing a reduction against it.

    python3 bench/tools/look.py --workload <cell> --seed <n> \
        --seconds <s> --out out/look
    python3 bench/tools/look.py --tiny --out out/tiny_trace
"""
import argparse
import json
import glob
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path

T0 = time.monotonic()
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
sys.path.insert(0, str(ROOT / "bench"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep-xplane", action="store_true")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    sys.argv = sys.argv[:1]
    from run import require_chips, use_cache  # noqa: F401
    from bench import harness, spec
    from bench import trace as T
    from bench.tests import tinycell

    use_cache()
    if args.tiny:
        root = tinycell.make_root(Path(tempfile.mkdtemp()), rate=20.0)
        cell = spec.load_cell(tinycell.CELL, root)
    else:
        cell = spec.load_cell(args.workload, ROOT)
    devices = require_chips(cell.chips)
    keep = args.out if args.keep_xplane else tempfile.mkdtemp()
    try:
        out = harness.run_cell(cell, args.seed, args.seconds, True, devices,
                               T0, keep_trace=keep)
        print(harness.result_line(out, devices), flush=True)
        Path(keep, "facts.json").write_text(json.dumps({
            "requests": out["attempted"], "stats": out["stats"],
            "prompt_tokens": out["prompt_tokens"],
            "busy_s": out["busy_s"], "window_s": out["window_s"]}))
    except Exception:
        traceback.print_exc()
    for xp in glob.glob(os.path.join(keep, "*.xplane.pb")):
        print(f"{xp}: {os.path.getsize(xp)} bytes")
        t = time.monotonic()
        text = T.describe(xp)
        Path(args.out, "describe.txt").write_text(text)
        print(f"described in {time.monotonic() - t:.1f} s")
        print(text[:6000])


if __name__ == "__main__":
    main()
