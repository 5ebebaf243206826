#!/usr/bin/env python3
"""The exchange between chips in a kept trace, by hand.

    python3 bench/tools/collectives.py <trace.xplane.pb> [--chunk-steps 8]

Reads a trace of a benchmark run (``bench/tools/look.py --keep-xplane``
keeps one) and prints, for each chip, the collective operations by
name (instance numbers dropped) with their count and seconds in the
window, and how many lie inside each execution of the decode-chunk
and prefill programs: the most common count per decode chunk over
``--chunk-steps`` is the count per decode step (a chunk that ends
early, when its slots run out of budget, holds fewer).  The last line
is the same as JSON.
"""
import argparse
import collections
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

PROGRAMS = ("_decode_chunk_impl", "_prefill_slot_impl")


def chips(tr, chunk_steps: int) -> list:
    from bench import collectives as C
    from bench import trace as T

    out = []
    for modules, ops in zip(tr.modules, tr.ops):
        names: dict = {}
        for n, s, t in zip(ops.names, ops.starts, ops.ends):
            if C.is_collective(n):
                key = T._SUFFIX.sub("", n)
                c, secs = names.get(key, (0, 0.0))
                names[key] = (c + 1, secs + (t - s) * 1e-9)
        row = {"names": {k: {"count": c, "seconds": s}
                         for k, (c, s) in sorted(names.items())}}
        for part in PROGRAMS:
            counts = [n for n, _ in C.per_execution(modules, ops, part)]
            common = collections.Counter(counts).most_common(1)
            row[part] = {"executions": len(counts),
                         "collectives": sum(counts),
                         "most_common_per_execution":
                             common[0][0] if common else None}
        mode = row["_decode_chunk_impl"]["most_common_per_execution"]
        row["per_decode_step"] = (None if mode is None
                                  else mode / chunk_steps)
        out.append(row)
    return out


def main(argv=None) -> None:
    from bench import trace as T

    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("--chunk-steps", type=int, default=8)
    args = ap.parse_args(argv)
    rows = chips(T.load(args.xplane), args.chunk_steps)
    for i, row in enumerate(rows):
        print(f"chip {i}: {row['per_decode_step']} collectives per decode "
              f"step; decode {row['_decode_chunk_impl']}; prefill "
              f"{row['_prefill_slot_impl']}")
        for name, v in row["names"].items():
            print(f"  {v['count']:9d} {v['seconds']:10.4f} s  {name}")
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
