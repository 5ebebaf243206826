#!/usr/bin/env python3
"""Split the device's idle time in a kept trace by engine phase.

    python3 bench/tools/idle_by_span.py <trace.xplane.pb>

Reads a trace of a benchmark run (``bench_window`` bounds the window;
``bench/tools/look.py --keep-xplane`` keeps one) and prints, for each
engine span name, its count and self time in the window and the idle
seconds of the device that fall in it where it is the innermost engine
span; self time of ``engine.step`` is its own row, and idle time outside
every engine span is a row too.  The last line is the same as JSON.
"""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def rows(tr) -> dict:
    """{name: {"count", "self_s", "idle_s"}} with the idle seconds and
    their total."""
    from bench import spans as S

    out: dict = {}
    for s, e, n in S.innermost(tr):
        out.setdefault(n, {"count": 0, "self_s": 0.0, "idle_s": 0.0})
        out[n]["self_s"] += (e - s) * 1e-9
    for n in out:
        out[n]["count"] = len(S.spans(tr, n))
    for n, v in S.idle_by_span(tr).items():
        out.setdefault(n, {"count": 0, "self_s": 0.0, "idle_s": 0.0})
        out[n]["idle_s"] = v
    return out


def main(argv=None) -> None:
    from bench import spans as S
    from bench import trace as T

    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        sys.exit(__doc__.split("\n\n")[1])
    tr = T.load(args[0])
    table = rows(tr)
    idle = sum(r["idle_s"] for r in table.values())
    inside = idle - table[S.OUTSIDE]["idle_s"]
    print(f"window {tr.window_s:.6f} s, device idle {idle:.6f} s "
          f"({100 * idle / tr.window_s:.3f}%), {inside:.6f} s of it "
          f"({100 * inside / idle if idle else 0.0:.2f}%) in engine spans")
    print(f"{'span':26} {'count':>7} {'self s':>11} {'idle s':>11} "
          f"{'of idle':>8}")
    for n, r in sorted(table.items(), key=lambda kv: -kv[1]["idle_s"]):
        share = 100 * r["idle_s"] / idle if idle else 0.0
        print(f"{n:26} {r['count']:7d} {r['self_s']:11.6f} "
              f"{r['idle_s']:11.6f} {share:7.2f}%")
    print(json.dumps({"window_s": tr.window_s, "idle_s": idle,
                      "rows": table}))


if __name__ == "__main__":
    main()
