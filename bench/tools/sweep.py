#!/usr/bin/env python3
"""Serve a cell's traffic at several rates with one engine, once, to
find the knee (Server mixes) or the completion rate R (Offline mixes).

    python3 bench/tools/sweep.py --workload <cell> --seconds 30 \
        --seed 7 --rates 1.2 1.5 1.8

For each rate: requests sent and completed, the share that met both
the mix's TTFT and TPOT limits, TTFT and TPOT p90, output tokens/s and
completed requests per second of window.  The knee is the highest
rate at which at least 90% meet both limits.
"""
import argparse
import json
import sys
import time
from pathlib import Path

T0 = time.monotonic()
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "bench")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    sys.argv = sys.argv[:1]
    import numpy as np
    from run import require_chips, use_cache

    from bench import harness, served, spec
    from bench import traffic as TR
    from repro.harness import PowerRun, TraceServer
    from repro.hw import system_for_device
    from repro.serving import Request

    use_cache()
    cell = spec.load_cell(args.workload, ROOT)
    devices = require_chips(cell.chips)
    cfg, mix = cell.config, cell.traffic
    mc, engine = served.build_engine(cfg, args.seed, devices)
    engine.serve([Request(rid=p.qid, prompt=p.prompt,
                          max_new_tokens=p.max_new_tokens)
                  for p in TR.warm_up_requests(mix, cfg["vocab_size"])],
                 honor_arrivals=False)
    print(f"set-up {time.monotonic() - T0:.1f} s", flush=True)
    rows = []
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate)
        plan = TR.schedule(m, args.seconds, args.seed, cfg["vocab_size"])
        sut = served.make_sut(engine, mc, plan,
                              system_for_device(devices[0]))
        res = PowerRun(sut, TraceServer(
            trace=np.asarray([p.arrival_s for p in plan]),
            latency_slo_s=1e9, ttft_slo_s=mix["slo"]["ttft_s"],
            tpot_slo_s=mix["slo"]["tpot_s"]), seed=args.seed).run()
        sm = res.outcome.server
        done = sut.completed
        window = max(r.done_s for r in done)
        row = {"rate": rate, "sent": len(plan), "done": len(done),
               "attainment": sm.tail_attainment,
               "ttft_p90_ms": float(np.percentile(sm.ttft_s, 90)) * 1e3,
               "tpot_p90_ms": float(np.percentile(sm.tpot_s, 90)) * 1e3,
               "output_tokens_per_s": sm.total_tokens / window,
               "completed_per_s": len(done) / window, "window_s": window}
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate"] for r in rows if r["attainment"] >= 0.9]
    harness.log(f"knee (highest rate with >= 90% meeting both limits): "
                f"{max(ok) if ok else None}")


if __name__ == "__main__":
    main()
