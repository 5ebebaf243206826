# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows aggregated from every benchmark module.
#
#   python -m benchmarks.run            full sweep
#   python -m benchmarks.run --smoke    reduced sizes (CI tier-1 gate)
#
# Modules whose ``csv`` accepts a ``smoke`` keyword scale themselves
# down under --smoke; the analytic ones run at full size either way.
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for the CI gate")
    args = ap.parse_args(argv)

    from benchmarks import (energy_per_inference, fleet_sweep,
                            power_breakdown, power_range, prefix_cache,
                            quantization_efficiency, resilience,
                            scale_sweep, serving_throughput, slo_sweep,
                            speculative_efficiency, tiny_edge_measured)

    modules = [
        ("fig2_power_range", power_range),
        ("fig6_energy_per_inference", energy_per_inference),
        ("fig8_quantization", quantization_efficiency),
        ("measured_tiny_edge", tiny_edge_measured),
        ("serving_throughput", serving_throughput),
        ("scale_sweep", scale_sweep),
        ("speculative_efficiency", speculative_efficiency),
        ("power_breakdown", power_breakdown),
        ("resilience", resilience),
        ("prefix_cache", prefix_cache),
        ("slo_sweep", slo_sweep),
        ("fleet_sweep", fleet_sweep),
    ]
    print("name,us_per_call,derived")
    n_rows = 0
    n_error = 0
    timings = []
    for name, mod in modules:
        t0 = time.perf_counter()
        try:
            kw = {}
            if args.smoke and \
                    "smoke" in inspect.signature(mod.csv).parameters:
                kw["smoke"] = True
            rows = list(mod.csv(**kw))
        except Exception as e:  # noqa: BLE001 — report all benches
            # which exception class fired goes into the derived column
            # (CSV stays 3 columns); the traceback goes to stderr
            rows = [f"{name},0.0,ERROR:{type(e).__name__}"]
            traceback.print_exc(file=sys.stderr)
        timings.append((name, time.perf_counter() - t0))
        for row in rows:
            print(row)
            n_rows += 1
            # a module may also *emit* ERROR rows instead of raising;
            # both forms must fail the gate, not just the exceptions
            if row.split(",", 2)[-1].startswith("ERROR"):
                n_error += 1
    # per-module wall time: how the sweep budget is actually spent
    # (comment rows, so CSV parsers and the perf gate skip them)
    for name, dt in timings:
        print(f"# elapsed: {name} {dt:.1f}s")
    total_s = sum(dt for _, dt in timings)
    print(f"# summary: {n_rows} rows, {n_error} ERROR, {total_s:.1f}s")
    if n_error:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
