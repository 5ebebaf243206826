#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  JAX must
find a TPU with at least the chips the cell asks for; otherwise the run
exits non-zero, naming what it found, and prints no result.  The last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; ``check`` comes last with each number compared beside
its limit, which also end standard error.

JAX's persistent compilation cache is kept in ``.jax_cache`` at the
root of the checkout, so only the first run of a cell there compiles.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def require_chips(count: int) -> list:
    """The first ``count`` TPU devices, or exit non-zero naming what JAX
    found: there is no fallback to another device."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU: JAX found {len(devs)} "
                 f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    if len(devs) < count:
        sys.exit(f"bench: the cell needs {count} TPU chips, JAX found "
                 f"{len(devs)} ({devs[0].device_kind})")
    return devs[:count]


def use_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        sys.exit("bench: --seed is a non-negative whole number")

    from bench import harness, spec

    cell = spec.load_cell(args.workload, ROOT)
    use_cache()
    devices = require_chips(cell.chips)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices, T_PROCESS)
    for name, c in out["check"].items():
        harness.log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(harness.result_line(out, devices), flush=True)


if __name__ == "__main__":
    main()
