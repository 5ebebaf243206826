"""The control at a size a test run holds: the plain reference put in
the program's place with every product with a weight in float8 e4m3,
one step below the served bfloat16, must come out as not correct under
the limit the configuration states, while the program passes it.

On the chip the same readings are taken at the cells' own sizes by
``bench/tools/control.py``; ``PERF.md`` gives them with the limits."""
import sys
import time
from pathlib import Path

import jax

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402
from bench.tests import tinycell  # noqa: E402


def test_fp8_control_is_not_correct(tmp_path):
    cell = spec.load_cell(tinycell.CELL, tinycell.make_root(tmp_path))
    limit = cell.config["check"]["max_logit_gap"]
    for seed in (3, 2 ** 32 + 9):
        out = harness.run_cell(cell, seed, 2.0, False, jax.devices()[:1],
                               time.monotonic(), pallas_interpret=True,
                               control=True)
        assert out["correct"] is True
        assert out["check"]["logit_gap"]["value"] <= limit
        assert out["control_gap"] > limit, out["control_gap"]
