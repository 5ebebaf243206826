"""The command on the CPU: a cell added from new files alone runs at a
reduced size (the Pallas kernels in the interpreter) and prints the
contract's line; a measured run with no TPU refuses to run."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402
from bench.tests import tinycell  # noqa: E402

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make_root(tmp_path_factory.mktemp("tinyroot"))


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_new_cell_runs_on_cpu_with_the_contract_line(root, trace):
    cell = spec.load_cell(tinycell.CELL, root)
    devices = jax.devices()[:1]
    out = harness.run_cell(cell, 2 ** 33 + 1, 2.0, trace, devices,
                           time.monotonic(), pallas_interpret=True)
    line = json.loads(harness.result_line(out, devices))
    assert list(line) == KEYS + (["breakdown"] if trace else []) + ["check"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 12
    want = ({"compiles_in_window", "decode_occupancy", "tokens_per_chunk"}
            if trace else {"ttft_p90_ms", "tpot_p90_ms", "setup_s"})
    assert set(line["metrics"]) == want
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert set(line["check"]) == {"logit_gap", "bad_requests"}
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        assert line["device"]["busy_s"] > 0
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, timeout=120,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


ARGS = ["--workload", "qwen3-1.7b.chat-server", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def test_no_tpu_exits_nonzero_naming_the_device():
    p = _run(ARGS, ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr and "cpu" in p.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(ARGS, tmp_path)
    assert p.returncode != 0 and p.stdout == ""
