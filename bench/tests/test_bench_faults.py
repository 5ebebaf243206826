"""The check against the faults a served cell can have, at a size the
CPU holds: the whole run is driven (no look for a chip) with the timed
path broken underneath, and ``correct`` must come out false.

- a token altered where it is produced (every cell);
- the exchange between chips left out (the tensor-parallel cell), on
  four virtual CPU devices.
"""
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402
from bench.tests import tinycell  # noqa: E402


def alter_tokens(engine):
    """Every decoded token leaves the chunk one id off the greedy one."""
    chunk = engine._decode_chunk
    vocab = engine.model.cfg.vocab_size

    def altered(params, state):
        state, buf = chunk(params, state)
        return state, (buf + 1) % vocab

    engine._decode_chunk = altered


def test_altered_token_is_not_correct(tmp_path):
    cell = spec.load_cell(tinycell.CELL, tinycell.make_root(tmp_path))
    out = harness.run_cell(cell, 17, 2.0, False, jax.devices()[:1],
                           time.monotonic(), pallas_interpret=True,
                           breaker=alter_tokens)
    assert out["correct"] is False
    c = out["check"]["logit_gap"]
    assert c["value"] > c["limit"]


TP_RUN = textwrap.dedent("""
    import json, sys, time
    from pathlib import Path
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from bench import harness, spec
    from bench.tests import tinycell

    def drop_exchange(engine):
        import repro.models.layers as L
        L.tp_psum = lambda x: x

    cell = spec.load_cell(tinycell.CELL, tinycell.make_root(
        Path({tmp!r}), chips=4))
    out = {{}}
    for name, brk in (("sound", None), ("no_exchange", drop_exchange)):
        r = harness.run_cell(cell, 23, 2.0, False, jax.devices()[:4],
                             time.monotonic(), pallas_interpret=True,
                             breaker=brk)
        out[name] = [r["correct"], r["check"]["logit_gap"]]
    print(json.dumps(out))
""")


def test_tensor_parallel_without_the_exchange_is_not_correct(tmp_path):
    code = TP_RUN.format(root=str(ROOT), src=str(ROOT / "src"),
                         tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, timeout=600,
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"][0] is True, out
    assert out["no_exchange"][0] is False, out
