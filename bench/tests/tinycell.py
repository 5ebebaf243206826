"""A cell made from new files alone, at a size a CPU test can hold.

``make_root(tmp)`` writes a ``BENCHMARK.json`` naming one cell, a
configuration file, a traffic file and a per-layer metric reader of its
own under ``tmp/bench/``, beside copies of the benchmark's references
and metric readers: the harness finds every one by name, with no edit
to a file it already has.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CELL = "tiny.chat"

TINY_METRIC = '''"""Served tokens per decode chunk (a counter of the engine)."""


def read(ctx):
    chunks = ctx.stats["decode_chunks"]
    return sum(len(r.output) for r in ctx.done) / chunks if chunks else None
'''


def make_root(tmp: Path, *, kind: str = "server", rate: float = 6.0,
              slots: int = 4, dtype: str = "bfloat16",
              chips: int = 1) -> Path:
    root = Path(tmp)
    for d in ("metrics", "reference"):
        shutil.copytree(BENCH / d, root / "bench" / d, dirs_exist_ok=True)
    (root / "bench" / "metrics" / "tokens_per_chunk.py").write_text(
        TINY_METRIC)
    for d in ("configs", "traffic"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    cfg = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    cfg.update(name="tiny", hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=500,
               torch_dtype=dtype)
    if chips > 1:       # every chip holds one KV head and two query heads
        cfg.update(num_attention_heads=2 * chips,
                   num_key_value_heads=chips)
    cfg["engine"].update(n_slots=slots, max_len=128, kv_page_size=16)
    # the tiny model's own limit, set from its own readings on the CPU
    # (bfloat16, seeds 1-12 and 2**32 + 9): the program's widest gap at
    # most 0.0246, the fp8 control's at least 0.2018
    cfg["check"]["max_logit_gap"] = 0.08
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "chat-server.json").read_text())
    mix.update(kind=kind, rate_per_s=rate, library=4, check_requests=3)
    mix["prompt"].update(median=40, min=8, max=64)
    mix["output"].update(median=10, min=2, max=32)
    (root / "bench" / "traffic" / "tiny-chat.json").write_text(
        json.dumps(mix))
    bench = {
        "workloads": [{"name": CELL, "config": "tiny",
                       "traffic": "tiny-chat", "chips": chips}],
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "end_to_end": [
            {"name": "ttft_p90_ms", "unit": "ms"},
            {"name": "tpot_p90_ms", "unit": "ms"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "compiles_in_window", "unit": "count"},
            {"name": "decode_occupancy", "unit": "%"},
            {"name": "tokens_per_chunk", "unit": "tokens"}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
