"""The tensor-parallel cell ``yi-9b.tp4-offline`` at a size the CPU holds.

- Yi-9B's pattern (no qk_norm, 8 query heads to each KV head, RoPE at
  10000, an output head of its own) at TP=4 on four virtual CPU devices,
  one KV head a shard: prefill and paged decode through
  ``ShardedContinuousBatchingEngine`` against the plain reference, with
  and without the exchange between shards; the decode chunk's compiled
  program holds two all-reduces a layer; a tiny four-chip cell is
  ``correct`` through ``harness.run_cell``.
- The readers of the exchange (``collective_ms_per_step``,
  ``collective_share``) on a synthetic trace of two chips worked by
  hand.
- The traffic file ``tp4-offline``.
"""
import json
import os
import re
import subprocess
import sys
import textwrap
import types
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench import trace as T  # noqa: E402
from bench import traffic as TR  # noqa: E402
from bench.tests.test_bench_trace import _line, _plane  # noqa: E402

CELL = "yi-9b.tp4-offline"
CONFIG = json.loads((BENCH / "configs" / "yi-9b-tp4.json").read_text())
MIX = json.loads((BENCH / "traffic" / "tp4-offline.json").read_text())
BIG = 2 ** 31 + 12345

# Tolerances of the logits of the first decode step against the plain
# reference.  float32: the engine and the reference differ only in the
# order of their sums (readings at most 3.3e-6 on logits of spread ~1).
# bfloat16: weights, activations and the cache rounded to 8 bits of
# mantissa, a relative step of 2^-8 at each of some dozen roundings on
# the path, against a float32 reference of the same rounded weights
# (readings 0.027-0.040 on logits of spread ~1 and magnitude up to 3.5):
# 0.1 is 2.5 times the widest reading and a tenth of the logits'
# spread.  Without the exchange the readings are 3-6.
F32_TOL = 1e-4
BF16_TOL = 0.1

TP_RUN = textwrap.dedent("""
    import json, sys, time
    from pathlib import Path
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    import numpy as np
    from bench import check, harness, served, spec
    from bench.tests.test_bench_reference import REF, ref_logits
    from bench.tests.test_bench_tp4 import make_root, tiny_config
    import repro.models.layers as L
    from repro.serving import Request

    seed = 2 ** 33 + 5
    devices = jax.devices()[:4]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, s, dtype=np.int32) for s in (5, 40)]
    out = {{}}

    def first_step_gaps(cfg, engine):
        got = []
        for p in prompts:
            logits = np.asarray(engine.first_step_logits(p))
            tok0 = int(ref_logits(cfg, seed, p)[-1].argmax())
            want = ref_logits(cfg, seed, np.append(p, tok0))[-1]
            got.append(float(np.abs(logits[:len(want)] - want).max()))
        return got

    f32 = tiny_config("float32")
    _, engine = served.build_engine(f32, seed, devices,
                                    pallas_interpret=True)
    out["f32"] = first_step_gaps(f32, engine)
    reqs = [Request(rid=i, prompt=rng.integers(0, 500, s, dtype=np.int32),
                    max_new_tokens=n)
            for i, (s, n) in enumerate([(7, 30), (33, 9), (18, 21)])]
    done = engine.serve(reqs, honor_arrivals=False)
    seqs = [(np.asarray(r.prompt), r.output) for r in done]
    out["f32_served"] = check.served_gaps(REF, f32, seed, seqs,
                                          f32["engine"]["max_len"])["served"]
    Path({tmp!r}, "chunk.hlo").write_text(engine._decode_chunk.lower(
        engine.params, engine.state).compile().as_text())

    bf16 = tiny_config("bfloat16")
    _, engine = served.build_engine(bf16, seed, devices,
                                    pallas_interpret=True)
    out["bf16"] = first_step_gaps(bf16, engine)

    exchange, L.tp_psum = L.tp_psum, lambda x: x
    _, engine = served.build_engine(f32, seed, devices,
                                    pallas_interpret=True)
    out["no_exchange"] = first_step_gaps(f32, engine)
    L.tp_psum = exchange

    cell = spec.load_cell("tiny.tp4", make_root(Path({tmp!r}) / "root"))
    r = harness.run_cell(cell, 2 ** 32 + 7, 2.0, False, devices,
                         time.monotonic(), pallas_interpret=True)
    out["cell"] = {{"correct": r["correct"], "failed": r["failed"],
                   "attempted": r["attempted"], "check": r["check"]}}
    print(json.dumps(out))
""")


def tiny_config(dtype: str) -> dict:
    """Yi-9B's configuration file cut to a CPU's size, with its pattern
    kept: 32 query heads on 4 KV heads (one a shard at TP=4), no
    qk_norm, RoPE at 10000, an output head of its own."""
    cfg = json.loads(json.dumps(CONFIG))
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               head_dim=16, vocab_size=500, torch_dtype=dtype)
    cfg["engine"].update(n_slots=4, max_len=128, kv_page_size=16)
    # the tiny model's own limit, from its own readings on the CPU
    # (bfloat16, four chips, seeds 1-6 and 2**32 + 7): the program's
    # widest gap at most 0.0198, the fp8 control's at least 0.3631
    cfg["check"]["max_logit_gap"] = 0.08
    return cfg


def make_root(tmp: Path) -> Path:
    """A benchmark root holding one four-chip cell, ``tiny.tp4``: the
    tiny Yi-9B pattern under the ``tp4-offline`` mix cut to its size."""
    from bench.tests import tinycell

    root = tinycell.make_root(tmp, kind="offline", chips=4)
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(tiny_config("bfloat16")))
    mix = json.loads(json.dumps(MIX))
    mix.update(rate_per_s=8.0, library=4, check_requests=3)
    mix["prompt"].update(median=40, min=8, max=64)
    mix["output"].update(median=10, min=2, max=32)
    (root / "bench" / "traffic" / "tiny-tp4.json").write_text(
        json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "tiny.tp4", "config": "tiny",
                           "traffic": "tiny-tp4", "chips": 4}]
    bench["end_to_end"] = [{"name": "output_tokens_per_s",
                            "unit": "tokens/s"},
                           {"name": "tpot_p90_ms", "unit": "ms"},
                           {"name": "setup_s", "unit": "s"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def tp4(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tp4")
    code = TP_RUN.format(root=str(ROOT), src=str(ROOT / "src"),
                         tmp=str(tmp))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, timeout=600,
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    out["hlo"] = (tmp / "chunk.hlo").read_text()
    return out


def test_tp4_matches_the_reference_in_float32(tp4):
    assert max(tp4["f32"]) <= F32_TOL, tp4["f32"]
    # served through ``serve``: pages written and read across chunks
    assert max(tp4["f32_served"]) <= F32_TOL, tp4["f32_served"]


def test_tp4_matches_the_reference_in_bfloat16(tp4):
    assert max(tp4["bf16"]) <= BF16_TOL, tp4["bf16"]


def test_tp4_without_the_exchange_does_not_match(tp4):
    assert min(tp4["no_exchange"]) > BF16_TOL, tp4["no_exchange"]


def test_tiny_tp4_cell_is_correct(tp4):
    c = tp4["cell"]
    assert c["correct"] is True and c["failed"] == 0, c
    assert c["attempted"] == round(8.0 * 2.0)


def _computations(hlo: str) -> dict:
    """{name: [instruction lines]} of an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def all_reduces_per_step(hlo: str) -> int:
    """All-reduces one step of the decode chunk executes: those in the
    body of the chunk's loop (the one loop whose trip count the
    compiler cannot know, as it ends early once every slot is done),
    with every loop inside it counted its known number of times."""
    comps = _computations(hlo)

    def count(comp: str) -> int:
        n = 0
        for line in comps[comp]:
            if re.search(r"\ball-reduce(-start)?\(", line):
                n += 1
            for callee in re.findall(r"calls=%([\w.-]+)", line):
                n += count(callee)
            loop = re.search(r"\bwhile\(.*body=%([\w.-]+)", line)
            if loop:
                trip = re.search(r'"known_trip_count":\{"n":"(\d+)"\}',
                                 line)
                if trip is None:
                    raise ValueError("a loop of unknown trip count "
                                     "inside a step")
                n += int(trip.group(1)) * count(loop.group(1))
        return n

    steps = [re.search(r"body=%([\w.-]+)", line).group(1)
             for comp in comps.values() for line in comp
             if re.search(r"\bwhile\(", line)
             and "known_trip_count" not in line]
    assert len(steps) == 1, steps
    return count(steps[0])


def test_decode_step_holds_two_all_reduces_a_layer(tp4):
    layers = tiny_config("float32")["num_hidden_layers"]
    assert all_reduces_per_step(tp4["hlo"]) == 2 * layers


# --- the readers of the exchange, on a synthetic trace ---------------

OPS = {1: "%psum.30 = bf16[64,1,4096] all-reduce(x)",
       2: "%all-reduce-start.2 = bf16[64,1,4096] all-reduce-start(x)",
       3: "%all-reduce-done.2 = bf16[64,1,4096] all-reduce-done(x)",
       4: "%fusion.5 = bf16[64,4096] fusion(x)",
       5: "%all-reduce.7 = bf16[1,512,4096] all-reduce(x)",
       6: "%all-gather.3 = bf16[4,8] all-gather(x)",
       11: "jit__decode_chunk_impl(1)",
       12: "jit__prefill_slot_impl(2)"}


def synthetic():
    """Window [100, 300) ns, two chips.  Chip 0: a decode chunk over
    [100, 160) holding ``psum.30`` [110, 120), a fusion [120, 130) and
    an async all-reduce [130, 132) + [140, 146); a prefill over [200,
    240) holding ``all-reduce.7`` [210, 220); ``all-gather.3`` [260, 270)
    outside every program.  Chip 1: two decode chunks, [100, 150) with
    ``psum.30`` [105, 125) and [150, 190) with the fusion [150, 170);
    ``all-gather.3`` [280, 284)."""
    from jax.profiler import ProfileData

    tpu0 = (_line(1, "XLA Modules", 0, [(11, 100, 60), (12, 200, 40)])
            + _line(2, "XLA Ops", 0, [(1, 110, 10), (4, 120, 10),
                                      (2, 130, 2), (3, 140, 6),
                                      (5, 210, 10), (6, 260, 10)]))
    tpu1 = (_line(1, "XLA Modules", 0, [(11, 100, 50), (11, 150, 40)])
            + _line(2, "XLA Ops", 0, [(1, 105, 20), (4, 150, 20),
                                      (6, 280, 4)]))
    host = _line(1, "main", 0, [(1, 100, 200)])
    text = (_plane(1, "/device:TPU:0", tpu0, OPS)
            + _plane(2, "/device:TPU:1", tpu1, OPS)
            + _plane(3, "/host:CPU", host, {1: T.WINDOW}))
    return T.load(ProfileData.from_text_proto(text))


def _read(metric, tr, chunk_steps=2):
    cell = spec.load_cell(CELL, ROOT)
    ctx = types.SimpleNamespace(trace=tr, chunk_steps=chunk_steps)
    return spec.metric_reader(cell, metric)(ctx)


def test_collective_ms_per_step_by_hand():
    # inside decode chunks: chip 0 10 + 2 + 6 = 18 ns in one chunk, chip
    # 1 20 ns in two; 19 ns a chip over 1.5 chunks a chip of 2 steps
    want = 1e3 * 19e-9 / (1.5 * 2)
    assert _read("collective_ms_per_step", synthetic()) == \
        pytest.approx(want)


def test_collective_share_by_hand():
    # every collective: chip 0 18 + 10 + 10 = 38 ns of 48 busy (the
    # fusion's 10 ns is busy, not exchange), chip 1 20 + 4 = 24 ns of 44
    # busy: 31 of 46 ns on average
    assert _read("collective_share", synthetic()) == \
        pytest.approx(100.0 * 31 / 46)


def test_readers_without_collectives_read_nothing():
    from bench.tests.test_bench_trace import synthetic as plain

    tr = T.load(plain())
    assert _read("collective_ms_per_step", tr) is None
    assert _read("collective_share", tr) is None


def test_collectives_tool_counts_by_hand():
    tool = spec.load_module(BENCH / "tools" / "collectives.py",
                            "bench_tool_collectives")
    chip0 = tool.chips(synthetic(), chunk_steps=2)[0]
    assert {k: v["count"] for k, v in chip0["names"].items()} == {
        "psum": 1, "all-reduce-start": 1, "all-reduce-done": 1,
        "all-reduce": 1, "all-gather": 1}
    assert chip0["names"]["psum"]["seconds"] == pytest.approx(10e-9)
    assert chip0["_decode_chunk_impl"] == {
        "executions": 1, "collectives": 3, "most_common_per_execution": 3}
    assert chip0["_prefill_slot_impl"]["collectives"] == 1
    assert chip0["per_decode_step"] == 1.5


def test_collective_names():
    from bench import collectives as C

    for name in ("psum.30", "all-reduce.7", "all-reduce-start.2",
                 "all-reduce-done.2", "all-gather-start", "reduce-scatter.1",
                 "collective-permute-done.4", "all-to-all.2",
                 "all-reduce-fusion.3", "psum_scatter.1"):
        assert C.is_collective(name), name
    for name in ("fusion.5", "bitcast_add_fusion", "paged_decode_attention.7",
                 "copy.73", "convolution_bitcast_fusion.2", "while.52"):
        assert not C.is_collective(name), name


# --- the traffic file -------------------------------------------------

def test_the_mix_holds_its_parameters():
    assert MIX["kind"] == "offline" and MIX["library"] == 32
    assert MIX["check_requests"] == 8
    for key, want in (("prompt", (1024, 0.5, 64, 1536)),
                      ("output", (128, 1.0, 16, 512))):
        d = MIX[key]
        assert (d["median"], d["sigma"], d["min"], d["max"]) == want
    assert MIX["slo"] == {"ttft_s": 2.0, "tpot_s": 0.1}


def test_seed_reproduces_its_schedule():
    a = TR.schedule(MIX, 10, BIG, 1000)
    b = TR.schedule(MIX, 10, BIG, 1000)
    c = TR.schedule(MIX, 10, BIG + 1, 1000)
    assert [p.max_new_tokens for p in a] == [p.max_new_tokens for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    assert any(not np.array_equal(p.prompt, q.prompt)
               for p, q in zip(a, c))


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_every_seed_sends_the_library_lengths(seed):
    lib_p = TR.quantile_lengths(MIX["prompt"], MIX["library"])
    lib_o = TR.quantile_lengths(MIX["output"], MIX["library"])
    assert {len(p.prompt) for p in TR.warm_up_requests(MIX, 1000)} == \
        set(lib_p)
    seconds = 2 * MIX["library"] / MIX["rate_per_s"]
    plan = TR.schedule(MIX, seconds, seed, 1000)
    assert Counter(len(p.prompt) for p in plan) == Counter(lib_p * 2)
    assert Counter(p.max_new_tokens for p in plan) == Counter(lib_o * 2)


@pytest.mark.parametrize("seed", [1, BIG])
def test_requests_are_due_at_once_and_fit_the_slots(seed):
    seconds = 51
    plan = TR.schedule(MIX, seconds, seed, CONFIG["vocab_size"])
    assert len(plan) == round(MIX["rate_per_s"] * seconds)
    assert all(p.arrival_s == 0.0 for p in plan)
    max_len = CONFIG["engine"]["max_len"]
    assert all(len(p.prompt) + p.max_new_tokens <= max_len for p in plan)
    assert all(0 <= int(p.prompt.min()) and int(p.prompt.max())
               < CONFIG["vocab_size"] for p in plan)


def test_the_cell_finds_its_files():
    cell = spec.load_cell(CELL, ROOT)
    assert cell.chips == 4 and cell.config["name"] == "yi-9b"
    assert cell.traffic == MIX
    names = {m["name"] for m in cell.per_layer}
    assert {"collective_ms_per_step", "collective_share",
            "paged_attn_roofline", "decode_step_mfu"} <= names
    assert {m["name"] for m in cell.end_to_end} == \
        {"output_tokens_per_s", "tpot_p90_ms", "setup_s"}
