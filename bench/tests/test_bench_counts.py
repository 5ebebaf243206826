"""The benchmark's counts of work, against values worked by hand at
the published qwen3-1.7b and yi-9b widths."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))

from bench import counts as C  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


QWEN, YI = cfg("qwen3-1.7b"), cfg("yi-9b")


def test_kv_bytes_per_token():
    # 28 layers x (K and V) x 8 heads x 128 x 2 bytes = 112 KiB
    assert C.kv_bytes_per_token(QWEN) == 112 * 1024
    # 48 x 2 x 4 x 128 x 2 = 96 KiB over four chips, 24 KiB on each
    assert C.kv_bytes_per_token(YI) == 4 * 24576


def test_matmul_params():
    # per layer: q 2048x2048, k and v 2048x1024 each, o 2048x2048,
    # gate/up/down 3 x 2048x6144 = 50,331,648; x 28 = 1,409,286,144;
    # head 2048 x 151936 = 311,164,928
    assert C.matmul_params(QWEN) == 1_409_286_144 + 311_164_928
    # yi: 4096 x (4096 + 1024 + 4096) + 3 x 4096 x 11008 = 173,015,040
    # per layer, x 48, + head 4096 x 64000
    assert C.matmul_params(YI) == 48 * 173_015_040 + 262_144_000


def test_decode_token_flops():
    ctx = 1000
    attn = 4 * 28 * 16 * 128 * ctx           # 229,376,000
    assert C.attn_flops(QWEN, ctx) == attn
    assert C.decode_token_flops(QWEN, ctx) == 2 * 1_720_451_072 + attn


def test_paged_attention_call():
    # two slots at 100 and 300 tokens, one layer, one chip
    flops, nbytes = C.paged_attn_call(QWEN, [100, 300])
    assert flops == 4 * 16 * 128 * 400
    kv = 2 * 8 * 128 * 400 * 2                # K and V rows, bf16
    qo = 2 * 2 * 16 * 128 * 2                 # q and out of 2 slots
    assert nbytes == kv + qo
    # at TP=4 a chip holds 8 of 32 query heads and 1 of 4 KV heads
    f4, b4 = C.paged_attn_call(YI, [100], shards=4)
    assert f4 == 4 * 8 * 128 * 100
    assert b4 == 2 * 1 * 128 * 100 * 2 + 2 * 8 * 128 * 2
    # bound by HBM on a v5e: ~2 FLOPs per byte against ~240
    p = peaks_for("TPU v5 lite")
    assert flops / nbytes < p["bf16_flops"] / p["hbm_bytes_per_s"] / 50


def test_decode_contexts():
    # a 10-token prompt answered with 4 tokens: the first comes from the
    # prefill, the next three from decode steps at 11, 12, 13 rows
    assert list(C.decode_contexts(10, 4)) == [11, 12, 13]
    assert list(C.decode_contexts(10, 1)) == []


def test_prefill_flops():
    s = 1536
    body = 2 * 1_409_286_144 * s
    attn = 4 * 28 * 16 * 128 * (s * (s + 1) // 2)
    head = 2 * 2048 * 151936
    assert C.prefill_flops(QWEN, s) == body + attn + head
    assert C.prefill_flops(QWEN, s) == pytest.approx(4.6e12, rel=0.01)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
