"""Compile-only checks of the served path's decode kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology, and refuses what the
chip would refuse (unaligned slices, too much fast memory, programs
that do not fit).  Shapes are qwen3-1.7b's (16 query / 8 KV heads of
128, 28 layers) with 8 slots of 4096 tokens, paged as 257 pages of 128;
the paged decode kernel also at yi-9b's share of one chip at TP=4 (64
slots of 16 pages, 8 query heads over 1 KV head, 48 layers).
Nothing runs; these guard the compiled kernels at real widths, and the
whole decode-chunk program of the served configuration against copies
of its KV page pool.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import dataclasses
import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention,
                                                paged_verify_attention)

B, H, KVH, D, S = 8, 16, 8, 128, 4096
LAYERS = 28
PAGE = 128
N_PAGES = B * S // PAGE + 1          # + the reserved garbage page
POOL = (LAYERS, KVH, N_PAGES, PAGE, D)
GiB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not lowered"
    return compiled


def test_decode_attention_compiles_for_v5e(one_chip):
    bf16 = jnp.bfloat16
    _compile(partial(decode_attention, interpret=False),
             _sds((B, 1, H, D), bf16, one_chip),
             _sds((B, S, KVH, D), bf16, one_chip),
             _sds((B, S, KVH, D), bf16, one_chip),
             _sds((B,), jnp.int32, one_chip))


# (slots, pages a slot, query heads, KV heads, layers) of the paged
# decode kernel on one chip: qwen3-1.7b's as above, and yi-9b's share
# at TP=4 (64 slots of 2048 tokens, 8 query heads over 1 KV head)
PAGED_SHAPES = {"qwen3": (B, S // PAGE, H, KVH, LAYERS),
                "yi-9b-tp4": (64, 16, 8, 1, 48)}


@pytest.mark.parametrize("shape", sorted(PAGED_SHAPES))
def test_paged_decode_attention_compiles_for_v5e(one_chip, shape):
    b, nb, h, kvh, layers = PAGED_SHAPES[shape]
    pool = (layers, kvh, b * nb + 1, PAGE, D)
    bf16 = jnp.bfloat16
    _compile(partial(paged_decode_attention, interpret=False),
             _sds((b, 1, h, D), bf16, one_chip),
             _sds(pool, bf16, one_chip),
             _sds(pool, bf16, one_chip),
             _sds((b, nb), jnp.int32, one_chip),
             _sds((b,), jnp.int32, one_chip),
             _sds((), jnp.int32, one_chip))


@pytest.mark.parametrize("t", [5, 32])
def test_paged_verify_attention_compiles_for_v5e(one_chip, t):
    bf16 = jnp.bfloat16
    _compile(partial(paged_verify_attention, interpret=False),
             _sds((B, t, H, D), bf16, one_chip),
             _sds(POOL, bf16, one_chip),
             _sds(POOL, bf16, one_chip),
             _sds((B, S // PAGE), jnp.int32, one_chip),
             _sds((B,), jnp.int32, one_chip),
             _sds((), jnp.int32, one_chip))


# an HLO instruction: ``%name = dtype[dims]{layout} opcode(``
_HLO_OP = re.compile(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def pool_sized_moves(hlo: str, pool_shape) -> list:
    """Instructions of ``hlo`` (fused computations included) that copy,
    transpose or slice a whole pool: a ``copy``, ``transpose`` or
    ``dynamic-slice`` whose result has as many elements as one layer's
    pool or the stacked pool of every layer."""
    whole = math.prod(pool_shape)
    sizes = {whole, whole // pool_shape[0]}
    moves = []
    for line in hlo.splitlines():
        m = _HLO_OP.search(line)
        if m is None or m.group(2) not in ("copy", "transpose",
                                           "dynamic-slice"):
            continue
        dims = [int(x) for x in m.group(1).split(",") if x]
        if math.prod(dims) in sizes:
            moves.append(line.strip()[:160])
    return moves


def _served_engine(one_chip):
    """The served qwen3-1.7b engine (16 slots of 2048 tokens, pages of
    128) over shapes only, and its parameters and state placed on the
    described chip."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.param import abstract_params
    from repro.serving import ContinuousBatchingEngine

    class ShapesOnly(ContinuousBatchingEngine):
        def _zeros(self, specs):
            return specs

    cfg = dataclasses.replace(get_config("qwen3-1.7b"), use_pallas=True,
                              pallas_interpret=False)
    model = build_model(cfg)
    params = abstract_params(model.param_defs())
    eng = ShapesOnly(model, params, n_slots=16, max_len=2048,
                     kv_page_size=128, chunk_steps=8)

    def placed(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                            tree)

    pool = eng.state["cache"]["layers"]["blocks"]["k"].shape
    assert pool == (28, 8, 16 * 16 + 1, 128, 128), pool
    return eng, placed(params), placed(eng.state), pool


def test_decode_chunk_keeps_kv_pool_in_place_for_v5e(one_chip):
    """The served decode chunk writes only the new tokens' rows into its
    KV page pools: no whole-pool or per-layer copy, transpose or slice,
    and temporaries far below a second copy of the pools (3.5 GiB)."""
    eng, params, state, pool = _served_engine(one_chip)
    compiled = eng._decode_chunk.lower(params, state).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, "the Pallas kernel was not lowered"
    assert pool_sized_moves(hlo, pool) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1 * GiB, f"decode chunk temporaries {temp / GiB:.3f} GiB"


@pytest.mark.parametrize("program", ["prefill_chunk", "extend_slot"])
def test_paged_verify_programs_keep_kv_pool_in_place_for_v5e(one_chip,
                                                             program):
    """Chunked prefill and a prefix-cache hit's suffix write through the
    paged verify path into the same pools, also in place."""
    eng, params, state, pool = _served_engine(one_chip)
    i32 = jnp.int32
    row = _sds((eng.pages_per_slot,), i32, one_chip)
    scalar = _sds((), i32, one_chip)
    if program == "prefill_chunk":
        low = eng._prefill_chunk.lower(params, state,
                                       _sds((1, 512), i32, one_chip), row,
                                       scalar)
    else:
        low = eng._extend_slot.lower(params, None, state,
                                     _sds((1, 1024), i32, one_chip),
                                     _sds((1, 256), i32, one_chip),
                                     scalar, row, scalar, scalar)
    compiled = low.compile()
    assert pool_sized_moves(compiled.as_text(), pool) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 * GiB
