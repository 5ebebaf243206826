"""Compile-only checks of the served path's decode kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology, and refuses what the
chip would refuse (unaligned slices, too much fast memory, programs
that do not fit).  Shapes are qwen3-1.7b's (16 query / 8 KV heads of
128) with 8 slots of 4096 tokens, paged as 257 pages of 128.  Nothing
runs; these guard the compiled kernels at real widths.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention,
                                                paged_verify_attention)

B, H, KVH, D, S = 8, 16, 8, 128, 4096
PAGE = 128
N_PAGES = B * S // PAGE + 1          # + the reserved garbage page


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


def test_paged_decode_attention_compiles_for_v5e(one_chip):
    bf16 = jnp.bfloat16
    _compile(partial(paged_decode_attention, interpret=False),
             _sds((B, 1, H, D), bf16, one_chip),
             _sds((N_PAGES, PAGE, KVH, D), bf16, one_chip),
             _sds((N_PAGES, PAGE, KVH, D), bf16, one_chip),
             _sds((B, S // PAGE), jnp.int32, one_chip),
             _sds((B,), jnp.int32, one_chip))


@pytest.mark.parametrize("t", [5, 32])
def test_paged_verify_attention_compiles_for_v5e(one_chip, t):
    bf16 = jnp.bfloat16
    _compile(partial(paged_verify_attention, interpret=False),
             _sds((B, t, H, D), bf16, one_chip),
             _sds((N_PAGES, PAGE, KVH, D), bf16, one_chip),
             _sds((N_PAGES, PAGE, KVH, D), bf16, one_chip),
             _sds((B, S // PAGE), jnp.int32, one_chip),
             _sds((B,), jnp.int32, one_chip))
