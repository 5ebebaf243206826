"""W8A8 int8 GEMM with per-channel dequant epilogue — Pallas TPU kernel.

The mechanism behind the paper's Fig. 8 quantization-efficiency study:
int8 x int8 -> int32 accumulation on the MXU (2x bf16 throughput, half
the HBM bytes), with per-row activation scales and per-column weight
scales applied once in the epilogue.

Grid (nM, nN, nK), K innermost; int32 accumulator in VMEM scratch.
Block 256x256x256 int8 = 3 x 64 KB inputs + 256 KB accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_scr, *, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ik == n_k - 1)
    def _finish():
        sx = sx_ref[...]                      # (bm, 1) f32
        sw = sw_ref[...]                      # (1, bn) f32
        o_ref[...] = (acc_scr[...].astype(jnp.float32) * sx * sw
                      ).astype(o_ref.dtype)


def int8_matmul_kernel(x, w, sx, sw, *, block_m: int = 256,
                       block_n: int = 256, block_k: int = 256,
                       out_dtype=jnp.bfloat16,
                       interpret: bool = False) -> jax.Array:
    """x: (M, K) int8; w: (K, N) int8; sx: (M, 1) f32; sw: (1, N) f32."""
    m, k = x.shape
    n = w.shape[1]
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    grid = (m // block_m, n // block_n, k // block_k)
    kernel = functools.partial(_kernel, n_k=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, sx, sw)
