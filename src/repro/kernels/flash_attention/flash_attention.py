"""Causal GQA flash attention — Pallas TPU kernel.

Online-softmax across KV blocks: grid = (B*KVH, G, nQ, nK) with the KV
axis innermost ("arbitrary" semantics), f32 running (m, l, acc) in VMEM
scratch persisting across KV steps.  Block shapes are MXU-aligned
(multiples of 128 on the contracting/lane dims).  Causal blocks above
the diagonal are skipped with ``pl.when`` (no MXU work issued).

VMEM working set per step (bq=bk=128, d=128, f32 accum):
  q (bq, d) + k/v (bk, d) + acc (bq, d) + stats ~ 0.26 MB << 16 MB VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            n_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # block fully above the diagonal contributes nothing
        run = (ik * block_k) <= ((iq + 1) * block_q - 1)

    @pl.when(run if causal else (ik >= 0))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)[:, None]           # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (BH, G, Sq, D); k, v: (BH, Skv, D).  BH = batch * kv_heads,
    G = query heads per kv head.  Returns (BH, G, Sq, D)."""
    bh, g, sq, d = q.shape
    skv = k.shape[1]
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv)
    n_q = sq // block_q
    n_k = skv // block_k
    scale = 1.0 / math.sqrt(d)

    grid = (bh, g, n_q, n_k)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, gg, iq, ik: (b, gg, iq, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, gg, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, gg, iq, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, gg, iq, ik: (b, gg, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, g, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
