"""Split-KV decode attention — Pallas TPU kernel (flash-decoding on TPU).

One new token per sequence attends to a long KV cache.  GPU
flash-decoding splits KV across SMs and merges by LSE; on TPU we
re-tile: the KV axis is the innermost ("arbitrary") grid dim streaming
cache blocks HBM->VMEM, and the G query heads of a KV head form the
(tiny) MXU row block.  Running (m, l, acc) live in VMEM scratch.

The kernel is *ragged*: ``pos`` is a per-row vector (BH,) held in SMEM,
so slots of a continuous-batching decode batch sitting at different
sequence depths decode in one fused call.  Each row masks its own
cache tail and skips (``pl.when``) every KV block entirely past its
position — a slot at depth 100 does one block of work while its
neighbour at depth 8000 streams sixteen, with no host round-trip to
regroup them.  A scalar ``pos`` broadcasts (the fixed-batch path).

VMEM per step (bk=512, d=128): k/v 0.5 MB + acc ~0.06 MB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_k: int, n_k: int):
    ib = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[ib]                              # this row's depth

    @pl.when(ik * block_k <= pos)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (G, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, bk)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _verify_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_k: int, n_k: int,
                   t: int, g: int):
    """Multi-token verify attention: ``t`` query tokens per row.

    The speculative-decoding verify path: row ``ib`` holds the window
    ``[pos, pos + t)`` of one (batch, KV-head) pair — query token ``j``
    of the window attends to cache positions ``<= pos + j`` (causal
    inside the window, the committed prefix below it).  The ``t * g``
    query rows share one MXU block; each masks its own diagonal via the
    row's window offset (``row // g``).  Block skipping covers the whole
    window: a KV block is visited iff it starts at or below the
    window's last position.
    """
    ib = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[ib]                              # window start

    @pl.when(ik * block_k <= pos + t - 1)
    def _step():
        q = q_ref[0].astype(jnp.float32).reshape(t * g, -1)   # (t*g, d)
        k = k_ref[0].astype(jnp.float32)                      # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (t*g, bk)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        q_off = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        s = jnp.where(k_pos <= pos + q_off, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = o.reshape(t, g, o.shape[-1]).astype(o_ref.dtype)


def verify_attention_kernel(q, k, v, pos, *, block_k: int = 512,
                            interpret: bool = False) -> jax.Array:
    """q: (BH, T, G, D); k, v: (BH, S, D); pos: () or (BH,) int32 —
    per-row window start (query token j sits at position pos + j).
    Returns (BH, T, G, D)."""
    bh, t, g, d = q.shape
    s = k.shape[1]
    assert s % block_k == 0, (s, block_k)
    n_k = s // block_k
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_verify_kernel, scale=scale,
                               block_k=block_k, n_k=n_k, t=t, g=g)
    pos_arr = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (bh,))
    return pl.pallas_call(
        kernel,
        grid=(bh, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, t, g, d), lambda b, ik: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, g, d), lambda b, ik: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, q, k, v)


def _paged_kernel(pages_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                  page_size: int, kvh: int):
    """Page-table-indirect decode attention.

    Same flash-decoding recurrence as ``_kernel``, but the KV block for
    grid step ``(ib, ik)`` is *physical page* ``pages[ib // kvh, ik]``
    of head ``ib % kvh`` in layer ``layer`` of the stacked pool — the
    scalar-prefetched table and layer index drive the block index maps,
    so the DMA engine streams pages in logical order while they sit
    anywhere in the pool, and no layer of the pool is ever sliced out.
    Because K/V values at positions ``<= pos`` are identical to the
    contiguous layout and every other position is masked to ``NEG_INF``
    before the softmax, the output is bit-identical to ``_kernel`` for
    any page permutation (garbage-page reads included: those rows are
    always masked).
    """
    ib = pl.program_id(0)
    ik = pl.program_id(1)
    del pages_ref, layer_ref   # consumed by the index maps

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[ib // kvh]                       # this slot's depth

    @pl.when(ik * page_size <= pos)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (G, d)
        k = k_ref[0, 0, 0].astype(jnp.float32)        # (ps, d)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, ps)
        k_pos = ik * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _paged_verify_kernel(pages_ref, pos_ref, layer_ref, q_ref, k_ref,
                         v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         scale: float, page_size: int, kvh: int, t: int,
                         g: int):
    """Multi-token verify through the page table (``_verify_kernel``
    with paged KV blocks): window rows mask their own causal diagonal,
    and a page is visited iff it starts at or below the window's last
    position — windows spanning page boundaries just visit both
    pages."""
    ib = pl.program_id(0)
    ik = pl.program_id(1)
    del pages_ref, layer_ref

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[ib // kvh]                       # window start

    @pl.when(ik * page_size <= pos + t - 1)
    def _step():
        q = q_ref[0].astype(jnp.float32).reshape(t * g, -1)   # (t*g, d)
        k = k_ref[0, 0, 0].astype(jnp.float32)                # (ps, d)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (t*g, ps)
        k_pos = ik * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        q_off = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        s = jnp.where(k_pos <= pos + q_off, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finish():
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = o.reshape(t, g, o.shape[-1]).astype(o_ref.dtype)


def _paged_kv_spec(kvh: int, page_size: int, d: int) -> pl.BlockSpec:
    """One page of one KV head of one layer of the stacked pool
    (L, KVH, P, page_size, D): grid row ``ib`` is slot ``ib // kvh``,
    head ``ib % kvh``; its ``ik``-th block is the table's page."""
    return pl.BlockSpec(
        (1, 1, 1, page_size, d),
        lambda ib, ik, pages, pos, layer: (
            layer[0], ib % kvh, pages[ib // kvh, ik], 0, 0))


def paged_decode_attention_kernel(q, k, v, pages, pos, layer, *,
                                  interpret: bool = False) -> jax.Array:
    """q: (BH, G, D) slot-major (row = slot * KVH + head); k, v:
    (L, KVH, P, page_size, D) stacked pool; pages: (B, NB) int32 page
    table; pos: (B,) int32 per-slot depth; layer: () int32, the layer
    of the pool to read.  Returns (BH, G, D)."""
    bh, g, d = q.shape
    _, kvh, _, page_size, _ = k.shape
    b, nb = pages.shape
    assert bh == b * kvh, (bh, b, kvh)
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_paged_kernel, scale=scale,
                               page_size=page_size, kvh=kvh)
    kv_spec = _paged_kv_spec(kvh, page_size, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, g, d),
                         lambda ib, ik, pages, pos, layer: (ib, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, g, d),
                               lambda ib, ik, pages, pos, layer: (ib, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pages.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k, v)


def paged_verify_attention_kernel(q, k, v, pages, pos, layer, *,
                                  interpret: bool = False) -> jax.Array:
    """q: (BH, T, G, D) slot-major; k, v: (L, KVH, P, page_size, D)
    stacked pool; pages: (B, NB) int32; pos: (B,) int32 per-slot window
    start; layer: () int32.  Returns (BH, T, G, D)."""
    bh, t, g, d = q.shape
    _, kvh, _, page_size, _ = k.shape
    b, nb = pages.shape
    assert bh == b * kvh, (bh, b, kvh)
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_paged_verify_kernel, scale=scale,
                               page_size=page_size, kvh=kvh, t=t, g=g)
    kv_spec = _paged_kv_spec(kvh, page_size, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, t, g, d),
                         lambda ib, ik, pages, pos, layer: (ib, 0, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, t, g, d), lambda ib, ik, pages, pos, layer: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pages.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k, v)


def decode_attention_kernel(q, k, v, pos, *, block_k: int = 512,
                            interpret: bool = False) -> jax.Array:
    """q: (BH, G, D); k, v: (BH, S, D); pos: () or (BH,) int32 —
    per-row current index (a scalar broadcasts to every row).
    Returns (BH, G, D)."""
    bh, g, d = q.shape
    s = k.shape[1]
    assert s % block_k == 0, (s, block_k)
    n_k = s // block_k
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_kernel, scale=scale, block_k=block_k,
                               n_k=n_k)
    pos_arr = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (bh,))
    return pl.pallas_call(
        kernel,
        grid=(bh, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, d), lambda b, ik: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda b, ik: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, q, k, v)
