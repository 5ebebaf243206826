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

The paged decode kernel (``paged_decode_attention_kernel``) reads the
stacked page pool ``(L, KVH, P, page_size, D)`` where it lies, in HBM.
Its grid is ``(slots, NB / pages_per_block)``: one step covers one slot,
every (shard-local) KV head and ``pages_per_block`` consecutive entries
of the slot's page table.  It copies only the live pages of a block
(``pos // page_size + 1`` of them in the slot) by hand into one half of
a VMEM double buffer, after starting the next live block's copies --
the slot's next block, or the first block of the next slot that holds
a request -- into the other half.  Page 0 of the pool is reserved
(``EMPTY_PAGE``): no allocation returns it, a retired slot's row points
at it, and a slot whose row starts with it is skipped whole, however
deep its frozen ``pos``.  The wrapper (``ops.fit_pages_per_block``)
derives ``pages_per_block`` from the shapes: the most pages whose K and
V over the local heads fit a fixed byte budget, dividing NB -- 4 pages
a step for 8 KV heads of 128, all 16 for one head.
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


# Page 0 of every pool is reserved: no allocation returns it, a retired
# slot's table row points at it, and a row that starts with it holds no
# request.
EMPTY_PAGE = 0


def _paged_kernel(pages_ref, pos_ref, layer_ref, q_ref, k_hbm, v_hbm,
                  o_ref, k_buf, v_buf, sems, cur_ref, nxt_ref, m_scr, l_scr,
                  acc_scr, *, scale: float, page_size: int, ppb: int,
                  ppc: int):
    """Page-table-indirect decode attention, one slot and one block of
    ``ppb`` table entries per grid step, every local KV head at once.

    The pools stay in HBM; the live pages of a block are copied by
    hand (one DMA per page and pool, all heads) into one half of a
    double buffer, and the next live block -- the same slot's next, or
    the first block of the next slot that holds a request -- is started
    into the other half before this one is computed, so a slot's first
    fetch hides behind its predecessor too.  A slot has
    ``pos // page_size + 1`` live pages, none if its row starts with
    ``EMPTY_PAGE``; nothing else is copied, and arithmetic runs on the
    live pages in runs of ``ppc`` (``pages_per_branch``).  The
    recurrence is ``_kernel``'s at one page a block, for each head:
    float32 scores, running max, sum and accumulator, and a float32
    ``p @ v`` whose ``v`` is upcast one page at a time and zeroed past
    ``pos``, where the buffer may hold stale or uninitialised rows (a
    masked page of a run is then an exact no-op).  An empty slot's
    output is zeros.
    """
    b = pl.program_id(0)
    blk = pl.program_id(1)
    n_slots, nb = pages_ref.shape
    kvh = k_buf.shape[1]
    layer = layer_ref[0]

    def live_pages(s):
        n = jnp.minimum(pos_ref[s] // page_size + 1, nb)
        return jnp.where(pages_ref[s, 0] == EMPTY_PAGE, 0, n)

    def block_copies(s, i, half):
        """(live, [k copy, v copy]) for each page of block ``i`` of
        slot ``s``, into buffer half ``half``."""
        n = live_pages(s)
        out = []
        for j in range(ppb):
            page = pages_ref[s, i * ppb + j]
            out.append((i * ppb + j < n, [
                pltpu.make_async_copy(hbm.at[layer, :, page],
                                      buf.at[half, :, j], sems.at[x, half])
                for x, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]))
        return out

    def start(s, i, half):
        for live, copies in block_copies(s, i, half):
            @pl.when(live)
            def _():
                for c in copies:
                    c.start()

    def wait(s, i, half):
        for live, copies in block_copies(s, i, half):
            @pl.when(live)
            def _():
                for c in copies:
                    c.wait()

    @pl.when((b == 0) & (blk == 0))
    def _prologue():
        # nxt_ref[s]: the first slot at or after s that holds a request,
        # n_slots if none; a fixed-length pass, once per call
        nxt_ref[n_slots] = n_slots

        def back(i, carry):
            s = n_slots - 1 - i
            nxt_ref[s] = jnp.where(live_pages(s) > 0, s, nxt_ref[s + 1])
            return carry

        jax.lax.fori_loop(0, n_slots, back, 0)
        cur_ref[0] = 0
        first = nxt_ref[0]

        @pl.when(first < n_slots)
        def _():
            start(first, 0, 0)

    @pl.when(blk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n = live_pages(b)
    pos = pos_ref[b]

    @pl.when(blk * ppb < n)
    def _block():
        half = cur_ref[0]
        more = (blk + 1) * ppb < n
        nxt = jnp.where(more, b, nxt_ref[b + 1])

        @pl.when(nxt < n_slots)
        def _():
            start(nxt, jnp.where(more, blk + 1, 0), 1 - half)

        wait(b, blk, half)
        k_rows = jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        v_rows = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
        for j0 in range(0, ppb, ppc):
            @pl.when(blk * ppb + j0 < n)
            def _pages():
                for h in range(kvh):
                    m, l, acc = m_scr[h], l_scr[h], acc_scr[h]
                    for j in range(j0, j0 + ppc):
                        row0 = (blk * ppb + j) * page_size
                        s = jax.lax.dot_general(
                            q_ref[0, h], k_buf[half, h, j],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
                        s = jnp.where(row0 + k_rows <= pos, s, NEG_INF)
                        m_new = jnp.maximum(m, jnp.max(s, axis=1)[:, None])
                        p = jnp.exp(s - m_new)
                        alpha = jnp.exp(m - m_new)
                        l = alpha * l + jnp.sum(p, axis=1)[:, None]
                        v = jnp.where(row0 + v_rows <= pos,
                                      v_buf[half, h, j].astype(jnp.float32),
                                      0.0)
                        acc = acc * alpha + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        m = m_new
                    m_scr[h], l_scr[h], acc_scr[h] = m, l, acc

        cur_ref[0] = 1 - half

    @pl.when(blk == pl.num_programs(1) - 1)
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


def pages_per_branch(kvh: int, pages_per_block: int) -> int:
    """Pages of a block the paged kernel computes between two live-page
    checks: about four page-heads of arithmetic, so that one KV head
    still gives the scheduler several independent pages, dividing the
    block.  Pages in a computed run past the slot's depth are exact
    no-ops (scores masked, ``v`` zeroed)."""
    ppc = max(1, min(pages_per_block, 4 // kvh))
    while pages_per_block % ppc:
        ppc -= 1
    return ppc


def paged_decode_attention_kernel(q, k, v, pages, pos, layer, *,
                                  pages_per_block: int,
                                  interpret: bool = False) -> jax.Array:
    """q: (BH, G, D) slot-major (row = slot * KVH + head); k, v:
    (L, KVH, P, page_size, D) stacked pool, left in HBM; pages: (B, NB)
    int32 page table, page 0 reserved (``EMPTY_PAGE``); pos: (B,) int32
    per-slot depth; layer: () int32, the layer of the pool to read;
    ``pages_per_block`` divides NB.  Returns (BH, G, D)."""
    bh, g, d = q.shape
    _, kvh, _, page_size, _ = k.shape
    b, nb = pages.shape
    ppb = pages_per_block
    ppc = pages_per_branch(kvh, ppb)
    assert bh == b * kvh, (bh, b, kvh)
    assert nb % ppb == 0 and ppb % ppc == 0, (nb, ppb, ppc)
    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(d),
                               page_size=page_size, ppb=ppb, ppc=ppc)
    slot_spec = pl.BlockSpec((1, kvh, g, d), lambda s, i, *_: (s, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb // ppb),
        in_specs=[
            slot_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=slot_spec,
        scratch_shapes=[
            pltpu.VMEM((2, kvh, ppb, page_size, d), k.dtype),
            pltpu.VMEM((2, kvh, ppb, page_size, d), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SMEM((b + 1,), jnp.int32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(pages.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(b, kvh, g, d),
      k, v)
    return out.reshape(bh, g, d)


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
