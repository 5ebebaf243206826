"""Shared transformer layers: norms, RoPE, attention (GQA / MLA), FFN, MoE.

All attention paths use a chunked, online-softmax formulation (the pure
jnp analogue of the Pallas flash kernels in ``repro.kernels``) so that
no S x S score matrix is ever materialized at 32k context.  When
``cfg.use_pallas`` is set (real TPU), the hot paths dispatch to the
Pallas kernels instead.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.param import ParamDef
from repro.parallel.sharding import (current_rules, expert_axes, shard,
                                     tp_psum)

MASK_VALUE = -1e30
VOCAB_PAD = 2048
ATTN_CHUNK = 1024      # q-chunk of the jnp flash attention


def pad_vocab(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def pad_seq(x: jax.Array, max_len: int) -> jax.Array:
    """Zero-pad axis 1 (sequence) up to ``max_len``."""
    if x.shape[1] == max_len:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, max_len - x.shape[1])
    return jnp.pad(x, pad)


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------
def rmsnorm_def(d: int, dtype: str) -> ParamDef:
    return ParamDef((d,), ("embed",), "ones", dtype)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float,
         rot_dim: Optional[int] = None) -> jax.Array:
    """x: (..., S, H, D); positions: (S,) or (B, S)."""
    d = rot_dim or x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq      # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if ang.ndim == 2:                                          # (S, half)
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:                                                      # (B, S, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    xr, rest = x[..., :d], x[..., d:]
    x1, x2 = xr[..., :half], xr[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([out.astype(x.dtype), rest], -1)


# ----------------------------------------------------------------------
# Attention cores
# ----------------------------------------------------------------------
def _attend_block(q, k, v, bias, scale):
    """One (q-chunk x full-KV) attention with f32 softmax.

    q: (B, Cq, H, D); k, v: (B, S, KVH, D) with H % KVH == 0.
    bias: broadcastable to (B, H, Cq, S) additive mask.
    """
    b, cq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, cq, kvh, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = s.reshape(b, h, cq, k.shape[1]) + bias
    p = jax.nn.softmax(s, axis=-1)
    p = p.reshape(b, kvh, g, cq, k.shape[1])
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, cq, h, d).astype(q.dtype)


def flash_attention_jnp(q, k, v, *, causal: bool, q_offset=0,
                        kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Chunked attention: scan over q chunks of ``ATTN_CHUNK``, full KV
    per chunk.

    Memory is O(Cq * S) instead of O(S^2).  ``q_offset`` is the absolute
    position of q[0] (for prefill continuation); ``kv_len`` masks a
    partially-filled KV cache.
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    kv_pos = jnp.arange(skv)
    valid = jnp.ones((skv,), bool) if kv_len is None else kv_pos < kv_len
    chunk = ATTN_CHUNK

    def bias_for(q_pos):
        m = valid[None, :]
        if causal:
            m = m & (kv_pos[None, :] <= (q_offset + q_pos)[:, None])
        return jnp.where(m, 0.0, MASK_VALUE)[None, None]   # (1,1,Cq,S)

    if sq <= chunk:
        return _attend_block(q, k, v, bias_for(jnp.arange(sq)), scale)

    pad_q = (-sq) % chunk
    if pad_q:                      # e.g. whisper's 1500-frame encoder
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    sq_p = sq + pad_q
    n = sq_p // chunk
    qc = q.reshape(b, n, chunk, h, d).transpose(1, 0, 2, 3, 4)

    def body(_, qi_i):
        qi, i = qi_i
        pos = i * chunk + jnp.arange(chunk)
        return None, _attend_block(qi, k, v, bias_for(pos), scale)

    _, oc = jax.lax.scan(body, None, (qc, jnp.arange(n)))
    out = oc.transpose(1, 0, 2, 3, 4).reshape(b, sq_p, h, d)
    return out[:, :sq] if pad_q else out


def decode_attention_jnp(q, k_cache, v_cache, pos) -> jax.Array:
    """One-token attention against a (possibly seq-sharded) KV cache.

    q: (B, 1, H, D); caches: (B, S, KVH, D); pos: scalar current index
    or a per-slot (B,) vector (ragged continuous-batching decode).
    Softmax reductions over the sharded S axis become psums under SPMD —
    this is flash-decoding's split-KV merge, expressed for GSPMD.
    """
    b, _, h, d = q.shape
    skv, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, kvh, g, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.asarray(pos)
    kv_pos = jnp.arange(skv)
    if pos.ndim == 1:
        mask = kv_pos[None, :] <= pos[:, None]              # (B, S)
        s = jnp.where(mask[:, None, None, :], s, MASK_VALUE)
    else:
        s = jnp.where((kv_pos <= pos)[None, None, None, :], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, d).astype(q.dtype)


def verify_attention_jnp(q, k_cache, v_cache, pos) -> jax.Array:
    """Multi-token attention against a KV cache (speculative verify).

    q: (B, T, H, D); caches: (B, S, KVH, D); pos: scalar or per-slot
    (B,) vector — the window start.  Query token ``t`` of slot ``b``
    attends to cache positions ``<= pos_b + t``: causal within the
    ``[pos, pos + T)`` draft window, full prefix below it.  The T = 1
    case reduces exactly to ``decode_attention_jnp``.
    """
    b, t, h, d = q.shape
    skv, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, t, kvh, g, d)
    s = jnp.einsum("bthgd,bkhd->bthgk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.full((b,), pos, jnp.int32)
    kv_pos = jnp.arange(skv)
    q_pos = pos[:, None] + jnp.arange(t)[None, :]             # (B, T)
    mask = kv_pos[None, None, :] <= q_pos[:, :, None]         # (B, T, S)
    s = jnp.where(mask[:, :, None, None, :], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bthgk,bkhd->bthgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, t, h, d).astype(q.dtype)


def cache_update_window(cache: jax.Array, new: jax.Array,
                        pos) -> jax.Array:
    """Insert ``new`` (B, T, KVH, D) at rows ``[pos, pos + T)`` of the
    cache — the speculative verify window write.  ``pos`` is a scalar
    or a per-slot (B,) vector; every slot writes its own contiguous
    window, by the same one-hot masked update as ``cache_update``."""
    t = new.shape[1]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.full((cache.shape[0],), pos, jnp.int32)
    rows = pos[:, None] + jnp.arange(t)[None, :]              # (B, T)
    oh = (jnp.arange(cache.shape[1])[None, None, :]
          == rows[:, :, None]).astype(cache.dtype)            # (B, T, S)
    hit = oh.sum(axis=1)                                      # (B, S)
    return (cache * (1 - hit[:, :, None, None])
            + jnp.einsum("bts,btkd->bskd", oh, new.astype(cache.dtype)))


def cache_update(cache: jax.Array, new: jax.Array, pos) -> jax.Array:
    """Insert ``new`` (B, 1, KVH, D) at index ``pos`` of a seq-sharded cache.

    ``pos`` is a scalar (whole batch at one depth) or a per-slot (B,)
    vector (continuous batching: every row writes its own depth).

    One-hot masked update: elementwise, shards cleanly, but costs
    2 reads + 1 write of the whole cache.
    """
    pos = jnp.asarray(pos)
    if pos.ndim == 1:
        oh = (jnp.arange(cache.shape[1])[None, :]
              == pos[:, None]).astype(cache.dtype)       # (B, S)
        oh = oh[:, :, None, None]
        return cache * (1 - oh) + new.astype(cache.dtype) * oh
    oh = (jnp.arange(cache.shape[1]) == pos).astype(cache.dtype)
    oh = oh[None, :, None, None]
    return cache * (1 - oh) + new.astype(cache.dtype) * oh


# ----------------------------------------------------------------------
# GQA attention block
# ----------------------------------------------------------------------
def gqa_defs(cfg, *, cross: bool = False) -> dict:
    d, dh = cfg.d_model, cfg.head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    dt = cfg.dtype
    defs = {
        "wq": ParamDef((d, h * dh), ("fsdp", "heads_flat"), "normal", dt),
        "wk": ParamDef((d, kvh * dh), ("fsdp", "kv_flat"), "normal", dt),
        "wv": ParamDef((d, kvh * dh), ("fsdp", "kv_flat"), "normal", dt),
        "wo": ParamDef((h * dh, d), ("heads_flat", "fsdp"), "normal", dt,
                       1.0 / math.sqrt(h * dh * max(1, 2 * cfg.n_layers))),
    }
    if cfg.qkv_bias and not cross:
        defs["bq"] = ParamDef((h * dh,), ("heads_flat",), "zeros", dt)
        defs["bk"] = ParamDef((kvh * dh,), ("kv_flat",), "zeros", dt)
        defs["bv"] = ParamDef((kvh * dh,), ("kv_flat",), "zeros", dt)
    if cfg.qk_norm:
        defs["q_norm"] = rmsnorm_def(dh, dt)
        defs["k_norm"] = rmsnorm_def(dh, dt)
    return defs


def _proj_qkv(x, p, cfg):
    b, s, _ = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, kvh, dh)
    v = v.reshape(b, s, kvh, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def gqa_attention(x, p, cfg, *, causal=True, positions=None, use_rope=True):
    """Full-sequence attention (training / prefill).

    x enters sequence-sharded (seq_sp); q/k/v are resharded to
    head-parallel full-sequence layout (Megatron SP <-> TP reshard),
    attention runs, and the output returns sequence-sharded.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _proj_qkv(x, p, cfg)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    o = flash_attention_jnp(q, k, v, causal=causal)
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim)
    o = tp_psum(o @ p["wo"])
    return shard(o, "batch", "seq_sp", "embed")


def gqa_prefill(x, p, cfg, positions=None):
    """Prefill returning output and the KV to cache (post-RoPE)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _proj_qkv(x, p, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    o = flash_attention_jnp(q, k, v, causal=True)
    o = tp_psum(o.reshape(b, s, -1) @ p["wo"])
    return shard(o, "batch", "seq_sp", "embed"), (k, v)


def gqa_decode(x, p, cfg, cache, pos):
    """One-token decode; cache = dict(k, v) seq-sharded over the model axis.

    ``pos`` is a scalar or a per-slot (B,) vector; with a vector every
    batch row ropes, caches and attends at its own sequence depth (the
    ragged decode of the continuous-batching engine).  On TPU
    (``cfg.use_pallas``) attention dispatches to the ragged split-KV
    Pallas kernel; the jnp path below is its CPU-exact analogue.
    """
    b = x.shape[0]
    q, k, v = _proj_qkv(x, p, cfg)
    pos = jnp.asarray(pos)
    poss = pos[:, None] if pos.ndim == 1 else jnp.full((1,), pos)
    q = rope(q, poss, cfg.rope_theta)
    k = rope(k, poss, cfg.rope_theta)
    k_cache = cache_update(cache["k"], k, pos)
    v_cache = cache_update(cache["v"], v, pos)
    k_cache = shard(k_cache, "batch", "kv_seq", None, None)
    v_cache = shard(v_cache, "batch", "kv_seq", None, None)
    if cfg.use_pallas:
        from repro.kernels.decode_attention.ops import decode_attention
        # block_k auto-fits to the cache length the op sees — the full
        # S on one device, or the shard-local slice under shard_map
        o = decode_attention(q, k_cache, v_cache, pos,
                             interpret=cfg.pallas_interpret)
    else:
        o = decode_attention_jnp(q, k_cache, v_cache, pos)
    o = tp_psum(o.reshape(b, 1, -1) @ p["wo"])
    return o, {"k": k_cache, "v": v_cache}


def gqa_verify(x, p, cfg, cache, pos):
    """Multi-token verify (speculative decoding): score T draft tokens
    per slot in one forward.

    x: (B, T, d); ``pos`` is a scalar or per-slot (B,) window start.
    Token ``t`` ropes/caches at position ``pos + t`` and attends
    causally within the window (committed prefix below it).  Rejected
    tokens need no explicit rollback: attention masks by position, and
    the next verify window starts at the accepted frontier, overwriting
    the stale rows in place.  T = 1 reduces to ``gqa_decode``.
    """
    b, t, _ = x.shape
    q, k, v = _proj_qkv(x, p, cfg)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.full((b,), pos, jnp.int32)
    positions = pos[:, None] + jnp.arange(t)[None, :]         # (B, T)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    k_cache = cache_update_window(cache["k"], k, pos)
    v_cache = cache_update_window(cache["v"], v, pos)
    k_cache = shard(k_cache, "batch", "kv_seq", None, None)
    v_cache = shard(v_cache, "batch", "kv_seq", None, None)
    if cfg.use_pallas:
        from repro.kernels.decode_attention.ops import verify_attention
        o = verify_attention(q, k_cache, v_cache, pos,
                             interpret=cfg.pallas_interpret)
    else:
        o = verify_attention_jnp(q, k_cache, v_cache, pos)
    o = tp_psum(o.reshape(b, t, -1) @ p["wo"])
    return o, {"k": k_cache, "v": v_cache}


# ----------------------------------------------------------------------
# Paged KV: gather/scatter through a per-slot page table
# ----------------------------------------------------------------------
def paged_view(pool: jax.Array, pages: jax.Array, layer) -> jax.Array:
    """Gather a slot-contiguous view of one layer of a paged KV pool.

    pool: (L, KVH, P, page_size, D), the stacked per-layer pools of
    physical pages, KV-head-major; pages: (B, NB) int32 page table;
    layer: the layer to read.  Returns (B, NB * page_size, KVH, D) —
    logical row ``j`` of slot ``b`` and head ``h`` is
    ``pool[layer, h, pages[b, j // ps], j % ps]``, so for any
    permutation of physical pages the view is bit-identical to the
    contiguous cache layout (rows beyond ``pos`` are stale and masked
    by the position-aware attention, exactly like the zero tail of the
    contiguous cache).
    """
    b, nb = pages.shape
    _, kvh, _, ps, d = pool.shape
    g = pool[layer, :, pages]                  # (B, NB, KVH, ps, D)
    return g.transpose(0, 1, 3, 2, 4).reshape(b, nb * ps, kvh, d)


def paged_insert_window(pool: jax.Array, new: jax.Array, pages: jax.Array,
                        pos, layer) -> jax.Array:
    """Scatter ``new`` (B, T, KVH, D) into layer ``layer`` of the
    stacked pool (L, KVH, P, page_size, D) at logical rows
    ``[pos, pos + T)`` of each slot, resolved through the page table —
    the paged analogue of ``cache_update_window`` (T = 1 of
    ``cache_update``).  Only the new rows are written, each at its own
    ``(layer, head, page, offset)``, so a pool that is donated or
    carried through a loop is updated in place.  A window may span a
    page boundary.  Rows whose logical block falls off the table clamp
    to the slot's last table entry — retired slots' tables are reset to
    the reserved garbage page 0, so their frozen in-chunk writes can
    never corrupt a reallocated page."""
    ps = pool.shape[3]
    b, t, kvh = new.shape[:3]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.full((b,), pos, jnp.int32)
    rows = pos[:, None] + jnp.arange(t)[None, :]              # (B, T)
    blk = jnp.minimum(rows // ps, pages.shape[1] - 1)
    page = jnp.take_along_axis(pages, blk, axis=1)            # (B, T)
    # one index per (slot, token, head), so each update is a single
    # D-row: the pool keeps its row-major layout, the one the kernel reads
    head = jnp.arange(kvh)[None, None, :]
    return pool.at[layer, head, page[..., None],
                   (rows % ps)[..., None]].set(new.astype(pool.dtype))


def _paged_attend(q, k, v, cfg, cache, pos, pages, layer, kernel, ref):
    """Write this layer's new K/V rows into the stacked pools and
    attend over them (the shared tail of paged decode and verify)."""
    k_pool = paged_insert_window(cache["k"], k, pages, pos, layer)
    v_pool = paged_insert_window(cache["v"], v, pages, pos, layer)
    k_pool = shard(k_pool, None, "kv_heads", None, None, None)
    v_pool = shard(v_pool, None, "kv_heads", None, None, None)
    if cfg.use_pallas:
        o = kernel(q, k_pool, v_pool, pages, pos, layer,
                   interpret=cfg.pallas_interpret)
    else:
        o = ref(q, paged_view(k_pool, pages, layer),
                paged_view(v_pool, pages, layer), pos)
    return o, {"k": k_pool, "v": v_pool}


def gqa_decode_paged(x, p, cfg, cache, pos, pages, layer):
    """One-token decode against a paged KV pool.

    cache = dict(k, v) with the stacked pools (L, KVH, P, page_size, D)
    shared by all slots and all layers; ``layer`` picks this layer's
    pool, ``pages`` (B, NB) is the per-slot page table and ``pos`` the
    per-slot depth vector.  Returns the pools with this layer's rows
    written, nothing else touched.  The gather/scatter indirection
    preserves the contiguous layout's values bit-for-bit, so greedy
    output is token-identical to ``gqa_decode`` for any page
    permutation.  With ``cfg.use_pallas`` attention dispatches to the
    paged kernel, which copies each slot's live pages of this layer out
    of the pools in place (page 0 of the table marks an empty slot);
    the jnp gather path is its CPU-exact analogue.
    """
    from repro.kernels.decode_attention.ops import paged_decode_attention
    b = x.shape[0]
    q, k, v = _proj_qkv(x, p, cfg)
    pos = jnp.asarray(pos)
    poss = pos[:, None] if pos.ndim == 1 else jnp.full((1,), pos)
    q = rope(q, poss, cfg.rope_theta)
    k = rope(k, poss, cfg.rope_theta)
    o, kvc = _paged_attend(q, k, v, cfg, cache, pos, pages, layer,
                           paged_decode_attention, decode_attention_jnp)
    o = tp_psum(o.reshape(b, 1, -1) @ p["wo"])
    return o, kvc


def gqa_verify_paged(x, p, cfg, cache, pos, pages, layer):
    """Multi-token verify against a paged KV pool (speculative window,
    and the suffix prefill of a prefix-cache hit).

    x: (B, T, d); window rows ``[pos, pos + T)`` scatter through the
    page table into layer ``layer`` of the stacked pools and may span
    page boundaries.  Rollback is unchanged from the contiguous path:
    rejected tokens' rows go stale and the next window overwrites them
    in place — the table is only re-read, never rewritten, so crossing
    a boundary needs no special casing.
    """
    from repro.kernels.decode_attention.ops import paged_verify_attention
    b, t, _ = x.shape
    q, k, v = _proj_qkv(x, p, cfg)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.full((b,), pos, jnp.int32)
    positions = pos[:, None] + jnp.arange(t)[None, :]         # (B, T)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    o, kvc = _paged_attend(q, k, v, cfg, cache, pos, pages, layer,
                           paged_verify_attention, verify_attention_jnp)
    o = tp_psum(o.reshape(b, t, -1) @ p["wo"])
    return o, kvc


# ----------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention), absorbed formulation
# ----------------------------------------------------------------------
def mla_defs(cfg) -> dict:
    m, d, h, dt = cfg.mla, cfg.d_model, cfg.n_heads, cfg.dtype
    qk = m.qk_nope_head_dim
    return {
        "wq_a": ParamDef((d, m.q_lora_rank), ("fsdp", None), "normal", dt),
        "q_a_norm": rmsnorm_def(m.q_lora_rank, dt),
        "wq_b": ParamDef((m.q_lora_rank, h, qk + m.qk_rope_head_dim),
                         (None, "heads", None), "normal", dt),
        "wkv_a": ParamDef((d, m.kv_lora_rank + m.qk_rope_head_dim),
                          ("fsdp", None), "normal", dt),
        "kv_a_norm": rmsnorm_def(m.kv_lora_rank, dt),
        "wk_b": ParamDef((h, m.kv_lora_rank, qk), ("heads", None, None),
                         "normal", dt),
        "wv_b": ParamDef((h, m.kv_lora_rank, m.v_head_dim),
                         ("heads", None, None), "normal", dt),
        "wo": ParamDef((h * m.v_head_dim, d), ("heads_flat", "fsdp"),
                       "normal", dt,
                       1.0 / math.sqrt(h * m.v_head_dim * 2 * cfg.n_layers)),
    }


def _mla_qc(x, p, cfg, positions):
    """Project to absorbed-query (B,S,H,rank+rope) and latent KV."""
    m = cfg.mla
    qa = rmsnorm(x @ p["wq_a"], p["q_a_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhd->bshd", qa, p["wq_b"])
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    # absorb W_uk into q: q' = q_nope @ W_uk^T  -> (B,S,H,kv_rank)
    q_abs = jnp.einsum("bshd,hrd->bshr", q_nope, p["wk_b"])
    kv = x @ p["wkv_a"]
    c_kv = rmsnorm(kv[..., :m.kv_lora_rank], p["kv_a_norm"], cfg.norm_eps)
    k_rope = kv[..., m.kv_lora_rank:][:, :, None, :]          # (B,S,1,rope)
    k_rope = rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]
    return q_abs, q_rope, c_kv, k_rope


def _mla_attend(q_abs, q_rope, c_kv, k_rope, cfg, *, causal, pos=None):
    """Absorbed attention over latent cache.

    q_abs: (B,Sq,H,R); q_rope: (B,Sq,H,P); c_kv: (B,S,R); k_rope: (B,S,P).
    Scores = q_abs . c_kv + q_rope . k_rope, softmax over S, then output
    latent o_l = p @ c_kv, un-absorbed by W_uv afterwards.
    """
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    b, sq = q_abs.shape[:2]
    s = c_kv.shape[1]
    sc = jnp.einsum("bqhr,bsr->bhqs", q_abs.astype(jnp.float32),
                    c_kv.astype(jnp.float32))
    sc += jnp.einsum("bqhp,bsp->bhqs", q_rope.astype(jnp.float32),
                     k_rope.astype(jnp.float32))
    sc *= scale
    kv_pos = jnp.arange(s)
    if causal:
        q_pos = jnp.arange(sq) if pos is None else jnp.full((sq,), pos)
        msk = kv_pos[None, :] <= q_pos[:, None]
        sc = jnp.where(msk[None, None], sc, MASK_VALUE)
    elif pos is not None:
        pos = jnp.asarray(pos)
        if pos.ndim == 1:                       # per-slot depths (B,)
            msk = kv_pos[None, :] <= pos[:, None]
            sc = jnp.where(msk[:, None, None, :], sc, MASK_VALUE)
        else:
            sc = jnp.where((kv_pos <= pos)[None, None, None], sc,
                           MASK_VALUE)
    pr = jax.nn.softmax(sc, axis=-1)
    o_l = jnp.einsum("bhqs,bsr->bqhr", pr, c_kv.astype(jnp.float32))
    return o_l.astype(q_abs.dtype)


def _mla_ol_chunked(q_abs, q_rope, c_kv, k_rope, cfg, q_chunk=1024):
    """Causal absorbed-MLA output-latent, chunked over q (flash-style)."""
    b, s = q_abs.shape[:2]
    if s <= q_chunk:
        return _mla_attend(q_abs, q_rope, c_kv, k_rope, cfg, causal=True)
    n = s // q_chunk
    qa = q_abs.reshape(b, n, q_chunk, *q_abs.shape[2:]).transpose(1, 0, 2, 3, 4)
    qr = q_rope.reshape(b, n, q_chunk, *q_rope.shape[2:]).transpose(1, 0, 2, 3, 4)

    def body(_, args):
        qa_i, qr_i, i = args
        return None, _mla_attend_chunk(qa_i, qr_i, c_kv, k_rope, cfg,
                                       i * q_chunk)

    _, oc = jax.lax.scan(body, None, (qa, qr, jnp.arange(n)))
    return oc.transpose(1, 0, 2, 3, 4).reshape(b, s, cfg.n_heads,
                                               cfg.mla.kv_lora_rank)


def mla_attention(x, p, cfg, *, q_chunk=1024):
    """Training/prefill MLA, chunked over q like flash attention."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q_abs, q_rope, c_kv, k_rope = _mla_qc(x, p, cfg, positions)
    q_abs = shard(q_abs, "batch", None, "heads", None)
    q_rope = shard(q_rope, "batch", None, "heads", None)

    o_l = _mla_ol_chunked(q_abs, q_rope, c_kv, k_rope, cfg, q_chunk)
    o = jnp.einsum("bqhr,hrd->bqhd", o_l, p["wv_b"])
    o = o.reshape(b, s, -1) @ p["wo"]
    return shard(o, "batch", "seq_sp", "embed")


def _mla_attend_chunk(q_abs, q_rope, c_kv, k_rope, cfg, offset):
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    cq = q_abs.shape[1]
    s = c_kv.shape[1]
    sc = jnp.einsum("bqhr,bsr->bhqs", q_abs.astype(jnp.float32),
                    c_kv.astype(jnp.float32))
    sc += jnp.einsum("bqhp,bsp->bhqs", q_rope.astype(jnp.float32),
                     k_rope.astype(jnp.float32))
    sc *= scale
    msk = jnp.arange(s)[None, :] <= (offset + jnp.arange(cq))[:, None]
    sc = jnp.where(msk[None, None], sc, MASK_VALUE)
    pr = jax.nn.softmax(sc, axis=-1)
    o_l = jnp.einsum("bhqs,bsr->bqhr", pr, c_kv.astype(jnp.float32))
    return o_l.astype(q_abs.dtype)


def mla_prefill(x, p, cfg):
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q_abs, q_rope, c_kv, k_rope = _mla_qc(x, p, cfg, positions)
    q_abs = shard(q_abs, "batch", None, "heads", None)
    q_rope = shard(q_rope, "batch", None, "heads", None)
    o_l = _mla_ol_chunked(q_abs, q_rope, c_kv, k_rope, cfg)
    o = jnp.einsum("bqhr,hrd->bqhd", o_l, p["wv_b"])
    o = o.reshape(b, s, -1) @ p["wo"]
    return shard(o, "batch", "seq_sp", "embed"), (c_kv, k_rope)


def mla_decode(x, p, cfg, cache, pos):
    """MLA decode: latent cache (B, S, R) + rope cache (B, S, P).

    ``pos`` is a scalar or a per-slot (B,) vector (ragged decode).
    """
    b = x.shape[0]
    pos = jnp.asarray(pos)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full((1,), pos)
    q_abs, q_rope, c_new, kr_new = _mla_qc(x, p, cfg, positions)
    ckv = cache["c_kv"]
    krp = cache["k_rope"]
    if pos.ndim == 1:
        oh = (jnp.arange(ckv.shape[1])[None, :]
              == pos[:, None]).astype(ckv.dtype)          # (B, S)
        ckv = ckv * (1 - oh[:, :, None]) + c_new * oh[:, :, None]
        krp = krp * (1 - oh[:, :, None]) + kr_new * oh[:, :, None]
    else:
        oh = (jnp.arange(ckv.shape[1]) == pos).astype(ckv.dtype)
        ckv = ckv * (1 - oh[None, :, None]) + c_new * oh[None, :, None]
        krp = krp * (1 - oh[None, :, None]) + kr_new * oh[None, :, None]
    ckv = shard(ckv, "batch", "kv_seq", None)
    krp = shard(krp, "batch", "kv_seq", None)
    o_l = _mla_attend(q_abs, q_rope, ckv, krp, cfg, causal=False, pos=pos)
    o = jnp.einsum("bqhr,hrd->bqhd", o_l, p["wv_b"])
    o = o.reshape(b, 1, -1) @ p["wo"]
    return o, {"c_kv": ckv, "k_rope": krp}


# ----------------------------------------------------------------------
# Dense FFN (SwiGLU)
# ----------------------------------------------------------------------
def ffn_defs(cfg, d_ff: Optional[int] = None) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    f = d_ff or cfg.d_ff
    return {
        "w_gate": ParamDef((d, f), ("fsdp", "d_ff"), "normal", dt),
        "w_up": ParamDef((d, f), ("fsdp", "d_ff"), "normal", dt),
        "w_down": ParamDef((f, d), ("d_ff", "fsdp"), "normal", dt,
                           1.0 / math.sqrt(f * max(1, 2 * cfg.n_layers))),
    }


def ffn(x, p):
    """SwiGLU FFN.  Under ``tp_ctx`` the gate/up weights are
    column-split and ``w_down`` row-split over the TP axis, so the
    down-projection output is a partial sum — ``tp_psum`` completes it
    (identity outside the context)."""
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = shard(h, "batch", "seq_sp", "d_ff")
    o = tp_psum(h @ p["w_down"])
    return shard(o, "batch", "seq_sp", "embed")


# ----------------------------------------------------------------------
# Mixture of Experts: sort-based capacity dispatch, expert-parallel
# ----------------------------------------------------------------------
def moe_defs(cfg) -> dict:
    mo, d, dt = cfg.moe, cfg.d_model, cfg.dtype
    e, f = mo.n_experts, mo.d_expert
    scale_down = 1.0 / math.sqrt(f * max(1, 2 * cfg.n_layers))
    defs = {
        "router": ParamDef((d, e), (None, "experts"), "normal", "float32"),
        "w_gate": ParamDef((e, d, f), ("experts", "fsdp", "d_expert"), "normal", dt),
        "w_up": ParamDef((e, d, f), ("experts", "fsdp", "d_expert"), "normal", dt),
        "w_down": ParamDef((e, f, d), ("experts", "d_expert", "fsdp"),
                           "normal", dt, scale_down),
    }
    if mo.n_shared:
        sf = mo.d_expert * mo.n_shared
        defs["shared"] = {
            "w_gate": ParamDef((d, sf), ("fsdp", "d_ff"), "normal", dt),
            "w_up": ParamDef((d, sf), ("fsdp", "d_ff"), "normal", dt),
            "w_down": ParamDef((sf, d), ("d_ff", "fsdp"), "normal", dt,
                               scale_down),
        }
    return defs


def _route(x2d, router_w, mo, router_type):
    logits = (x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))
    if router_type == "sigmoid":            # DeepSeek-V3 style
        scores = jax.nn.sigmoid(logits)
        topv, topi = jax.lax.top_k(scores, mo.top_k)
        topv = topv / (topv.sum(-1, keepdims=True) + 1e-9)
        probs = scores / (scores.sum(-1, keepdims=True) + 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, mo.top_k)
        topv = topv / (topv.sum(-1, keepdims=True) + 1e-9)
    # load-balance aux loss (Switch style): E * sum_e f_e * P_e
    e = router_w.shape[-1]
    assign = jax.nn.one_hot(topi[..., 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(jnp.mean(assign, 0) * jnp.mean(probs, 0))
    return topv, topi, aux


def _moe_local(x2d, topv, topi, wg, wu, wd, capacity: int):
    """Sort-based capacity-limited expert compute on local tokens.

    x2d: (N, d); topi/topv: (N, k); weights: (E, d, f) / (E, f, d).
    Gathers (no one-hot einsum FLOPs), batched expert GEMMs, weighted
    scatter-add combine.  Tokens beyond capacity are dropped (GShard).
    """
    n, k = topi.shape
    e = wg.shape[0]
    flat_e = topi.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    tok = order // k
    se = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(n * k) - starts[se]
    keep = pos_in_e < capacity
    slot = jnp.where(keep, se * capacity + pos_in_e, e * capacity)
    buf = jnp.zeros((e * capacity + 1, x2d.shape[1]), x2d.dtype)
    buf = buf.at[slot].set(x2d[tok], mode="drop")
    buf = buf[:-1].reshape(e, capacity, -1)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
    out = jnp.einsum("ecf,efd->ecd", h, wd)
    out_flat = out.reshape(e * capacity, -1)
    y_sorted = jnp.where(keep[:, None], out_flat[jnp.minimum(slot, e * capacity - 1)], 0.0)
    w_sorted = topv.reshape(-1)[order].astype(y_sorted.dtype)
    y = jnp.zeros_like(x2d).at[tok].add(y_sorted * w_sorted[:, None])
    return y


def _capacity(n_tokens: int, mo) -> int:
    return max(1, int(math.ceil(n_tokens * mo.top_k / mo.n_experts
                                * mo.capacity_factor)))


def moe_ffn(x, p, cfg, router_type="softmax"):
    """MoE layer. Under a mesh: shard_map expert parallelism with
    all_to_all dispatch over the expert axis; standalone: local path."""
    mo = cfg.moe
    b, s, d = x.shape
    rules = current_rules()
    eax = expert_axes(rules)

    shared_out = 0.0
    if mo.n_shared:
        shared_out = ffn(x, p["shared"])

    aux_box = {}

    if rules is None or rules.mesh is None or eax is None:
        x2d = x.reshape(-1, d)
        topv, topi, aux = _route(x2d, p["router"], mo, router_type)
        aux_box["aux"] = aux
        y = _moe_local(x2d, topv, topi, p["w_gate"], p["w_up"], p["w_down"],
                       _capacity(x2d.shape[0], mo))
        return y.reshape(b, s, d) + shared_out, aux

    y, aux = _moe_shard_map(x, p, cfg, router_type, rules, eax)
    return y + shared_out, aux


def _moe_shard_map(x, p, cfg, router_type, rules, eax):
    """Expert-parallel MoE via shard_map + all_to_all.

    Tokens are sharded (batch over dp, seq over the expert axis); each
    device routes its local tokens, builds per-peer capacity buffers,
    exchanges them with a tiled all_to_all along the expert axis,
    computes its local experts, and reverses the exchange.
    """
    from jax.sharding import PartitionSpec as P

    mo = cfg.moe
    mesh = rules.mesh
    eaxes = (eax,) if isinstance(eax, str) else tuple(eax)

    from repro.parallel.sharding import logical_pspec
    x_pspec = logical_pspec(("batch", "seq_sp", "embed"), rules)
    wg_pspec = logical_pspec(("experts", "fsdp", "d_expert"), rules)
    wd_pspec = logical_pspec(("experts", "d_expert", "fsdp"), rules)
    # routing needs ALL experts' scores on every shard: replicate the
    # (tiny) router matrix inside the shard_map
    r_pspec = logical_pspec((None, None), rules)
    fsdp_ax = rules.table.get("fsdp")

    def local_fn(xl, rw, wg, wu, wd):
        # xl: (b_loc, s_loc, d); weights local expert slices.
        if fsdp_ax is not None:
            wg = jax.lax.all_gather(wg, fsdp_ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_ax, axis=2, tiled=True)
        bl, sl, dd = xl.shape
        x2d = xl.reshape(-1, dd)
        n_loc = x2d.shape[0]
        topv, topi, aux = _route(x2d, rw, mo, router_type)
        cap = _capacity(n_loc, mo)
        # Build (E, cap) send buffers, sorted-dispatch as in _moe_local.
        k = mo.top_k
        flat_e = topi.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        tok = order // k
        se = flat_e[order]
        counts = jnp.bincount(flat_e, length=mo.n_experts)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(n_loc * k) - starts[se]
        keep = pos_in_e < cap
        slot = jnp.where(keep, se * cap + pos_in_e, mo.n_experts * cap)
        buf = jnp.zeros((mo.n_experts * cap + 1, dd), x2d.dtype)
        buf = buf.at[slot].set(x2d[tok], mode="drop")
        buf = buf[:-1]                                    # (E*cap, d)
        # all_to_all: send expert-block j to peer j along the expert axis
        recv = jax.lax.all_to_all(
            buf.reshape(mo.n_experts, cap, dd), eaxes, split_axis=0,
            concat_axis=1, tiled=True)                    # (e_loc, ep*cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, wg))
        h = h * jnp.einsum("ecd,edf->ecf", recv, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)           # (e_loc, ep*cap, d)
        back = jax.lax.all_to_all(out, eaxes, split_axis=1,
                                  concat_axis=0, tiled=True)  # (E, cap, d)
        out_flat = jnp.concatenate(
            [back.reshape(mo.n_experts * cap, dd),
             jnp.zeros((1, dd), back.dtype)], 0)
        y_sorted = jnp.where(keep[:, None], out_flat[slot], 0.0)
        w_sorted = topv.reshape(-1)[order].astype(y_sorted.dtype)
        y = jnp.zeros_like(x2d).at[tok].add(y_sorted * w_sorted[:, None])
        aux = jax.lax.pmean(aux, eaxes)
        return y.reshape(bl, sl, dd), aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_pspec, r_pspec, wg_pspec, wg_pspec, wd_pspec),
        out_specs=(x_pspec, P()),
        check_vma=False)
    y, aux = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if rules.table.get("batch") is not None:
        pass  # aux already pmean'd over expert axis; batch mean via loss
    return y, jnp.mean(aux)


def moe_decode(x, p, cfg, router_type="softmax"):
    """Decode-time MoE: few tokens, experts sharded over the full mesh.

    Gathers all tokens to every device (tiny at decode), computes local
    experts, and psum-combines — avoids all_to_all latency at batch≈128.
    Under pjit this is expressed directly: the einsum over the one-hot
    combine is avoided by the same sort-based local path; GSPMD inserts
    the (small) gathers/reductions.
    """
    mo = cfg.moe
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    topv, topi, aux = _route(x2d, p["router"], mo, router_type)
    y = _moe_local(x2d, topv, topi, p["w_gate"], p["w_up"], p["w_down"],
                   _capacity(x2d.shape[0], mo))
    shared_out = ffn(x, p["shared"]) if mo.n_shared else 0.0
    return y.reshape(b, s, d) + shared_out, aux
