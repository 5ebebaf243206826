"""jit'd wrapper: model-layout decode attention against a KV cache.

Accepts a per-sequence ``pos`` vector (ragged continuous-batching
decode) or a scalar (fixed batch, all rows at the same depth).

Shard-aware: the grid and block specs are derived from the shapes the
wrapper actually sees, so calling it inside ``shard_map`` with a
KV-head-partitioned cache (tensor-parallel serving) tiles each shard's
``B * KVH_local`` rows independently — ragged multi-slot decode stays
one fused kernel call per shard.  Leave ``block_k`` unset to auto-fit
the KV block to the (shard-local) cache length.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.analysis.contracts import (
    KernelContract, KernelInstance, OperandSpec, ScratchSpec, dtype_bytes,
)
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_kernel, paged_decode_attention_kernel,
    paged_verify_attention_kernel, verify_attention_kernel,
)


def fit_block_k(s: int, block_k: Optional[int] = None,
                max_block: int = 512) -> int:
    """KV block size for a (shard-local) cache of length ``s``: the
    requested size, else ``max_block`` clamped down to one lane-aligned
    block when the whole cache fits in less."""
    if block_k is not None:
        return block_k
    return min(max_block, -(-s // 128) * 128)


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *,
                     block_k: Optional[int] = None,
                     interpret: bool = False):
    """q: (B, 1, H, D); caches: (B, S, KVH, D); pos: () or (B,) int32.
    Returns (B, 1, H, D)."""
    b, _, h, d = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    block_k = fit_block_k(s, block_k)
    qr = q[:, 0].reshape(b, kvh, g, d).reshape(b * kvh, g, d)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    pk = (-s) % block_k
    if pk:
        kr = jnp.pad(kr, ((0, 0), (0, pk), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, pk), (0, 0)))
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:                      # (B,) -> (B*KVH,): row b*kvh+j
        pos = jnp.repeat(pos, kvh)
    o = decode_attention_kernel(qr, kr, vr, pos, block_k=block_k,
                                interpret=interpret)
    return o.reshape(b, kvh, g, d).reshape(b, h, d)[:, None].transpose(
        0, 1, 2, 3).reshape(b, 1, h, d)


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def verify_attention(q, k_cache, v_cache, pos, *,
                     block_k: Optional[int] = None,
                     interpret: bool = False):
    """Multi-token verify attention against a KV cache (the speculative
    decode verify path).

    q: (B, T, H, D); caches: (B, S, KVH, D); pos: () or (B,) int32 —
    per-slot window start; query token ``t`` attends to cache positions
    ``<= pos + t``.  Returns (B, T, H, D).  Like ``decode_attention``,
    the grid is derived from the shapes the wrapper sees, so it tiles
    shard-local rows under ``shard_map`` unchanged.
    """
    b, t, h, d = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    block_k = fit_block_k(s, block_k)
    qr = q.reshape(b, t, kvh, g, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b * kvh, t, g, d)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    pk = (-s) % block_k
    if pk:
        kr = jnp.pad(kr, ((0, 0), (0, pk), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, pk), (0, 0)))
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:                      # (B,) -> (B*KVH,): row b*kvh+j
        pos = jnp.repeat(pos, kvh)
    o = verify_attention_kernel(qr, kr, vr, pos, block_k=block_k,
                                interpret=interpret)
    return o.reshape(b, kvh, t, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, t, h, d)


# One half of the paged kernel's double buffer holds about this much K
# and V: large enough that a grid step's fixed cost is small beside its
# copies, small enough that both halves sit well inside the default
# scoped VMEM.
PAGED_BLOCK_BYTES = 2 * 2 ** 20


def fit_pages_per_block(kvh: int, page_size: int, d: int, nb: int,
                        itemsize: int) -> int:
    """Table entries per grid step of the paged decode kernel: the most
    pages whose K and V, over the ``kvh`` (shard-local) heads, fit in
    ``PAGED_BLOCK_BYTES``, at least one, dividing the ``nb`` pages of a
    slot."""
    page = 2 * kvh * page_size * d * itemsize
    ppb = max(1, min(nb, PAGED_BLOCK_BYTES // page))
    while nb % ppb:
        ppb -= 1
    return ppb


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, pages, pos, layer, *,
                           interpret: bool = False):
    """Page-table-indirect decode attention in model layout.

    q: (B, 1, H, D); pools: (L, KVH, P, page_size, D), the stacked
    per-layer pools of shared physical pages, KV-head-major; pages:
    (B, NB) int32 per-slot page table; pos: () or (B,) int32; layer: ()
    int32, the layer to attend over.  Returns (B, 1, H, D).

    The pools stay where they lie, in HBM: one grid step takes one slot
    and ``fit_pages_per_block`` consecutive table entries, for all of
    the (shard-local) KV heads, and copies only the slot's live pages,
    ``pos // page_size + 1`` of them, double-buffered so the next
    block's copies run under this block's arithmetic.  Page 0 of the
    pool is reserved (``EMPTY_PAGE``): a slot whose table row starts
    with it holds no request, costs no copy and no arithmetic, and
    returns zeros.  The block size comes from the shapes alone (heads,
    page size, head width, table width), so the same call adapts to a
    whole model's heads on one chip and to one head a chip under
    tensor parallelism.  A live slot's values are bit-identical to
    ``decode_attention`` at ``block_k = page_size`` on the equivalent
    contiguous cache, for any placement of its pages in the pool.
    """
    b, _, h, d = q.shape
    kvh, page_size = k_pool.shape[1], k_pool.shape[3]
    g = h // kvh
    qr = q[:, 0].reshape(b, kvh, g, d).reshape(b * kvh, g, d)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    ppb = fit_pages_per_block(kvh, page_size, d, pages.shape[1],
                              k_pool.dtype.itemsize)
    o = paged_decode_attention_kernel(qr, k_pool, v_pool, pages, pos,
                                      layer, pages_per_block=ppb,
                                      interpret=interpret)
    return o.reshape(b, h, d)[:, None]


@partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention(q, k_pool, v_pool, pages, pos, layer, *,
                           interpret: bool = False):
    """Multi-token verify through the page table (speculative windows
    and prefix-cache suffix prefill).

    q: (B, T, H, D); pools: (L, KVH, P, page_size, D); pages: (B, NB)
    int32; pos: () or (B,) int32 per-slot window start; layer: ()
    int32.  Returns (B, T, H, D)."""
    b, t, h, d = q.shape
    kvh = k_pool.shape[1]
    g = h // kvh
    qr = q.reshape(b, t, kvh, g, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b * kvh, t, g, d)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    o = paged_verify_attention_kernel(qr, k_pool, v_pool, pages, pos,
                                      layer, interpret=interpret)
    return o.reshape(b, kvh, t, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, t, h, d)


# --- static contracts (repro.analysis) -----------------------------------
# Each build() reproduces the shape arithmetic above (fit_block_k +
# pad-to-multiple), so the checker enumerates exactly the grid the
# pallas_call would run — including the shard-local clamp path where
# the whole cache fits in one lane-aligned block.

def _decode_contract(case):
    b, s = case["b"], case["s"]
    h, kvh, d = case["h"], case["kvh"], case["d"]
    g = h // kvh
    block_k = fit_block_k(s, case.get("block_k"))
    sp = s + (-s) % block_k                 # cache length after padding
    bh = b * kvh
    dt = case.get("dtype", "bfloat16")
    return KernelInstance(
        grid=(bh, sp // block_k),
        semantics=("parallel", "arbitrary"),
        inputs=(
            OperandSpec("pos", (bh,), "int32", memory_space="smem"),
            OperandSpec("q", (bh, g, d), dt, block=(1, g, d),
                        index_map=lambda bb, ik: (bb, 0, 0)),
            OperandSpec("k", (bh, sp, d), dt, block=(1, block_k, d),
                        index_map=lambda bb, ik: (bb, ik, 0)),
            OperandSpec("v", (bh, sp, d), dt, block=(1, block_k, d),
                        index_map=lambda bb, ik: (bb, ik, 0)),
        ),
        outputs=(
            OperandSpec("o", (bh, g, d), dt, block=(1, g, d),
                        index_map=lambda bb, ik: (bb, 0, 0)),
        ),
        scratch=(
            ScratchSpec((g, 1), "float32"),
            ScratchSpec((g, 1), "float32"),
            ScratchSpec((g, d), "float32"),
        ),
    )


def _verify_contract(case):
    b, t, s = case["b"], case["t"], case["s"]
    h, kvh, d = case["h"], case["kvh"], case["d"]
    g = h // kvh
    block_k = fit_block_k(s, case.get("block_k"))
    sp = s + (-s) % block_k
    bh = b * kvh
    dt = case.get("dtype", "bfloat16")
    return KernelInstance(
        grid=(bh, sp // block_k),
        semantics=("parallel", "arbitrary"),
        inputs=(
            OperandSpec("pos", (bh,), "int32", memory_space="smem"),
            OperandSpec("q", (bh, t, g, d), dt, block=(1, t, g, d),
                        index_map=lambda bb, ik: (bb, 0, 0, 0)),
            OperandSpec("k", (bh, sp, d), dt, block=(1, block_k, d),
                        index_map=lambda bb, ik: (bb, ik, 0)),
            OperandSpec("v", (bh, sp, d), dt, block=(1, block_k, d),
                        index_map=lambda bb, ik: (bb, ik, 0)),
        ),
        outputs=(
            OperandSpec("o", (bh, t, g, d), dt, block=(1, t, g, d),
                        index_map=lambda bb, ik: (bb, 0, 0, 0)),
        ),
        scratch=(
            ScratchSpec((t * g, 1), "float32"),
            ScratchSpec((t * g, 1), "float32"),
            ScratchSpec((t * g, d), "float32"),
        ),
    )


def _paged_table(case):
    """Representative page-table closure for the contract index maps.

    The real table is data (scalar-prefetched at run time); the static
    checker never enumerates input index maps, but the contract still
    carries a faithful callable — a fixed pseudo-random permutation of
    the usable pages — so the indirect addressing pattern is recorded
    alongside the blocked shapes it must stay consistent with.
    """
    b, nb, n_pages = case["b"], case["nb"], case["n_pages"]
    usable = list(range(1, n_pages))
    perm = [usable[(i * 7919) % len(usable)] for i in range(b * nb)]
    return lambda slot, ik: perm[slot * nb + ik]


def _paged_kv_operands(case, dt):
    """The stacked K and V pools (L, KVH, P, page_size, D) as the paged
    verify kernel blocks them: one page of one head of layer
    ``layer``."""
    kvh, d = case["kvh"], case["d"]
    ps, n_pages = case["page_size"], case["n_pages"]
    n_layers, layer = case["n_layers"], case["layer"]
    table = _paged_table(case)
    kv_map = lambda bb, ik: (layer, bb % kvh, table(bb // kvh, ik), 0, 0)
    return tuple(
        OperandSpec(name, (n_layers, kvh, n_pages, ps, d), dt,
                    block=(1, 1, 1, ps, d), index_map=kv_map)
        for name in ("k", "v"))


def _paged_decode_contract(case):
    """One slot and ``fit_pages_per_block`` table entries per grid step,
    all KV heads; the pools stay in HBM (``any``) and are copied by hand
    into a double buffer guarded by one DMA semaphore per half and pool.
    Both grid axes are sequential: each step prefetches the next live
    block, which may belong to the next slot."""
    b, nb = case["b"], case["nb"]
    h, kvh, d = case["h"], case["kvh"], case["d"]
    ps, n_pages, n_layers = case["page_size"], case["n_pages"], \
        case["n_layers"]
    g = h // kvh
    dt = case.get("dtype", "bfloat16")
    ppb = fit_pages_per_block(kvh, ps, d, nb, dtype_bytes(dt))
    pool = (n_layers, kvh, n_pages, ps, d)
    slot = lambda s, i: (s, 0, 0, 0)
    return KernelInstance(
        grid=(b, nb // ppb),
        semantics=("arbitrary", "arbitrary"),
        inputs=(
            OperandSpec("pages", (b, nb), "int32", memory_space="smem"),
            OperandSpec("pos", (b,), "int32", memory_space="smem"),
            OperandSpec("layer", (1,), "int32", memory_space="smem"),
            OperandSpec("q", (b, kvh, g, d), dt, block=(1, kvh, g, d),
                        index_map=slot),
            OperandSpec("k", pool, dt, memory_space="any"),
            OperandSpec("v", pool, dt, memory_space="any"),
        ),
        outputs=(
            OperandSpec("o", (b, kvh, g, d), dt, block=(1, kvh, g, d),
                        index_map=slot),
        ),
        scratch=(
            ScratchSpec((2, kvh, ppb, ps, d), dt),
            ScratchSpec((2, kvh, ppb, ps, d), dt),
            ScratchSpec((2, 2), "int32", memory_space="semaphore"),
            ScratchSpec((1,), "int32", memory_space="smem"),
            ScratchSpec((b + 1,), "int32", memory_space="smem"),
            ScratchSpec((kvh, g, 1), "float32"),
            ScratchSpec((kvh, g, 1), "float32"),
            ScratchSpec((kvh, g, d), "float32"),
        ),
    )


def _paged_verify_contract(case):
    b, t, nb = case["b"], case["t"], case["nb"]
    h, kvh, d = case["h"], case["kvh"], case["d"]
    g = h // kvh
    bh = b * kvh
    dt = case.get("dtype", "bfloat16")
    return KernelInstance(
        grid=(bh, nb),
        semantics=("parallel", "arbitrary"),
        inputs=(
            OperandSpec("pages", (b, nb), "int32", memory_space="smem"),
            OperandSpec("pos", (b,), "int32", memory_space="smem"),
            OperandSpec("layer", (1,), "int32", memory_space="smem"),
            OperandSpec("q", (bh, t, g, d), dt, block=(1, t, g, d),
                        index_map=lambda bb, ik: (bb, 0, 0, 0)),
        ) + _paged_kv_operands(case, dt),
        outputs=(
            OperandSpec("o", (bh, t, g, d), dt, block=(1, t, g, d),
                        index_map=lambda bb, ik: (bb, 0, 0, 0)),
        ),
        scratch=(
            ScratchSpec((t * g, 1), "float32"),
            ScratchSpec((t * g, 1), "float32"),
            ScratchSpec((t * g, d), "float32"),
        ),
    )


CONTRACTS = (
    KernelContract(
        name="decode_attention",
        build=_decode_contract,
        cases=(
            # serving shape: 8-way continuous batch, 4 KV heads, GQA 4
            {"b": 8, "s": 4096, "h": 16, "kvh": 4, "d": 128},
            # shard-local clamp path: cache shorter than max_block,
            # fit_block_k rounds 160 -> one 256-wide padded block
            {"b": 1, "s": 160, "h": 8, "kvh": 8, "d": 64},
            # explicit block_k, MHA (kvh == h)
            {"b": 2, "s": 1024, "h": 8, "kvh": 8, "d": 128,
             "block_k": 256},
        ),
        dtype_groups=(("q", "k", "v", "o"),),
    ),
    KernelContract(
        name="verify_attention",
        build=_verify_contract,
        cases=(
            # speculative verify window of 4 draft tokens
            {"b": 8, "t": 4, "s": 4096, "h": 16, "kvh": 4, "d": 128},
            {"b": 2, "t": 8, "s": 512, "h": 8, "kvh": 2, "d": 64,
             "block_k": 128},
        ),
        dtype_groups=(("q", "k", "v", "o"),),
    ),
    KernelContract(
        name="paged_decode_attention",
        build=_paged_decode_contract,
        cases=(
            # qwen3-1.7b served: 16 slots x 16 pages of 128 tokens, 8 KV
            # heads of 128 -> 4 pages a step
            {"b": 16, "nb": 16, "page_size": 128, "n_pages": 257,
             "n_layers": 28, "h": 16, "kvh": 8, "d": 128},
            # yi-9b at TP=4, one chip's share: 64 slots, 1 KV head ->
            # all 16 pages in one step
            {"b": 64, "nb": 16, "page_size": 128, "n_pages": 1025,
             "n_layers": 48, "h": 8, "kvh": 1, "d": 128},
            # small-page CI shape (matches the engine parity tests)
            {"b": 3, "nb": 8, "page_size": 128, "n_pages": 25,
             "n_layers": 2, "h": 8, "kvh": 2, "d": 64},
        ),
        dtype_groups=(("q", "k", "v", "o"),),
    ),
    KernelContract(
        name="paged_verify_attention",
        build=_paged_verify_contract,
        cases=(
            # speculative verify window of 4 draft tokens, paged pool
            {"b": 8, "t": 4, "nb": 32, "page_size": 128, "n_pages": 257,
             "n_layers": 28, "layer": 13, "h": 16, "kvh": 4, "d": 128},
            # prefix-cache suffix prefill: longer window, fewer slots
            {"b": 2, "t": 8, "nb": 8, "page_size": 128, "n_pages": 17,
             "n_layers": 2, "layer": 1, "h": 8, "kvh": 2, "d": 64},
        ),
        dtype_groups=(("q", "k", "v", "o"),),
    ),
)
