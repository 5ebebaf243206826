"""Property-based tests (hypothesis) on the paged-KV invariants: the
page-pool refcounting protocol under arbitrary traffic, and page-table
permutation bit-identity of the paged attention kernels."""
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dep: property tests are skipped, not collection-fatal")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.serving import GARBAGE_PAGE, PagePool, PoolExhausted


@given(ops=st.lists(st.integers(0, 2 ** 30), min_size=1, max_size=200),
       n_pages=st.integers(2, 17))
@settings(max_examples=100, deadline=None)
def test_pagepool_refcount_conservation(ops, n_pages):
    """Under arbitrary alloc/ref/unref traffic: a referenced page is
    never on the free list, page 0 is never handed out, and used + free
    always equals the usable pool."""
    pool = PagePool(n_pages, 4)
    live: list[int] = []                   # one entry per owner
    for op in ops:
        kind = op % 3
        if kind == 0:
            n = op % (n_pages // 2 + 1)
            try:
                got = pool.alloc(n)
            except PoolExhausted:
                assert n > pool.free_pages()
            else:
                assert GARBAGE_PAGE not in got
                live.extend(got)
        elif kind == 1 and live:
            page = live[op % len(live)]
            pool.ref(page)
            live.append(page)
        elif kind == 2 and live:
            pool.unref(live.pop(op % len(live)))
        assert pool.used_pages() + pool.free_pages() == n_pages - 1
        for page in set(live):
            assert pool.refcount[page] == live.count(page)
            assert page not in pool._free
    shared = [p for p in set(live) if live.count(p) > 1]
    for page in shared:
        assert pool.refcount[page] > 1     # shared pages still owned


N_LAYERS = 4
LAYERS = [0, N_LAYERS // 2, N_LAYERS - 1]      # first, middle, last


def _stacked_pools(rng, caches, tables, n_pages, ps, layer):
    """Random stacked pools (L, KVH, P, ps, D), one per contiguous cache
    (B, S, KVH, D) in ``caches``, whose layer ``layer`` holds that
    cache's blocks at the table's physical pages."""
    b, s, kvh, d = caches[0].shape
    pools = []
    for c in caches:
        pool = rng.standard_normal(
            (N_LAYERS, kvh, n_pages, ps, d)).astype(np.float32)
        for bb in range(b):
            for i in range(s // ps):
                pool[layer, :, tables[bb, i]] = np.asarray(
                    c[bb, i * ps:(i + 1) * ps]).transpose(1, 0, 2)
        pools.append(jnp.asarray(pool))
    return pools


# (KV heads, query heads per KV head): qwen3-1.7b on one chip, and
# yi-9b's one KV head a chip at TP=4
FAMILIES = {"qwen3": (8, 2), "yi-local": (1, 8)}
PS, NB = 8, 4                                  # pages of 8 rows, 4 a slot
# depth 0, both sides of each page boundary (and so of each block
# boundary at 1 or 2 pages a block), and every page live
EDGE_DEPTHS = [0, PS - 1, PS, 2 * PS - 1, 2 * PS, 3 * PS, NB * PS - 1]


@lru_cache(maxsize=None)
def _paged_kernel_at(pages_per_block):
    """The paged decode kernel at a fixed number of pages a grid step
    (the wrapper derives it from the shapes), jitted once."""
    from repro.kernels.decode_attention.decode_attention import (
        paged_decode_attention_kernel)
    return jax.jit(partial(paged_decode_attention_kernel,
                           pages_per_block=pages_per_block, interpret=True))


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("layer", LAYERS)
@given(seed=st.integers(0, 2 ** 31 - 1),
       pos=st.lists(st.one_of(st.sampled_from(EDGE_DEPTHS),
                              st.integers(0, NB * PS - 1)),
                    min_size=3, max_size=3),
       empty=st.lists(st.booleans(), min_size=3, max_size=3))
@settings(max_examples=8, deadline=None)
def test_paged_attention_permutation_bit_identity(family, layer, seed, pos,
                                                  empty):
    """A slot that holds a request attends over its live pages wherever
    they lie in the pool, bit-identical to the contiguous kernel at one
    page a block (the same recurrence) and close to the jnp reference,
    at any layer, at one page, two pages and the derived number of
    pages a grid step.  A slot whose row starts with the reserved page 0
    holds no request: its output is zeros whatever its frozen depth.
    Page 0, every page no live slot owns and every row past a slot's
    depth hold NaN, so a row that is read but not masked shows."""
    from repro.kernels.decode_attention.decode_attention import EMPTY_PAGE
    from repro.kernels.decode_attention.ops import (
        decode_attention, paged_decode_attention)
    from repro.models.layers import decode_attention_jnp

    assert EMPTY_PAGE == GARBAGE_PAGE       # the kernel reads the engine's
    kvh, g = FAMILIES[family]
    b, h, d = len(pos), kvh * g, 16
    n_pages = b * NB + 1
    rng = np.random.default_rng(seed)
    kc = rng.standard_normal((b, NB * PS, kvh, d)).astype(np.float32)
    vc = rng.standard_normal((b, NB * PS, kvh, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)

    # live slots own their first pos // PS + 1 entries; the rest of a
    # row is unused pages or page 0, an empty slot's row is all page 0
    tables = rng.permutation(np.arange(1, n_pages)).reshape(b, NB)
    pools = np.full((2, N_LAYERS, kvh, n_pages, PS, d), np.nan, np.float32)
    for bb in range(b):
        if empty[bb]:
            tables[bb] = EMPTY_PAGE
            continue
        n_live = pos[bb] // PS + 1
        tables[bb, n_live:] = rng.choice([EMPTY_PAGE, tables[bb, -1]])
        for i in range(n_live):
            rows = slice(i * PS, min((i + 1) * PS, pos[bb] + 1))
            for pool, c in zip(pools, (kc, vc)):
                pool[layer, :, tables[bb, i], :rows.stop - rows.start] = \
                    c[bb, rows].transpose(1, 0, 2)
    k_pool, v_pool = jnp.asarray(pools[0]), jnp.asarray(pools[1])
    tables = jnp.asarray(tables, jnp.int32)

    live = ~np.asarray(empty)
    ref = np.asarray(decode_attention(q, jnp.asarray(kc), jnp.asarray(vc),
                                      posv, block_k=PS, interpret=True))
    outs = [np.asarray(paged_decode_attention(
        q, k_pool, v_pool, tables, posv, jnp.int32(layer),
        interpret=True))]
    for ppb in (1, 2):
        o = _paged_kernel_at(ppb)(
            q[:, 0].reshape(b * kvh, g, d), k_pool, v_pool, tables, posv,
            jnp.int32(layer))
        outs.append(np.asarray(o).reshape(b, 1, h, d))
    want = np.asarray(decode_attention_jnp(q, jnp.asarray(kc),
                                           jnp.asarray(vc), posv))
    for out in outs:
        np.testing.assert_array_equal(out[live], ref[live])
        np.testing.assert_array_equal(out[~live], 0.0)
        np.testing.assert_allclose(out[live], want[live], rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("layer", LAYERS)
@given(seed=st.integers(0, 2 ** 31 - 1),
       pos=st.lists(st.integers(0, 29), min_size=2, max_size=2))
@settings(max_examples=8, deadline=None)
def test_paged_verify_permutation_bit_identity(layer, seed, pos):
    """The verify window (T = 3, crossing page boundaries) through the
    page table at any layer of the stacked pool is bit-identical to the
    contiguous verify kernel and matches the jnp reference."""
    from repro.kernels.decode_attention.ops import (
        paged_verify_attention, verify_attention)
    from repro.models.layers import verify_attention_jnp

    b, t, h, kvh, d, ps, nb = 2, 3, 4, 2, 16, 8, 4
    n_pages = b * nb + 3
    rng = np.random.default_rng(seed)
    kc = jnp.asarray(rng.standard_normal((b, nb * ps, kvh, d)),
                     jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, nb * ps, kvh, d)),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)

    tables = rng.permutation(np.arange(1, n_pages))[:b * nb] \
        .reshape(b, nb)
    k_pool, v_pool = _stacked_pools(rng, (kc, vc), tables, n_pages, ps,
                                    layer)

    ref = verify_attention(q, kc, vc, posv, block_k=ps, interpret=True)
    out = paged_verify_attention(q, k_pool, v_pool,
                                 jnp.asarray(tables, jnp.int32), posv,
                                 jnp.int32(layer), interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(verify_attention_jnp(q, kc, vc, posv)),
        rtol=1e-5, atol=1e-5)


@given(seed=st.integers(0, 2 ** 31 - 1),
       pos=st.lists(st.integers(0, 31), min_size=3, max_size=3),
       layer=st.integers(0, N_LAYERS - 1), t=st.sampled_from([1, 3]))
@settings(max_examples=25, deadline=None)
def test_paged_insert_window_writes_only_its_rows(seed, pos, layer, t):
    """``paged_insert_window`` writes each new row at exactly
    ``(layer, head, page, offset)`` of the stacked pool and leaves every
    other row of every layer bit-identical; a window that runs off the
    table clamps to the slot's last page."""
    from repro.models.layers import paged_insert_window

    b, kvh, d, ps, nb = 3, 2, 4, 8, 4
    n_pages = b * nb + 1
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal(
        (N_LAYERS, kvh, n_pages, ps, d)).astype(np.float32)
    new = rng.standard_normal((b, t, kvh, d)).astype(np.float32)
    tables = rng.permutation(np.arange(1, n_pages)).reshape(b, nb)

    out = paged_insert_window(jnp.asarray(pool), jnp.asarray(new),
                              jnp.asarray(tables, jnp.int32),
                              jnp.asarray(pos, jnp.int32), layer)
    want = pool.copy()
    for bb in range(b):
        for j in range(t):
            row = pos[bb] + j
            page = tables[bb, min(row // ps, nb - 1)]
            want[layer, :, page, row % ps] = new[bb, j]
    np.testing.assert_array_equal(np.asarray(out), want)
