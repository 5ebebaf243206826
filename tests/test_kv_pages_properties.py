"""Property-based tests (hypothesis) on the paged-KV invariants: the
page-pool refcounting protocol under arbitrary traffic, and page-table
permutation bit-identity of the paged attention kernels."""
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


@pytest.mark.parametrize("layer", LAYERS)
@given(seed=st.integers(0, 2 ** 31 - 1),
       pos=st.lists(st.integers(0, 31), min_size=2, max_size=2))
@settings(max_examples=8, deadline=None)
def test_paged_attention_permutation_bit_identity(layer, seed, pos):
    """Any page-table permutation of the KV pool is bit-identical to
    the contiguous layout at equal block size — the page indirection
    changes only *where* a block lives, never the arithmetic — at any
    layer of the stacked pool, and matches the jnp reference."""
    from repro.kernels.decode_attention.ops import (
        decode_attention, paged_decode_attention)
    from repro.models.layers import decode_attention_jnp

    b, h, kvh, d, ps, nb = 2, 4, 2, 16, 8, 4
    n_pages = b * nb + 1
    rng = np.random.default_rng(seed)
    kc = jnp.asarray(rng.standard_normal((b, nb * ps, kvh, d)),
                     jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, nb * ps, kvh, d)),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)

    tables = rng.permutation(np.arange(1, n_pages))[:b * nb] \
        .reshape(b, nb)
    k_pool, v_pool = _stacked_pools(rng, (kc, vc), tables, n_pages, ps,
                                    layer)

    ref = decode_attention(q, kc, vc, posv, block_k=ps, interpret=True)
    out = paged_decode_attention(q, k_pool, v_pool,
                                 jnp.asarray(tables, jnp.int32), posv,
                                 jnp.int32(layer), interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(decode_attention_jnp(q, kc, vc, posv)),
        rtol=1e-5, atol=1e-5)


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
