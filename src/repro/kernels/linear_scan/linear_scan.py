"""RWKV-6 chunked data-dependent-decay scan — Pallas TPU kernel.

The hot loop of the attention-free architectures (rwkv6-3b; the same
chunked structure serves GLA/Mamba-2-style kernels):

  S_t = diag(w_t) S_{t-1} + k_t v_t^T
  y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)

Grid (B*H, n_chunks) with the chunk axis innermost; the (dh x dh) f32
state lives in VMEM scratch and carries across chunk steps — the
inter-chunk recurrence never touches HBM.  Intra-chunk work uses the
stable pairwise-difference decay matrix (all exponents <= 0), computed
blockwise in VMEM.

VMEM per step (C=64, dh=64): r/k/v/logw 4x16 KB + pairwise (C,C,dh)
f32 1 MB + state 16 KB — comfortably inside 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_final_ref,
            s_scr, *, chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)              # (C, dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)            # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)              # (1, dh) bonus

    clw = jnp.cumsum(lw, axis=0)                  # inclusive
    clw_prev = clw - lw
    s_in = s_scr[...]                             # (dh, dh)

    # inter-chunk: y_cross = (r * exp(clw_prev)) @ S_in
    r_dec = r * jnp.exp(clw_prev)
    y_cross = jax.lax.dot_general(
        r_dec, s_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # intra-chunk: A[t,s] = sum_d r[t,d] k[s,d] exp(clw_prev[t,d]-clw[s,d])
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = clw_prev[:, None, :] - clw[None, :, :]          # (C, C, dh)
    decay = jnp.where(tri[..., None], jnp.exp(diff), 0.0)
    att = jnp.einsum("td,sd,tsd->ts", r, k, decay)
    y_intra = jax.lax.dot_general(
        att, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # diagonal bonus: u * (r_t . k_t) v_t
    y_diag = jnp.sum(r * u * k, axis=1)[:, None] * v

    y_ref[0] = (y_cross + y_intra + y_diag).astype(y_ref.dtype)

    # state update: S' = diag(exp(clw_C)) S + sum_s k_s exp(clw_C-clw_s) v_s
    dec_end = jnp.exp(clw[-1])                             # (dh,)
    k_dec = k * jnp.exp(clw[-1][None, :] - clw)
    s_scr[...] = dec_end[:, None] * s_in + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        s_final_ref[0] = s_scr[...]


def linear_scan_kernel(r, k, v, logw, u, *, chunk: int = 64,
                       interpret: bool = False):
    """r/k/v/logw: (BH, T, dh); u: (BH, 1, dh).
    Returns y (BH, T, dh), final state (BH, dh, dh) f32."""
    bh, t, dh = r.shape
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, dh), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dh, dh), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dh), r.dtype),
            jax.ShapeDtypeStruct((bh, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u)
