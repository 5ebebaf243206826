"""Mamba (S6) block for the Jamba hybrid architecture.

Selective state-space layer: input-dependent (dt, B, C) with diagonal A.
Sequence recurrence is computed with a two-level chunked scan: an outer
``lax.scan`` over chunks carrying the SSM state, an inner associative
scan within each chunk — O(T) FLOPs, bounded memory, and no cross-device
recurrence (Mamba layers are tensor-parallel over d_inner, NOT
sequence-parallel).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.param import ParamDef
from repro.parallel.sharding import shard

CHUNK = 512


def mamba_defs(cfg) -> dict:
    mc, d, dt = cfg.mamba, cfg.d_model, cfg.dtype
    d_in = mc.expand * d
    dt_rank = mc.dt_rank or -(-d // 16)
    return {
        "in_proj": ParamDef((d, 2 * d_in), ("fsdp", "d_inner"), "normal", dt),
        "conv_w": ParamDef((mc.d_conv, d_in), (None, "d_inner"), "normal", dt,
                           1.0 / math.sqrt(mc.d_conv)),
        "conv_b": ParamDef((d_in,), ("d_inner",), "zeros", dt),
        "x_proj": ParamDef((d_in, dt_rank + 2 * mc.d_state),
                           ("d_inner", None), "normal", dt),
        "dt_proj": ParamDef((dt_rank, d_in), (None, "d_inner"), "normal", dt),
        "dt_bias": ParamDef((d_in,), ("d_inner",), "zeros", "float32"),
        "A_log": ParamDef((d_in, mc.d_state), ("d_inner", None), "zeros",
                          "float32"),
        "D": ParamDef((d_in,), ("d_inner",), "ones", "float32"),
        "out_proj": ParamDef((d_in, d), ("d_inner", "fsdp"), "normal", dt,
                             1.0 / math.sqrt(d_in * max(1, 2 * cfg.n_layers))),
    }


def _ssm_scan(a, b):
    """h_t = a_t * h_{t-1} + b_t over axis 1 (seq), chunked.

    a, b: (B, S, Din, N) float32.  Returns h for every t.
    """
    bsz, s, d_in, n = a.shape
    chunk = min(CHUNK, s)
    nchunk = s // chunk
    assert s % chunk == 0
    a_c = a.reshape(bsz, nchunk, chunk, d_in, n).transpose(1, 0, 2, 3, 4)
    b_c = b.reshape(bsz, nchunk, chunk, d_in, n).transpose(1, 0, 2, 3, 4)

    def combine(l, r):
        (al, bl), (ar, br) = l, r
        return al * ar, bl * ar + br

    def outer(h, ab):
        ai, bi = ab
        aa, bb = jax.lax.associative_scan(combine, (ai, bi), axis=1)
        h_all = aa * h[:, None] + bb
        return h_all[:, -1], h_all

    h0 = jnp.zeros((bsz, d_in, n), jnp.float32)
    _, h_c = jax.lax.scan(outer, h0, (a_c, b_c))
    return h_c.transpose(1, 0, 2, 3, 4).reshape(bsz, s, d_in, n)


def _ssm_params(x_in, p, cfg):
    mc = cfg.mamba
    dt_rank = mc.dt_rank or -(-cfg.d_model // 16)
    proj = x_in @ p["x_proj"]
    dt_raw, B, C = jnp.split(proj, [dt_rank, dt_rank + mc.d_state], axis=-1)
    dt = jax.nn.softplus(dt_raw @ p["dt_proj"] + p["dt_bias"]).astype(jnp.float32)
    A = -jnp.exp(p["A_log"])                                   # (Din, N)
    return dt, A, B.astype(jnp.float32), C.astype(jnp.float32)


def causal_conv(x_in, w, b, state=None):
    """Depthwise causal conv along seq. x_in: (B, S, Din); w: (K, Din).

    If ``state`` (B, K-1, Din) is given (decode), it is prepended and the
    updated state returned.
    """
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x_in.shape[0], k - 1, x_in.shape[2]), x_in.dtype)
    else:
        pad = state.astype(x_in.dtype)
    xp = jnp.concatenate([pad, x_in], axis=1)
    out = sum(xp[:, i:i + x_in.shape[1]] * w[i] for i in range(k))
    new_state = xp[:, -(k - 1):]
    return out + b, new_state


def mamba_block(x, p, cfg, return_state: bool = False):
    """Full-sequence Mamba mixer. x: (B, S, d) -> (B, S, d)."""
    xz = x @ p["in_proj"]
    xz = shard(xz, "batch", None, "d_inner")
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in, conv_state = causal_conv(x_in, p["conv_w"], p["conv_b"])
    x_in = jax.nn.silu(x_in)
    dt, A, B, C = _ssm_params(x_in, p, cfg)
    xf = x_in.astype(jnp.float32)
    a_bar = jnp.exp(dt[..., None] * A)                         # (B,S,Din,N)
    b_bar = (dt * xf)[..., None] * B[:, :, None, :]
    h = _ssm_scan(a_bar, b_bar)
    y = jnp.einsum("bsdn,bsn->bsd", h, C) + p["D"] * xf
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = y @ p["out_proj"]
    out = shard(out, "batch", "seq_sp", "embed")
    if return_state:
        return out, {"h": h[:, -1], "conv": conv_state}
    return out


def mamba_decode(x, p, cfg, state):
    """Single-token step. state = {"h": (B,Din,N) f32, "conv": (B,K-1,Din)}."""
    xz = x @ p["in_proj"]
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in, conv_state = causal_conv(x_in, p["conv_w"], p["conv_b"],
                                   state["conv"])
    x_in = jax.nn.silu(x_in)
    dt, A, B, C = _ssm_params(x_in, p, cfg)
    xf = x_in.astype(jnp.float32)
    a_bar = jnp.exp(dt[:, 0, :, None] * A)                     # (B,Din,N)
    b_bar = (dt[:, 0] * xf[:, 0])[..., None] * B[:, 0, None, :]
    h = a_bar * state["h"] + b_bar
    y = jnp.einsum("bdn,bn->bd", h, C[:, 0]) + p["D"] * xf[:, 0]
    y = y[:, None].astype(x.dtype) * jax.nn.silu(z)
    out = y @ p["out_proj"]
    return out, {"h": h, "conv": conv_state}


def mamba_state_defs(cfg, batch: int) -> dict:
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    return {
        "h": jax.ShapeDtypeStruct((batch, d_in, mc.d_state), jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, mc.d_conv - 1, d_in),
                                     jnp.dtype(cfg.dtype)),
    }
