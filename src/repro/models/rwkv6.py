"""RWKV-6 "Finch" block: attention-free time-mix with data-dependent
decay [arXiv:2404.05892], plus the squared-ReLU channel-mix.

The defining Finch feature — per-channel, per-token decay ``w_t``
produced from the input through a low-rank projection — is implemented
exactly.  Token-shift interpolation uses learned static mix vectors
(RWKV-5 style) rather than the full 5-way data-dependent ddlerp; this is
a simplification that does not change the kernel structure.

Heads are padded from 40 to 48 (multiple of 16) so the time-mix state
shards over the model axis; the padding is a fixed, mesh-independent
constant.

The sequence recurrence uses the chunked linear-attention form (the
same algorithm as the ``linear_scan`` Pallas kernel):
  intra-chunk:  pairwise decay matrix exp(clw_t - clw_s), s <= t
  inter-chunk:  carried state S (H, dh, dh) decayed by the chunk product
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.param import ParamDef
from repro.parallel.sharding import shard

CHUNK = 256
LORA = 64


def padded_heads(cfg) -> int:
    return -(-cfg.n_heads // 16) * 16


def rwkv_time_defs(cfg) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    hp = padded_heads(cfg)
    dh = cfg.head_dim
    dp = hp * dh
    return {
        "mix_r": ParamDef((d,), ("embed",), "small", dt, 0.5),
        "mix_k": ParamDef((d,), ("embed",), "small", dt, 0.5),
        "mix_v": ParamDef((d,), ("embed",), "small", dt, 0.5),
        "mix_w": ParamDef((d,), ("embed",), "small", dt, 0.5),
        "mix_g": ParamDef((d,), ("embed",), "small", dt, 0.5),
        "w_r": ParamDef((d, dp), ("fsdp", "rwkv_heads"), "normal", dt),
        "w_k": ParamDef((d, dp), ("fsdp", "rwkv_heads"), "normal", dt),
        "w_v": ParamDef((d, dp), ("fsdp", "rwkv_heads"), "normal", dt),
        "w_g": ParamDef((d, dp), ("fsdp", "rwkv_heads"), "normal", dt),
        "w_o": ParamDef((dp, d), ("rwkv_heads", "fsdp"), "normal", dt,
                        1.0 / math.sqrt(dp * max(1, 2 * cfg.n_layers))),
        # data-dependent decay: w_t = exp(-exp(w0 + lora(x)))
        "decay_w0": ParamDef((dp,), ("rwkv_heads",), "small", "float32", 0.3),
        "decay_a": ParamDef((d, LORA), ("fsdp", None), "normal", dt),
        "decay_b": ParamDef((LORA, dp), (None, "rwkv_heads"), "small", dt),
        "bonus_u": ParamDef((dp,), ("rwkv_heads",), "small", "float32", 0.3),
        "ln_out": ParamDef((dp,), ("rwkv_heads",), "ones", dt),
    }


def rwkv_channel_defs(cfg) -> dict:
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.dtype
    return {
        "mix_k": ParamDef((d,), ("embed",), "small", dt, 0.5),
        "mix_r": ParamDef((d,), ("embed",), "small", dt, 0.5),
        "w_k": ParamDef((d, f), ("fsdp", "d_ff"), "normal", dt),
        "w_v": ParamDef((f, d), ("d_ff", "fsdp"), "normal", dt,
                        1.0 / math.sqrt(f * max(1, 2 * cfg.n_layers))),
        "w_r": ParamDef((d, d), ("fsdp", "embed"), "normal", dt),
    }


def _token_shift(x, last=None):
    """x_{t-1} stream; ``last`` (B, 1, d) carries state at decode."""
    if last is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return last


def _mix(x, x_prev, mu):
    return x + (x_prev - x) * mu


def _group_norm(x, w, n_heads, eps=1e-5):
    b, s, _ = x.shape
    xh = x.reshape(b, s, n_heads, -1).astype(jnp.float32)
    mu = xh.mean(-1, keepdims=True)
    var = xh.var(-1, keepdims=True)
    xh = (xh - mu) * jax.lax.rsqrt(var + eps)
    return (xh.reshape(b, s, -1) * w).astype(x.dtype)


def wkv_chunked(r, k, v, logw, u, chunk=CHUNK, state0=None,
                return_state=False):
    """Chunked RWKV-6 recurrence.

    r,k,v: (B, S, H, dh); logw: (B, S, H, dh) = log decay (<= 0);
    u: (H, dh) bonus.  Returns y: (B, S, H, dh) [and final state].
      S_t = diag(w_t) S_{t-1} + k_t v_t^T
      y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)
    """
    b, s, h, dh = r.shape
    # seq-adaptive chunking: bound the scan trip count at 32 (intra-
    # chunk pairwise work stays <6% of the time-mix matmuls either way)
    chunk = min(max(chunk, s // 32), s)
    assert s % chunk == 0
    n = s // chunk
    rf = r.astype(jnp.float32).reshape(b, n, chunk, h, dh)
    kf = k.astype(jnp.float32).reshape(b, n, chunk, h, dh)
    vf = v.astype(jnp.float32).reshape(b, n, chunk, h, dh)
    lw = logw.astype(jnp.float32).reshape(b, n, chunk, h, dh)
    rf, kf, vf, lw = (t.transpose(1, 0, 2, 3, 4) for t in (rf, kf, vf, lw))

    if state0 is None:
        state0 = jnp.zeros((b, h, dh, dh), jnp.float32)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)       # s < t strict

    def body(S, args):
        ri, ki, vi, lwi = args                                  # (B,C,H,dh)
        clw = jnp.cumsum(lwi, axis=1)                           # inclusive
        # decay from chunk start to just-before t: exp(clw_{t-1})
        clw_prev = clw - lwi
        # inter-chunk: y_cross_t = (r_t * exp(clw_prev_t)) @ S
        r_dec = ri * jnp.exp(clw_prev)
        y_cross = jnp.einsum("bchd,bhde->bche", r_dec, S)
        # intra-chunk: A[t,s] = sum_d r_t,d k_s,d exp(clw_prev_t - clw_s,d)
        # computed stably: (r_t exp(clw_prev_t)) . (k_s exp(-clw_s)) would
        # overflow; use pairwise difference which is <= 0 for s < t.
        diff = clw_prev[:, :, None] - clw[:, None, :]           # (B,C,C,H,dh)
        att = jnp.einsum("bchd,bshd,bcshd->bcsh", ri, ki,
                         jnp.exp(jnp.where(tri[None, :, :, None, None],
                                           diff, -jnp.inf)))
        att = jnp.where(tri[None, :, :, None], att, 0.0)
        y_intra = jnp.einsum("bcsh,bshd->bchd", att, vi)
        # diagonal bonus term: u * (r_t . k_t) v_t
        y_diag = jnp.einsum("bchd,bchd->bch", ri * u, ki)[..., None] * vi
        # state update: S' = diag(exp(clw_C)) S + sum_s k_s exp(clw_C-clw_s) v_s
        dec_end = jnp.exp(clw[:, -1])                           # (B,H,dh)
        k_dec = ki * jnp.exp(clw[:, -1][:, None] - clw)
        S = dec_end[..., None] * S + jnp.einsum("bchd,bche->bhde", k_dec, vi)
        return S, y_cross + y_intra + y_diag

    S, y = jax.lax.scan(body, state0, (rf, kf, vf, lw))
    y = y.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh)
    if return_state:
        return y.astype(r.dtype), S
    return y.astype(r.dtype)


def _time_mix_io(x, p, cfg, x_prev):
    hp = padded_heads(cfg)
    dh = cfg.head_dim
    b, s, _ = x.shape
    xs = _token_shift(x, x_prev)
    r = _mix(x, xs, p["mix_r"]) @ p["w_r"]
    k = _mix(x, xs, p["mix_k"]) @ p["w_k"]
    v = _mix(x, xs, p["mix_v"]) @ p["w_v"]
    g = _mix(x, xs, p["mix_g"]) @ p["w_g"]
    xw = _mix(x, xs, p["mix_w"])
    dd = jnp.tanh(xw @ p["decay_a"]) @ p["decay_b"]             # data-dep decay
    logw = -jnp.exp(p["decay_w0"] + dd.astype(jnp.float32))     # <= 0
    shp = (b, s, hp, dh)
    return (r.reshape(shp), k.reshape(shp), v.reshape(shp), g,
            logw.reshape(shp))


def time_mix(x, p, cfg, state0=None, return_state=False):
    """RWKV-6 time-mix over a full sequence. x: (B, S, d)."""
    hp = padded_heads(cfg)
    b, s, _ = x.shape
    r, k, v, g, logw = _time_mix_io(x, p, cfg, None)
    r = shard(r, "batch", None, "rwkv_heads", None)
    k = shard(k, "batch", None, "rwkv_heads", None)
    v = shard(v, "batch", None, "rwkv_heads", None)
    u = p["bonus_u"].reshape(hp, cfg.head_dim)
    out = wkv_chunked(r, k, v, logw, u, state0=state0,
                      return_state=return_state)
    y, S = out if return_state else (out, None)
    y = _group_norm(y.reshape(b, s, -1), p["ln_out"], hp)
    y = (y * jax.nn.silu(g)) @ p["w_o"]
    y = shard(y, "batch", "seq_sp", "embed")
    if return_state:
        return y, S
    return y


def time_mix_decode(x, p, cfg, state):
    """One token. state = {"S": (B,H,dh,dh) f32, "x_prev": (B,1,d)}."""
    hp = padded_heads(cfg)
    dh = cfg.head_dim
    b = x.shape[0]
    r, k, v, g, logw = _time_mix_io(x, p, cfg, state["x_prev"])
    u = p["bonus_u"].reshape(hp, dh)
    rf, kf, vf = (t.astype(jnp.float32)[:, 0] for t in (r, k, v))
    lw = logw[:, 0]
    S = state["S"]
    kv = jnp.einsum("bhd,bhe->bhde", kf, vf)
    y = jnp.einsum("bhd,bhde->bhe", rf, S + u[None, :, :, None] * kv)
    S = jnp.exp(lw)[..., None] * S + kv
    y = y.astype(x.dtype)          # keep the residual stream in bf16
    y = _group_norm(y.reshape(b, 1, -1), p["ln_out"], hp)
    y = (y * jax.nn.silu(g)) @ p["w_o"]
    return y, {"S": S, "x_prev": x}


def channel_mix(x, p, x_prev=None):
    xs = _token_shift(x, x_prev)
    k = _mix(x, xs, p["mix_k"]) @ p["w_k"]
    k = shard(jnp.square(jax.nn.relu(k)), "batch", "seq_sp", "d_ff")
    kv = k @ p["w_v"]
    r = jax.nn.sigmoid(_mix(x, xs, p["mix_r"]) @ p["w_r"])
    return shard(r * kv, "batch", "seq_sp", "embed")


def rwkv_state_defs(cfg, batch: int) -> dict:
    hp = padded_heads(cfg)
    dh = cfg.head_dim
    return {
        "S": jax.ShapeDtypeStruct((batch, hp, dh, dh), jnp.float32),
        "x_prev_t": jax.ShapeDtypeStruct((batch, 1, cfg.d_model),
                                         jnp.dtype(cfg.dtype)),
        "x_prev_c": jax.ShapeDtypeStruct((batch, 1, cfg.d_model),
                                         jnp.dtype(cfg.dtype)),
    }
