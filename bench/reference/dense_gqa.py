"""Plain reference of a dense decoder with grouped-query attention.

Written from the published descriptions of the Qwen3 and Llama-style
decoders (as in Hugging Face ``Qwen3ForCausalLM`` and
``LlamaForCausalLM``), in straightforward ``jax.numpy``: no cache, no
kernel, no batching of requests, the whole causal sequence at once.

- RMSNorm: ``w * x / sqrt(mean(x^2) + eps)``.
- Attention: ``q = h Wq``, ``k = h Wk``, ``v = h Wv`` split into heads of
  ``head_dim``; with ``qk_norm`` an RMSNorm over each head of q and k
  before RoPE; RoPE rotating the two halves of each head
  (``rotate_half``) at frequencies ``theta^(-2i/head_dim)``; query head
  ``i`` reads key/value head ``i // (heads / kv_heads)``; causal
  softmax of ``q k / sqrt(head_dim)``; output ``o Wo``.
- SwiGLU feed-forward: ``(silu(h W_gate) * h W_up) W_down``.
- Pre-norm residual blocks, a final RMSNorm and an output head.

``mode="f32"`` is the reference: float32 throughout, every matrix
product at ``Precision.HIGHEST``.  ``mode="fp8"`` is the control, one
precision step below the served bfloat16: both operands of every
product with a weight are quantised to float8 e4m3 (a scale per weight
tensor, and per token for the activations) before the product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale over ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def linear(x, w, mode):
    """``x @ w`` for activations (..., K) and a weight (K, N)."""
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, theta):
    """x: (S, H, D); rotate-half RoPE at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def attention(q, k, v):
    """Causal GQA. q: (S, H, D); k, v: (S, KVH, D)."""
    s, h, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


class Reference:
    """The decoder of one configuration (the published keys of its
    ``config.json``: ``hidden_size``, ``num_attention_heads``, ...)."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.h = cfg["num_attention_heads"]
        self.kvh = cfg["num_key_value_heads"]
        self.dh = cfg["head_dim"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = float(cfg["rope_theta"])
        self.qk_norm = bool(cfg["qk_norm"])

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def block(self, x, w, mode):
        """One decoder layer over one sequence. x: (S, d) float32."""
        s = x.shape[0]
        h = rmsnorm(x, w["attn_norm"], self.eps)
        q = linear(h, w["wq"], mode).reshape(s, self.h, self.dh)
        k = linear(h, w["wk"], mode).reshape(s, self.kvh, self.dh)
        v = linear(h, w["wv"], mode).reshape(s, self.kvh, self.dh)
        if self.qk_norm:
            q = rmsnorm(q, w["q_norm"], self.eps)
            k = rmsnorm(k, w["k_norm"], self.eps)
        q, k = rope(q, self.theta), rope(k, self.theta)
        o = attention(q, k, v).reshape(s, self.h * self.dh)
        x = x + linear(o, w["wo"], mode)
        h = rmsnorm(x, w["ffn_norm"], self.eps)
        g = jax.nn.silu(linear(h, w["w_gate"], mode))
        return x + linear(g * linear(h, w["w_up"], mode), w["w_down"], mode)

    @functools.partial(jax.jit, static_argnums=(0, 4))
    def head(self, x, final_norm, lm_head, mode):
        """Logits (P, vocab) of final hidden states x: (P, d)."""
        return linear(rmsnorm(x, final_norm, self.eps), lm_head, mode)
