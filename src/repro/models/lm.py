"""Decoder-only language models: dense / MoE / MLA-MoE / hybrid / RWKV / VLM.

One ``LM`` class covers all assigned decoder-only architectures through
a per-family block builder.  Layers are scanned (``lax.scan``) with
optional remat; parameters come from a single ``ParamDef`` tree (see
``repro.models.param``) so real init, shape-only ShapeDtypeStructs and
PartitionSpecs never drift.

Public API (uniform across families; whisper has its own class):
  defs = lm.param_defs()
  loss, metrics = lm.train_loss(params, batch)
  logits, cache = lm.prefill(params, inputs)
  logits, cache = lm.decode_step(params, cache, tokens)
  cache_specs   = lm.cache_specs(batch, max_len)
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import rwkv6 as R
from repro.models.param import ParamDef, stack_tree
from repro.parallel.sharding import shard

AUX_COEF = 0.01
MTP_COEF = 0.3


def _ce_loss(logits_f32, labels, vocab, vocab_padded, weights=None):
    """Stable vocab-parallel cross entropy. logits: (..., Vp) f32."""
    if vocab_padded > vocab:
        pad_mask = jnp.arange(vocab_padded) >= vocab
        logits_f32 = jnp.where(pad_mask, L.MASK_VALUE, logits_f32)
    lse = jax.nn.logsumexp(logits_f32, axis=-1)
    tgt = jnp.take_along_axis(logits_f32, labels[..., None], axis=-1)[..., 0]
    ce = lse - tgt
    if weights is None:
        return jnp.mean(ce)
    w = weights.astype(ce.dtype)
    return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.vp = L.pad_vocab(cfg.vocab_size)

    # ------------------------------------------------------------------
    # Parameter definitions
    # ------------------------------------------------------------------
    def _block_defs(self, kind: str) -> dict:
        cfg = self.cfg
        d = {"ln1": L.rmsnorm_def(cfg.d_model, cfg.dtype),
             "ln2": L.rmsnorm_def(cfg.d_model, cfg.dtype)}
        if kind == "attn_dense":
            d["attn"] = L.gqa_defs(cfg)
            d["ffn"] = L.ffn_defs(cfg)
        elif kind == "attn_moe":
            d["attn"] = L.gqa_defs(cfg)
            d["moe"] = L.moe_defs(cfg)
        elif kind == "mla_dense":
            d["attn"] = L.mla_defs(cfg)
            d["ffn"] = L.ffn_defs(cfg, cfg.moe.d_ff_dense if cfg.moe else None)
        elif kind == "mla_moe":
            d["attn"] = L.mla_defs(cfg)
            d["moe"] = L.moe_defs(cfg)
        elif kind == "mamba_dense":
            d["mamba"] = M.mamba_defs(cfg)
            d["ffn"] = L.ffn_defs(cfg, cfg.moe.d_ff_dense if cfg.moe else None)
        elif kind == "mamba_moe":
            d["mamba"] = M.mamba_defs(cfg)
            d["moe"] = L.moe_defs(cfg)
        elif kind == "rwkv":
            d["time"] = R.rwkv_time_defs(cfg)
            d["chan"] = R.rwkv_channel_defs(cfg)
        else:
            raise ValueError(kind)
        return d

    def _layer_kinds(self) -> list[str]:
        cfg = self.cfg
        kinds = []
        for i in range(cfg.n_layers):
            if cfg.family == "rwkv":
                kinds.append("rwkv")
                continue
            is_attn = True
            if cfg.hybrid is not None:
                is_attn = (i % cfg.hybrid.attn_period) == cfg.hybrid.attn_offset
            mix = ("mla" if cfg.mla is not None else
                   ("attn" if is_attn else "mamba"))
            is_moe = False
            if cfg.moe is not None:
                is_moe = (i >= cfg.moe.first_k_dense
                          and (i % cfg.moe.moe_every) == 0)
            kinds.append(f"{mix}_{'moe' if is_moe else 'dense'}")
        return kinds

    def param_defs(self):
        cfg = self.cfg
        dt = cfg.dtype
        defs: dict[str, Any] = {
            "embed": ParamDef((self.vp, cfg.d_model), ("vocab", "fsdp"),
                              "embed", dt),
            "final_norm": L.rmsnorm_def(cfg.d_model, dt),
            "lm_head": ParamDef((cfg.d_model, self.vp), ("fsdp", "vocab"),
                                "normal", dt),
        }
        kinds = self._layer_kinds()
        if cfg.family == "hybrid":
            period = cfg.hybrid.attn_period
            n_super = cfg.n_layers // period
            super_defs = {f"l{j}_{kinds[j]}": self._block_defs(kinds[j])
                          for j in range(period)}
            defs["superblocks"] = stack_tree(super_defs, n_super)
        elif cfg.family == "mla_moe":
            k_dense = cfg.moe.first_k_dense
            defs["dense_blocks"] = stack_tree(
                self._block_defs("mla_dense"), k_dense)
            defs["moe_blocks"] = stack_tree(
                self._block_defs("mla_moe"), cfg.n_layers - k_dense)
        else:
            # homogeneous stack (dense / moe / rwkv / vlm backbones)
            defs["blocks"] = stack_tree(self._block_defs(kinds[0]),
                                        cfg.n_layers)
        if cfg.vlm is not None:
            pe = cfg.vlm.patch_embed_dim or cfg.d_model
            defs["patch_proj"] = ParamDef((pe, cfg.d_model),
                                          (None, "fsdp"), "normal", dt)
        if cfg.mtp:
            defs["mtp"] = {
                "proj": ParamDef((2 * cfg.d_model, cfg.d_model),
                                 ("fsdp", "embed"), "normal", dt),
                "norm_h": L.rmsnorm_def(cfg.d_model, dt),
                "norm_e": L.rmsnorm_def(cfg.d_model, dt),
                "block": self._block_defs(
                    "mla_dense" if cfg.mla is not None else "attn_dense"),
            }
        return defs

    # ------------------------------------------------------------------
    # Block application
    # ------------------------------------------------------------------
    def _apply_block(self, x, bp, kind, mode, cache, pos, pages=None,
                     layer=None):
        """Returns (x, new_cache, aux).  ``pages`` (B, NB) switches the
        attention decode/verify paths to the paged KV pool — the cache
        leaves are then the stacked pools of shared physical pages of
        every layer, and ``layer`` says which of them this block owns."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
        new_cache = cache
        if kind.startswith("rwkv"):
            if mode == "verify":
                raise NotImplementedError(
                    "speculative verify needs random-access KV attention; "
                    "rwkv state has no multi-token verify path")
            if mode == "decode":
                o, st = R.time_mix_decode(h, bp["time"],
                                          cfg, {"S": cache["S"],
                                                "x_prev": cache["x_prev_t"]})
                new_cache = dict(cache, S=st["S"], x_prev_t=st["x_prev"])
            elif mode == "prefill":
                o, S = R.time_mix(h, bp["time"], cfg, return_state=True)
                new_cache = dict(cache, S=S, x_prev_t=h[:, -1:])
            else:
                o = R.time_mix(h, bp["time"], cfg)
            x = x + o
            h2 = L.rmsnorm(x, bp["ln2"], cfg.norm_eps)
            if mode == "decode":
                o2 = R.channel_mix(h2, bp["chan"], cache["x_prev_c"])
                new_cache = dict(new_cache, x_prev_c=h2)
            else:
                o2 = R.channel_mix(h2, bp["chan"])
                if mode == "prefill":
                    new_cache = dict(new_cache, x_prev_c=h2[:, -1:])
            return x + o2, new_cache, aux

        mix, ff = kind.split("_")
        if mode == "verify" and mix != "attn":
            raise NotImplementedError(
                f"speculative verify needs random-access KV attention; "
                f"layer kind {kind!r} has no multi-token verify path")
        if pages is not None and mix != "attn":
            raise NotImplementedError(
                f"paged KV needs random-access KV attention; layer kind "
                f"{kind!r} has no page-table path")
        if mix == "attn":
            if mode == "train":
                o = L.gqa_attention(h, bp["attn"], cfg)
            elif mode == "verify" and pages is not None:
                o, kvc = L.gqa_verify_paged(
                    h, bp["attn"], cfg,
                    {"k": cache["k"], "v": cache["v"]}, pos, pages, layer)
                new_cache = dict(cache, **kvc)
            elif mode == "verify":
                o, kvc = L.gqa_verify(h, bp["attn"], cfg,
                                      {"k": cache["k"], "v": cache["v"]},
                                      pos)
                new_cache = dict(cache, **kvc)
            elif mode == "prefill":
                o, (k, v) = L.gqa_prefill(h, bp["attn"], cfg)
                s_max = cache["k"].shape[1]
                k = L.pad_seq(k, s_max)
                v = L.pad_seq(v, s_max)
                new_cache = dict(cache, k=shard(k, "batch", "kv_seq", None, None),
                                 v=shard(v, "batch", "kv_seq", None, None))
            elif pages is not None:
                o, kvc = L.gqa_decode_paged(
                    h, bp["attn"], cfg,
                    {"k": cache["k"], "v": cache["v"]}, pos, pages, layer)
                new_cache = dict(cache, **kvc)
            else:
                o, kvc = L.gqa_decode(h, bp["attn"], cfg,
                                      {"k": cache["k"], "v": cache["v"]}, pos)
                new_cache = dict(cache, **kvc)
        elif mix == "mla":
            if mode == "train":
                o = L.mla_attention(h, bp["attn"], cfg)
            elif mode == "prefill":
                o, (c_kv, k_rope) = L.mla_prefill(h, bp["attn"], cfg)
                s_max = cache["c_kv"].shape[1]
                c_kv = L.pad_seq(c_kv, s_max)
                k_rope = L.pad_seq(k_rope, s_max)
                new_cache = dict(cache,
                                 c_kv=shard(c_kv, "batch", "kv_seq", None),
                                 k_rope=shard(k_rope, "batch", "kv_seq", None))
            else:
                o, c = L.mla_decode(h, bp["attn"], cfg,
                                    {"c_kv": cache["c_kv"],
                                     "k_rope": cache["k_rope"]}, pos)
                new_cache = dict(cache, **c)
        else:  # mamba
            if mode == "decode":
                o, st = M.mamba_decode(h, bp["mamba"], cfg,
                                       {"h": cache["h"], "conv": cache["conv"]})
                new_cache = dict(cache, **st)
            elif mode == "prefill":
                o, st = M.mamba_block(h, bp["mamba"], cfg, return_state=True)
                new_cache = dict(cache, **st)
            else:
                o = M.mamba_block(h, bp["mamba"], cfg)
        x = x + o
        h2 = L.rmsnorm(x, bp["ln2"], cfg.norm_eps)
        if ff == "moe":
            if mode == "decode":
                o2, aux = L.moe_decode(h2, bp["moe"], cfg, self._router_type())
            else:
                o2, aux = L.moe_ffn(h2, bp["moe"], cfg, self._router_type())
        else:
            o2 = L.ffn(h2, bp["ffn"])
        return x + o2, new_cache, aux

    def _router_type(self) -> str:
        return "sigmoid" if self.cfg.family == "mla_moe" else "softmax"

    # ------------------------------------------------------------------
    # Stacks
    # ------------------------------------------------------------------
    def _maybe_remat(self, fn):
        if self.cfg.remat:
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.nothing_saveable)
        return fn

    def _run_stack(self, params, x, mode, cache, pos, pages=None):
        """Run all blocks; returns (x, new_cache, aux_mean).  ``pages``
        (the paged KV pool's table) takes the paged stack."""
        cfg = self.cfg
        if pages is not None:
            return self._run_paged_stack(params, x, mode, cache, pos, pages)

        def scan_group(x, stacked, kinds_key, cache_g):
            """Scan homogeneous stacked blocks (cache as scan xs/ys)."""
            def body(carry, xs):
                bp, c = xs
                xx, nc, aux = self._apply_block(carry, bp, kinds_key,
                                                mode, c, pos)
                return xx, (nc, aux)

            body = self._maybe_remat(body) if mode == "train" else body
            x, (nc, aux) = jax.lax.scan(body, x, (stacked, cache_g))
            return x, nc, jnp.mean(aux)

        if cfg.family == "hybrid":
            period = cfg.hybrid.attn_period
            kinds = self._layer_kinds()[:period]

            def body(carry, xs):
                bp, c = xs
                xx = carry
                aux_sum = jnp.zeros((), jnp.float32)
                nc = {}
                for j in range(period):
                    key = f"l{j}_{kinds[j]}"
                    xx, nc[key], aux = self._apply_block(
                        xx, bp[key], kinds[j], mode, c[key], pos)
                    aux_sum += aux
                return xx, (nc, aux_sum / period)

            body = self._maybe_remat(body) if mode == "train" else body
            x, (new_cache, aux) = jax.lax.scan(
                body, x, (params["superblocks"], cache["superblocks"]))
            return x, {"superblocks": new_cache}, jnp.mean(aux)

        if cfg.family == "mla_moe":
            x, c_d, aux_d = scan_group(x, params["dense_blocks"], "mla_dense",
                                       cache["dense_blocks"])
            x, c_m, aux_m = scan_group(x, params["moe_blocks"], "mla_moe",
                                       cache["moe_blocks"])
            return x, {"dense_blocks": c_d, "moe_blocks": c_m}, aux_m

        kind = self._layer_kinds()[0]
        x, nc, aux = scan_group(x, params["blocks"], kind, cache["blocks"])
        return x, {"blocks": nc}, aux

    def _run_paged_stack(self, params, x, mode, cache, pos, pages):
        """The paged homogeneous stack: the stacked pools ride the layer
        loop as carry, and each layer writes its new rows into them at
        its own index.  No layer's pool is sliced out or stacked anew,
        so under a donated or loop-carried state the pools stay in one
        buffer, updated in place.  One page table serves every layer."""
        kind = self._layer_kinds()[0]

        def body(carry, xs):
            xx, c = carry
            bp, layer = xs
            xx, c, aux = self._apply_block(xx, bp, kind, mode, c, pos,
                                           pages, layer)
            return (xx, c), aux

        (x, nc), aux = jax.lax.scan(
            body, (x, cache["blocks"]),
            (params["blocks"], jnp.arange(self.cfg.n_layers, dtype=jnp.int32)))
        return x, {"blocks": nc}, jnp.mean(aux)

    # ------------------------------------------------------------------
    # Caches
    # ------------------------------------------------------------------
    def _block_cache_specs(self, kind, batch, max_len) -> dict:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        if kind.startswith("rwkv"):
            return R.rwkv_state_defs(cfg, batch)
        mix = kind.split("_")[0]
        if mix == "attn":
            kvh, dh = cfg.n_kv_heads, cfg.head_dim
            return {
                "k": jax.ShapeDtypeStruct((batch, max_len, kvh, dh), dt),
                "v": jax.ShapeDtypeStruct((batch, max_len, kvh, dh), dt),
            }
        if mix == "mla":
            m = cfg.mla
            return {
                "c_kv": jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dt),
                "k_rope": jax.ShapeDtypeStruct((batch, max_len, m.qk_rope_head_dim), dt),
            }
        return M.mamba_state_defs(cfg, batch)

    def cache_specs(self, batch: int, max_len: int,
                    per_slot_pos: bool = False):
        """ShapeDtypeStruct cache tree (stacked per scan group) + pos.

        ``per_slot_pos``: track one position per batch row — the slot
        layout of the continuous-batching engine, where rows sit at
        different sequence depths.
        """
        cfg = self.cfg

        def stack_specs(tree, n):
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)

        if cfg.family == "hybrid":
            period = cfg.hybrid.attn_period
            kinds = self._layer_kinds()[:period]
            grp = {f"l{j}_{kinds[j]}": self._block_cache_specs(
                kinds[j], batch, max_len) for j in range(period)}
            layers = {"superblocks": stack_specs(grp, cfg.n_layers // period)}
        elif cfg.family == "mla_moe":
            k = cfg.moe.first_k_dense
            layers = {
                "dense_blocks": stack_specs(
                    self._block_cache_specs("mla_dense", batch, max_len), k),
                "moe_blocks": stack_specs(
                    self._block_cache_specs("mla_moe", batch, max_len),
                    cfg.n_layers - k),
            }
        else:
            kind = self._layer_kinds()[0]
            layers = {"blocks": stack_specs(
                self._block_cache_specs(kind, batch, max_len), cfg.n_layers)}
        pos_shape = (batch,) if per_slot_pos else ()
        return {"layers": layers,
                "pos": jax.ShapeDtypeStruct(pos_shape, jnp.int32)}

    def init_cache(self, batch: int, max_len: int,
                   per_slot_pos: bool = False):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_specs(batch, max_len, per_slot_pos))

    def cache_pspecs(self, rules, per_slot_pos: bool = False):
        """PartitionSpecs matching cache_specs structure."""
        from repro.parallel.sharding import logical_pspec

        def spec_of(path: str, ndim: int):
            if path.endswith(("/k", "/v")):
                names = (None, "batch", "kv_seq", "kv_heads", None)
            elif path.endswith(("/c_kv", "/k_rope")):
                names = (None, "batch", "kv_seq", None)
            elif path.endswith("/S"):
                names = (None, "batch", "rwkv_heads", None, None)
            elif path.endswith("/h"):
                names = (None, "batch", "d_inner", None)
            elif path.endswith("/conv"):
                names = (None, "batch", None, "d_inner")
            elif path.endswith("pos"):
                return logical_pspec(("batch",)[:ndim], rules)
            else:
                names = (None, "batch") + (None,) * (ndim - 2)
            return logical_pspec(names[:ndim], rules)

        specs = self.cache_specs(1, 2, per_slot_pos)

        def walk(tree, prefix=""):
            if isinstance(tree, dict):
                return {k: walk(v, f"{prefix}/{k}") for k, v in tree.items()}
            return spec_of(prefix, len(tree.shape))

        return walk(specs)

    # -- paged KV cache (page pool + per-slot page tables) -------------
    def paged_cache_specs(self, batch: int, n_pages: int, page_size: int,
                          pages_per_slot: int):
        """ShapeDtypeStruct tree for the paged engine state: per-layer
        K/V page *pools* shared by all slots, stacked as (L, KVH, P,
        page_size, D), a per-slot ``pos`` vector, and the per-slot page
        table.  Only homogeneous dense-attention stacks are supported —
        paged decode needs random-access KV."""
        cfg = self.cfg
        kinds = set(self._layer_kinds())
        if cfg.family not in ("dense", "moe") or not all(
                k.startswith("attn_") for k in kinds):
            raise NotImplementedError(
                f"paged KV needs a homogeneous attention stack; family "
                f"{cfg.family!r} has layer kinds {sorted(kinds)}")
        dt = jnp.dtype(cfg.dtype)
        # KV-head-major, the layout the paged kernels stream
        pool = jax.ShapeDtypeStruct(
            (cfg.n_layers, cfg.n_kv_heads, n_pages, page_size,
             cfg.head_dim), dt)
        return {"layers": {"blocks": {"k": pool, "v": pool}},
                "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
                "pages": jax.ShapeDtypeStruct((batch, pages_per_slot),
                                              jnp.int32)}

    def paged_cache_pspecs(self, rules):
        """PartitionSpecs for ``paged_cache_specs``: pools partitioned
        by KV head (the TP split), table and positions replicated."""
        from repro.parallel.sharding import logical_pspec
        pool = logical_pspec((None, "kv_heads", None, None, None), rules)
        return {"layers": {"blocks": {"k": pool, "v": pool}},
                "pos": logical_pspec(("batch",), rules),
                "pages": logical_pspec(("batch", None), rules)}

    # ------------------------------------------------------------------
    # Embedding / head
    # ------------------------------------------------------------------
    def _embed_inputs(self, params, inputs, offset: int = 0):
        cfg = self.cfg
        tok = inputs["tokens"]
        x = jnp.take(params["embed"], tok, axis=0)
        if cfg.vlm is not None and "patch_embeds" in inputs:
            pe = inputs["patch_embeds"].astype(x.dtype) @ params["patch_proj"]
            x = jnp.concatenate([pe, x], axis=1)
        return shard(x, "batch", "seq_sp", "embed")

    def _logits(self, params, x):
        """f32 logits over the padded vocabulary; the pad columns are
        masked, so neither argmax nor sampling can emit a token id past
        ``vocab_size``."""
        x = L.rmsnorm(x, params["final_norm"], self.cfg.norm_eps)
        logits = (x @ params["lm_head"]).astype(jnp.float32)
        if self.vp > self.cfg.vocab_size:
            pad = jnp.arange(self.vp) >= self.cfg.vocab_size
            logits = jnp.where(pad, L.MASK_VALUE, logits)
        return shard(logits, "batch", "seq_sp", "vocab")

    # ------------------------------------------------------------------
    # Train / prefill / decode entry points
    # ------------------------------------------------------------------
    def train_loss(self, params, batch):
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        dummy_cache = self._dummy_cache_tree()
        x, _, aux = self._run_stack(params, x, "train", dummy_cache, None)
        logits = self._logits(params, x)
        labels = batch["labels"]
        weights = batch.get("loss_mask")
        if cfg.vlm is not None and "patch_embeds" in batch:
            n_p = batch["patch_embeds"].shape[1]
            logits = logits[:, n_p:]
        loss = _ce_loss(logits, labels, cfg.vocab_size, self.vp, weights)
        metrics = {"ce": loss, "aux": aux}
        if cfg.moe is not None:
            loss = loss + AUX_COEF * aux
        if cfg.mtp:
            mtp_loss = self._mtp_loss(params, x, batch)
            metrics["mtp"] = mtp_loss
            loss = loss + MTP_COEF * mtp_loss
        return loss, metrics

    def _mtp_loss(self, params, h, batch):
        """DeepSeek-V3 multi-token prediction: depth-1 MTP module."""
        cfg = self.cfg
        mp = params["mtp"]
        tok = batch["tokens"]
        # h_t combined with emb(tok_{t+1}) predicts label_{t+1} (= tok_{t+2})
        emb_next = jnp.take(params["embed"], jnp.roll(tok, -1, axis=1), axis=0)
        z = jnp.concatenate([L.rmsnorm(h, mp["norm_h"], cfg.norm_eps),
                             L.rmsnorm(emb_next, mp["norm_e"], cfg.norm_eps)],
                            axis=-1) @ mp["proj"]
        z = shard(z, "batch", "seq_sp", "embed")
        kind = "mla_dense" if cfg.mla is not None else "attn_dense"
        z, _, _ = self._apply_block(z, mp["block"], kind, "train", None, None)
        logits = self._logits(params, z)
        labels = jnp.roll(batch["labels"], -1, axis=1)
        w = jnp.ones_like(labels, jnp.float32).at[:, -2:].set(0.0)
        if "loss_mask" in batch:
            w = w * batch["loss_mask"]
        return _ce_loss(logits, labels, cfg.vocab_size, self.vp, w)

    def _dummy_cache_tree(self):
        """Zero-size per-layer cache placeholders for the train scan."""
        cfg = self.cfg
        if cfg.family == "hybrid":
            period = cfg.hybrid.attn_period
            kinds = self._layer_kinds()[:period]
            grp = {f"l{j}_{kinds[j]}": jnp.zeros((cfg.n_layers // period,),
                                                 jnp.float32)
                   for j in range(period)}
            return {"superblocks": grp}
        if cfg.family == "mla_moe":
            k = cfg.moe.first_k_dense
            return {"dense_blocks": jnp.zeros((k,), jnp.float32),
                    "moe_blocks": jnp.zeros((cfg.n_layers - k,), jnp.float32)}
        return {"blocks": jnp.zeros((cfg.n_layers,), jnp.float32)}

    def prefill(self, params, inputs, max_len: Optional[int] = None):
        x = self._embed_inputs(params, inputs)
        seq = x.shape[1]
        max_len = max_len or seq
        cache = self.init_cache(x.shape[0], max_len)
        x, layers, _ = self._run_stack(params, x, "prefill",
                                       cache["layers"], None)
        logits = self._logits(params, x[:, -1:])
        return logits, {"layers": layers,
                        "pos": jnp.asarray(seq, jnp.int32)}

    def verify_step(self, params, cache, tokens):
        """tokens: (B, T) -> logits (B, T, Vp), updated cache.

        The speculative-decoding verify path: T = k + 1 tokens per slot
        enter at per-slot positions ``[pos, pos + T)``; each writes its
        K/V at ``pos + t`` and attends causally within the window.
        ``cache["pos"]`` is returned *unchanged* — the engine advances
        it by each slot's accepted length, which is what rolls rejected
        tokens back in place (their cache rows sit beyond the advanced
        frontier and are overwritten by the next window write).
        """
        pos = cache["pos"]
        pages = cache.get("pages")
        x = self._embed_inputs(params, {"tokens": tokens})
        x, layers, _ = self._run_stack(params, x, "verify",
                                       cache["layers"], pos, pages)
        logits = self._logits(params, x)
        out = {"layers": layers, "pos": pos}
        if pages is not None:
            out["pages"] = pages
        return logits, out

    def decode_step(self, params, cache, tokens):
        """tokens: (B, 1) -> logits (B, 1, Vp), updated cache.

        ``cache["pos"]`` may be a scalar (fixed batch, every row at the
        same depth) or a per-slot (B,) vector (continuous batching);
        the attention/cache ops handle either rank.
        """
        pos = cache["pos"]
        pages = cache.get("pages")
        x = self._embed_inputs(params, {"tokens": tokens})
        x, layers, _ = self._run_stack(params, x, "decode",
                                       cache["layers"], pos, pages)
        logits = self._logits(params, x)
        out = {"layers": layers, "pos": pos + 1}
        if pages is not None:
            out["pages"] = pages
        return logits, out
