"""Config registry: ``get_config("qwen3-1.7b")`` and reduced smoke configs."""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import (  # noqa: F401
    EncDecConfig, HybridConfig, MLAConfig, MambaConfig, MoEConfig,
    ModelConfig, ShapeConfig, SHAPES, VLMConfig,
)

_ARCH_MODULES = {
    "granite-3-2b": "granite_3_2b",
    "qwen2.5-3b": "qwen2_5_3b",
    "qwen3-1.7b": "qwen3_1_7b",
    "yi-9b": "yi_9b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "phi-3-vision-4.2b": "phi_3_vision_4_2b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "rwkv6-3b": "rwkv6_3b",
    "whisper-small": "whisper_small",
    "tiny-kws": "tiny_kws",
    "edge-vit": "edge_vit",
}

# The ten assigned LM architectures (tiny/edge are paper-own extras).
ASSIGNED_ARCHS = [
    "granite-3-2b", "qwen2.5-3b", "qwen3-1.7b", "yi-9b",
    "qwen3-moe-30b-a3b", "deepseek-v3-671b", "phi-3-vision-4.2b",
    "jamba-v0.1-52b", "rwkv6-3b", "whisper-small",
]


def list_archs() -> list[str]:
    return list(_ARCH_MODULES)


def get_config(name: str, **overrides) -> ModelConfig:
    key = name.replace("_", "-") if name not in _ARCH_MODULES else name
    if key not in _ARCH_MODULES:
        # allow module-style names like qwen3_1_7b
        for arch, mod in _ARCH_MODULES.items():
            if mod == name:
                key = arch
                break
        else:
            raise KeyError(f"unknown arch {name!r}; have {list(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[key]}")
    cfg = mod.CONFIG
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def for_platform(cfg: ModelConfig) -> ModelConfig:
    """``cfg`` with its attention kernels chosen by the backend JAX runs on.

    On a TPU, decode, verify and paged attention run the compiled Pallas
    kernels, and asking for the interpreter there is an error.  Elsewhere
    they take the jnp path, or the Pallas interpreter when the config
    asks for it (``pallas_interpret``, the CPU parity tests).  Entry
    points call this once, where they build the config.
    """
    import jax

    if jax.default_backend() == "tpu":
        if cfg.pallas_interpret:
            raise ValueError(
                f"{cfg.name}: pallas_interpret=True on a TPU would run the "
                f"Pallas interpreter on the chip; the compiled kernels "
                f"are the TPU path")
        return dataclasses.replace(cfg, use_pallas=True)
    return dataclasses.replace(cfg, use_pallas=cfg.pallas_interpret)


def reduce_config(cfg: ModelConfig) -> ModelConfig:
    """Shrink a config to smoke-test scale, preserving the family.

    Small layers/width, few experts, tiny vocab — same code paths.
    """
    changes: dict = dict(
        n_layers=min(cfg.n_layers, 4),
        d_model=128,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2),
        d_head=32,
        d_ff=256,
        vocab_size=512,
        dtype="float32",
        remat=False,
    )
    if cfg.family == "rwkv":
        changes.update(n_heads=4, n_kv_heads=4, d_head=32)
    if cfg.moe is not None:
        changes["moe"] = dataclasses.replace(
            cfg.moe,
            n_experts=min(cfg.moe.n_experts, 8),
            top_k=min(cfg.moe.top_k, 2),
            d_expert=128,
            first_k_dense=min(cfg.moe.first_k_dense, 1),
            d_ff_dense=256 if cfg.moe.d_ff_dense else None,
        )
    if cfg.mla is not None:
        changes["mla"] = MLAConfig(q_lora_rank=64, kv_lora_rank=32,
                                   qk_nope_head_dim=32, qk_rope_head_dim=16,
                                   v_head_dim=32)
    if cfg.hybrid is not None:
        changes.update(n_layers=8)  # one full Jamba period
    if cfg.encdec is not None:
        changes["encdec"] = dataclasses.replace(cfg.encdec, enc_layers=2,
                                                enc_len=64)
        changes["n_layers"] = 2
    if cfg.vlm is not None:
        changes["vlm"] = dataclasses.replace(cfg.vlm, n_patches=16)
    if cfg.family == "tiny":
        return cfg  # already tiny
    return dataclasses.replace(cfg, **changes)
