"""Seeded weights of a dense GQA decoder, made on the device.

Every leaf of every layer has its own key, folded from the run's seed,
the leaf's index and the layer, and its values are exact functions of
the key's random bits: a 25-bit integer ``m`` per element, times one
constant, rounded once to the served type.  So the program's stacked
tree, made in one jitted call (sharded or not), and the reference's
layer-by-layer copy hold the same numbers bit for bit.

Distributions: uniform with the standard deviation of the usual
initialisers (``1/sqrt(fan_in)`` for input projections, divided by a
further ``sqrt(2 * layers)`` for the projections back into the
residual stream, 0.02 for the embedding), and norm weights uniform in
``[0.75, 1.25)`` so that a norm wired to the wrong place shows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> index folded into its key; the names are the reference's
GLOBAL_LEAVES = ("embed", "final_norm", "lm_head")
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                "ffn_norm", "w_gate", "w_up", "w_down")
_INDEX = {n: i for i, n in enumerate(GLOBAL_LEAVES + LAYER_LEAVES)}


def base_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative seed (64 bits and more)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def shapes(cfg: dict) -> dict:
    """Shape of each leaf (per layer for the layer leaves)."""
    d, h, kvh = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    dh, f, v = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {"embed": (v, d), "final_norm": (d,), "lm_head": (d, v),
           "attn_norm": (d,), "wq": (d, h * dh), "wk": (d, kvh * dh),
           "wv": (d, kvh * dh), "wo": (h * dh, d), "ffn_norm": (d,),
           "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    if cfg["qk_norm"]:
        out.update(q_norm=(dh,), k_norm=(dh,))
    return out


def _std(name: str, shape: tuple, cfg: dict) -> tuple[float, float]:
    """(centre, half-width) of the leaf's uniform distribution."""
    if name.endswith("norm"):
        return 1.0, 0.25
    if name == "embed":
        return 0.0, 0.02 * math.sqrt(3)
    std = 1.0 / math.sqrt(shape[0])
    if name in ("wo", "w_down"):
        std /= math.sqrt(2 * cfg["num_hidden_layers"])
    return 0.0, std * math.sqrt(3)


def _leaf(key, name: str, shape: tuple, cfg: dict, dtype):
    centre, half = _std(name, shape, cfg)
    bits = jax.random.bits(key, shape, jnp.uint32)
    m = (bits >> 7).astype(jnp.int32) - (1 << 24)       # [-2^24, 2^24)
    x = m.astype(jnp.float32) * jnp.float32(half / (1 << 24))
    if centre:
        x = x + jnp.float32(centre)
    return x.astype(dtype)


def global_leaf(seed_key, name: str, cfg: dict, dtype):
    shape = shapes(cfg)[name]
    return _leaf(jax.random.fold_in(seed_key, _INDEX[name]), name, shape,
                 cfg, dtype)


def layer_leaf(seed_key, name: str, layer, cfg: dict, dtype):
    shape = shapes(cfg)[name]
    key = jax.random.fold_in(jax.random.fold_in(seed_key, _INDEX[name]),
                             layer)
    return _leaf(key, name, shape, cfg, dtype)


def stacked_leaf(seed_key, name: str, cfg: dict, dtype):
    """(layers,) + shape: every layer's copy of ``name``."""
    layers = jnp.arange(cfg["num_hidden_layers"])
    return jax.vmap(lambda lyr: layer_leaf(seed_key, name, lyr, cfg,
                                           dtype))(layers)


def layer_names(cfg: dict) -> list[str]:
    return [n for n in LAYER_LEAVES if n in shapes(cfg)]
