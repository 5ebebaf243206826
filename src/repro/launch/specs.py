"""Input specifications for every (architecture x shape) cell.

``input_specs`` returns weak-type-correct ``ShapeDtypeStruct`` stand-ins
for every model input — shardable, no device allocation — plus the
matching PartitionSpec trees.  ``build_cell`` assembles the jit-able
step function and its abstract arguments for one cell, ready for
``.lower().compile()`` against abstract arguments or for real execution.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import build_model
from repro.models.param import abstract_params
from repro.parallel.sharding import (ShardingRules, make_rules, param_pspecs,
                                     pspec_for, sharding_ctx)
from repro.train.train_step import (TrainHParams, TrainState, make_train_step,
                                    train_state_pspecs)
from repro.optim import OptState


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for the *data* inputs of one cell."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return {"tokens": _sds((b, 1), jnp.int32)}
    specs = {"tokens": _sds((b, s), jnp.int32)}
    if shape.kind == "train":
        specs["labels"] = _sds((b, s), jnp.int32)
    if cfg.vlm is not None:
        n_p = cfg.vlm.n_patches
        pe_d = cfg.vlm.patch_embed_dim or cfg.d_model
        specs["tokens"] = _sds((b, s - n_p), jnp.int32)
        if "labels" in specs:
            specs["labels"] = _sds((b, s - n_p), jnp.int32)
        specs["patch_embeds"] = _sds((b, n_p, pe_d), jnp.float32)
    if cfg.family == "encdec":
        specs["frames"] = _sds((b, cfg.encdec.enc_len, cfg.d_model),
                               jnp.float32)
    return specs


def input_pspecs(cfg: ModelConfig, shape: ShapeConfig,
                 rules: ShardingRules) -> dict:
    specs = input_specs(cfg, shape)
    out = {}
    for k, v in specs.items():
        if k in ("tokens", "labels"):
            names = ("batch", "seq_sp")
        elif k == "patch_embeds":
            names = ("batch", None, None)
        else:  # frames
            names = ("batch", "seq_sp", None)
        out[k] = pspec_for(v.shape, names, rules)
    return out


@dataclasses.dataclass
class Cell:
    """One lowered-able (arch x shape x mesh) benchmark cell."""

    cfg: ModelConfig
    shape: ShapeConfig
    mesh: Optional[Mesh]
    rules: ShardingRules
    fn: Callable                 # jit-able python callable
    abstract_args: tuple         # ShapeDtypeStruct pytrees
    in_shardings: Any
    out_shardings: Any
    donate_argnums: tuple = ()

    def jitted(self):
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)

    def lower(self):
        return self.jitted().lower(*self.abstract_args)


def _named(mesh, pspec_tree):
    if mesh is None:
        return None
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pspec_tree, is_leaf=lambda x: isinstance(x, P))


def build_cell(cfg: ModelConfig, shape: ShapeConfig,
               mesh: Optional[Mesh], *,
               hp: Optional[TrainHParams] = None) -> Cell:
    model = build_model(cfg)
    mode = {"train": "train", "prefill": "prefill",
            "decode": "decode"}[shape.kind]
    rules = make_rules(cfg, mesh, mode)
    data_specs = input_specs(cfg, shape)
    data_pspecs = input_pspecs(cfg, shape, rules)

    if shape.kind == "train":
        hp = hp or TrainHParams()
        step = make_train_step(model, hp, rules)
        with sharding_ctx(rules):
            params_abs = abstract_params(model.param_defs())
            state_ps = train_state_pspecs(model, rules, hp)
        opt_abs = OptState(
            _sds((), jnp.int32),
            jax.tree.map(lambda p: _sds(p.shape, jnp.float32), params_abs),
            jax.tree.map(lambda p: _sds(p.shape, jnp.float32), params_abs),
        ) if not hp.adamw.quant_moments else OptState(
            _sds((), jnp.int32),
            jax.tree.map(lambda p: _sds(p.shape, jnp.int8), params_abs),
            jax.tree.map(lambda p: _sds(p.shape, jnp.bfloat16), params_abs),
            jax.tree.map(lambda p: _sds(p.shape[:-1] + (1,), jnp.float32),
                         params_abs),
            None,
        )
        state_abs = TrainState(params_abs, opt_abs, _sds((), jnp.int32))
        metrics_sh = None
        return Cell(
            cfg, shape, mesh, rules, step,
            (state_abs, data_specs),
            in_shardings=(_named(mesh, state_ps),
                          _named(mesh, data_pspecs)),
            out_shardings=(_named(mesh, state_ps), metrics_sh),
            donate_argnums=(0,),
        )

    # serving cells
    with sharding_ctx(rules):
        params_abs = abstract_params(model.param_defs())
        params_ps = param_pspecs(model.param_defs(), rules)

    if shape.kind == "prefill":
        def prefill_fn(params, inputs):
            with sharding_ctx(rules):
                return model.prefill(params, inputs)

        return Cell(
            cfg, shape, mesh, rules, prefill_fn,
            (params_abs, data_specs),
            in_shardings=(_named(mesh, params_ps),
                          _named(mesh, data_pspecs)),
            out_shardings=None,
        )

    # decode: one token against a full-length cache
    cache_abs = model.cache_specs(shape.global_batch, shape.seq_len)
    with sharding_ctx(rules):
        cache_ps = model.cache_pspecs(rules)
    cache_ps = _fit_cache(cache_ps, cache_abs, mesh)

    def decode_fn(params, cache, tokens):
        with sharding_ctx(rules):
            return model.decode_step(params, cache, tokens)

    return Cell(
        cfg, shape, mesh, rules, decode_fn,
        (params_abs, cache_abs, data_specs["tokens"]),
        in_shardings=(_named(mesh, params_ps), _named(mesh, cache_ps),
                      _named(mesh, data_pspecs["tokens"])),
        out_shardings=None,
        donate_argnums=(1,),
    )


def _fit_cache(cache_ps, cache_abs, mesh):
    """Validate cache pspecs against concrete cache shapes."""
    if mesh is None:
        return cache_ps
    from repro.parallel.sharding import _fit_spec

    def fit(ps, ab):
        return _fit_spec(ps, ab.shape, mesh)

    return jax.tree.map(fit, cache_ps, cache_abs,
                        is_leaf=lambda x: isinstance(x, P))
