"""Distributed train step: value_and_grad + clip + fused AdamW, with
optional gradient-accumulation microbatching.

The same ``make_train_step`` product is used by the real CPU training
examples, the compile-only cells of ``repro.launch.specs`` (lowered
against ShapeDtypeStructs) and the benchmarks; sharding comes from the ParamDef tree + logical rules.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.optim import AdamWConfig, OptState, adamw_init, adamw_update
from repro.optim.schedule import warmup_cosine
from repro.parallel.sharding import (ShardingRules, logical_pspec,
                                     param_pspecs, sharding_ctx)


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    step: jax.Array


@dataclasses.dataclass(frozen=True)
class TrainHParams:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    microbatches: int = 1
    adamw: AdamWConfig = AdamWConfig()


def init_train_state(model, key, hp: TrainHParams) -> TrainState:
    from repro.models.param import init_params
    params = init_params(model.param_defs(), key)
    return TrainState(params, adamw_init(params, hp.adamw),
                      jnp.zeros((), jnp.int32))


def _split_micro(batch, n):
    def sp(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape(n, b // n, *x.shape[1:])

    return jax.tree.map(sp, batch)


def make_train_step(model, hp: TrainHParams,
                    rules: Optional[ShardingRules] = None):
    """Returns step(state, batch) -> (state, metrics)."""

    def loss_fn(params, micro):
        with sharding_ctx(rules):
            return model.train_loss(params, micro)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        if hp.microbatches > 1:
            micros = _split_micro(batch, hp.microbatches)

            def acc(carry, micro):
                gsum, lsum = carry
                (loss, _), g = grad_fn(state.params, micro)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, lsum + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (gsum, lsum), _ = jax.lax.scan(
                acc, (zeros, jnp.zeros(())), micros)
            grads = jax.tree.map(lambda g: g / hp.microbatches, gsum)
            loss = lsum / hp.microbatches
            metrics = {"ce": loss}
        else:
            (loss, metrics), grads = grad_fn(state.params, batch)

        lr = warmup_cosine(state.step, peak_lr=hp.peak_lr,
                           warmup=hp.warmup, total=hp.total_steps)
        params, opt, gnorm = adamw_update(state.params, grads, state.opt,
                                          lr, hp.adamw)
        out_metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        for k, v in metrics.items():
            out_metrics[k] = v
        return TrainState(params, opt, state.step + 1), out_metrics

    return step


def train_state_pspecs(model, rules: ShardingRules, hp: TrainHParams):
    """PartitionSpec tree matching init_train_state's output."""
    with sharding_ctx(rules):
        pspecs = param_pspecs(model.param_defs(), rules)
        scalar = logical_pspec((), rules)

        from jax.sharding import PartitionSpec as P

        def scale_spec(ps):
            # per-row scales: size-1 last dim cannot stay sharded
            if len(ps) == 0:
                return ps
            return P(*ps[:-1], None)

        if hp.adamw.quant_moments:
            opt = OptState(scalar, pspecs, pspecs,
                           jax.tree.map(scale_spec, pspecs), None)
        else:
            opt = OptState(scalar, pspecs, pspecs)
        return TrainState(pspecs, opt, scalar)
