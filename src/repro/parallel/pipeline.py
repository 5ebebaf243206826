"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

For models too deep/large for pure DP x TP, layers are partitioned into
S stages; microbatches stream through with collective_permute moving
activations stage-to-stage.  The schedule is the classic GPipe fill /
steady / drain loop: T = M + S - 1 ticks for M microbatches, bubble
fraction (S-1)/(M+S-1).

Implementation: ``shard_map`` over the ``stage`` axis.  Every device
executes the same tick loop; at tick t it runs its stage on microbatch
(t - stage_id) when valid, then permutes its output to stage+1.  The
layers are stacked (S, L/S, ...) so each stage reads its slab.

This is the optional alternative to the production DP x TP(+EP) mesh
and is exercised by a real multi-device subprocess test.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, mesh: Mesh, n_stages: int,
                     n_micro: int):
    """Build a pipelined forward.

    ``stage_fn(stage_params, x) -> x`` runs one stage's layers on one
    microbatch.  Returns ``f(stacked_params, x_micro)`` where
    ``stacked_params`` has leading dim S (sharded over 'stage') and
    ``x_micro`` is (M, mb, ...) microbatched input (replicated).
    """

    def local(params_local, x_micro):
        # params_local: (1, ...) this stage's slab; x_micro: (M, mb, d)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index("stage")
        m, mb = x_micro.shape[0], x_micro.shape[1]
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros((n_micro,) + x_micro.shape[1:], x_micro.dtype)

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 injects microbatch t; others take the permuted input
            mb_id = t - stage
            take_new = (stage == 0)
            x_in = jnp.where(
                take_new,
                x_micro[jnp.clip(t, 0, n_micro - 1)],
                inflight)
            active = (mb_id >= 0) & (mb_id < n_micro)
            y = stage_fn(params_local, x_in)
            y = jnp.where(active, y, x_in)
            # last stage banks its result; others forward it
            outputs = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda o: o.at[jnp.clip(mb_id, 0, n_micro - 1)].set(y),
                lambda o: o,
                outputs)
            nxt = jax.lax.ppermute(
                y, "stage",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (jnp.zeros_like(x_micro[0]), buf), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them so the
        # result is replicated (psum over one-hot contribution)
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * mask, "stage")
        return outputs

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("stage"), P()),
        out_specs=P(),
        check_vma=False)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def split_microbatches(batch: jax.Array, n_micro: int) -> jax.Array:
    b = batch.shape[0]
    assert b % n_micro == 0
    return batch.reshape(n_micro, b // n_micro, *batch.shape[1:])
