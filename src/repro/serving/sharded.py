"""Tensor-parallel continuous batching: the engine sharded over a mesh.

``ShardedContinuousBatchingEngine`` runs the exact slot-based engine of
``repro.serving.engine`` with its jitted bodies wrapped in ``shard_map``
over a 1-D tensor-parallel mesh (Megatron layout):

- attention heads and FFN width are column/row-split over the TP axis
  (weight in_specs derived from the model's own ``ParamDef`` tree via
  ``make_tp_rules`` — no second source of truth for the layout);
- each shard owns its KV heads' slice of the KV cache — its own
  partition of every slot's cache rows — while the per-slot ``pos``
  vector, sampled tokens and remaining-budget vector are replicated, so
  ragged multi-slot decode still runs as one fused call per shard (the
  Pallas decode kernel / its jnp analogue just sees a smaller BH);
- the model body inside the shard is the *same* LM code built from a
  per-shard config (``tp_local_config``: heads and d_ff divided by tp),
  with ``tp_psum`` completing each row-parallel projection; embeddings
  and the LM head stay replicated so every shard argmaxes the full
  logits and sampling needs no gather.

Host orchestration (admission queue, chunked decode, TTFT stamps) is
inherited unchanged — one engine, two execution layouts.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel.sharding import (logical_pspec, make_tp_rules,
                                     param_pspecs, tp_ctx,
                                     tp_local_config)
from repro.serving.engine import ContinuousBatchingEngine


def replicate_kv_heads(model, params, tp: int):
    """GQA with fewer KV heads than shards: duplicate each KV head
    ``tp / n_kv_heads`` times so every shard owns exactly one copy.

    Repeating KV heads (and regrouping queries accordingly) computes
    bit-identical attention — each query head still sees its original
    K/V rows — so parity with the unsharded engine is preserved; the
    cost is the duplicated KV-cache rows, the standard GQA trade under
    tensor parallelism.  Returns the equivalent ``(model, params)``
    with ``n_kv_heads == tp``.
    """
    cfg = model.cfg
    kvh, dh = cfg.n_kv_heads, cfg.head_dim
    if tp % kvh != 0:
        raise ValueError(f"{cfg.name}: n_kv_heads={kvh} neither divides "
                         f"nor is divided by tp={tp}")
    r = tp // kvh

    def expand(w):
        """Repeat the per-KV-head blocks of a trailing (kvh*dh) dim."""
        lead = w.shape[:-1]
        w = w.reshape(lead + (kvh, dh))
        return jnp.repeat(w, r, axis=-2).reshape(lead + (kvh * r * dh,))

    blocks = dict(params["blocks"])
    attn = dict(blocks["attn"])
    for name in ("wk", "wv", "bk", "bv"):
        if name in attn:
            attn[name] = expand(attn[name])
    blocks["attn"] = attn
    params = dict(params, blocks=blocks)
    cfg2 = dataclasses.replace(cfg, n_kv_heads=tp, d_head=dh)
    return type(model)(cfg2), params


def _needs_kv_replication(cfg, tp: int) -> bool:
    return tp > 1 and cfg.family == "dense" and cfg.n_kv_heads % tp != 0


def tp_param_shardings(model, mesh: Mesh, axis: str = "model"):
    """``NamedSharding`` tree of ``model``'s parameters in the
    tensor-parallel engine's layout — pass it to ``init_params`` to
    create a model that does not fit one device straight into its
    shards.  (With fewer KV heads than shards the engine repeats the
    K/V weights after creation; they are placed by the repeated
    layout's rules here, and re-placed by the engine.)"""
    cfg = model.cfg
    tp = mesh.shape[axis]
    if _needs_kv_replication(cfg, tp):
        cfg = dataclasses.replace(cfg, n_kv_heads=tp, d_head=cfg.head_dim)
    specs = param_pspecs(model.param_defs(), make_tp_rules(cfg, mesh, axis))
    return jax.tree.map(lambda p: NamedSharding(mesh, p), specs)


class ShardedContinuousBatchingEngine(ContinuousBatchingEngine):
    """``ContinuousBatchingEngine`` partitioned ``tp`` ways.

    Usage (4 virtual host devices on CPU)::

        eng = ShardedContinuousBatchingEngine(model, params, tp=4,
                                              max_len=96, n_slots=4)
        done = eng.serve(requests)       # same contract as the base

    ``tp=1`` degenerates to a 1-device mesh and is token-identical to
    the unsharded engine (the parity gate CI runs on virtual devices).

    Parameters are placed in their shardings on entry (a no-op for
    params created there by ``init_params(..., tp_param_shardings(...))``,
    which is how a model larger than one device is built), and the KV
    cache is created straight into its shards.
    """

    def __init__(self, model, params, *, tp: Optional[int] = None,
                 mesh: Optional[Mesh] = None, axis: str = "model", **kw):
        from repro.launch.mesh import make_tp_mesh

        if mesh is None:
            mesh = make_tp_mesh(tp or len(jax.devices()), axis)
        self.mesh = mesh
        self.tp_axis = axis
        self.tp = mesh.shape[axis]
        cfg = model.cfg
        if _needs_kv_replication(cfg, self.tp):
            model, params = replicate_kv_heads(model, params, self.tp)
            cfg = model.cfg
        local_cfg = tp_local_config(cfg, self.tp)
        rules = make_tp_rules(cfg, mesh, axis)
        self._param_specs = param_pspecs(model.param_defs(), rules)
        params = jax.device_put(params, jax.tree.map(
            lambda p: NamedSharding(mesh, p), self._param_specs))
        # paged mode (kw is parsed by super().__init__, but the cache
        # specs must exist first): the page pool partitions by KV head
        # exactly like the contiguous cache; page tables and positions
        # are replicated host-managed indices
        if kw.get("kv_page_size"):
            self._cache_specs = model.paged_cache_pspecs(rules)
            # handed-off blocks (L, NB, page, kvh, dh) split on their
            # own KV-head axis, like the pool pages they are written to
            blk = logical_pspec((None, None, None, "kv_heads", None), rules)
            self._block_specs = {"blocks": {"k": blk, "v": blk}}
        else:
            self._cache_specs = model.cache_pspecs(rules,
                                                   per_slot_pos=True)
        if kw.get("rules") is not None:
            raise ValueError("ShardedContinuousBatchingEngine manages its "
                             "own sharding; rules must be None")
        super().__init__(model, params, **kw)
        # the shard-local body traces through the per-shard model; the
        # global ``self.model`` keeps defining the (full) cache layout.
        # The draft (if any) stays replicated: ``draft_compute_model``
        # is the full draft, run per shard outside the TP context.
        self.compute_model = type(model)(local_cfg)
        # draft weights are replicated onto every shard; so is the
        # draft cache / sampling key — everything in the state except
        # the target cache, whose specs partition it by KV head
        self._dparam_specs = jax.tree.map(lambda _: P(),
                                          self.draft_params)
        self._state_specs = dict(
            jax.tree.map(lambda _: P(), self.state),
            cache=self._cache_specs)

    def reset(self):
        super().reset()
        # the rest of the state replicated over the mesh, as the jitted
        # bodies return it: a fresh state then hits the same compiled
        # programs as a used one
        rep = NamedSharding(self.mesh, P())
        self.state = {k: v if k == "cache" else jax.device_put(v, rep)
                      for k, v in self.state.items()}

    def _zeros(self, specs):
        return jax.tree.map(
            lambda s, p: jnp.zeros(s.shape, s.dtype,
                                   device=NamedSharding(self.mesh, p)),
            specs, self._cache_specs)

    def _shard_mapped(self, base_impl, in_specs, out_specs):
        """Wrap a base engine body in shard_map: params and cache enter
        partitioned (weights by head/FFN column, cache by KV head),
        scalars/tokens/draft state replicated; outputs are
        device-invariant by construction (every row-parallel projection
        ends in a psum; the draft model runs fully replicated)."""

        def local_fn(*args):
            with tp_ctx(self.tp_axis):
                return base_impl(*args)

        return jax.shard_map(local_fn, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _prefill_slot_impl(self, params, dparams, state, tokens, slot,
                           budget, pages=None):
        base = super()._prefill_slot_impl
        extra = () if pages is None else (pages,)
        return self._shard_mapped(
            base,
            in_specs=(self._param_specs, self._dparam_specs,
                      self._state_specs) + (P(),) * (3 + len(extra)),
            out_specs=(self._state_specs, P()),
        )(params, dparams, state, tokens, slot, budget, *extra)

    def _extend_slot_impl(self, params, dparams, state, tokens, suffix,
                          slot, pages, start, budget):
        base = super()._extend_slot_impl
        return self._shard_mapped(
            base,
            in_specs=(self._param_specs, self._dparam_specs,
                      self._state_specs) + (P(),) * 6,
            out_specs=(self._state_specs, P()),
        )(params, dparams, state, tokens, suffix, slot, pages, start,
          budget)

    def _prefill_chunk_impl(self, params, state, chunk, pages, start):
        base = super()._prefill_chunk_impl
        return self._shard_mapped(
            base,
            in_specs=(self._param_specs, self._state_specs)
            + (P(),) * 3,
            out_specs=self._state_specs,
        )(params, state, chunk, pages, start)

    def _install_slot_impl(self, state, blocks, tok0, slot, pages,
                           row, n_tokens, budget):
        # handed-off K/V blocks partition by KV head exactly like the
        # pool leaves they scatter into
        base = super()._install_slot_impl
        return self._shard_mapped(
            base,
            in_specs=(self._state_specs, self._block_specs)
            + (P(),) * 6,
            out_specs=self._state_specs,
        )(state, blocks, tok0, slot, pages, row, n_tokens, budget)

    def _decode_chunk_impl(self, params, state):
        base = super()._decode_chunk_impl
        return self._shard_mapped(
            base,
            in_specs=(self._param_specs, self._state_specs),
            out_specs=(self._state_specs, P()),
        )(params, state)

    def _spec_chunk_impl(self, params, dparams, state):
        base = super()._spec_chunk_impl
        return self._shard_mapped(
            base,
            in_specs=(self._param_specs, self._dparam_specs,
                      self._state_specs),
            out_specs=(self._state_specs, P()),
        )(params, dparams, state)

    def _step_logits_impl(self, params, state):
        # every shard holds the full (replicated) logits
        base = super()._step_logits_impl
        return self._shard_mapped(
            base,
            in_specs=(self._param_specs, self._state_specs),
            out_specs=P(),
        )(params, state)
