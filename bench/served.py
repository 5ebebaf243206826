"""The system under test, as the benchmark drives it.

The window drives the program's own entry: ``PowerRun`` in its queue
form (``TraceServer`` with the benchmark's arrival schedule) over a
``ContinuousBatchingSUT`` and ``ContinuousBatchingEngine.serve`` on one
chip, or a ``ShardedSUT`` and ``ShardedContinuousBatchingEngine.serve``
at tensor parallelism over the cell's chips.  This module maps the
configuration file onto the program's config, places the benchmark's
seeded weights into the program's parameter tree, and builds the
engine with the settings the configuration file states.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W


def program_config(cfg: dict, *, pallas_interpret: bool = False):
    """The program's ``ModelConfig`` for a configuration file, with the
    attention kernels the backend runs (compiled Pallas on a TPU)."""
    from repro.configs import ModelConfig, for_platform

    mc = ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"], qk_norm=cfg["qk_norm"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], pallas_interpret=pallas_interpret)
    return for_platform(mc)


def _dtype(cfg: dict):
    return jnp.dtype(cfg["torch_dtype"])


def program_params(cfg: dict, model, seed: int, shardings=None):
    """The benchmark's weights for ``seed`` in the program's parameter
    tree, made on the device in one jitted call (into ``shardings``
    where given).  The padded vocabulary rows and columns the program
    adds are zero; its logits mask those columns."""
    from repro.models.param import abstract_params

    dt = _dtype(cfg)
    vp, v = model.vp, cfg["vocab_size"]

    def build(key):
        def stacked(name):
            return W.stacked_leaf(key, name, cfg, dt)

        attn = {n: stacked(n) for n in ("wq", "wk", "wv", "wo")}
        if cfg["qk_norm"]:
            attn.update(q_norm=stacked("q_norm"), k_norm=stacked("k_norm"))
        embed = W.global_leaf(key, "embed", cfg, dt)
        head = W.global_leaf(key, "lm_head", cfg, dt)
        return {
            "embed": jnp.pad(embed, ((0, vp - v), (0, 0))),
            "final_norm": W.global_leaf(key, "final_norm", cfg, dt),
            "lm_head": jnp.pad(head, ((0, 0), (0, vp - v))),
            "blocks": {
                "ln1": stacked("attn_norm"), "ln2": stacked("ffn_norm"),
                "attn": attn,
                "ffn": {n: stacked(n) for n in ("w_gate", "w_up",
                                                 "w_down")},
            },
        }

    want = abstract_params(model.param_defs())
    got = jax.eval_shape(build, W.base_key(seed))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{cfg['name']}: the benchmark's weights do not "
                         f"match the program's parameter tree")
    return jax.jit(build, out_shardings=shardings)(W.base_key(seed))


def build_engine(cfg: dict, seed: int, devices: list, *,
                 pallas_interpret: bool = False):
    """(program config, engine) for the configuration file, its weights
    made from ``seed``: one chip, or tensor parallelism over
    ``devices``."""
    from repro.models import build_model
    from repro.serving import (ContinuousBatchingEngine,
                               ShardedContinuousBatchingEngine,
                               tp_param_shardings)

    mc = program_config(cfg, pallas_interpret=pallas_interpret)
    model = build_model(mc)
    e = cfg["engine"]
    kw = dict(n_slots=e["n_slots"], max_len=e["max_len"],
              kv_page_size=e["kv_page_size"], chunk_steps=e["chunk_steps"],
              prefill_chunk_tokens=e["prefill_chunk_tokens"],
              prefix_caching=e["prefix_caching"], spec_k=e["spec_k"])
    if len(devices) == 1:
        with jax.default_device(devices[0]):
            params = program_params(cfg, model, seed)
            engine = ContinuousBatchingEngine(model, params, **kw)
        return mc, engine
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devices), ("model",))
    params = program_params(cfg, model, seed,
                            tp_param_shardings(model, mesh))
    return mc, ShardedContinuousBatchingEngine(model, params, mesh=mesh,
                                               **kw)


class WindowClock:
    """``time.monotonic`` that remembers its first reading: the engine
    reads it once as its serve clock's zero, which is when the first
    request is due."""

    def __init__(self):
        self.t0 = None

    def __call__(self) -> float:
        t = time.monotonic()
        if self.t0 is None:
            self.t0 = t
        return t


def make_sut(engine, mc, plan, system=None, on_serve=None):
    """The program's SUT adapter for ``engine``, building each request
    from the plan; ``on_serve(clock, serve)`` wraps the engine call
    (the window)."""
    from repro.harness import ContinuousBatchingSUT, ShardedSUT
    from repro.serving import Request

    by_qid = {p.qid: p for p in plan}
    base = ShardedSUT if hasattr(engine, "tp") else ContinuousBatchingSUT

    @dataclasses.dataclass
    class Window:
        clock: WindowClock
        t_end: float = 0.0

    class BenchSUT(base):
        window: Window = None

        def serve_queue(self, arrivals):
            reqs = [self.make_request(i, s, a)
                    for i, (s, a) in enumerate(arrivals)]
            clock = WindowClock()

            def serve():
                return self.engine.serve(reqs, now=clock)

            self.completed = on_serve(serve) if on_serve else serve()
            self.window = Window(clock, time.monotonic())
            return self.completed

    def make_request(i, sample, arrival_s):
        p = by_qid[sample["qid"]]
        return Request(rid=p.qid, prompt=p.prompt,
                       max_new_tokens=p.max_new_tokens,
                       arrival_s=arrival_s)

    kw = {} if system is None else {"system": system}
    return BenchSUT(engine, mc, name=f"{mc.name}-bench",
                    make_request=make_request, **kw)
