"""Logical-axis sharding rules (DP / TP / EP / SP) for the production mesh.

Models annotate tensors with *logical* axis names; ``make_rules`` maps
them onto the physical mesh axes ``(pod, data, model)`` with per-config
divisibility fallbacks.  Outside a sharding context every annotation is
a no-op, so the same model code runs single-device smoke tests and
multi-device meshes unchanged.

Logical axes
------------
- ``batch``     data parallelism over ``(pod, data)``
- ``seq_sp``    Megatron-style sequence parallelism (norm/FFN regions)
- ``kv_seq``    sequence-sharded KV cache / flash-decoding split-KV
- ``heads``     tensor parallelism over attention heads
- ``d_ff`` / ``d_inner``  tensor parallelism over FFN / Mamba channels
- ``experts``   expert parallelism (training: model axis)
- ``experts_big``  expert parallelism over the whole mesh (decode EP)
- ``vocab``     vocab-parallel embedding / lm head / cross-entropy
- ``fsdp``      ZeRO-3 style weight sharding over the data axes
- ``stage``     pipeline stage (only on pipeline meshes)
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, tuple[str, ...]]


@dataclasses.dataclass
class ShardingRules:
    mesh: Optional[Mesh]
    table: dict[str, Axis]
    mode: str = "train"

    def axis_size(self, logical: str) -> int:
        ax = self.table.get(logical)
        if ax is None or self.mesh is None:
            return 1
        axes = (ax,) if isinstance(ax, str) else ax
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n


_local = threading.local()


def current_rules() -> Optional[ShardingRules]:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def sharding_ctx(rules: Optional[ShardingRules]):
    prev = getattr(_local, "rules", None)
    _local.rules = rules
    try:
        yield rules
    finally:
        _local.rules = prev


def _dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _size(mesh: Mesh, axes: Axis) -> int:
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def expert_axes(rules: Optional[ShardingRules]) -> Optional[Axis]:
    """The mesh axes experts are sharded over (for shard_map collectives)."""
    if rules is None or rules.mesh is None:
        return None
    return rules.table.get("experts")


# ----------------------------------------------------------------------
# Tensor-parallel shard_map context (serving)
# ----------------------------------------------------------------------
# The GSPMD path above annotates tensors and lets the compiler insert
# collectives.  The sharded serving engine instead runs the model body
# *inside* ``shard_map`` with per-shard weights (Megatron layout:
# attention heads and FFN width column/row-split over one mesh axis),
# which needs explicit ``psum`` after every row-parallel projection.
# ``tp_ctx`` names the mapped axis for the duration of a trace;
# ``tp_psum`` is the reduction hook the layers call — a no-op outside
# the context, so single-device code is untouched.

@contextlib.contextmanager
def tp_ctx(axis: Optional[str]):
    prev = getattr(_local, "tp_axis", None)
    _local.tp_axis = axis
    try:
        yield axis
    finally:
        _local.tp_axis = prev


def tp_axis_name() -> Optional[str]:
    return getattr(_local, "tp_axis", None)


def tp_psum(x: jax.Array) -> jax.Array:
    """Sum partial row-parallel outputs over the tensor-parallel axis;
    identity when no ``tp_ctx`` is active (single-device / GSPMD)."""
    ax = tp_axis_name()
    if ax is None:
        return x
    return jax.lax.psum(x, ax)


def tp_local_config(cfg, tp: int):
    """The per-shard view of ``cfg`` under ``tp``-way tensor parallelism.

    Attention heads, KV heads and FFN width divide by ``tp``; everything
    a shard computes locally (embeddings, norms, lm head) is unchanged.
    ``d_head`` is pinned explicitly because the derived default
    ``d_model // n_heads`` would change when ``n_heads`` shrinks.
    """
    import dataclasses as _dc

    if tp == 1:
        return cfg
    unsupported = [n for n, v in (("moe", cfg.moe), ("mla", cfg.mla),
                                  ("mamba", cfg.mamba),
                                  ("hybrid", cfg.hybrid),
                                  ("encdec", cfg.encdec))
                   if v is not None]
    if cfg.family != "dense" or unsupported:
        raise ValueError(
            f"tensor-parallel serving supports dense GQA models; "
            f"{cfg.name} is family={cfg.family} ({unsupported})")
    for dim, val in (("n_heads", cfg.n_heads),
                     ("n_kv_heads", cfg.n_kv_heads), ("d_ff", cfg.d_ff)):
        if val % tp != 0:
            raise ValueError(f"{cfg.name}: {dim}={val} not divisible by "
                             f"tp={tp}")
    return _dc.replace(cfg, n_heads=cfg.n_heads // tp,
                       n_kv_heads=cfg.n_kv_heads // tp,
                       d_ff=cfg.d_ff // tp, d_head=cfg.head_dim)


def make_tp_rules(cfg, mesh: Mesh, axis: str = "model") -> ShardingRules:
    """Rules describing the Megatron weight layout for the TP engine.

    Built from the decode-mode table, then restricted to pure tensor
    parallelism: heads / KV heads / FFN width live on ``axis``; the KV
    cache is partitioned by KV head (each shard owns its heads' cache
    rows, per-slot ``pos`` replicated), so ``kv_seq`` sharding is
    disabled; vocab/embed stay replicated so every shard can argmax the
    full logits without a gather.
    """
    rules = make_rules(cfg, mesh, "decode")
    table = dict(rules.table, kv_seq=None, vocab=None, seq_sp=None,
                 batch=None, fsdp=None)
    for logical in ("heads", "kv_heads", "heads_flat", "kv_flat", "d_ff"):
        if table.get(logical) is None:
            raise ValueError(
                f"{cfg.name}: logical dim {logical!r} does not divide "
                f"mesh axis {axis!r} (size {mesh.shape[axis]})")
    return ShardingRules(mesh, table, "decode")


def make_rules(cfg, mesh: Optional[Mesh], mode: str = "train") -> ShardingRules:
    """Build the logical->physical table for a config on a mesh.

    ``mode``: "train" | "prefill" | "decode".  Falls back to replication
    for any logical dim whose size does not divide the axis product.
    """
    if mesh is None:
        return ShardingRules(None, {}, mode)
    dp = _dp_axes(mesh)
    tp_axis = "model" if "model" in mesh.axis_names else None

    def fits(n: int, ax: Axis) -> Axis:
        return ax if ax is not None and n % _size(mesh, ax) == 0 else None

    heads = cfg.n_heads
    kvh = cfg.n_kv_heads
    table: dict[str, Axis] = {
        "batch": dp if dp else None,
        "seq_sp": tp_axis if mode in ("train", "prefill") else None,
        "kv_seq": tp_axis,
        "heads": fits(heads, tp_axis),
        "kv_heads": fits(kvh, tp_axis),
        "heads_flat": fits(heads, tp_axis),
        "kv_flat": fits(kvh, tp_axis),
        "d_ff": fits(cfg.d_ff, tp_axis),
        "d_expert": None,
        "vocab": tp_axis,     # vocab is padded to a multiple of 2048
        "embed": None,
        "fsdp": dp if (dp and mode == "train") else None,
        "experts": None,
        "experts_big": None,
        "d_inner": None,
        "rwkv_heads": None,
        "stage": "stage" if "stage" in mesh.axis_names else None,
    }
    if cfg.moe is not None:
        table["experts"] = fits(cfg.moe.n_experts, tp_axis)
        # decode-time EP: widest axis set that divides n_experts, so the
        # big expert stacks (deepseek: 256e) spread over the whole mesh.
        candidates: list[Axis] = []
        if dp and tp_axis:
            candidates.append(dp + (tp_axis,))
        if "data" in mesh.axis_names and tp_axis:
            candidates.append(("data", tp_axis))
        candidates.append(tp_axis)
        table["experts_big"] = table["experts"]
        if mode == "decode":
            for cand in candidates:
                if cand is not None and \
                        cfg.moe.n_experts % _size(mesh, cand) == 0:
                    # decode shards the expert weight stacks themselves
                    # over the widest dividing axis set
                    table["experts_big"] = cand
                    table["experts"] = cand
                    break
    if cfg.mamba is not None:
        d_inner = cfg.mamba.expand * cfg.d_model
        table["d_inner"] = fits(d_inner, tp_axis)
    if cfg.family == "rwkv":
        from repro.models.rwkv6 import padded_heads
        table["rwkv_heads"] = fits(padded_heads(cfg), tp_axis)
    return ShardingRules(mesh, table, mode)


def logical_pspec(names: Sequence[Optional[str]],
                  rules: Optional[ShardingRules] = None) -> P:
    rules = rules if rules is not None else current_rules()
    if rules is None or rules.mesh is None:
        return P()
    used: set[str] = set()
    dims = []
    for name in names:
        ax = rules.table.get(name) if name else None
        if ax is None:
            dims.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        if any(a in used for a in axes):
            dims.append(None)     # a mesh axis may appear only once
            continue
        used.update(axes)
        dims.append(ax)
    return P(*dims)


def _fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop axes that do not divide the dimension (GSPMD-uneven guard)."""
    dims = []
    for i, ax in enumerate(spec):
        if ax is None or i >= len(shape):
            dims.append(None)
            continue
        dims.append(ax if shape[i] % _size(mesh, ax) == 0 else None)
    return P(*dims)


def pspec_for(shape: tuple[int, ...], names: Sequence[Optional[str]],
              rules: Optional[ShardingRules] = None) -> P:
    """Divisibility-validated PartitionSpec for a concrete shape."""
    rules = rules if rules is not None else current_rules()
    if rules is None or rules.mesh is None:
        return P()
    spec = logical_pspec(names, rules)
    return _fit_spec(spec, shape, rules.mesh)


def shard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` by logical names; no-op w/o context."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    spec = pspec_for(x.shape, names, rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


def param_pspecs(defs, rules: ShardingRules):
    """Map a pytree of ParamDef to (shape-validated) PartitionSpecs."""
    from repro.models.param import ParamDef

    def one(d: ParamDef) -> P:
        return pspec_for(d.shape, d.names, rules)

    return jax.tree.map(one, defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))
