"""Single-source parameter definitions.

A model declares its parameters as a pytree of ``ParamDef`` (shape +
logical axis names + init).  From that one tree we derive, without
drift: real initialized params, ``ShapeDtypeStruct`` stand-ins for
shape-only lowering, and ``PartitionSpec`` trees for pjit in/out
shardings.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class ParamDef(NamedTuple):
    shape: tuple[int, ...]
    names: tuple[Optional[str], ...]
    init: str = "normal"            # normal | zeros | ones | embed | small
    dtype: str = "bfloat16"
    scale: Optional[float] = None   # stddev override


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _init_one(d: ParamDef, key: jax.Array) -> jax.Array:
    dtype = jnp.dtype(d.dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "embed":
        std = d.scale or 0.02
        return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dtype)
    if d.init == "small":
        std = d.scale or 1e-3
        return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dtype)
    # fan-in scaled normal
    fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
    std = d.scale or (1.0 / np.sqrt(max(1, fan_in)))
    return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dtype)


def init_params(defs, key: jax.Array, shardings=None):
    """Seeded parameters for a ``ParamDef`` tree.

    ``shardings``: a matching tree of ``jax.sharding.Sharding``.  With
    it, every leaf is created straight into its sharding by one jitted
    program, so no full copy of a sharded model lands on one device.
    The values do not depend on the sharding.
    """
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)

    def build(key):
        return jax.tree.unflatten(treedef, [
            _init_one(d, jax.random.fold_in(key, i))
            for i, d in enumerate(leaves)])

    if shardings is None:
        return build(key)
    return jax.jit(build, out_shardings=shardings)(key)


def abstract_params(defs):
    """ShapeDtypeStruct tree for .lower() without allocation."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
        defs, is_leaf=is_def)


def param_bytes(defs) -> int:
    total = 0
    for d in jax.tree.leaves(defs, is_leaf=is_def):
        total += int(np.prod(d.shape)) * jnp.dtype(d.dtype).itemsize
    return total


def param_count(defs) -> int:
    return sum(int(np.prod(d.shape))
               for d in jax.tree.leaves(defs, is_leaf=is_def))


def stacked(d: ParamDef, n: int) -> ParamDef:
    """Prepend a scan-over-layers dimension."""
    return d._replace(shape=(n,) + d.shape, names=(None,) + d.names)


def stack_tree(defs, n: int):
    return jax.tree.map(lambda d: stacked(d, n), defs, is_leaf=is_def)
