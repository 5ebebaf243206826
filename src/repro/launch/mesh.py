"""Mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state: a caller that wants virtual host
devices sets XLA_FLAGS before the first jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests, pipeline stages)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1) -> Mesh:
    """Whatever this host actually has (tests/examples on CPU)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def make_tp_mesh(tp: int, axis: str = "model") -> Mesh:
    """1-D tensor-parallel mesh for the sharded serving engine.

    On CPU CI run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (set before the first jax call) to get ``N`` virtual host devices.
    """
    n = len(jax.devices())
    if n < tp:
        raise ValueError(
            f"tp={tp} needs {tp} devices, have {n}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp}")
    return make_mesh((tp,), (axis,))
