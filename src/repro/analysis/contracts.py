"""Declarative kernel contracts the static checker consumes.

Each kernel package's ``ops.py`` exports ``CONTRACTS``: one
``KernelContract`` per ``pallas_call`` it wraps.  A contract is a pure
description — ``build(case)`` returns the grid, block specs, operand
shapes/dtypes, and scratch allocation the real call would construct
for that shape case, using the *same* shape arithmetic as the wrapper
(``fit_block_k``, pad-to-multiple), so the checker can enumerate the
grid and prove coverage without ever touching a device.

Index maps follow Pallas blocked-indexing semantics: the map returns
*block* indices (element offset = index * block_shape), exactly the
convention the kernels use.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

MEMORY_SPACES = ("vmem", "smem", "any", "semaphore")

# itemsize table so contracts stay importable without jax
_DTYPE_BYTES = {
    "float32": 4, "bfloat16": 2, "float16": 2, "float64": 8,
    "int32": 4, "int8": 1, "uint8": 1, "int16": 2, "int64": 8,
    "bool": 1,
}


def dtype_bytes(dtype: str) -> int:
    try:
        return _DTYPE_BYTES[dtype]
    except KeyError:
        raise ValueError(f"unknown dtype {dtype!r} in contract") from None


@dataclasses.dataclass(frozen=True)
class OperandSpec:
    """One input/output ref: full shape + BlockSpec as the kernel sees
    it.  ``block``/``index_map`` are ``None`` for whole-array refs
    (e.g. an SMEM scalar-prefetch vector)."""

    name: str
    shape: tuple
    dtype: str
    block: Optional[tuple] = None
    index_map: Optional[Callable] = None
    memory_space: str = "vmem"

    def __post_init__(self):
        assert self.memory_space in MEMORY_SPACES, self.memory_space
        if (self.block is None) != (self.index_map is None):
            raise ValueError(
                f"operand {self.name!r}: block and index_map come "
                f"together (both or neither)")
        if self.block is not None and len(self.block) != len(self.shape):
            raise ValueError(
                f"operand {self.name!r}: block rank {len(self.block)} "
                f"!= shape rank {len(self.shape)}")

    def block_bytes(self) -> int:
        shape = self.block if self.block is not None else self.shape
        n = 1
        for d in shape:
            n *= int(d)
        return n * dtype_bytes(self.dtype)


@dataclasses.dataclass(frozen=True)
class ScratchSpec:
    """One scratch allocation (VMEM/SMEM, or DMA semaphores), persistent
    across the grid."""

    shape: tuple
    dtype: str
    memory_space: str = "vmem"

    def nbytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n * dtype_bytes(self.dtype)


@dataclasses.dataclass(frozen=True)
class KernelInstance:
    """The fully-instantiated call for one shape case."""

    grid: tuple
    semantics: tuple               # "parallel" | "arbitrary" per dim
    inputs: tuple                  # OperandSpec...
    outputs: tuple                 # OperandSpec...
    scratch: tuple = ()            # ScratchSpec...

    def __post_init__(self):
        if len(self.semantics) != len(self.grid):
            raise ValueError(
                f"semantics {self.semantics} does not match grid "
                f"{self.grid}")
        for s in self.semantics:
            assert s in ("parallel", "arbitrary"), s


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """What a kernel promises; ``repro.analysis.kernels`` proves it.

    - ``build(case)`` -> ``KernelInstance`` for one ``cases`` entry
      (a plain dict of dims), mirroring the wrapper's shape arithmetic
      including padding/``fit_block_k``.
    - ``dtype_groups``: operand-name groups that must share a dtype
      (MXU inputs vs f32 accumulators).
    - Budgets are per *program* (one grid step): streamed input/output
      blocks are double-buffered by the pipeline, scratch is resident.
    """

    name: str
    build: Callable
    cases: tuple
    dtype_groups: tuple = ()
    vmem_budget_bytes: int = 16 * 2 ** 20      # TPU VMEM per core
    smem_budget_bytes: int = 256 * 2 ** 10     # scalar memory
    max_grid_points: int = 1 << 20             # enumeration safety cap
