"""Pass 1 — kernel contract checker (KRN rules).

Imports each kernel package's ``ops`` module, reads its declarative
``CONTRACTS`` (``repro.analysis.contracts.KernelContract``), and for
every declared shape case enumerates the grid in plain Python — no
device, no tracing — to prove:

- KRN001  every output block is written at least once (no gaps: an
          under-covering grid silently leaves stale/zero output rows,
          which corrupts both the perf and the energy columns),
- KRN002  no two *parallel* grid points write the same output block
          (revisits along ``arbitrary`` dims are the accumulation
          pattern and are legal; parallel double-writes race),
- KRN003  block shapes divide the operand shapes the kernel sees
          (wrappers pad first — the contract reproduces that
          arithmetic, including ``fit_block_k`` shard-local shapes),
- KRN004  dtype consistency across each declared operand group,
- KRN005  the per-program VMEM/SMEM footprint fits the platform
          budget (double-buffered blocks + resident scratch).
"""
from __future__ import annotations

import importlib
import inspect
import itertools
from typing import Optional

from repro.analysis.contracts import KernelContract, KernelInstance
from repro.analysis.findings import Finding, relpath

KERNEL_PACKAGES = (
    "repro.kernels.decode_attention",
    "repro.kernels.flash_attention",
    "repro.kernels.int8_matmul",
    "repro.kernels.linear_scan",
)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _loc(contract: KernelContract, root: str) -> tuple[str, int]:
    """file:line of the contract's build function (the declaration)."""
    try:
        path = inspect.getsourcefile(contract.build)
        _, line = inspect.getsourcelines(contract.build)
        return relpath(path, root), line
    except (TypeError, OSError):
        return contract.name, 1


def check_instance(contract: KernelContract, case: dict,
                   inst: KernelInstance, path: str, line: int
                   ) -> list[Finding]:
    out: list[Finding] = []
    obj = contract.name

    def finding(rule, message, hint, severity="error"):
        out.append(Finding(rule, severity, path, line,
                           f"{contract.name}{case}: {message}", hint,
                           obj=obj))

    # --- KRN003: divisibility of every blocked operand ---------------
    for op in list(inst.inputs) + list(inst.outputs):
        if op.block is None:
            continue
        for d, (dim, blk) in enumerate(zip(op.shape, op.block)):
            if blk <= 0:
                finding("KRN003",
                        f"operand {op.name!r} axis {d} has non-positive "
                        f"block size {blk}",
                        "block dims must be >= 1")
            elif dim % blk:
                finding(
                    "KRN003",
                    f"operand {op.name!r} axis {d}: block {blk} does "
                    f"not divide shape {dim}",
                    "pad the operand to a block multiple in the "
                    "wrapper (see fit_block_k) or shrink the block")
    if any(f.rule == "KRN003" for f in out):
        return out            # coverage math needs divisible blocks

    # --- grid enumeration (coverage + double-writes) ------------------
    n_points = 1
    for g in inst.grid:
        n_points *= int(g)
    if n_points > contract.max_grid_points:
        finding("KRN001",
                f"grid {inst.grid} has {n_points} points, beyond the "
                f"enumeration cap {contract.max_grid_points}",
                "declare a smaller representative case; coverage is "
                "shape-generic", severity="warning")
        return out
    par_dims = [i for i, s in enumerate(inst.semantics)
                if s == "parallel"]
    for op in inst.outputs:
        if op.block is None:
            continue
        nblocks = tuple(_ceil_div(dim, blk)
                        for dim, blk in zip(op.shape, op.block))
        expected = set(itertools.product(*[range(n) for n in nblocks]))
        written: dict[tuple, set] = {}
        for idx in itertools.product(*[range(int(g))
                                       for g in inst.grid]):
            bi = tuple(int(x) for x in op.index_map(*idx))
            if len(bi) != len(op.shape):
                finding("KRN001",
                        f"output {op.name!r} index_map returned rank "
                        f"{len(bi)} for a rank-{len(op.shape)} operand",
                        "index_map must return one block index per "
                        "operand axis")
                break
            if any(not (0 <= b < n) for b, n in zip(bi, nblocks)):
                finding("KRN001",
                        f"output {op.name!r}: grid point {idx} maps to "
                        f"out-of-range block {bi} (grid of blocks: "
                        f"{nblocks})",
                        "index_map must stay inside the output block "
                        "grid — check the grid extents")
                break
            written.setdefault(bi, set()).add(
                tuple(idx[d] for d in par_dims))
        else:
            gaps = sorted(expected - set(written))
            if gaps:
                finding(
                    "KRN001",
                    f"output {op.name!r}: {len(gaps)} of "
                    f"{len(expected)} blocks never written (first gap: "
                    f"block {gaps[0]})",
                    "the grid times index_map must tile the whole "
                    "output — an under-covering grid leaves stale "
                    "rows that corrupt results silently")
            for bi, combos in sorted(written.items()):
                if len(combos) > 1:
                    c = sorted(combos)
                    finding(
                        "KRN002",
                        f"output {op.name!r}: block {bi} written by "
                        f"{len(combos)} distinct parallel grid points "
                        f"(e.g. {c[0]} and {c[1]})",
                        "parallel programs may run concurrently — "
                        "revisit an output only along 'arbitrary' "
                        "dims (accumulation) or split the output")
                    break

    # --- KRN004: dtype groups -----------------------------------------
    by_name = {op.name: op for op in
               list(inst.inputs) + list(inst.outputs)}
    for group in contract.dtype_groups:
        dtypes = {}
        for name in group:
            if name not in by_name:
                finding("KRN004",
                        f"dtype group {group} names unknown operand "
                        f"{name!r}", "fix the contract's dtype_groups")
                continue
            dtypes.setdefault(by_name[name].dtype, []).append(name)
        if len(dtypes) > 1:
            finding("KRN004",
                    f"operands {group} must share a dtype but have "
                    f"{ {d: n for d, n in dtypes.items()} }",
                    "cast at the wrapper boundary; mixed MXU operand "
                    "dtypes change numerics per backend")

    # --- KRN005: per-program footprint --------------------------------
    vmem = 0
    smem = 0
    for op in list(inst.inputs) + list(inst.outputs):
        size = op.block_bytes()
        if op.memory_space == "smem":
            smem += size
        elif op.memory_space == "vmem":
            # streamed blocks are double-buffered by the pipeline
            vmem += 2 * size if op.block is not None else size
    for sc in inst.scratch:
        if sc.memory_space == "smem":
            smem += sc.nbytes()
        elif sc.memory_space == "vmem":
            vmem += sc.nbytes()
    if vmem > contract.vmem_budget_bytes:
        finding("KRN005",
                f"per-program VMEM footprint {vmem / 2**20:.2f} MiB "
                f"exceeds the {contract.vmem_budget_bytes / 2**20:.0f} "
                f"MiB budget",
                "shrink block_k/block_q or move accumulators to "
                "smaller blocks; an over-budget kernel spills or "
                "fails to compile on real hardware")
    if smem > contract.smem_budget_bytes:
        finding("KRN005",
                f"per-program SMEM footprint {smem / 2**10:.1f} KiB "
                f"exceeds the {contract.smem_budget_bytes / 2**10:.0f} "
                f"KiB budget",
                "SMEM holds scalars (grid metadata, per-row indices); "
                "large vectors belong in VMEM")
    return out


def check_contract(contract: KernelContract, root: str) -> list[Finding]:
    path, line = _loc(contract, root)
    out: list[Finding] = []
    for case in contract.cases:
        try:
            inst = contract.build(dict(case))
        except Exception as e:                       # noqa: BLE001
            out.append(Finding(
                "KRN000", "error", path, line,
                f"{contract.name}{case}: contract build raised "
                f"{type(e).__name__}: {e}",
                "the contract must instantiate for every declared "
                "case", obj=contract.name))
            continue
        out.extend(check_instance(contract, dict(case), inst, path,
                                  line))
    return out


def check_package(module_name: str, root: str) -> list[Finding]:
    """Import ``<package>.ops`` and check its ``CONTRACTS``."""
    mod = importlib.import_module(module_name + ".ops")
    contracts = getattr(mod, "CONTRACTS", None)
    if not contracts:
        path = relpath(mod.__file__, root)
        return [Finding(
            "KRN000", "error", path, 1,
            f"{module_name}.ops exports no CONTRACTS",
            "declare a KernelContract per pallas_call so the grid/"
            "block/footprint proofs cover this kernel",
            obj=module_name)]
    out: list[Finding] = []
    for contract in contracts:
        out.extend(check_contract(contract, root))
    return out


def run(root: str, packages: Optional[tuple] = None) -> list[Finding]:
    out: list[Finding] = []
    for pkg in packages or KERNEL_PACKAGES:
        out.extend(check_package(pkg, root))
    return out
