"""Cost calibration: exact HLO costs despite lax.scan undercounting.

XLA's ``cost_analysis()`` counts a while-loop body ONCE, ignoring trip
count (verified in EXPERIMENTS.md §Dry-run).  Since every production
config scans over layers (and chunks), raw cost numbers undercount by
the layer count.  Fix: compile 2-3 *fully unrolled* reduced-depth
variants of the same cell (``unroll_scans=True`` replaces every scan
with a python loop), fit the exact linear model

    cost(depths) = a + sum_i b_i * depth_i

and extrapolate to the real depth.  Layers within a group are
shape-identical, so the model is exact, not a regression.
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import ModelConfig, ShapeConfig


@dataclasses.dataclass
class CalibratedCosts:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    coll_breakdown: dict
    variants_compiled: int

    def to_json(self):
        return dataclasses.asdict(self)


def _variant(cfg: ModelConfig, **over) -> ModelConfig:
    over.setdefault("unroll_scans", True)
    over.setdefault("scan_layers", False)
    return dataclasses.replace(cfg, **over)


def _depth_plan(cfg: ModelConfig) -> tuple[list[dict], list[list[float]],
                                           list[float]]:
    """Returns (config-override list, depth matrix, real depth vector).

    Each variant contributes row [1, d1, d2, ...]; solving A x = cost
    gives [a, b1, b2, ...]; the real cost is [1, D1, D2, ...] . x.
    """
    if cfg.family == "mla_moe":
        import dataclasses as dc
        k = cfg.moe.first_k_dense

        def ov(d, m):
            return {"n_layers": d + m,
                    "moe": dc.replace(cfg.moe, first_k_dense=d)}

        return ([ov(1, 1), ov(2, 1), ov(1, 2)],
                [[1, 1, 1], [1, 2, 1], [1, 1, 2]],
                [1, k, cfg.n_layers - k])
    if cfg.family == "hybrid":
        p = cfg.hybrid.attn_period
        return ([{"n_layers": p}, {"n_layers": 2 * p}],
                [[1, 1], [1, 2]],
                [1, cfg.n_layers // p])
    if cfg.family == "encdec":
        import dataclasses as dc

        def ov(e, d):
            return {"n_layers": d,
                    "encdec": dc.replace(cfg.encdec, enc_layers=e)}

        return ([ov(1, 1), ov(2, 1), ov(1, 2)],
                [[1, 1, 1], [1, 2, 1], [1, 1, 2]],
                [1, cfg.encdec.enc_layers, cfg.n_layers])
    # homogeneous stacks
    return ([{"n_layers": 1}, {"n_layers": 2}],
            [[1, 1], [1, 2]],
            [1, cfg.n_layers])


def _solve(rows: list[list[float]], costs: list[float],
           real: list[float]) -> float:
    import numpy as np
    A = np.asarray(rows, dtype=np.float64)
    y = np.asarray(costs, dtype=np.float64)
    x, *_ = np.linalg.lstsq(A, y, rcond=None)
    val = float(np.asarray(real, dtype=np.float64) @ x)
    return max(val, 0.0)


def calibrated_costs(cfg: ModelConfig, shape: ShapeConfig, mesh,
                     *, hp=None, verbose: bool = False) -> CalibratedCosts:
    from repro.launch.roofline import collective_bytes
    from repro.launch.specs import build_cell
    import numpy as np

    overrides, rows, real = _depth_plan(cfg)
    flops, hbm, coll_tot = [], [], []
    coll_kinds: dict[str, list[float]] = {}
    n_dev = int(np.prod(list(mesh.shape.values()))) if mesh else 1
    for ov in overrides:
        vcfg = _variant(cfg, **ov)
        cell = build_cell(vcfg, shape, mesh, hp=hp)
        compiled = cell.lower().compile()
        c = compiled.cost_analysis()
        flops.append(float(c.get("flops", 0.0)))
        hbm.append(float(c.get("bytes accessed", 0.0)))
        coll = collective_bytes(compiled.as_text(), n_dev)
        coll.pop("_counts", None)
        coll_tot.append(float(sum(coll.values())))
        for k in ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute"):
            coll_kinds.setdefault(k, []).append(float(coll.get(k, 0.0)))
        if verbose:
            print(f"    calib {ov}: flops={flops[-1]:.3e} "
                  f"bytes={hbm[-1]:.3e} coll={coll_tot[-1]:.3e}",
                  flush=True)
    breakdown = {k: _solve(rows, v, real) for k, v in coll_kinds.items()
                 if any(v)}
    return CalibratedCosts(
        flops=_solve(rows, flops, real),
        hbm_bytes=_solve(rows, hbm, real),
        coll_bytes=_solve(rows, coll_tot, real),
        coll_breakdown=breakdown,
        variants_compiled=len(overrides),
    )
