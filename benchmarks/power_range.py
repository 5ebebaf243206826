"""Fig. 2: power consumption range across MLPerf categories.

Reproduces the low end of the paper's headline span: tiny systems at µW
average power (duty-cycled mW peaks) and edge at watts."""
from __future__ import annotations

from benchmarks.common import csv_row
from repro.configs import get_config
from repro.core.power_model import (StepWork, SystemPowerModel,
                                    TinyPowerModel)
from repro.hw import EDGE_SYSTEM
from repro.models import tiny as tiny_mod


def run() -> list[dict]:
    rows = []
    # --- tiny: duty-cycled keyword spotting
    tm = TinyPowerModel()
    cfg = get_config("tiny-kws")
    macs, sram = tiny_mod.macs(cfg), tiny_mod.sram_bytes(cfg)
    period = 1.0                               # 1 inference/s detector
    e = tm.inference_energy(macs, sram)
    avg_w = e / period + tm.device.sleep_watts
    rows.append({"category": "tiny (avg, duty-cycled)", "watts": avg_w,
                 "note": f"{e * 1e3:.3f} mJ/inf @ {period}s period"})
    rows.append({"category": "tiny (active peak)",
                 "watts": e / tm.inference_time(macs), "note": "during inf"})
    # --- edge: single SoC running edge-vit offline
    edge = SystemPowerModel(EDGE_SYSTEM, 1)
    ecfg = get_config("edge-vit")
    n = ecfg.param_count()
    w = StepWork(flops=2.0 * n * 197, hbm_bytes=2.0 * n)   # 1 img batch
    rows.append({"category": "edge (ViT-S inference)",
                 "watts": edge.system_watts(w), "note": "single SoC"})
    return rows


def csv() -> list[str]:
    out = []
    for r in run():
        out.append(csv_row(f"fig2_power_range[{r['category']}]", 0.0,
                           f"watts={r['watts']:.6g}"))
    return out


if __name__ == "__main__":
    for r in run():
        print(f"{r['category']:<38} {r['watts']:>14.6g} W   {r['note']}")
