"""Fig. 6: energy per inference of the tiny workload (the mJ end of the
paper's span)."""
from __future__ import annotations

from benchmarks.common import csv_row
from repro.configs import get_config
from repro.core.power_model import TinyPowerModel
from repro.models import tiny as tiny_mod


def run() -> list[dict]:
    rows = []
    tm = TinyPowerModel()
    cfg = get_config("tiny-kws")
    e = tm.inference_energy(tiny_mod.macs(cfg), tiny_mod.sram_bytes(cfg))
    rows.append({"workload": "tiny-kws", "kind": "tiny-inference",
                 "j_per_sample": e, "n_chips": 1})
    return rows


def csv() -> list[str]:
    return [csv_row(f"fig6_energy_per_inf[{r['workload']}|{r['kind']}]",
                    0.0, f"j_per_sample={r['j_per_sample']:.6g}")
            for r in run()]


if __name__ == "__main__":
    for r in run():
        print(f"{r['workload']:<20} {r['kind']:<22} "
              f"{r['j_per_sample']:>12.6g} J")
