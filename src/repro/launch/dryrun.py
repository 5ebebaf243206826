import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count at first initialization).  Do not move them.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we build the production-mesh pjit program from
ShapeDtypeStruct stand-ins (no allocation), compile it, and record
``memory_analysis()`` (fits-per-device proof) + ``cost_analysis()`` +
collective bytes (for §Roofline).  Results are cached as JSON under
``experiments/dryrun/`` so reruns are incremental.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun               # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape train_4k --mesh multipod
  PYTHONPATH=src python -m repro.launch.dryrun --list
"""
import argparse
import dataclasses
import json
import time
import traceback

from repro.configs import (ASSIGNED_ARCHS, SHAPES, get_config,
                           shape_applicable)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import CellReport, analyze, render_table
from repro.launch.specs import build_cell

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "experiments", "dryrun")

MESHES = {"pod": dict(multi_pod=False), "multipod": dict(multi_pod=True)}


def cell_path(arch: str, shape: str, mesh: str, tag: str = "") -> str:
    suffix = f"__{tag}" if tag else ""
    return os.path.join(
        RESULTS_DIR, f"{arch}__{shape}__{mesh}{suffix}.json")


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             tag: str = "", overrides: dict | None = None,
             hp_overrides: dict | None = None,
             verbose: bool = True, calibrate: bool = True) -> dict:
    from repro.launch.calibrate import calibrated_costs
    from repro.launch.roofline import apply_calibration
    from repro.optim import AdamWConfig
    from repro.train.train_step import TrainHParams

    cfg = get_config(arch, **(overrides or {}))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(**MESHES[mesh_name])
    hp = None
    if hp_overrides:
        hp = TrainHParams(adamw=AdamWConfig(**hp_overrides))
    t0 = time.monotonic()
    cell = build_cell(cfg, shape, mesh, hp=hp)
    lowered = cell.lower()
    t_lower = time.monotonic() - t0
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0 - t_lower
    mem = compiled.memory_analysis()
    if verbose:
        print(f"  memory_analysis: {mem}")
        cost = compiled.cost_analysis()
        print(f"  cost_analysis (raw, scan bodies counted once): "
              f"flops={cost.get('flops', 0):.4g} "
              f"bytes={cost.get('bytes accessed', 0):.4g}", flush=True)
    report = analyze(cell, compiled, mesh_name=mesh_name)
    raw = {"raw_flops": report.flops, "raw_hbm_bytes": report.hbm_bytes,
           "raw_coll_bytes": report.coll_bytes}
    if calibrate:
        cal = calibrated_costs(cfg, shape, mesh, hp=hp, verbose=verbose)
        report = apply_calibration(report, cal)
    rec = report.to_json()
    rec.update(raw)
    rec.update({"tag": tag, "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "total_s": round(time.monotonic() - t0, 2), "ok": True})
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--mesh", nargs="*", default=None)
    ap.add_argument("--tag", default="", help="variant tag (perf iters)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--table", action="store_true",
                    help="render the roofline table from cached results")
    args = ap.parse_args()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    archs = args.arch or ASSIGNED_ARCHS
    meshes = args.mesh or list(MESHES)
    shapes = args.shape or list(SHAPES)

    cells = []
    for arch in archs:
        cfg = get_config(arch)
        for sname in shapes:
            if not shape_applicable(cfg, SHAPES[sname]):
                continue
            for mname in meshes:
                cells.append((arch, sname, mname))

    if args.list:
        for c in cells:
            print(*c)
        print(f"total {len(cells)} cells")
        return

    if args.table:
        reports = []
        for arch, sname, mname in cells:
            p = cell_path(arch, sname, mname, args.tag)
            if os.path.exists(p):
                with open(p) as f:
                    d = json.load(f)
                d2 = {k: v for k, v in d.items()
                      if k in {f.name for f in
                               dataclasses.fields(CellReport)}}
                reports.append(CellReport.from_json(d2))
        print(render_table(reports))
        return

    failures = []
    for i, (arch, sname, mname) in enumerate(cells):
        p = cell_path(arch, sname, mname, args.tag)
        if os.path.exists(p) and not args.force:
            print(f"[{i + 1}/{len(cells)}] cached {arch} {sname} {mname}")
            continue
        print(f"[{i + 1}/{len(cells)}] {arch} {sname} {mname} ...",
              flush=True)
        try:
            # §Roofline is single-pod only: multipod cells need the
            # compile + memory proof, not the (expensive) calibration
            rec = run_cell(arch, sname, mname, tag=args.tag,
                           calibrate=(mname == "pod"))
            with open(p, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"  OK lower={rec['lower_s']}s compile={rec['compile_s']}s "
                  f"bottleneck={rec['bottleneck']} "
                  f"live={(rec['arg_bytes'] + rec['temp_bytes']) / 2**30:.2f}"
                  f" GiB/dev fits={rec['fits_hbm']}", flush=True)
        except Exception as e:  # noqa: BLE001 — record and continue
            failures.append((arch, sname, mname, repr(e)))
            with open(p + ".err", "w") as f:
                f.write(traceback.format_exc())
            print(f"  FAIL {e!r}", flush=True)
    if failures:
        print(f"\n{len(failures)} failures:")
        for f_ in failures:
            print("  ", *f_)
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
