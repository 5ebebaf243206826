"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``: it names a configuration (an
entry of ``configs`` whose ``file`` holds the sizes as run) and a
traffic mix (``bench/traffic/<traffic>.json``).  Per-layer metrics are
read by ``bench/metrics/<name>.py`` and the plain reference of a
configuration is ``bench/reference/<reference>.py``.  Nothing here
knows a cell by name: a later cell, mix, configuration or metric is a
new file and a new entry.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    """Everything one run of one cell needs from the benchmark's files."""

    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: list      # entries of ``end_to_end`` this cell reports
    per_layer: list       # entries of ``per_layer`` this cell reports
    root: Path


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def load_module(path: Path, name: str):
    """Import the Python file ``path`` as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(cell: Cell, metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = cell.root / "bench" / "metrics" / f"{metric}.py"
    return load_module(path, f"bench_metric_{metric}").read


def reference_module(cell: Cell):
    """The configuration's plain reference, beside its sizes."""
    ref = cell.config["reference"]
    return load_module(cell.root / "bench" / "reference" / f"{ref}.py",
                       f"bench_reference_{ref}")
