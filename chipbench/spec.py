"""Find a cell's pieces by name: ``BENCHMARK.json`` and the files beside it.

Nothing here names a configuration, a traffic mix or a metric.  A cell
is the ``workloads`` entry of ``BENCHMARK.json``; its configuration is
``chipbench/configs/<config>.json``, its traffic
``chipbench/traffic/<traffic>.json``, each per-layer metric a reader
``chipbench/layer_metrics/<metric>.py`` and each kernel's work count
``chipbench/work/<kernel>.py``.  A later cell, mix or metric is added as
new files and new entries, never as an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    def reader(self, metric: str) -> Callable:
        """The ``read(ctx)`` function of a per-layer metric."""
        return load_module(self.root / "chipbench" / "layer_metrics"
                           / f"{metric}.py").read


def load_module(path: Path):
    """Import a benchmark file by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def work_module(kernel: str, root: Path = ROOT):
    """``chipbench/work/<kernel>.py``: ops and bytes from shapes."""
    return load_module(root / "chipbench" / "work" / f"{kernel}.py")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = load_json(root / "BENCHMARK.json")
    by_name: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(root / "chipbench" / "traffic"
                        / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                root=root)


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The published peaks of ``device_kind``; a device missing from
    ``chipbench/peaks.json`` is an error, never a default."""
    table = load_json(root / "chipbench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"chipbench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
