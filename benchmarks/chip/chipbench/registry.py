"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A configuration is the JSON file its entry names; a traffic mix is
``traffic/<name>.json``; a configuration's data generator and plain
reference are ``generators/<generator>.py`` and
``references/<reference>.py``; a per-layer metric's reader is
``layers/<metric>.py``, or ``layers/<stem>.py`` for a metric named
``<stem>.<suffix>``.  A later cell, mix or metric is added with new
files and entries alone.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: Path


def _read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: Dict, name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The workload entry ``name`` with its configuration, traffic mix
    and the metrics it reports; raises KeyError for an unknown name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = _read_json(Path(root) / entry["file"])
    traffic = _read_json(Path(bench_dir) / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                bench_dir=Path(bench_dir))


def load_module(path: Path):
    """Import one file of the benchmark by path, under a name of its own."""
    path = Path(path)
    mod_name = "chipbench_file_" + "_".join(path.with_suffix("").parts[-2:])
    mod = sys.modules.get(mod_name)
    if mod is not None:
        return mod
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[mod_name] = mod
    return mod


def generator(cell: Cell):
    return load_module(cell.bench_dir / "generators"
                       / f"{cell.config['generator']}.py")


def reference(cell: Cell):
    return load_module(cell.bench_dir / "references"
                       / f"{cell.config['reference']}.py")


def layer_reader(bench_dir: Path, metric: str):
    """The reader module of a per-layer metric: ``layers/<metric>.py``,
    else ``layers/<stem>.py`` for ``<stem>.<suffix>``."""
    bench_dir = Path(bench_dir)
    for stem in (metric, metric.split(".", 1)[0]):
        path = bench_dir / "layers" / f"{stem}.py"
        if path.exists():
            return load_module(path)
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r} "
                            f"under {bench_dir / 'layers'}")


def kernel_names(bench_dir: Path, kind: str) -> List[str]:
    """Names (substrings of device op names) of the kernels registered
    under ``kernels/<kind>/*.json``."""
    names: List[str] = []
    for path in sorted((Path(bench_dir) / "kernels" / kind).glob("*.json")):
        names += list(_read_json(path)["match"])
    return names
