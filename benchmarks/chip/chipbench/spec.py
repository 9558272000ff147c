"""Finds a cell's parts by the names in ``BENCHMARK.json``.

A configuration is the file its entry names, a traffic mix is
``traffic/<name>.json``, a per-layer metric's reader is
``metrics/<name>.py`` and a configuration's plain reference is
``reference/<name>.py``, all under ``benchmarks/chip/``. Adding a cell,
a mix, a metric or a model adds files and entries; no file is edited.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file's contents
    traffic: dict          # the traffic mix's contents
    end_to_end: list       # BENCHMARK.json entries this cell reports
    per_layer: list
    bench_dir: Path

    @property
    def reference(self):
        return load_module(self.bench_dir / "reference"
                           / f"{self.config['reference']}.py")

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py")


def load_module(path: Path):
    """Import a file by its path (names may hold '.' and '-')."""
    name = "chipbench_file_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic"
                          / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)],
                bench_dir=bench_dir)


def peaks() -> dict:
    return json.loads((BENCH_DIR / "chipbench" / "peaks.json").read_text())
