"""What a cell is made of, found by name.

`BENCHMARK.json` names each cell's configuration and traffic mix, and each
per-layer metric. Everything that belongs to one of them sits in a file of
its own under `bench/`, found from that name alone:

- `configs/<config>.json`: the sizes as run, the registry name, the family
  (which names `models/<family>.py`: the plain reference, the weight layout
  and the operation and byte counts), and the serving tier's settings;
- `traffic/<traffic>.json`: the mix's parameters, read by the one generator
  in `load.py`, and the driver (`drivers/<driver>.py`) that feeds it;
- `metrics/<metric>.py`: one reader, `read(run) -> float | None`.

So a new configuration, traffic mix or metric is new files plus new entries
in `BENCHMARK.json`, and no edit here.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """`BENCHMARK.json` or a file it names is missing or inconsistent."""


def load_module(path: Path, name: str) -> ModuleType:
    """Import one file by path (names may hold `.` and `-`)."""
    if not path.is_file():
        raise SpecError(f"no file {path}")
    key = f"bench_{name}_{path}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def _json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    """One workload of `BENCHMARK.json`, with the files it names loaded."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]           # configs/<config>.json
    traffic: Dict[str, Any]          # traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]  # the end-to-end metrics it reports
    per_layer: List[Dict[str, Any]]   # the per-layer metrics it reports
    bench_dir: Path

    def _code(self, kind: str, name: str) -> ModuleType:
        """`<kind>/<name>.py` of this cell's benchmark directory, or of
        this one where that has none."""
        path = self.bench_dir / kind / f"{name}.py"
        if not path.is_file():
            path = BENCH / kind / f"{name}.py"
        return load_module(path, f"{kind}_{name}")

    def family(self) -> ModuleType:
        return self._code("models", self.config["family"])

    def driver(self) -> ModuleType:
        return self._code("drivers", self.traffic["driver"])

    def metric_reader(self, name: str) -> ModuleType:
        return self._code("metrics", name)


def reports(metric: Dict[str, Any], cell: str) -> bool:
    """Whether `cell` reports `metric`: listed, or no list at all."""
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`, files loaded."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in cfgs:
        raise SpecError(f"workload {name} names unknown config {w['config']}")
    bench_dir = root / bench["paths"][0]
    config = _json(root / cfgs[w["config"]]["file"])
    traffic = _json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if reports(m, name)]
    layer = [m for m in bench["per_layer"] if reports(m, name)]
    return Cell(name, int(w["chips"]), w["config"], w["traffic"], config,
                traffic, e2e, layer, bench_dir)
