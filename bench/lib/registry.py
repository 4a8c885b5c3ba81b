"""Find every piece of a cell by the names in ``BENCHMARK.json``.

Nothing here names a configuration, a traffic mix, a cell or a metric.
A later change adds one by adding files:

  configs/<config>.json          a deployment (sizes, FitConfig fields,
                                 data generator, reference, reduced,
                                 assumed)
  traffic/<traffic>.json         a traffic mix: ``{"driver": ...}`` and
                                 the parameters that driver reads
  traffic/<driver>.py            one general driver per kind of traffic
  data/<generator>.py            data made on the device from the seed
  reference/<reference>.py       the plain reference that decides
                                 ``correct``
  metrics/<metric>.py            one reader per per-layer metric
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_module(path: Path) -> ModuleType:
    """Import one file by path under a name derived from it."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    key = hashlib.sha1(str(path.resolve()).encode()).hexdigest()[:12]
    name = "bench_dyn_" + re.sub(r"\W", "_", path.stem) + "_" + key
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One resolved cell: its entry, configuration and traffic mix."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


class Registry:
    """``BENCHMARK.json`` and the files it names, under one root."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        # the system under test: the program's sources in the checkout
        src = str(self.root / "src")
        if src not in sys.path:
            sys.path.insert(0, src)

    def _entry(self, key: str, name: str) -> Dict[str, Any]:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {key} entry named {name!r}")

    def config_file(self, name: str) -> Path:
        return self.root / self._entry("configs", name)["file"]

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", name)
        config = json.loads(self.config_file(w["config"]).read_text())
        traffic = json.loads(
            (self.bench / "traffic" / f"{w['traffic']}.json").read_text())

        def reports(metric: Dict[str, Any]) -> bool:
            return name in metric.get("workloads", [name])

        e2e = [m for m in self.spec["end_to_end"] if reports(m)]
        e2e_names = {m["name"] for m in e2e}
        per_layer = [m for m in self.spec["per_layer"]
                     if reports(m) and m["moves"] in e2e_names]
        return Cell(name=name, chips=int(w["chips"]),
                    config_name=w["config"], config=config,
                    traffic_name=w["traffic"], traffic=traffic,
                    end_to_end=e2e, per_layer=per_layer)

    def driver(self, cell: Cell) -> ModuleType:
        return load_module(self.bench / "traffic"
                           / f"{cell.traffic['driver']}.py")

    def data(self, name: str) -> ModuleType:
        return load_module(self.bench / "data" / f"{name}.py")

    def reference(self, name: str) -> ModuleType:
        return load_module(self.bench / "reference" / f"{name}.py")

    def metric(self, name: str) -> ModuleType:
        return load_module(self.bench / "metrics" / f"{name}.py")
