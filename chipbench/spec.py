"""Everything the benchmark runs is found by name from ``BENCHMARK.json``:
a cell names its configuration (``configs/<file>``, with its reference
``refs/<model_type>.py`` and its program adapter
``program/<model_type>.py``) and its traffic mix
(``traffic/<traffic>.json``); every metric is read by
``metrics/<name>.py``.  Adding a cell, a configuration or a metric adds
files and entries only."""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

__all__ = ["HERE", "REPO", "Spec", "Cell", "load_module", "reader"]

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def load_module(path: Path) -> ModuleType:
    """Import one file by path (names may hold dots and dashes)."""
    name = "chipbench_" + "_".join(path.relative_to(HERE).with_suffix(
        "").parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    config: dict          # the configuration file's contents
    traffic: dict         # the mix file's contents
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]

    def reference(self) -> ModuleType:
        return load_module(HERE / "refs" / f"{self.config['model_type']}.py")

    def program(self) -> ModuleType:
        return load_module(HERE / "program"
                           / f"{self.config['model_type']}.py")


class Spec:
    def __init__(self, root: Path = REPO):
        self.root = root
        self.doc = json.loads((root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.doc["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"have {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in self.doc["configs"]}
        cfg = json.loads((self.root / configs[w["config"]]["file"]
                          ).read_text())
        mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json"
                          ).read_text())

        def mine(m):
            return "workloads" not in m or name in m["workloads"]
        return Cell(name, cfg, mix, int(w["chips"]),
                    [m for m in self.doc["end_to_end"] if mine(m)],
                    [m for m in self.doc["per_layer"] if mine(m)])



def reader(metric: str) -> ModuleType:
    """The metric's reader, ``metrics/<name>.py``."""
    return load_module(HERE / "metrics" / f"{metric}.py")
