"""``BENCHMARK.json`` holds to the benchmark's contract, and everything it
names is found by name: configurations, their references and program
adapters, traffic mixes and one reader per metric."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench.spec import Spec, reader                 # noqa: E402

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in DOC["workloads"]]
METRICS = DOC["end_to_end"] + DOC["per_layer"]


def test_top_level_keys():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["command"] == ["python3", "chipbench/run.py"]
    assert DOC["paths"] == ["chipbench"]
    assert 1 <= DOC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_keys():
    names = [c["name"] for c in DOC["configs"]] + CELLS \
        + [m["name"] for m in METRICS]
    assert all(NAME.match(n) for n in names)
    for group in (DOC["configs"], DOC["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m) - {"workloads"} <= {
            "name", "unit", "better", "bound", "source", "layer", "moves"}
    for m in DOC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in DOC["end_to_end"])


def test_per_layer_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    for m in DOC["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in target.get("workloads", CELLS), (m["name"], cell)
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    c = Spec().cell(cell)
    assert c.config["name"] in {x["name"] for x in DOC["configs"]}
    assert hasattr(c.reference(), "final_hidden")
    assert hasattr(c.program(), "model_config")
    assert {"n_slots", "max_seq_len"} <= set(c.traffic["serve"])
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for key in c.config["reduced"]:
        assert NAME.match(key)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(metric):
    assert callable(reader(metric).read)


def test_config_files_lie_under_paths():
    for c in DOC["configs"]:
        assert c["file"].startswith("chipbench/")
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
