"""BENCHMARK.json keeps the benchmark contract's shape and character
rules, and names only files that exist."""

import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"] == ["python3", "bench/run.py"]


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]]
                         + list(CELLS) + [m["name"] for m in METRICS]
                         + [w["traffic"] for w in SPEC["workloads"]]
                         + [k for c in SPEC["configs"] for k in c["reduced"]])
def test_names_use_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert all(w in CELLS for w in metric.get("workloads", CELLS))
    if metric in SPEC["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves",
                               "workloads"}
        assert metric["source"] in ("device_trace", "program_span", "program_counter",
                                    "host_clock")
        moved = next(m for m in SPEC["end_to_end"] if m["name"] == metric["moves"])
        assert set(metric["workloads"]) <= set(moved.get("workloads", CELLS))
        assert (BENCH / "metrics" / f"{metric['name']}.py").is_file()


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_has_its_files_and_metrics(cell):
    names = {c["name"]: c for c in SPEC["configs"]}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    conf = names[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert (BENCH / "drivers" / f"{cfg['kind']}.py").is_file()
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    e2e = [m for m in SPEC["end_to_end"] if cell["name"] in m.get("workloads", CELLS)]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert any(cell["name"] in m["workloads"] for m in SPEC["per_layer"])
    assert {c["name"] for c in SPEC["configs"]} == {w["config"] for w in SPEC["workloads"]}


def test_layers_are_named_one_way():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert "\n" not in layer and f"| {layer} |" in perf, layer
