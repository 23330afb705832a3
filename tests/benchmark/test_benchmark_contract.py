"""``BENCHMARK.json`` parses, and every name in it resolves to a file."""

import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_top_level_keys_are_the_contracts():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_holds_what_it_is_run_with(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    held = json.loads((REPO / config["file"]).read_text())
    assert held["name"] == config["name"] and held["source"] == config["source"]
    assert all(key in held for key in config["reduced"])
    reference = REPO / "benchmark" / "reference" / f"{held['reference']['module']}.py"
    assert reference.is_file()
    widths = ("_dim", "_rank", "hidden", "intermediate", "head")
    assert not [k for k in config["reduced"] if any(w in k for w in widths)]


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_to_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    traffic = json.loads((REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json").read_text())
    assert traffic["chips"] == cell["chips"]
    assert (REPO / "benchmark" / "drivers" / f"{traffic['kind']}.py").is_file()
    limits = json.loads((REPO / "benchmark" / "limits" / f"{cell['name']}.json").read_text())
    assert all(isinstance(v, (int, float)) for v in limits.values())
    assert len(cell["why"]) <= 200


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_of_its_own(metric):
    assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (REPO / "benchmark" / "metrics" / f"{metric['name']}.py").is_file()
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    assert set(metric["workloads"]) <= {w["name"] for w in SPEC["workloads"]}
    assert metric["source"] in {"device_trace", "program_span", "program_counter", "host_clock"}


def test_end_to_end_metrics_and_names():
    names = [m["name"] for m in SPEC["end_to_end"]]
    assert "setup_s" in names
    for metric in SPEC["end_to_end"]:
        assert set(metric) == {"name", "unit", "better", "bound", "source"}
        assert 0 < metric["bound"] <= 0.1 and metric["source"] in {"host_clock", "device_trace"}
    every = names + [m["name"] for m in SPEC["per_layer"]]
    every += [w["name"] for w in SPEC["workloads"]] + [c["name"] for c in SPEC["configs"]]
    assert all(NAME.match(n) for n in every) and len(set(every)) == len(every)
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 4)


def test_run_py_names_no_model_cell_or_metric():
    text = (REPO / "benchmark" / "run.py").read_text().lower()
    named = [c["name"] for c in SPEC["configs"]] + [m["name"] for m in SPEC["per_layer"]]
    named += ["sasrec", "bert4rec", "fit_samples_per_s"]
    assert not [n for n in named if n in text]
