"""Shared by the benchmark's tests: a temporary checkout that holds the
benchmark as committed plus the toy cells kept beside these tests.

The toy cells are ADDED the way a later PR adds a cell: configuration, traffic
and limits files are copied in, entries are appended to ``BENCHMARK.json``, and
no file that was there is edited.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent.parent
TOY_CELLS = (
    ("tiny_sasrec", "tiny_fit", 1),
    ("tiny_bert4rec_f32", "tiny_fit", 1),
    ("tiny_sasrec_f32", "tiny_fit_dp4", 4),
)


def make_checkout(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    per_layer_cells = []
    for config, traffic, chips in TOY_CELLS:
        name = f"{config}.{traffic}"
        shutil.copy(HERE / "configs" / f"{config}.json", root / "benchmark" / "configs")
        shutil.copy(HERE / "traffic" / f"{traffic}.json", root / "benchmark" / "traffic")
        shutil.copy(HERE / "limits" / f"{name}.json", root / "benchmark" / "limits")
        if config not in {c["name"] for c in spec["configs"]}:
            spec["configs"].append(
                {"name": config, "source": "tests only", "reduced": [], "why": "toy",
                 "file": f"benchmark/configs/{config}.json"}
            )
        spec["workloads"].append(
            {"name": name, "config": config, "traffic": traffic, "chips": chips, "why": "toy"}
        )
        per_layer_cells.append(name)
    for metric in spec["per_layer"]:
        metric["workloads"] = metric["workloads"] + per_layer_cells
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def toy_cell(root: Path, workload: str, devices, seed=7, seconds=0.5, trace=False):
    from benchmark import run as bench_run

    cell = bench_run.load_cell(root, workload)
    cell.seed, cell.seconds, cell.trace = seed, seconds, trace
    cell.devices = devices
    return cell
